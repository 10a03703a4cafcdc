"""Host milliseconds per interval in CARAT's stage 2: the summed
``policy.stage2`` telemetry spans (the scan for nodes at a boundary and
Algorithm 2 over them) over the window's intervals."""


def read(run):
    s = run.spans_s.get("policy.stage2")
    if s is None or run.intervals == 0:
        return None
    return s / run.intervals * 1e3
