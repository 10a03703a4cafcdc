"""Host milliseconds per interval in CARAT's observe phase: the summed
``policy.observe`` telemetry spans over the window's intervals."""


def read(run):
    s = run.spans_s.get("policy.observe")
    if s is None or run.intervals == 0:
        return None
    return s / run.intervals * 1e3
