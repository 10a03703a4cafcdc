"""Host milliseconds per interval pulling the fleet's state back from the
device: the summed ``fleet.sync_host`` telemetry spans over the window's
intervals."""


def read(run):
    s = run.spans_s.get("fleet.sync_host")
    if s is None or run.intervals == 0:
        return None
    return s / run.intervals * 1e3
