"""Host milliseconds per interval re-uploading the fused step's statics:
the summed ``fleet.statics`` telemetry spans (the plan-term recompute,
the one-hot OST matrix and their ``device_put``) over the window's
intervals."""


def read(run):
    s = run.spans_s.get("fleet.statics")
    if s is None or run.intervals == 0:
        return None
    return s / run.intervals * 1e3
