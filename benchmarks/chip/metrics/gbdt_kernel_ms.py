"""Device milliseconds per decision interval in the GBDT Pallas kernel:
the summed device time of its operations (``gbdt_logits_pallas``) in the
profiler trace over the intervals that made a decision."""

KERNEL = "gbdt_logits_pallas"


def read(run):
    if run.trace is None or run.decision_intervals == 0:
        return None
    s = run.trace.op_time(KERNEL)
    if s <= 0.0:
        return None
    return s / run.decision_intervals * 1e3
