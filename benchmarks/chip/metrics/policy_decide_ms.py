"""Host milliseconds per decision interval in CARAT's decide phase: the
summed ``policy.decide`` telemetry spans over the intervals that made a
decision."""


def read(run):
    s = run.spans_s.get("policy.decide")
    if s is None or run.decision_intervals == 0:
        return None
    return s / run.decision_intervals * 1e3
