"""Host milliseconds per decision interval scoring CARAT's candidates:
the summed ``carat.score`` telemetry spans (the scorer's host cross
product, the transfer, the GBDT kernel and the pull of its
probabilities) over the intervals that made a decision."""


def read(run):
    s = run.spans_s.get("carat.score")
    if s is None or run.decision_intervals == 0:
        return None
    return s / run.decision_intervals * 1e3
