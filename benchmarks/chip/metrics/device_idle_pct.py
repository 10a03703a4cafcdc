"""Share of the traced window in which no operation ran on the device:
100 x (1 - busy / window), busy being the union of the device's
operation intervals."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0.0:
        return None
    return (1.0 - run.trace.busy_s / run.trace.window_s) * 100.0
