"""Device milliseconds per interval in the fused fleet step: the summed
device time of the ``jit_step`` program's runs in the profiler trace
over the window's intervals."""

MODULE = "jit_step"


def read(run):
    if run.trace is None or run.intervals == 0:
        return None
    s = run.trace.module_s.get(MODULE, 0.0)
    if s <= 0.0:
        return None
    return s / run.intervals * 1e3
