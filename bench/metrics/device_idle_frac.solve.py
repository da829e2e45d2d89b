"""``device_idle_frac.solve``: the share of the traced window in which no
operation ran on the chip, 1 - busy / window, from the profiler trace
(``trace_reduce``: busy is the union of the ``XLA Ops`` intervals)."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 1.0 - run.trace.busy_s / run.trace.window_s
