"""``step_compact_ms.solve``: device milliseconds per traced solve in
the cycle step's AVQ compaction (scope ``wbpr.cycle/compact``: the
active mask and the ``nonzero``), from the profiler trace's ops
(``op_scopes``)."""
import op_scopes


def read(run):
    return op_scopes.phase_ms(run, "compact")
