"""``step_minh_ms.solve``: device milliseconds per traced solve in the
cycle step's min-height search (scope ``wbpr.cycle/minh``: the two
``segment_min``s and their sentinel, or a kernel ``minh_fn``), from the
profiler trace's ops (``op_scopes``)."""
import op_scopes


def read(run):
    return op_scopes.phase_ms(run, "minh")
