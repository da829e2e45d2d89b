"""``global_relabel_ms.solve``: device milliseconds per traced solve in
the global relabels (scope ``wbpr.global_relabel``, program
``jit_global_relabel_impl``), from the profiler trace's ops
(``op_scopes``)."""
import op_scopes


def read(run):
    return op_scopes.phase_ms(run, "global_relabel")
