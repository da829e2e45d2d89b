"""``step_apply_ms.solve``: device milliseconds per traced solve in the
cycle step's push/relabel apply (scope ``wbpr.cycle/apply``: the
decision and the scatters), from the profiler trace's ops
(``op_scopes``)."""
import op_scopes


def read(run):
    return op_scopes.phase_ms(run, "apply")
