"""``step_loop_ms.solve``: device milliseconds per traced solve in the
cycle loop around the step (scope ``wbpr.cycle/loop``: the condition,
the engine's chunk gating and carry), from the profiler trace's ops
(``op_scopes``)."""
import op_scopes


def read(run):
    return op_scopes.phase_ms(run, "loop")
