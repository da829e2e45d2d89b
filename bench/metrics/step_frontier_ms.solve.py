"""``step_frontier_ms.solve``: device milliseconds per traced solve in
the cycle step's flat frontier (scope ``wbpr.cycle/frontier``: ``deg``,
``cumsum``, ``repeat``, the arc and key gathers), from the profiler
trace's ops (``op_scopes``)."""
import op_scopes


def read(run):
    return op_scopes.phase_ms(run, "frontier")
