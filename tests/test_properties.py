"""System-invariant property tests (hypothesis)."""
import numpy as np
from hypothesis import given, settings, strategies as st


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10**6))
def test_pushrelabel_flow_bounds(seed):
    """0 <= flow <= min(cap out of s, cap into t) for any graph."""
    from repro.api import MaxflowProblem, Solver
    from repro.core.csr import Graph
    rng = np.random.default_rng(seed)
    n = int(rng.integers(4, 20))
    m = int(rng.integers(2, 50))
    e = rng.integers(0, n, size=(m, 2)).astype(np.int64)
    caps = rng.integers(1, 30, size=m).astype(np.int64)
    g = Graph(n, e, caps)
    flow = Solver().solve(MaxflowProblem(g, 0, n - 1)).value
    out_cap = caps[(e[:, 0] == 0) & (e[:, 1] != 0)].sum()
    in_cap = caps[(e[:, 1] == n - 1) & (e[:, 0] != n - 1)].sum()
    assert 0 <= flow <= min(out_cap, in_cap)
