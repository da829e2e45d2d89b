"""Min-cut extraction."""
import numpy as np

from repro.core.csr import build_residual
from repro.core.mincut import solve_min_cut
from repro.core.ref_maxflow import dinic_maxflow
from tests.conftest import random_graph


def test_mincut_matches_maxflow(rng):
    for _ in range(5):
        g = random_graph(rng, n_lo=8, n_hi=30)
        want = dinic_maxflow(g, 0, g.n - 1)
        r = build_residual(g, "bcsr")
        flow, cut = solve_min_cut(r, 0, g.n - 1)
        assert flow == want
        assert cut.value == want  # max-flow = min-cut
        assert cut.source_side[0] and not cut.source_side[g.n - 1]


def test_mincut_is_actually_minimal(rng):
    """Removing the cut arcs disconnects s from t in the original graph."""
    g = random_graph(rng, n_lo=8, n_hi=20)
    r = build_residual(g, "bcsr")
    flow, cut = solve_min_cut(r, 0, g.n - 1)
    if flow == 0:
        return
    tails = np.asarray(r.tails)
    heads = np.asarray(r.heads)
    res0 = np.asarray(r.res0)
    keep = np.ones(r.num_arcs, bool)
    keep[cut.cut_arcs] = False
    reach = np.zeros(r.n, bool)
    reach[0] = True
    for _ in range(r.n):
        ok = keep & (res0 > 0) & reach[tails]
        new = reach.copy()
        new[heads[ok]] = True
        if (new == reach).all():
            break
        reach = new
    assert not reach[r.n - 1]

