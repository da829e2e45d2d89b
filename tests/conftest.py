import pathlib
import sys

import numpy as np
import pytest

# bare `pytest` puts only tests/ on sys.path; the modules here import
# `tests.conftest`, so make the repo root importable too
_ROOT = str(pathlib.Path(__file__).resolve().parents[1])
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

try:  # prefer the real property-testing engine when available
    import hypothesis  # noqa: F401
except ImportError:  # gate the missing dep: deterministic fallback shim
    from tests import _hypothesis_fallback

    sys.modules["hypothesis"] = _hypothesis_fallback
    sys.modules["hypothesis.strategies"] = _hypothesis_fallback.strategies


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def random_graph(rng, n_lo=5, n_hi=40, cap_hi=15):
    from repro.core.csr import Graph
    n = int(rng.integers(n_lo, n_hi))
    m = int(rng.integers(n, 5 * n))
    edges = rng.integers(0, n, size=(m, 2)).astype(np.int64)
    caps = rng.integers(1, cap_hi, size=m).astype(np.int64)
    return Graph(n, edges, caps)


#: generator families on fixed seeds: name -> (generator, args, seed)
FAMILIES = {
    "rlg": ("washington_rlg", (6, 8), 11),
    "genrmf": ("genrmf", (3, 4), 11),
    "grid": ("grid_road", (8, 8), 11),
    "powerlaw": ("powerlaw", (250, 3), 5),
    "sparse": ("random_sparse", (60, 240), 7),
}


def family_instance(family, seed=None):
    """``(graph, s, t)`` of a family, on its fixed seed unless given."""
    from repro.graphs import generators as G
    gen, args, fixed = FAMILIES[family]
    return getattr(G, gen)(*args, seed=fixed if seed is None else seed)


def assert_certified(sol, g, s, t):
    """``sol`` is an exact max flow of ``g`` from s to t, checked against
    the graph alone: its value is Dinic's, ``flows()`` is a feasible flow
    of that value and ``min_cut()`` a cut of that capacity."""
    from repro.core.ref_maxflow import dinic_maxflow
    assert sol.value == dinic_maxflow(g, s, t)
    # capacity of each ordered pair, summed over parallel edges
    key = g.edges[:, 0] * g.n + g.edges[:, 1]
    cap = np.bincount(key, weights=g.cap, minlength=g.n * g.n)
    r = sol.warm_start.residual
    arc = np.asarray(r.pair_arc)
    u, v = np.asarray(r.tails)[arc], np.asarray(r.heads)[arc]
    f = sol.flows()
    assert np.all(f <= cap[u * g.n + v]) and np.all(-f <= cap[v * g.n + u])
    net_out = np.bincount(u, f, g.n) - np.bincount(v, f, g.n)
    inner = np.ones(g.n, bool)
    inner[[s, t]] = False
    assert not net_out[inner].any()
    assert net_out[s] == -net_out[t] == sol.value
    side = sol.min_cut().source_side
    assert side[s] and not side[t]
    crossing = side[g.edges[:, 0]] & ~side[g.edges[:, 1]]
    assert g.cap[crossing].sum() == sol.value
