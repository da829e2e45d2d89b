"""Bipartite matching via WBPR through the facade: size vs oracle,
matching validity, the König cut and phase 2's counters, on
``bipartite_random`` and the two-sided power-law ``bipartite_powerlaw``."""
import numpy as np
import pytest

from repro.api import MatchingProblem, Solver
from repro.core.csr import Graph
from repro.core.ref_maxflow import dinic_maxflow, ref_matching
from repro.graphs.generators import (BipartiteProblem, bipartite_powerlaw,
                                     bipartite_random)
from repro.obs import TRACER


def test_matching_size_matches_oracle():
    for seed in (0, 1, 2):
        bp = bipartite_random(40, 30, 3.0, seed=seed)
        want = dinic_maxflow(bp.graph, bp.s, bp.t)
        assert Solver().solve(MatchingProblem(bp)).value == want


def test_matching_is_valid():
    bp = bipartite_random(50, 35, 4.0, seed=7)
    sol = Solver().solve(MatchingProblem(bp))
    pairs = sol.matching()
    assert len(pairs) == sol.value
    # each vertex used at most once
    assert len(set(pairs[:, 0].tolist())) == len(pairs)
    assert len(set(pairs[:, 1].tolist())) == len(pairs)
    # every pair is an original edge
    eset = set(map(tuple, bp.lr_edges.tolist()))
    for u, v in pairs.tolist():
        assert (u, v) in eset


def test_unit_caps_flow_at_most_left():
    bp = bipartite_random(20, 8, 6.0, seed=9)
    sol = Solver().solve(MatchingProblem(bp))
    assert sol.value <= min(bp.n_left, bp.n_right)


# -- the two-sided power-law generator and the Hopcroft–Karp reference --------

#: seeded small instances: (n_left, n_right, n_edges, left_exp, right_exp,
#: seed); "hub" puts most memberships on one group
POWERLAW = {
    "left_heavy": (60, 15, 150, 0.5, 0.8, 3),
    "right_heavy": (15, 60, 150, 0.5, 0.8, 4),
    "hub": (60, 10, 100, 0.5, 3.0, 5),
}


def _identity(k: int = 12) -> BipartiteProblem:
    """k users each in their own group: every unit of the preflow reaches
    the sink, so no excess is stranded."""
    lr = np.stack([np.arange(k), k + np.arange(k)], 1).astype(np.int64)
    s, t = 2 * k, 2 * k + 1
    edges = np.concatenate([
        lr, np.stack([np.full(k, s), np.arange(k)], 1),
        np.stack([k + np.arange(k), np.full(k, t)], 1)]).astype(np.int64)
    return BipartiteProblem(Graph(2 * k + 2, edges,
                                  np.ones(len(edges), np.int64)),
                            s, t, k, k, lr)


def _stranded(sol) -> np.ndarray:
    """The preflow's excess on each vertex but s and t, before any view
    of ``sol`` ran phase 2."""
    h = sol.warm_start
    assert not h.corrected
    e = np.asarray(h._e).copy()
    e[[h.s, h.t]] = 0
    return e


@pytest.mark.parametrize("shape", sorted(POWERLAW) + ["identity"])
def test_matching_agrees_with_reference(shape):
    """Value, pairs, the König cut and the phase-2 counters of a
    ``MatchingProblem`` solve, against ``ref_matching``."""
    bp = _identity() if shape == "identity" else \
        bipartite_powerlaw(*POWERLAW[shape][:5], seed=POWERLAW[shape][5])
    if shape == "hub":
        deg = np.bincount(bp.lr_edges[:, 1])
        assert deg.max() > len(bp.lr_edges) // 2
    sol = Solver().solve(MatchingProblem(bp))
    stranded = _stranded(sol)
    assert sol.phase2_stats is None  # no view has needed phase 2 yet
    want = ref_matching(bp.lr_edges, bp.n_left, bp.n_right)
    assert sol.value == len(want)
    pairs = sol.matching()
    assert len(pairs) == sol.value
    assert len(np.unique(pairs[:, 0])) == len(np.unique(pairs[:, 1])) \
        == len(pairs)
    members = set(map(tuple, bp.lr_edges.tolist()))
    assert all(p in members for p in map(tuple, pairs.tolist()))
    # König: the cut's capacity is the matching size, and its vertex cover
    # (left vertices off the source side, right vertices on it) covers
    # every membership
    cut = sol.min_cut()
    assert cut.value == sol.value
    side = cut.source_side
    u, v = bp.lr_edges[:, 0], bp.lr_edges[:, 1]
    assert np.all(~side[u] | side[v])
    assert np.count_nonzero(~side[:bp.n_left]) \
        + np.count_nonzero(side[bp.n_left:bp.n_left + bp.n_right]) \
        == sol.value
    stats = sol.phase2_stats
    if stranded.any():
        # unit arcs: each cancel moves one unit, and every stranded unit
        # crosses one arc at least; a vertex drains all its inbound flow
        # arcs in one step, so a pass takes depth + 1 steps, not one per
        # unit (flow heights are at most 2: group -> user -> s)
        assert stats.passes >= 1 and stats.cancels >= stranded.sum()
        assert stats.steps <= 3 * stats.passes
    else:
        assert stats == (0, 0, 0)
    assert shape != "identity" or not stranded.any()
    assert shape != "hub" or stranded.any()


@pytest.mark.parametrize("shape", sorted(POWERLAW))
def test_bipartite_powerlaw_shape(shape):
    """Deterministic per seed, no duplicate memberships, every vertex in
    one at least, the flow network laid out as ``bipartite_random``'s."""
    *size, seed = POWERLAW[shape]
    n_left, n_right, n_edges = size[:3]
    bp = bipartite_powerlaw(*size, seed=seed)
    again = bipartite_powerlaw(*size, seed=seed)
    other = bipartite_powerlaw(*size, seed=seed + 1)
    np.testing.assert_array_equal(bp.graph.edges, again.graph.edges)
    assert not np.array_equal(bp.lr_edges, other.lr_edges)
    lr = bp.lr_edges
    assert lr.shape == (n_edges, 2)
    assert len(np.unique(lr[:, 0] * (n_left + n_right) + lr[:, 1])) \
        == n_edges
    assert np.all(np.bincount(lr[:, 0], minlength=n_left) >= 1)
    assert np.all(np.bincount(lr[:, 1] - n_left, minlength=n_right) >= 1)
    assert lr[:, 0].max() < n_left <= lr[:, 1].min()
    assert (bp.s, bp.t) == (n_left + n_right, n_left + n_right + 1)
    assert bp.graph.n == n_left + n_right + 2
    assert len(bp.graph.edges) == n_edges + n_left + n_right
    assert np.all(bp.graph.cap == 1)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_ref_matching_is_maximum(seed):
    """Hopcroft–Karp's size is the unit-capacity max flow (Dinic), and
    its pairs are a matching of memberships."""
    bp = bipartite_random(25, 18, 2.5, seed=seed)
    pairs = ref_matching(bp.lr_edges, bp.n_left, bp.n_right)
    assert len(pairs) == dinic_maxflow(bp.graph, bp.s, bp.t)
    assert len(np.unique(pairs[:, 0])) == len(np.unique(pairs[:, 1])) \
        == len(pairs)
    members = set(map(tuple, bp.lr_edges.tolist()))
    assert all(p in members for p in map(tuple, pairs.tolist()))


def test_matching_and_phase2_spans():
    """``solution.matching`` carries the pair count, ``solution.phase2``
    the passes, cancel steps and cancelled arcs, on the Chrome events of
    the tracer."""
    bp = bipartite_powerlaw(*POWERLAW["hub"][:5], seed=POWERLAW["hub"][5])
    TRACER.clear()
    TRACER.enable()
    try:
        sol = Solver().solve(MatchingProblem(bp))
        pairs = sol.matching()
    finally:
        TRACER.disable()
    ends = {e["name"]: e.get("args") for e in TRACER.to_dict()["traceEvents"]
            if e["ph"] == "E"}
    TRACER.clear()
    assert ends["solution.matching"] == {"pairs": len(pairs)}
    assert ends["solution.phase2"] == sol.phase2_stats._asdict()
    assert sol.phase2_stats.steps > 0
    assert set(ends["solution.phase2"]) == {"passes", "steps", "cancels"}
