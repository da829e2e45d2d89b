"""End-to-end max-flow correctness: WBPR vs Dinic oracle + invariants,
driven through the ``repro.api`` facade."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.api import MaxflowProblem, Solver, SolverOptions
from repro.core import pushrelabel as pr
from repro.core.csr import Graph, build_residual
from repro.core.ref_maxflow import dinic_maxflow
from tests.conftest import (FAMILIES, assert_certified, family_instance,
                            random_graph)


def _solve(g, s, t, mode="vc", layout="bcsr"):
    return Solver(SolverOptions(mode=mode, layout=layout)).solve(
        MaxflowProblem(g, s, t))


@pytest.mark.parametrize("layout", ["rcsr", "bcsr"])
@pytest.mark.parametrize("mode", ["vc", "tc"])
def test_random_graphs_match_oracle(layout, mode, rng):
    for _ in range(6):
        g = random_graph(rng)
        want = dinic_maxflow(g, 0, g.n - 1)
        assert _solve(g, 0, g.n - 1, mode=mode, layout=layout).value == want


@pytest.mark.parametrize("mode", ["vc", "tc"])
@pytest.mark.parametrize("layout", ["rcsr", "bcsr"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_certified_solution(family, layout, mode):
    """Value, flow and cut of one solve, each against the graph: the
    certificate every chip benchmark cell checks."""
    g, s, t = family_instance(family)
    assert_certified(_solve(g, s, t, mode=mode, layout=layout), g, s, t)


def test_flow_conservation_and_cut(rng):
    """Final state: e(t) equals both the s-side net outflow and a saturated
    cut (max-flow = min-cut certificate via residual reachability)."""
    g = random_graph(rng, n_lo=10, n_hi=30)
    s, t = 0, g.n - 1
    r = build_residual(g, "bcsr")
    dg, meta, res0 = pr.to_device(r)
    maxflow = _solve(g, s, t).value
    # re-run to capture final state
    state = pr.preflow(dg, meta, res0, s)
    from repro.core import globalrelabel as gr
    state, _, _ = gr.global_relabel(dg, meta, state, s, t)
    for _ in range(10000):
        state, _ = pr.run_cycles(dg, meta, state, s, t, mode="vc",
                                 max_cycles=256)
        state, nact, _ = gr.global_relabel(dg, meta, state, s, t)
        if int(nact) == 0:
            break
    assert int(state.e[t]) == maxflow
    # phase 2: cancel stranded preflow excess -> genuine max flow
    res = pr.convert_preflow_to_flow(r, state, s, t)
    # residual-reachable set from s defines a cut; every crossing arc is
    # saturated and the net flow across it equals the max flow (max-flow =
    # min-cut certificate)
    n = meta.n
    heads, tails = np.asarray(dg.heads), np.asarray(dg.tails)
    reach = np.zeros(n, bool)
    reach[s] = True
    for _ in range(n):
        newr = reach.copy()
        ok = reach[tails] & (res > 0)
        newr[heads[ok]] = True
        if (newr == reach).all():
            break
        reach = newr
    assert not reach[t]
    res0_np = np.asarray(r.res0)
    crossing = (reach[tails]) & (~reach[heads])
    assert np.all(res[crossing] == 0)  # saturated cut
    cut_flow = (res0_np - res)[crossing].sum()
    assert cut_flow == maxflow


def test_disconnected_sink():
    g = Graph(4, np.array([[0, 1], [1, 0]], np.int64),
              np.array([3, 2], np.int64))
    assert _solve(g, 0, 3).value == 0


def test_single_edge():
    g = Graph(2, np.array([[0, 1]], np.int64), np.array([7], np.int64))
    assert _solve(g, 0, 1).value == 7


def test_antiparallel_edges():
    g = Graph(3, np.array([[0, 1], [1, 0], [1, 2]], np.int64),
              np.array([5, 4, 3], np.int64))
    assert _solve(g, 0, 2, layout="rcsr").value == 3


@settings(max_examples=10, deadline=None)  # capped for tier-1 wall clock
@given(st.integers(3, 16), st.data())
def test_property_matches_oracle(n, data):
    m = data.draw(st.integers(2, 40))
    edges = data.draw(st.lists(
        st.tuples(st.integers(0, n - 1), st.integers(0, n - 1)),
        min_size=m, max_size=m))
    caps = data.draw(st.lists(st.integers(1, 20), min_size=m, max_size=m))
    g = Graph(n, np.array(edges, np.int64), np.array(caps, np.int64))
    want = dinic_maxflow(g, 0, n - 1)
    assert _solve(g, 0, n - 1).value == want
