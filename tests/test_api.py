"""The ``repro.api`` facade: equivalence with the internal engines,
options validation, warm-start handles (both capacity signs), and lazy
solution views."""
import pathlib
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.api import (CapacityUpdate, MatchingProblem, MaxflowProblem,
                       MinCutProblem, Solver, SolverOptions, WarmStartHandle)
from repro.core import batched
from repro.core import pushrelabel as pr
from repro.core.csr import Graph, build_residual
from repro.core.ref_maxflow import dinic_maxflow
from repro.graphs import generators as G
from tests.conftest import random_graph


# -- Solver.solve == legacy solve -------------------------------------------

@pytest.mark.parametrize("layout", ["rcsr", "bcsr"])
@pytest.mark.parametrize("mode", ["vc", "tc"])
def test_solve_matches_legacy(layout, mode, rng):
    for _ in range(3):
        g = random_graph(rng)
        sol = Solver(SolverOptions(mode=mode, layout=layout)).solve(
            MaxflowProblem(g, 0, g.n - 1))
        legacy = pr.solve_impl(build_residual(g, layout), 0, g.n - 1,
                               mode=mode)
        assert sol.value == legacy.maxflow == dinic_maxflow(g, 0, g.n - 1)
        assert sol.stats.backend == "single"
        assert sol.stats.layout == layout and sol.stats.mode == mode


@settings(max_examples=8, deadline=None)  # capped for tier-1 wall clock
@given(st.integers(0, 10**6), st.sampled_from(["vc", "tc"]),
       st.sampled_from(["bcsr", "rcsr"]))
def test_solve_matches_legacy_property(seed, mode, layout):
    rng = np.random.default_rng(seed)
    g = random_graph(rng, n_lo=5, n_hi=25)
    sol = Solver(SolverOptions(mode=mode, layout=layout)).solve(
        MaxflowProblem(g, 0, g.n - 1))
    legacy = pr.solve_impl(build_residual(g, layout), 0, g.n - 1, mode=mode)
    assert sol.value == legacy.maxflow


def test_batched_backend_matches_single(rng):
    g = random_graph(rng)
    p = MaxflowProblem(g, 0, g.n - 1)
    assert (Solver(backend="batched").solve(p).value
            == Solver(backend="single").solve(p).value)


# -- Solver.solve_many == per-instance solves -------------------------------

@settings(max_examples=4, deadline=None)  # capped for tier-1 wall clock
@given(st.integers(0, 10**6), st.integers(1, 4))
def test_solve_many_matches_per_instance(seed, k):
    rng = np.random.default_rng(seed)
    graphs = [random_graph(rng, n_lo=5, n_hi=25) for _ in range(k)]
    problems = [MaxflowProblem(g, 0, g.n - 1) for g in graphs]
    solver = Solver()
    many = solver.solve_many(problems)
    assert [s.value for s in many] == \
        [solver.solve(p).value for p in problems]
    assert all(s.stats.backend == "batched" and s.stats.batch_size == k
               for s in many)


def test_solve_many_trivial_and_views(rng):
    g = random_graph(rng, n_lo=8, n_hi=20)
    sols = Solver().solve_many([
        MaxflowProblem(g, 0, 0),  # s == t -> trivial
        MaxflowProblem(g, 0, g.n - 1),
    ])
    assert sols[0].value == 0
    assert sols[0].warm_start.corrected  # idle handle, nothing to correct
    # views work on batched solutions too
    cut = sols[1].min_cut()
    assert cut.value == sols[1].value


def test_solve_many_accepts_kernel_modes(rng):
    """The Pallas kernels carry a batch grid axis: bucketed microbatches
    run the faithful kernel modes with values identical to 'vc'."""
    gs = [random_graph(rng, n_lo=6, n_hi=20) for _ in range(3)]
    probs = [MaxflowProblem(g, 0, g.n - 1) for g in gs]
    want = [s.value for s in Solver(backend="batched").solve_many(probs)]
    for mode in ("vc_kernel", "vc_kernel_bsearch"):
        sols = Solver(backend="batched", mode=mode).solve_many(probs)
        assert [s.value for s in sols] == want
        assert all(s.stats.mode == mode for s in sols)


# -- Solver.resolve ---------------------------------------------------------

@settings(max_examples=6, deadline=None)  # capped for tier-1 wall clock
@given(st.integers(0, 10**6))
def test_resolve_increase_matches_cold_property(seed):
    """Warm re-solve after random capacity increases == cold solve."""
    rng = np.random.default_rng(seed)
    g = random_graph(rng, n_lo=8, n_hi=25)
    solver = Solver()
    sol = solver.solve(MaxflowProblem(g, 0, g.n - 1))
    r = sol.warm_start.residual
    fwd = np.where(r.res0 > 0)[0]
    if fwd.size == 0:
        return
    picks = rng.choice(fwd, size=min(int(rng.integers(1, 4)), fwd.size),
                       replace=False)
    ups = [CapacityUpdate(int(r.tails[a]), int(r.heads[a]),
                          int(rng.integers(1, 9))) for a in picks]
    warm = solver.resolve(sol.warm_start, ups)
    assert warm.stats.warm
    r2 = warm.warm_start.residual
    assert warm.value == pr.solve_impl(r2, 0, g.n - 1).maxflow


def test_resolve_decrease_stays_warm():
    g = Graph(3, np.array([[0, 1], [1, 2]], np.int64),
              np.array([5, 5], np.int64))
    solver = Solver()
    sol = solver.solve(MaxflowProblem(g, 0, 2))
    assert sol.value == 5
    dec = solver.resolve(sol.warm_start, [CapacityUpdate(0, 1, -3)])
    assert dec.stats.warm and dec.stats.rerouted
    assert dec.value == 2
    # decrease below zero capacity is rejected
    with pytest.raises(ValueError):
        solver.resolve(sol.warm_start, [CapacityUpdate(0, 1, -9)])


def test_resolve_structural_change_raises(rng):
    g = Graph(3, np.array([[0, 1], [1, 2]], np.int64),
              np.array([5, 5], np.int64))
    sol = Solver().solve(MaxflowProblem(g, 0, 2))
    with pytest.raises(KeyError):  # no 0->2 arc exists
        Solver().resolve(sol.warm_start, [CapacityUpdate(0, 2, 3)])
    with pytest.raises(ValueError):  # empty update set
        Solver().resolve(sol.warm_start, [])


def test_resolve_chains(rng):
    """Handles compose: resolve of a resolve stays consistent with cold."""
    g = random_graph(rng, n_lo=8, n_hi=16)
    solver = Solver()
    sol = solver.solve(MaxflowProblem(g, 0, g.n - 1))
    r = sol.warm_start.residual
    a = int(np.where(r.res0 > 0)[0][0])
    up = [CapacityUpdate(int(r.tails[a]), int(r.heads[a]), 4)]
    step1 = solver.resolve(sol.warm_start, up)
    step2 = solver.resolve(step1.warm_start, up)
    want = pr.solve_impl(step2.warm_start.residual, 0, g.n - 1).maxflow
    assert step2.value == want


# -- WarmStartHandle semantics ----------------------------------------------

def test_handle_lazy_phase2_correction(rng):
    g = random_graph(rng, n_lo=10, n_hi=25)
    sol = Solver().solve(MaxflowProblem(g, 0, g.n - 1))
    h = sol.warm_start
    assert not h.corrected  # phase 2 has not run yet
    res, e = h.arrays()
    assert h.corrected
    # corrected state is a genuine flow: only the sink holds excess
    assert e[g.n - 1] == sol.value and e.sum() == sol.value
    assert h.arrays()[0] is res  # conversion ran exactly once (cached)
    assert h.maxflow == sol.value


# -- lazy Solution views ----------------------------------------------------

def test_flows_conserve_and_bound(rng):
    g = random_graph(rng, n_lo=8, n_hi=25)
    s, t = 0, g.n - 1
    sol = Solver().solve(MaxflowProblem(g, s, t))
    flows = sol.flows()
    r = sol.warm_start.residual
    pu = np.asarray(r.pair_u)
    pv = np.asarray(r.heads)[np.asarray(r.pair_arc)]
    div = np.zeros(g.n, np.int64)
    np.add.at(div, pu, -flows)
    np.add.at(div, pv, flows)
    assert div[t] == sol.value and div[s] == -sol.value
    inner = np.ones(g.n, bool)
    inner[[s, t]] = False
    assert not div[inner].any()  # conservation at every inner vertex


def test_min_cut_view(rng):
    g = random_graph(rng, n_lo=8, n_hi=25)
    sol = Solver().solve(MinCutProblem(g, 0, g.n - 1))
    cut = sol.min_cut()
    assert cut.value == sol.value
    assert cut.source_side[0] and not cut.source_side[g.n - 1]


def test_matching_view_and_type_guard():
    bp = G.bipartite_random(25, 18, 3.0, seed=5)
    sol = Solver().solve(MatchingProblem(bp))
    pairs = sol.matching()
    assert len(pairs) == sol.value == dinic_maxflow(bp.graph, bp.s, bp.t)
    flow_sol = Solver().solve(MaxflowProblem(bp.graph, bp.s, bp.t))
    with pytest.raises(TypeError):
        flow_sol.matching()


# -- problems ---------------------------------------------------------------

def test_problem_residual_cached_per_layout(rng):
    g = random_graph(rng)
    p = MaxflowProblem(g, 0, g.n - 1)
    assert p.residual("bcsr") is p.residual("bcsr")
    assert p.residual("rcsr").layout == "rcsr"


def test_problem_from_residual_guards():
    g = Graph(3, np.array([[0, 1], [1, 2]], np.int64),
              np.array([5, 5], np.int64))
    p = MaxflowProblem.from_residual(build_residual(g, "bcsr"), 0, 2)
    assert Solver().solve(p).value == 5
    with pytest.raises(ValueError):  # no Graph to build the other layout
        p.residual("rcsr")
    with pytest.raises(ValueError):  # terminals out of range
        MaxflowProblem(g, 0, 7)


# -- options validation -----------------------------------------------------

@pytest.mark.parametrize("bad", [
    dict(mode="warp"),
    dict(layout="csc"),
    dict(backend="gpu"),
    dict(backend="distributed", mode="tc"),
    dict(mode="vc_kernel_bsearch", layout="rcsr"),
    dict(global_relabel_cadence=0),
    dict(max_cycles=-1),
    dict(dtype="float32"),
    dict(interpret="yes"),
])
def test_options_validation(bad):
    with pytest.raises(ValueError):
        SolverOptions(**bad)


def test_options_cadence_and_budget():
    opts = SolverOptions(global_relabel_cadence=16, max_cycles=100)
    assert opts.cycle_chunk(5000) == 16
    assert opts.max_rounds(5000) == 7  # ceil(100 / 16)
    auto = SolverOptions()
    assert auto.cycle_chunk(5000) == 1024 and auto.max_rounds(5000) == 100000


# -- distributed backend ----------------------------------------------------

def test_distributed_single_device_guidance():
    import jax
    if len(jax.devices()) > 1:  # pragma: no cover - CI runs single-device
        pytest.skip("multi-device runtime; guidance path not reachable")
    g = Graph(3, np.array([[0, 1], [1, 2]], np.int64),
              np.array([5, 5], np.int64))
    with pytest.raises(NotImplementedError, match="ROADMAP"):
        Solver(backend="distributed").solve(MaxflowProblem(g, 0, 2))


_DIST_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, %(src)r)
import numpy as np
from repro.api import MaxflowProblem, Solver
from repro.core.csr import Graph
from repro.core.ref_maxflow import dinic_maxflow
rng = np.random.default_rng(3)
n = 24
m = 80
g = Graph(n, rng.integers(0, n, size=(m, 2)).astype(np.int64),
          rng.integers(1, 9, size=m).astype(np.int64))
sol = Solver(backend="distributed").solve(MaxflowProblem(g, 0, n - 1))
assert sol.value == dinic_maxflow(g, 0, n - 1), sol.value
assert sol.stats.backend == "distributed"
assert sol.warm_start is None
try:
    sol.flows()
except RuntimeError:
    pass
else:
    raise AssertionError("flows() must raise without a warm-start handle")
print("DIST-API-OK")
"""


@pytest.mark.slow
def test_distributed_backend_matches_oracle():
    """``Solver(backend='distributed')`` really runs ``solve_distributed``
    when a multi-device mesh is available (forced host devices)."""
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    r = subprocess.run([sys.executable, "-c", _DIST_SCRIPT % {"src": src}],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DIST-API-OK" in r.stdout


# -- legacy entry points are gone -------------------------------------------

def test_legacy_entry_points_removed():
    """The deprecation shims were dropped: the facade is the only public
    entry, the ``*_impl`` engines the only module-level callables."""
    from repro.core import bipartite

    assert not hasattr(pr, "solve") and hasattr(pr, "solve_impl")
    assert not hasattr(batched, "batched_solve")
    assert hasattr(batched, "batched_solve_impl")
    assert not hasattr(bipartite, "max_matching")
    assert hasattr(bipartite, "max_matching_impl")


def test_service_cache_stores_handles():
    """The serving cache consumes the same WarmStartHandle the facade
    hands out — no hand-rolled array triples left.  Correction stays
    deferred until a resubmit needs it, and then runs as one batched
    device dispatch for the handle's whole microbatch."""
    from repro.serving import MaxflowService, ServiceConfig

    svc = MaxflowService(ServiceConfig(max_batch=1, cycle_chunk=16,
                                       mode="vc"))
    g, s, t = G.random_sparse(30, 100, seed=3)
    res = svc.submit(g, s, t).result()
    entry = svc.results.peek(res.graph_id)
    assert isinstance(entry.handle, WarmStartHandle)
    assert not entry.handle.corrected  # correction stays lazy until resubmit
    svc.resubmit(res.graph_id, [(int(g.edges[0, 0]), int(g.edges[0, 1]), 2)])
    assert entry.handle.corrected
    assert svc.stats()["phase2_time_s"] > 0.0  # ran on device, batched
