"""The round's stall rule: a round of push-relabel cycles also ends once
the sink's excess has not risen for ``round_patience(sweeps)`` cycles.
Exact answers with the rule, the depth guard, chunk invariance of the
``patience`` loop, an unchanged trace without it, exact ``max_cycles``
budgets, and the counter and span args that report it."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import ir
from repro.analysis import surfaces as S
from repro.api import MatchingProblem, MaxflowProblem, Solver, SolverOptions
from repro.core import globalrelabel
from repro.core import pushrelabel as pr
from repro.core.csr import Graph, build_residual
from repro.errors import BudgetExhausted
from repro.graphs import generators as G
from repro.obs import TRACER
from tests.conftest import assert_certified

#: small instances of the generator families, each with the stalled
#: rounds its solve takes (CPU; one of a family is enough to engage)
INSTANCES = {
    "washington_rlg": (lambda: G.washington_rlg(64, 8, seed=1), 1),
    "genrmf": (lambda: G.genrmf(4, 6, seed=3), 1),
    "grid_road": (lambda: G.grid_road(12, 12, seed=4), 0),
}


def _path(length: int, cap: int = 5):
    """s -> 1 -> ... -> length -> t: the preflow fills vertex 1, and its
    excess needs ``length`` cycles to reach t."""
    n = length + 2
    s, t = 0, n - 1
    edges = np.stack([np.arange(n - 1), np.arange(1, n)], axis=1)
    return Graph(n, edges.astype(np.int64),
                 np.full(n - 1, cap, np.int64)), s, t


def _spans(solve):
    """``solve()`` under the tracer; the ``solve.cycles`` B/E args."""
    TRACER.clear()
    TRACER.enable()
    try:
        out = solve()
    finally:
        TRACER.disable()
    evs = [(e["ph"], e.get("args")) for e in TRACER.to_dict()["traceEvents"]
           if e["name"] == "solve.cycles"]
    TRACER.clear()
    return out, evs


@pytest.mark.parametrize("family", sorted(INSTANCES))
def test_solve_with_the_rule_is_exact(family):
    """Flow, cut and value hold the certificate on every family; the
    stalled rounds are those the instance is known to take, and a stalled
    round ends before the cadence's cap."""
    make, stalled = INSTANCES[family]
    g, s, t = make()
    sol = Solver().solve(MaxflowProblem(g, s, t))
    assert_certified(sol, g, s, t)
    st = sol.stats
    assert st.stalled_rounds == stalled <= st.rounds
    if stalled:
        assert st.cycles < st.rounds * SolverOptions().cycle_chunk(g.n)


def test_matching_with_the_rule_is_exact():
    """A power-law matching stalls its round and still matches Dinic."""
    bp = G.bipartite_powerlaw(300, 120, 900, seed=5)
    sol = Solver().solve(MatchingProblem(bp))
    assert_certified(sol, bp.graph, bp.s, bp.t)
    assert sol.stats.stalled_rounds >= 1


def test_depth_guard_keeps_a_deep_path_in_one_round():
    """Excess needs 80 cycles to reach t, beyond a fixed window of 32:
    the window of ``2 * sweeps`` keeps the solve in one round with no
    stall, where ``patience=32`` alone would cut the round short."""
    g, s, t = _path(80)
    p = MaxflowProblem(g, s, t)
    sol = Solver(SolverOptions(global_relabel_cadence=1024)).solve(p)
    assert sol.value == 5
    assert sol.stats.rounds == 1 and sol.stats.stalled_rounds == 0
    assert sol.stats.cycles == 80

    r = build_residual(g, "bcsr")
    dg, meta, res0 = pr.to_device(r)
    state = pr.preflow(dg, meta, res0, s)
    state, _, sweeps = globalrelabel.global_relabel(dg, meta, state, s, t)
    assert pr.round_patience(int(sweeps)) > 2 * 80
    out, cycles = pr.run_cycles(dg, meta, state, s, t, max_cycles=1024,
                                patience=jnp.int32(32))
    assert int(cycles) == 32 and bool(pr.any_active(out, meta.n, s, t))


def _prepped(layout="bcsr"):
    g, s, t = G.washington_rlg(16, 6, seed=2)
    r = build_residual(g, layout)
    dg, meta, res0 = pr.to_device(r)
    state = pr.preflow(dg, meta, res0, s)
    state, _, _ = globalrelabel.global_relabel(dg, meta, state, s, t)
    return dg, meta, state, s, t


@pytest.mark.parametrize("mode,layout", [
    ("vc", "bcsr"), ("vc", "rcsr"), ("tc", "bcsr"),
    ("vc_kernel", "bcsr"), ("vc_kernel_bsearch", "bcsr"),
])
def test_run_cycles_patience_chunk_invariant(mode, layout):
    """A stalled loop ends on the same cycle, in the same state, whatever
    the scan chunking; chunk=1 is the bare per-step while_loop."""
    g, meta, state, s, t = _prepped(layout)
    p = jnp.int32(8)
    ref_st, ref_cyc = pr.run_cycles(g, meta, state, s, t, mode=mode,
                                    max_cycles=256, chunk=1, patience=p)
    assert int(ref_cyc) < 256 and bool(pr.any_active(ref_st, meta.n, s, t))
    for chunk in (3, 4):
        st_c, cyc_c = pr.run_cycles(g, meta, state, s, t, mode=mode,
                                    max_cycles=256, chunk=chunk, patience=p)
        assert int(cyc_c) == int(ref_cyc)
        for a, b in zip((st_c.res, st_c.h, st_c.e),
                        (ref_st.res, ref_st.h, ref_st.e)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_cycles_patience_telemetry_chunk_invariant():
    """The telemetry body carries the counter too: counters, histories
    and the stall agree with the per-step loop."""
    g, meta, state, s, t = _prepped()
    kw = dict(max_cycles=256, telemetry=True, patience=jnp.int32(8))
    ref_st, ref_cyc, ref_tel = pr.run_cycles(g, meta, state, s, t, chunk=1,
                                             **kw)
    _, cyc, tel = pr.run_cycles(g, meta, state, s, t, chunk=4, **kw)
    assert int(ref_cyc) < 256 and bool(pr.any_active(ref_st, meta.n, s, t))
    assert int(cyc) == int(ref_cyc)
    for a, b in zip(jax.tree.leaves(tel), jax.tree.leaves(ref_tel)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_patience_beyond_the_loop_changes_nothing():
    """A patience the loop never reaches, or one behind a budget that
    ends the loop first, gives the plain loop's cycles and state."""
    g, meta, state, s, t = _prepped()
    for kw, p in ((dict(), 10**6), (dict(budget=jnp.int32(3)), 8)):
        plain = pr.run_cycles(g, meta, state, s, t, max_cycles=256, **kw)
        got = pr.run_cycles(g, meta, state, s, t, max_cycles=256,
                            patience=jnp.int32(p), **kw)
        for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(plain)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("surface", [
    "run_cycles/vc/bcsr", "run_cycles/tc/bcsr", "run_cycles/vc_kernel/bcsr",
    "run_cycles/vc_kernel_bsearch/bcsr",
])
def test_patience_none_keeps_the_trace(surface):
    """``patience=None`` traces the same jaxpr as a call without it, and
    the ``patience`` loop holds the surface's contract (its loop shape,
    launch and equation budgets) with the counter as its only growth."""
    surf = next(x for x in S.iter_surfaces() if x.name == surface)
    mode = surf.tag_dict()["mode"]
    g, meta, state, s, t, _, _ = S._single_fixture("bcsr")

    def loop(res, h, e, **kw):
        return pr.run_cycles(g, meta, pr.PRState(res, h, e), s, t,
                             mode=mode, max_cycles=S._MAX_CYCLES, **kw)

    args = (state.res, state.h, state.e)
    plain = ir.trace(loop, *args)
    assert str(ir.trace(loop, *args, patience=None)) == str(plain)

    def build():
        return (lambda res, h, e, p: loop(res, h, e, patience=p),
                args + (jnp.int32(32),))

    base, violations = S.analyze_surface(surf)
    assert violations == []
    assert base.eqn_count == ir.census_of(plain).eqn_count
    census, violations = S.analyze_surface(
        dataclasses.replace(surf, build=build))
    assert violations == []
    assert census.loop_counts() == base.loop_counts()
    assert census.pallas_call_count == base.pallas_call_count
    assert 0 < census.eqn_count - base.eqn_count <= 40


def test_exact_budget_raises_when_rounds_stall():
    """A stalled round is shorter than the cadence, so a budget can need
    more rounds than ``max_cycles / cadence``: the solve still spends
    exactly ``max_cycles`` and raises ``BudgetExhausted``."""
    g, s, t = G.genrmf(4, 6, seed=3)
    p = MaxflowProblem(g, s, t)
    free = Solver().solve(p)
    assert free.stats.stalled_rounds >= 1
    budget = free.stats.cycles - 1
    cadence = SolverOptions().cycle_chunk(g.n)
    assert free.stats.rounds > -(-budget // cadence)
    with pytest.raises(BudgetExhausted) as ei:
        Solver(SolverOptions(max_cycles=budget)).solve(p)
    assert ei.value.cycles_spent == budget
    # and a budget of the free solve's count converges, uninflated
    sol = Solver(SolverOptions(max_cycles=free.stats.cycles)).solve(p)
    assert sol.value == free.value
    assert sol.stats.cycles == free.stats.cycles


def test_cycles_span_carries_patience_and_stalled():
    """``solve.cycles`` opens with the round's patience and closes with
    its cycles and whether it stalled; the stalls sum to the counter."""
    g, s, t = G.genrmf(4, 6, seed=3)
    sol, evs = _spans(lambda: Solver().solve(MaxflowProblem(g, s, t)))
    begins = [a for ph, a in evs if ph == "B"]
    ends = [a for ph, a in evs if ph == "E"]
    assert len(begins) == len(ends) == sol.stats.rounds
    assert all(set(a) == {"patience"} and a["patience"] >= 32
               for a in begins)
    assert all(set(a) == {"cycles", "stalled"} for a in ends)
    assert sum(a["stalled"] for a in ends) == sol.stats.stalled_rounds >= 1
    assert sum(a["cycles"] for a in ends) == sol.stats.cycles


def test_round_patience_grows_with_depth():
    assert pr.round_patience(0) == 32
    assert pr.round_patience(16) == 32
    assert pr.round_patience(17) == 34
    assert pr.round_patience(500) == 1000
