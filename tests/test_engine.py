"""The shared sweep engine: parity of every ported loop shell with the
per-step ``while_loop`` semantics it replaced, scan-compiled trace-shape
assertions, and exact ``max_cycles`` budget accounting."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.analysis import ir, lint
from repro.api import MaxflowProblem, Solver, SolverOptions
from repro.core import batched, engine, globalrelabel
from repro.core import pushrelabel as pr
from repro.core.csr import build_residual
from repro.graphs import generators as G
from tests.conftest import random_graph

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"


# -- the engine core vs lax.while_loop --------------------------------------

@pytest.mark.parametrize("chunk", [1, 2, 4, 5])
def test_run_bulk_loop_matches_while_loop(chunk):
    """run_bulk_loop(step, cond) == lax.while_loop(cond, step) bit-for-bit
    on an arbitrary pytree carry, whatever the chunking."""

    def step(c):
        x, n, flag = c
        return x * 2 + 1, n + 1, flag & (x[0] < 100)

    def cond(c):
        x, n, flag = c
        return (n < 23) & jnp.any(x < 10**6)

    carry = (jnp.arange(5, dtype=jnp.int32), jnp.int32(0), jnp.bool_(True))
    want = jax.lax.while_loop(cond, step, carry)
    got = engine.run_bulk_loop(step, carry, cond_fn=cond, chunk=chunk)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_to_fixpoint_counts_sweeps_exactly():
    """Sweep count matches the historical per-sweep loop: the final
    no-change sweep (the one that discovers the fixpoint) is counted."""
    m = jnp.asarray(np.array([[0, 1, 0, 0],
                              [0, 0, 1, 0],
                              [0, 0, 0, 1],
                              [0, 0, 0, 0]], np.int32))

    def sweep(d):  # one Bellman-Ford relaxation toward vertex 0
        cand = jnp.min(jnp.where(m.T > 0, d[None, :] + 1, 10**6), axis=1)
        return jnp.minimum(d, cand).at[0].set(0)

    d0 = jnp.full(4, 10**6, jnp.int32).at[0].set(0)
    # manual reference loop
    d, sweeps = d0, 0
    while True:
        nd = sweep(d)
        sweeps += 1
        if bool(jnp.all(nd == d)):
            break
        d = nd
    for chunk in (1, 2, 4):
        got, nsweeps = engine.run_to_fixpoint(sweep, d0, cap=10,
                                              chunk=chunk)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(d))
        assert int(nsweeps) == sweeps


def test_normalize_chunk():
    assert engine.normalize_chunk(None) == engine.DEFAULT_CHUNK
    assert engine.normalize_chunk(7) == 7
    assert engine.normalize_chunk(None, budget=2) == 2
    assert engine.normalize_chunk(8, budget=3) == 3
    assert engine.normalize_chunk(None, budget=0) == 1


# -- ported loops: chunked == per-step, single and batched ------------------

def _prepped(mode, layout="bcsr", n=40, m=160, seed=3):
    adj, s, t = G.random_sparse(n, m, seed=seed)
    r = build_residual(adj, layout)
    g, meta, res0 = pr.to_device(r)
    state = pr.preflow(g, meta, res0, s)
    state, _, _ = globalrelabel.global_relabel(g, meta, state, s, t)
    return g, meta, state, s, t


@pytest.mark.parametrize("mode,layout", [
    ("vc", "bcsr"), ("vc", "rcsr"), ("tc", "bcsr"),
    ("vc_kernel", "bcsr"), ("vc_kernel_bsearch", "bcsr"),
])
def test_run_cycles_chunk_invariant(mode, layout):
    """chunk=1 runs the engine's bare while_loop path — the pre-engine
    per-step trace; every other chunking must match it bit-for-bit."""
    g, meta, state, s, t = _prepped(mode, layout)
    ref_st, ref_cyc = pr.run_cycles(g, meta, state, s, t, mode=mode,
                                    max_cycles=64, chunk=1)
    for chunk in (3, 4):
        st_c, cyc_c = pr.run_cycles(g, meta, state, s, t, mode=mode,
                                    max_cycles=64, chunk=chunk)
        assert int(cyc_c) == int(ref_cyc)
        for a, b in zip((st_c.res, st_c.h, st_c.e),
                        (ref_st.res, ref_st.h, ref_st.e)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_cycles_telemetry_chunk_invariant():
    """The gate freezes telemetry history writes too: every counter and
    per-cycle history matches the per-step loop exactly."""
    g, meta, state, s, t = _prepped("vc")
    _, ref_cyc, ref_tel = pr.run_cycles(g, meta, state, s, t, mode="vc",
                                        max_cycles=48, chunk=1,
                                        telemetry=True)
    _, cyc, tel = pr.run_cycles(g, meta, state, s, t, mode="vc",
                                max_cycles=48, chunk=4, telemetry=True)
    assert int(cyc) == int(ref_cyc)
    for a, b in zip(jax.tree.leaves(tel), jax.tree.leaves(ref_tel)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("mode", ["vc", "vc_kernel"])
def test_batched_run_cycles_chunk_invariant_with_padding(mode):
    """Stacked (B, ...) states through the engine: live lanes and the
    trivial padded dummy lane all match the per-step loop bit-for-bit."""
    insts = []
    for seed in (1, 2):
        adj, s, t = G.random_sparse(28, 100, seed=seed)
        insts.append((build_residual(adj, "bcsr"), s, t))
    insts.append((insts[0][0], 0, 0))  # padded dummy lane (s == t)
    bg, meta, res0, trivial = batched.pack_instances(insts)
    state = batched.batched_preflow(bg, meta, res0)
    state, _, _ = batched.batched_global_relabel(bg, meta, state)
    ref_st, ref_cyc = batched.batched_run_cycles(
        bg, meta, state, mode=mode, max_cycles=64, chunk=1)
    got_st, got_cyc = batched.batched_run_cycles(
        bg, meta, state, mode=mode, max_cycles=64, chunk=4)
    np.testing.assert_array_equal(np.asarray(got_cyc), np.asarray(ref_cyc))
    for a, b in zip((got_st.res, got_st.h, got_st.e),
                    (ref_st.res, ref_st.h, ref_st.e)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_global_relabel_and_solve_chunk_invariant():
    """End-to-end: whole solves agree across scan chunkings, every
    backend knob at its default."""
    adj, s, t = G.random_sparse(36, 150, seed=11)
    p = MaxflowProblem(adj, s, t)
    base = Solver(SolverOptions(scan_chunk=1)).solve(p)
    for chunk in (3, None):
        sol = Solver(SolverOptions(scan_chunk=chunk)).solve(p)
        assert sol.value == base.value
        assert sol.stats.cycles == base.stats.cycles
        assert sol.stats.gr_sweeps == base.stats.gr_sweeps


# -- trace-shape assertions: ONE scanned body per steady-state chunk --------
# (the walker lives in repro.analysis.ir — shared with the analyzer CLI)

_loop_counts = ir.loop_counts


@pytest.mark.parametrize("mode", ["vc", "vc_kernel", "vc_kernel_bsearch"])
def test_run_cycles_steady_state_is_one_scanned_body(mode):
    """The cycle loop compiles to ONE outer while over ONE scanned chunk
    body — not max_cycles step replicas; kernel modes hold one
    pallas_call per kernel inside it (two for the reverse-arc search).  ('tc' is excluded: its
    per-arc segment scan is itself a fori_loop and lowers to a second,
    step-internal scan.)"""
    g, meta, state, s, t = _prepped(mode)
    nwhile, nscan, npallas = _loop_counts(
        lambda res, h, e: pr.run_cycles(g, meta, pr.PRState(res, h, e),
                                        s, t, mode=mode, max_cycles=64),
        state.res, state.h, state.e)
    assert nwhile == 1, f"expected one outer while, saw {nwhile}"
    assert nscan == 1, f"expected one scanned chunk body, saw {nscan}"
    want = {"vc": 0, "vc_kernel": 1, "vc_kernel_bsearch": 2}[mode]
    assert npallas == want, f"expected {want} pallas_calls, saw {npallas}"


def test_batched_run_cycles_steady_state_is_one_scanned_body():
    insts = [(build_residual(G.random_sparse(20, 70, seed=i)[0], "bcsr"),
              0, 19) for i in (1, 2)]
    bg, meta, res0, _ = batched.pack_instances(insts)
    state = batched.batched_preflow(bg, meta, res0)
    nwhile, nscan, npallas = _loop_counts(
        lambda st: batched.batched_run_cycles(bg, meta, st,
                                              mode="vc_kernel",
                                              max_cycles=64), state)
    assert (nwhile, nscan) == (1, 1), (nwhile, nscan)
    # ONE batch-grid launch spans the whole (B, ...) stack per sweep step
    assert npallas == 1, npallas


def test_no_per_module_loop_shells_remain():
    """The refactor's gate, now AST-level: every bulk-synchronous device
    loop runs through repro.core.engine — no module-local
    ``lax.while_loop``/``lax.scan`` shells are left anywhere in solver
    code (repro.analysis.lint scopes the rule; this subsumes the
    historical per-file grep)."""
    findings = [f for f in lint.run_lint(SRC.parents[1], subdirs=("src",))
                if f.rule == "loop-shell"]
    assert not findings, "\n".join(map(str, findings))


# -- exact max_cycles budgets ------------------------------------------------

def test_run_cycles_budget_not_multiple_of_chunk():
    """A traced budget that is not a multiple of the scan chunk is honored
    to the cycle: no overrun into the gated chunk tail."""
    g, meta, state, s, t = _prepped("vc", n=60, m=260, seed=5)
    full_st, full_cyc = pr.run_cycles(g, meta, state, s, t, mode="vc",
                                      max_cycles=256, chunk=4)
    assert int(full_cyc) > 7  # needs enough work to hit the cap
    st7, cyc7 = pr.run_cycles(g, meta, state, s, t, mode="vc",
                              max_cycles=256, budget=jnp.int32(7), chunk=4)
    assert int(cyc7) == 7
    ref_st, ref_cyc = pr.run_cycles(g, meta, state, s, t, mode="vc",
                                    max_cycles=7, chunk=1)
    assert int(ref_cyc) == 7
    for a, b in zip((st7.res, st7.h, st7.e),
                    (ref_st.res, ref_st.h, ref_st.e)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_max_cycles_exhaustion_raises_single_and_batched():
    """An unconvergeable off-cadence budget raises on both drivers."""
    adj, s, t = G.random_sparse(60, 260, seed=5)
    p = MaxflowProblem(adj, s, t)
    for backend in ("single", "batched"):
        with pytest.raises(RuntimeError, match="max_cycles"):
            Solver(SolverOptions(backend=backend, max_cycles=3,
                                 global_relabel_cadence=4)).solve(p)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10**6), st.integers(0, 2))
def test_max_cycles_exact_property(seed, extra):
    """``SolverOptions.max_cycles`` is exact even when it is not a
    multiple of ``cycle_chunk(n)``: a budget below the convergence cycle
    count raises, a budget at/above it converges with UNINFLATED cycle
    telemetry (the same count as the unbudgeted solve)."""
    rng = np.random.default_rng(seed)
    gph = random_graph(rng, n_lo=10, n_hi=24)
    p = MaxflowProblem(gph, 0, gph.n - 1)
    cadence = 4
    free = Solver(SolverOptions(global_relabel_cadence=cadence)).solve(p)
    need = free.stats.cycles
    if need < 2:
        return  # trivially-converging instance: nothing to budget
    # a non-multiple-of-cadence budget >= need: converges, count uninflated
    cap = need + extra
    if cap % cadence == 0:
        cap += 1
    sol = Solver(SolverOptions(global_relabel_cadence=cadence,
                               max_cycles=cap)).solve(p)
    assert sol.value == free.value
    assert sol.stats.cycles == need
    # a short budget either raises or converges EARLY (its truncated
    # dispatch triggers the next global relabel sooner, which can
    # genuinely finish the flow) — but it is never overrun
    short = need - 1 if (need - 1) % cadence or need == 2 else need - 2
    try:
        tight = Solver(SolverOptions(global_relabel_cadence=cadence,
                                     max_cycles=short)).solve(p)
    except RuntimeError as exc:
        assert "max_cycles" in str(exc)
    else:
        assert tight.value == free.value
        assert tight.stats.cycles <= short
