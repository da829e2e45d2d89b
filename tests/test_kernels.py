"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)
and batch-grid-axis parity."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import pushrelabel as pr
from repro.core.csr import build_residual
from repro.kernels import ref as kref
from repro.kernels.revsearch import bcsr_rev_search
from repro.kernels.segmin import tile_min_neighbor
from tests.conftest import random_graph


def _graph_state(rng, **kw):
    g = random_graph(rng, **kw)
    r = build_residual(g, "bcsr")
    dg, meta, res0 = pr.to_device(r)
    state = pr.preflow(dg, meta, res0, 0)
    h = jnp.asarray(rng.integers(0, meta.n + 2, size=meta.n), jnp.int32)
    return r, dg, meta, pr.PRState(res=state.res, h=h, e=state.e)


@pytest.mark.parametrize("trial", range(4))
def test_segmin_matches_ref(trial):
    rng = np.random.default_rng(trial)
    r, dg, meta, state = _graph_state(rng)
    act = pr.active_mask(state, meta.n, 0, meta.n - 1)
    avq = jnp.nonzero(act, size=meta.n, fill_value=meta.n)[0].astype(jnp.int32)
    key = jnp.where(state.res > 0, state.h[dg.heads],
                    kref.INF).astype(jnp.int32)
    km, ka = tile_min_neighbor(avq, dg.indptr, key, n=meta.n)
    rm, ra = kref.min_neighbor_ref(avq, dg.indptr, key, n=meta.n)
    np.testing.assert_array_equal(np.asarray(km), np.asarray(rm))
    np.testing.assert_array_equal(np.asarray(ka), np.asarray(ra))


def test_segmin_empty_avq():
    rng = np.random.default_rng(3)
    r, dg, meta, state = _graph_state(rng)
    avq = jnp.full(meta.n, meta.n, jnp.int32)  # nothing active
    key = jnp.full(meta.num_arcs, kref.INF, jnp.int32)
    km, ka = tile_min_neighbor(avq, dg.indptr, key, n=meta.n)
    assert np.all(np.asarray(km) == int(kref.INF))


@pytest.mark.parametrize("n", [600, 5000])
def test_segmin_large_degree_vertex(n):
    """Star graph: one vertex with degree >> 128 exercises the row loop;
    at 5000 its window spans several VMEM window refills."""
    from repro.core.csr import Graph
    edges = np.array([[0, i] for i in range(1, n)], np.int64)
    g = Graph(n, edges, np.ones(n - 1, np.int64))
    r = build_residual(g, "bcsr")
    dg, meta, _ = pr.to_device(r)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.integers(0, 8, size=n), jnp.int32)
    res = jnp.asarray(rng.integers(0, 2, size=meta.num_arcs), jnp.int32)
    key = jnp.where(res > 0, h[dg.heads], kref.INF).astype(jnp.int32)
    avq = jnp.concatenate([jnp.zeros(1, jnp.int32),
                           jnp.full(n - 1, n, jnp.int32)])
    km, ka = tile_min_neighbor(avq, dg.indptr, key, n=n)
    rm, ra = kref.min_neighbor_ref(avq, dg.indptr, key, n=n)
    np.testing.assert_array_equal(np.asarray(km), np.asarray(rm))
    np.testing.assert_array_equal(np.asarray(ka), np.asarray(ra))


@pytest.mark.parametrize("trial", range(4))
def test_revsearch_matches_rev_table(trial):
    rng = np.random.default_rng(100 + trial)
    r, dg, meta, _ = _graph_state(rng)
    a = meta.num_arcs
    arcs = jnp.asarray(rng.integers(0, a + 4, size=2 * a), jnp.int32)
    got = bcsr_rev_search(arcs, dg.indptr, dg.heads, dg.tails)
    want = kref.rev_search_ref(arcs, dg.rev, a)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_modes_end_to_end(rng):
    from repro.api import MaxflowProblem, Solver
    from repro.core.ref_maxflow import dinic_maxflow
    g = random_graph(rng, n_lo=8, n_hi=20)
    want = dinic_maxflow(g, 0, g.n - 1)
    problem = MaxflowProblem(g, 0, g.n - 1)
    for mode in ("vc_kernel", "vc_kernel_bsearch"):
        assert Solver(mode=mode).solve(problem).value == want


@settings(max_examples=5, deadline=None)  # capped for tier-1 wall clock
@given(st.integers(0, 10_000))
def test_property_segmin(seed):
    rng = np.random.default_rng(seed)
    r, dg, meta, state = _graph_state(rng, n_lo=4, n_hi=25)
    act = pr.active_mask(state, meta.n, 0, meta.n - 1)
    avq = jnp.nonzero(act, size=meta.n, fill_value=meta.n)[0].astype(jnp.int32)
    key = jnp.where(state.res > 0, state.h[dg.heads],
                    kref.INF).astype(jnp.int32)
    km, ka = tile_min_neighbor(avq, dg.indptr, key, n=meta.n)
    rm, ra = kref.min_neighbor_ref(avq, dg.indptr, key, n=meta.n)
    np.testing.assert_array_equal(np.asarray(km), np.asarray(rm))
    np.testing.assert_array_equal(np.asarray(ka), np.asarray(ra))


def test_segmin_sentinel_matches_flat_frontier():
    """Every min-search path uses the one ``(INF, A)`` sentinel pair for
    'no eligible arc', so downstream consumers compare against a single
    value."""
    rng = np.random.default_rng(11)
    r, dg, meta, state = _graph_state(rng)
    avq = jnp.concatenate([jnp.zeros(1, jnp.int32),
                           jnp.full(meta.n - 1, meta.n, jnp.int32)])
    key = jnp.full(meta.num_arcs, kref.INF, jnp.int32)  # nothing eligible
    _, ka = tile_min_neighbor(avq, dg.indptr, key, n=meta.n)
    _, ra = kref.min_neighbor_ref(avq, dg.indptr, key, n=meta.n)
    st0 = pr.PRState(res=jnp.zeros_like(state.res), h=state.h, e=state.e)
    fm, fa = pr._flat_frontier_minh(dg, meta, st0, avq, avq < meta.n)
    assert int(ka[0]) == meta.num_arcs
    assert int(ra[0]) == meta.num_arcs
    assert int(fa[0]) == meta.num_arcs and int(fm[0]) == int(kref.INF)


def test_minh_paths_bitwise_identical():
    """All three min-search paths — flat-frontier XLA, tile kernel, pure
    oracle — agree bitwise on BOTH outputs, including the sentinel lanes
    (inactive rows, empty segments, all-INF keys)."""
    rng = np.random.default_rng(12)
    for _ in range(3):
        r, dg, meta, state = _graph_state(rng)
        act = pr.active_mask(state, meta.n, 0, meta.n - 1)
        avq = jnp.nonzero(act, size=meta.n,
                          fill_value=meta.n)[0].astype(jnp.int32)
        q_valid = avq < meta.n
        fm, fa = pr._flat_frontier_minh(dg, meta, state, avq, q_valid)
        key = jnp.where(state.res > 0, state.h[dg.heads],
                        kref.INF).astype(jnp.int32)
        km, ka = tile_min_neighbor(avq, dg.indptr, key, n=meta.n)
        rm, ra = kref.min_neighbor_ref(avq, dg.indptr, key, n=meta.n)
        for got_m, got_a in ((fm, fa), (km, ka)):
            np.testing.assert_array_equal(np.asarray(got_m), np.asarray(rm))
            np.testing.assert_array_equal(np.asarray(got_a), np.asarray(ra))


# -- batch grid axis --------------------------------------------------------

def _batched_fixture(rng, b=3):
    from repro.core import batched

    insts = []
    for _ in range(b):
        g = random_graph(rng, n_lo=6, n_hi=25)
        insts.append((build_residual(g, "bcsr"), 0, g.n - 1))
    bg, meta, res0, _ = batched.pack_instances(insts)
    state = batched.batched_preflow(bg, meta, res0)
    return bg, meta, state


def test_segmin_batch_axis_matches_single_rows():
    """(B, ...) inputs run one launch with a leading batch grid dim; every
    row equals the single-instance kernel on that row."""
    rng = np.random.default_rng(21)
    bg, meta, state = _batched_fixture(rng)
    n, b = meta.n, bg.batch
    h = jnp.asarray(rng.integers(0, n + 2, size=(b, n)), jnp.int32)
    key = jnp.where(
        state.res > 0,
        jnp.take_along_axis(h, jnp.clip(bg.heads, 0, n - 1), axis=1),
        kref.INF).astype(jnp.int32)
    avq = jnp.stack([
        jnp.nonzero(state.e[i] > 0, size=n, fill_value=n)[0].astype(jnp.int32)
        for i in range(b)])
    bm, ba = tile_min_neighbor(avq, bg.indptr, key, n=n)
    assert bm.shape == (b, n)
    for i in range(b):
        sm, sa = tile_min_neighbor(avq[i], bg.indptr[i], key[i], n=n)
        rm, ra = kref.min_neighbor_ref(avq[i], bg.indptr[i], key[i], n=n)
        np.testing.assert_array_equal(np.asarray(bm[i]), np.asarray(sm))
        np.testing.assert_array_equal(np.asarray(ba[i]), np.asarray(sa))
        np.testing.assert_array_equal(np.asarray(sm), np.asarray(rm))
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(ra))


def test_revsearch_batch_axis_matches_single_rows():
    rng = np.random.default_rng(22)
    bg, meta, _ = _batched_fixture(rng)
    a, b = meta.num_arcs, bg.batch
    # true arcs and the >= A sentinel only: padded self-loop arcs are
    # unfindable by construction (empty segments) and never pushed
    arcs = jnp.asarray(rng.integers(0, a + 4, size=(b, 2 * a)), jnp.int32)
    arcs = jnp.where(arcs < bg.num_arcs[:, None], arcs, jnp.int32(a))
    got = bcsr_rev_search(arcs, bg.indptr, bg.heads, bg.tails)
    assert got.shape == arcs.shape
    for i in range(b):
        single = bcsr_rev_search(arcs[i], bg.indptr[i], bg.heads[i],
                                 bg.tails[i])
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(single))
        want = kref.rev_search_ref(arcs[i], bg.rev[i], a)
        np.testing.assert_array_equal(np.asarray(single), np.asarray(want))


def test_segmin_dense_matches_arange_avq():
    """``avq=None`` (the sweep form: every vertex its own entry, no AVQ
    array) is bit-for-bit ``avq == arange(n)``, single and batched."""
    rng = np.random.default_rng(23)
    bg, meta, state = _batched_fixture(rng)
    n, b = meta.n, bg.batch
    key = jnp.where(
        state.res > 0,
        jnp.take_along_axis(state.h, jnp.clip(bg.heads, 0, n - 1), axis=1),
        kref.INF).astype(jnp.int32)
    avq = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    em, ea = tile_min_neighbor(avq, bg.indptr, key, n=n)
    dm, da = tile_min_neighbor(None, bg.indptr, key, n=n)
    np.testing.assert_array_equal(np.asarray(em), np.asarray(dm))
    np.testing.assert_array_equal(np.asarray(ea), np.asarray(da))
    sm, sa = tile_min_neighbor(None, bg.indptr[0], key[0], n=n)
    np.testing.assert_array_equal(np.asarray(sm), np.asarray(dm[0]))
    np.testing.assert_array_equal(np.asarray(sa), np.asarray(da[0]))


def test_kernels_span_several_tiles():
    """Queues longer than one tile of entries: tiles past a compacted
    AVQ's valid prefix only keep their sentinels, and every tile streams
    its windows through its own VMEM refills."""
    from repro.graphs import generators as G

    rng = np.random.default_rng(24)
    g0, _, _ = G.random_sparse(2500, 9000, seed=5)
    r = build_residual(g0, "bcsr")
    dg, meta, res0 = pr.to_device(r)
    n, a = meta.n, meta.num_arcs
    h = jnp.asarray(rng.integers(0, n, size=n), jnp.int32)
    key = jnp.where(res0 > 0, h[dg.heads], kref.INF).astype(jnp.int32)
    some = np.sort(rng.choice(n, size=1100, replace=False))
    avq = jnp.asarray(np.concatenate([some, np.full(n - 1100, n)]),
                      jnp.int32)
    for q in (avq, jnp.arange(n, dtype=jnp.int32)):
        km, ka = tile_min_neighbor(q, dg.indptr, key, n=n)
        rm, ra = kref.min_neighbor_ref(q, dg.indptr, key, n=n)
        np.testing.assert_array_equal(np.asarray(km), np.asarray(rm))
        np.testing.assert_array_equal(np.asarray(ka), np.asarray(ra))
    dm, da = tile_min_neighbor(None, dg.indptr, key, n=n)
    np.testing.assert_array_equal(np.asarray(dm), np.asarray(rm))
    np.testing.assert_array_equal(np.asarray(da), np.asarray(ra))
    arcs = jnp.asarray(rng.integers(0, a + 4, size=3000), jnp.int32)
    got = bcsr_rev_search(arcs, dg.indptr, dg.heads, dg.tails)
    want = kref.rev_search_ref(arcs, dg.rev, a)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _device_instance(rng, **kw):
    g0 = random_graph(rng, **kw)
    r = build_residual(g0, "bcsr")
    g, meta, res0 = pr.to_device(r)
    return g, meta, res0


# -- shared minh_fn hook routing -------------------------------------------

def test_global_relabel_kernel_minh_parity():
    """Bellman-Ford distance sweeps through the tile kernel == XLA
    segment_min sweeps, exactly."""
    from repro.core import globalrelabel
    from repro.kernels import ops as kops

    rng = np.random.default_rng(41)
    g, meta, res0 = _device_instance(rng)
    state = pr.preflow(g, meta, res0, 0)
    t = meta.n - 1
    d0, s0 = globalrelabel.residual_distances_impl(g, meta, state.res, t)
    d1, s1 = globalrelabel.residual_distances_impl(
        g, meta, state.res, t, minh_fn=kops.min_neighbor_minh_fn(None))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    assert int(s0) == int(s1)


def test_kernel_mode_handle_corrects_via_kernel():
    """Kernel solve modes hand out handles whose lazy phase-2 correction
    runs on the tile kernel — and the corrected flows equal the XLA
    handle's exactly."""
    from repro.api import MaxflowProblem, Solver

    rng = np.random.default_rng(43)
    g = random_graph(rng, n_lo=10, n_hi=24)
    p = MaxflowProblem(g, 0, g.n - 1)
    s_xla = Solver(mode="vc").solve(p)
    s_knl = Solver(mode="vc_kernel").solve(p)
    assert s_knl.warm_start._use_kernel
    assert not s_xla.warm_start._use_kernel
    np.testing.assert_array_equal(s_xla.flows(), s_knl.flows())


def test_phase2_kernel_minh_parity():
    """Phase-2 cancellation through the tile kernel selector is bit-for-bit
    the flat-frontier selector (both pick the smallest argmin arc)."""
    rng = np.random.default_rng(42)
    g0 = random_graph(rng, n_lo=10, n_hi=30)
    r = build_residual(g0, "bcsr")
    stats = pr.solve_impl(r, 0, g0.n - 1)
    res_xla = pr.convert_preflow_to_flow(r, stats.state, 0, g0.n - 1)
    res_knl = pr.convert_preflow_to_flow(r, stats.state, 0, g0.n - 1,
                                         use_kernel=True)
    np.testing.assert_array_equal(res_xla, res_knl)
