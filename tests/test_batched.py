"""Batched multi-instance solver core: equivalence with sequential
solves, padding invariants, and warm-started re-solves.  (Facade-level
equivalence is covered in tests/test_api.py.)"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import batched
from repro.core import pushrelabel as pr
from repro.core.csr import Graph, build_residual
from repro.core.ref_maxflow import dinic_maxflow
from tests.conftest import random_graph


def _random_instances(rng, k, layout):
    out = []
    for _ in range(k):
        g = random_graph(rng, n_lo=5, n_hi=30)
        out.append((build_residual(g, layout), 0, g.n - 1))
    return out


@pytest.mark.parametrize("layout", ["rcsr", "bcsr"])
@pytest.mark.parametrize("mode", ["vc", "tc"])
def test_batched_matches_sequential(layout, mode, rng):
    """One vmapped batch of K graphs == K sequential solve() calls."""
    insts = _random_instances(rng, 4, layout)  # capped for tier-1 wall clock
    want = [pr.solve_impl(r, s, t, mode=mode).maxflow for r, s, t in insts]
    out = batched.batched_solve_impl(insts, mode=mode)
    assert out.maxflows.tolist() == want
    assert out.converged.all()


@settings(max_examples=4, deadline=None)  # capped: each example is
# k full solves twice; 4 seeds x up to 5 instances keeps the property
# honest at a quarter of the wall clock
@given(st.integers(0, 10**6), st.integers(1, 5))
def test_batched_matches_sequential_property(seed, k):
    rng = np.random.default_rng(seed)
    insts = _random_instances(rng, k, "bcsr")
    want = [pr.solve_impl(r, s, t).maxflow for r, s, t in insts]
    got = batched.batched_solve_impl(insts).maxflows.tolist()
    assert got == want


def test_heterogeneous_shapes_one_batch(rng):
    """Instances of very different sizes pad into one batch correctly."""
    gs = [Graph(3, np.array([[0, 1], [1, 2]], np.int64),
                np.array([4, 2], np.int64)),
          random_graph(rng, n_lo=25, n_hi=30),
          random_graph(rng, n_lo=5, n_hi=8)]
    insts = [(build_residual(g, "bcsr"), 0, g.n - 1) for g in gs]
    want = [dinic_maxflow(g, 0, g.n - 1) for g in gs]
    assert batched.batched_solve_impl(insts).maxflows.tolist() == want


def test_trivial_instances_in_batch(rng):
    """s == t and empty graphs are forced to flow 0, not garbage."""
    g = random_graph(rng)
    r = build_residual(g, "bcsr")
    insts = [(r, 0, 0),  # s == t -> trivial
             (r, 0, g.n - 1),
             (build_residual(Graph(2, np.zeros((0, 2), np.int64),
                                   np.zeros(0, np.int64)), "bcsr"), 0, 1)]
    out = batched.batched_solve_impl(insts)
    assert out.maxflows[0] == 0
    assert out.maxflows[1] == pr.solve_impl(r, 0, g.n - 1).maxflow
    assert out.maxflows[2] == 0
    assert out.trivial.tolist() == [True, False, True]


def test_per_instance_convergence_flags(rng):
    """An early-converging instance stops accruing cycles while harder
    batchmates keep iterating."""
    easy = Graph(2, np.array([[0, 1]], np.int64), np.array([5], np.int64))
    hard = random_graph(rng, n_lo=30, n_hi=40)
    insts = [(build_residual(easy, "bcsr"), 0, 1),
             (build_residual(hard, "bcsr"), 0, hard.n - 1)]
    out = batched.batched_solve_impl(insts, cycle_chunk=8)
    assert out.converged.all()
    assert out.cycles[0] <= out.cycles[1]


def _warm_resolve(r2, res_upd, e_prev, s, t, budget):
    w = batched.warm_start_arrays(r2, res_upd, e_prev, s, budget=budget)
    bg, meta, _, triv = batched.pack_instances([(r2, s, t)])
    state0 = batched.pack_states([w], meta.n, meta.num_arcs)
    return batched.batched_resolve(bg, meta, state0, trivial=triv)


def test_warm_start_matches_cold_after_increase():
    """Bottleneck raise: the warm re-solve must find the larger flow."""
    edges = np.array([[0, 1], [1, 2], [2, 3]], np.int64)
    g = Graph(4, edges, np.array([10, 3, 10], np.int64))
    r = build_residual(g, "bcsr")
    cold = pr.solve_impl(r, 0, 3)
    assert cold.maxflow == 3
    updates = [(1, 2, 5)]
    r2, res_upd = batched.apply_capacity_increases(
        r, np.asarray(cold.state.res), updates)
    e_prev = np.asarray(cold.state.e)
    out = _warm_resolve(r2, res_upd, e_prev, 0, 3, budget=5)
    assert int(out.maxflows[0]) == pr.solve_impl(r2, 0, 3).maxflow == 8


@settings(max_examples=6, deadline=None)  # capped for tier-1 wall clock
@given(st.integers(0, 10**6))
def test_warm_start_matches_cold_property(seed):
    """Random graph + random capacity increases: warm == cold value.

    The warm start enters from the *phase-2 corrected* final state (a
    genuine max flow) with injection budgeted by the update total — the
    serving path's exact recipe."""
    rng = np.random.default_rng(seed)
    g = random_graph(rng, n_lo=8, n_hi=25)
    s, t = 0, g.n - 1
    r = build_residual(g, "bcsr")
    cold = pr.solve_impl(r, s, t)
    flow_res = pr.convert_preflow_to_flow(r, cold.state, s, t)
    e = np.zeros(r.n, np.int64)
    e[t] = cold.maxflow
    k = int(rng.integers(1, 4))
    fwd = np.where(r.res0 > 0)[0]
    if fwd.size == 0:
        return
    picks = rng.choice(fwd, size=min(k, fwd.size), replace=False)
    updates = [(int(r.tails[a]), int(r.heads[a]), int(rng.integers(1, 9)))
               for a in picks]
    r2, res_upd = batched.apply_capacity_increases(r, flow_res, updates)
    budget = sum(d for _, _, d in updates)
    out = _warm_resolve(r2, res_upd, e, s, t, budget)
    want = pr.solve_impl(r2, s, t).maxflow
    assert int(out.maxflows[0]) == want


def test_capacity_decrease_and_missing_arc_rejected():
    g = Graph(3, np.array([[0, 1]], np.int64), np.array([5], np.int64))
    r = build_residual(g, "bcsr")
    with pytest.raises(ValueError):
        batched.apply_capacity_increases(r, r.res0.copy(), [(0, 1, -2)])
    with pytest.raises(KeyError):  # no 0-2 pair in the graph
        batched.apply_capacity_increases(r, r.res0.copy(), [(0, 2, 3)])


def test_unknown_mode_rejected_in_batch(rng):
    g = random_graph(rng)
    insts = [(build_residual(g, "bcsr"), 0, g.n - 1)]
    with pytest.raises(ValueError, match="batched mode"):
        batched.batched_solve_impl(insts, mode="warp")


def test_bsearch_mode_needs_sorted_segments(rng):
    g = random_graph(rng)
    insts = [(build_residual(g, "rcsr"), 0, g.n - 1)]
    with pytest.raises(ValueError, match="head-sorted"):
        batched.batched_solve_impl(insts, mode="vc_kernel_bsearch")
    # the guard also holds at the shared depth (warm resolves and the
    # serving flush enter through batched_resolve, not batched_solve_impl)
    bg, meta, res0, trivial = batched.pack_instances(insts)
    assert meta.layout == "batched"  # not head-sorted
    state = batched.batched_preflow(bg, meta, res0)
    with pytest.raises(ValueError, match="head-sorted"):
        batched.batched_resolve(bg, meta, state, trivial=trivial,
                                mode="vc_kernel_bsearch")


def test_pack_states_raises_on_lossy_cast():
    """int64 staging arrays whose values exceed the int32 state dtype must
    raise, not silently wrap (large-capacity serving instances)."""
    big = np.array([2**40, 1], np.int64)
    ok = np.zeros(2, np.int64)
    with pytest.raises(OverflowError, match="int32"):
        batched.pack_states([(big, ok, ok)], 2, 2)
    with pytest.raises(OverflowError, match="int32"):
        batched.pack_states([(ok[:2], ok, -big)], 2, 2)
    # in-range wider dtypes are narrowed losslessly
    st = batched.pack_states([(ok, ok, ok)], 2, 2)
    assert st.res.dtype == np.int32


def test_warm_start_arrays_raise_on_overflow():
    g = Graph(3, np.array([[0, 1], [1, 2]], np.int64),
              np.array([5, 5], np.int64))
    r = build_residual(g, "bcsr")
    res = r.res0.astype(np.int64)
    res[0] = 2**35  # a residual occupancy beyond the state dtype
    with pytest.raises(OverflowError, match="int32"):
        batched.warm_start_arrays(r, res, np.zeros(3, np.int64), 0)


# -- pooled sweeps: batch-level global relabel / phase 2 --------------------

def _vmapped_global_relabel_reference(bg, meta, state):
    """The pre-batch-grid formulation: per-instance global relabel vmapped
    over the batch — the bit-for-bit oracle for the batch-level sweeps."""
    import jax

    from repro.core import globalrelabel as gr

    def one(indptr, heads, tails, rev, res, h, e, s, t):
        g = pr.DeviceGraph(indptr, heads, tails, rev)
        st, nact, _ = gr.global_relabel_impl(g, meta, pr.PRState(res, h, e),
                                             s, t)
        return st.res, st.h, st.e, nact

    res, h, e, nact = jax.vmap(one)(bg.indptr, bg.heads, bg.tails, bg.rev,
                                    *state, bg.s, bg.t)
    return batched.BatchedPRState(res=res, h=h, e=e), nact


def _vmapped_phase2_reference(bg, meta, res0, state):
    import jax

    from repro.core import phase2 as p2

    def one(indptr, heads, tails, rev, r0, res, h, e, s, t):
        g = pr.DeviceGraph(indptr, heads, tails, rev)
        return p2.phase2_impl(g, meta, r0, res, e, s, t)[:3]

    return jax.vmap(one)(bg.indptr, bg.heads, bg.tails, bg.rev, res0,
                         *state, bg.s, bg.t)


def _packed_with_padding(rng, layout, k=3):
    """A pack with padded dummy lanes: explicit oversize (n_pad, A_pad)
    plus a trivial s == t instance, so inert lanes are exercised."""
    insts = _random_instances(rng, k, layout)
    insts.append((insts[0][0], 0, 0))  # trivial lane
    n_pad = max(r.n for r, _, _ in insts) + 7
    A_pad = max(r.num_arcs for r, _, _ in insts) + 13
    return batched.pack_instances(insts, n_pad=n_pad, A_pad=A_pad)


@pytest.mark.parametrize("layout", ["bcsr", "rcsr"])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_batched_global_relabel_matches_vmapped(layout, use_kernel, rng):
    """The batch-level distance sweeps (XLA and batch-grid kernel) are
    bit-for-bit the vmapped per-instance global relabel, including padded
    dummy lanes."""
    bg, meta, res0, _ = _packed_with_padding(rng, layout)
    state = batched.batched_preflow(bg, meta, res0)
    want, want_nact = _vmapped_global_relabel_reference(bg, meta, state)
    minh_fn = None
    if use_kernel:
        from repro.kernels import ops as kops
        minh_fn = kops.min_neighbor_minh_fn(None)
    got, nact, _ = batched.batched_global_relabel(bg, meta, state,
                                                  minh_fn=minh_fn)
    np.testing.assert_array_equal(np.asarray(got.res), np.asarray(want.res))
    np.testing.assert_array_equal(np.asarray(got.h), np.asarray(want.h))
    np.testing.assert_array_equal(np.asarray(got.e), np.asarray(want.e))
    np.testing.assert_array_equal(np.asarray(nact), np.asarray(want_nact))


@pytest.mark.parametrize("layout", ["bcsr", "rcsr"])
@pytest.mark.parametrize("selector", ["flat", "scan", "kernel"])
def test_batched_phase2_matches_vmapped(layout, selector, rng):
    """The batch-level phase 2 equals the vmapped per-instance
    decomposition bit-for-bit, padded dummy lanes included: with XLA
    height sweeps ('flat'), on the preflow of the thread-centric scan
    mode ('scan': other stranded excess, the same drain), and with the
    height sweeps on the batch-grid kernel ('kernel')."""
    bg, meta, res0, triv = _packed_with_padding(rng, layout)
    state = batched.batched_preflow(bg, meta, res0)
    out = batched.batched_resolve(bg, meta, state, trivial=triv,
                                  mode="tc" if selector == "scan" else "vc")
    want_res, want_e, want_left = _vmapped_phase2_reference(
        bg, meta, res0, out.state)
    kw = {}
    if selector == "kernel":
        from repro.kernels import ops as kops
        kw["minh_fn"] = kops.min_neighbor_minh_fn(None)
    got, left = batched.batched_phase2(bg, meta, res0, out.state, **kw)
    batched.check_phase2_leftover(left)
    np.testing.assert_array_equal(np.asarray(got.res), np.asarray(want_res))
    np.testing.assert_array_equal(np.asarray(got.e), np.asarray(want_e))
    np.testing.assert_array_equal(np.asarray(left), np.asarray(want_left))


def test_batched_sweeps_one_pallas_call_per_step(rng):
    """The jaxpr-level contract: under the kernel hook the pooled sweeps
    lower to exactly ONE batch-grid ``pallas_call`` per sweep step —
    one in the global-relabel loop body, one for phase 2 (its height
    sweep; the cancellation selects no arc) — and to zero without it."""
    from repro.analysis import ir
    from repro.kernels import ops as kops

    bg, meta, res0, _ = _packed_with_padding(rng, "bcsr")
    state = batched.batched_preflow(bg, meta, res0)
    hook = kops.min_neighbor_minh_fn(None)

    def pallas_calls(fn):
        return ir.primitive_count(fn, "pallas_call", state)

    assert pallas_calls(
        lambda st: batched.batched_global_relabel(bg, meta, st)) == 0
    assert pallas_calls(
        lambda st: batched.batched_global_relabel(
            bg, meta, st, minh_fn=hook)) == 1
    assert pallas_calls(
        lambda st: batched.batched_phase2(bg, meta, res0, st)) == 0
    assert pallas_calls(
        lambda st: batched.batched_phase2(
            bg, meta, res0, st, minh_fn=hook)) == 1


@pytest.mark.parametrize("mode,layout", [
    ("vc_kernel", "bcsr"), ("vc_kernel", "rcsr"),
    ("vc_kernel_bsearch", "bcsr"),
])
def test_batched_kernel_modes_match_vc(mode, layout, rng):
    """Bucketed microbatches through the batch-grid Pallas kernels: same
    maxflows as batched 'vc' and as per-instance single solves, and (for
    the tile modes, which share the flat-frontier selector semantics)
    bit-for-bit identical final states."""
    insts = _random_instances(rng, 5, layout)
    base = batched.batched_solve_impl(insts, mode="vc")
    single = [pr.solve_impl(r, s, t, mode="vc").maxflow for r, s, t in insts]
    out = batched.batched_solve_impl(insts, mode=mode)
    assert out.maxflows.tolist() == base.maxflows.tolist() == single
    assert out.converged.all()
    if mode == "vc_kernel":
        np.testing.assert_array_equal(np.asarray(out.state.res),
                                      np.asarray(base.state.res))
        np.testing.assert_array_equal(np.asarray(out.state.h),
                                      np.asarray(base.state.h))
        np.testing.assert_array_equal(np.asarray(out.state.e),
                                      np.asarray(base.state.e))
