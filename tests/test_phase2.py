"""Device-resident phase 2 (preflow -> flow decomposition) vs the host-BFS
oracle.

The corrected residual must be a *genuine* max flow: capacity-respecting,
conserving at every non-terminal vertex, and carrying ``value`` units
s -> t.  Where the flow decomposition is unique (tree-shaped flow
subgraphs; states with no stranded excess) the device result must match
the host oracle bit-for-bit; on general graphs phase 2 is only unique up
to the choice of cancellation paths, so there the two are compared on
every well-defined observable (validity, value, min cut) instead.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.api import MaxflowProblem, Solver
from repro.core import batched, mincut, phase2
from repro.core import pushrelabel as pr
from repro.core.csr import Graph, build_residual


def _random_messy_graph(rng, n_lo=5, n_hi=24):
    """Random graph with guaranteed parallel arcs and self-loops."""
    n = int(rng.integers(n_lo, n_hi))
    m = int(rng.integers(n, 5 * n))
    edges = rng.integers(0, n, size=(m, 2)).astype(np.int64)
    caps = rng.integers(1, 20, size=m).astype(np.int64)
    dup = edges[rng.integers(m, size=max(2, m // 4))]  # parallel duplicates
    loops = np.stack([v := rng.integers(0, n, size=2), v], axis=1)
    edges = np.concatenate([edges, dup, loops.astype(np.int64)])
    caps = np.concatenate(
        [caps, rng.integers(1, 20, size=len(dup) + 2).astype(np.int64)])
    return Graph(n, edges, caps)


def _assert_valid_flow(r, res, s, t, value):
    """res encodes a feasible s-t flow of the given value."""
    res = np.asarray(res)
    res0 = np.asarray(r.res0)
    rev = np.asarray(r.rev)
    assert (res >= 0).all(), "negative residual capacity"
    # pushes and cancellations conserve each arc-pair's total capacity
    np.testing.assert_array_equal(res + res[rev], res0 + res0[rev])
    f = res0 - res  # f[rev[a]] == -f[a]: each pair counted from both ends
    div = np.zeros(r.n, np.int64)
    np.add.at(div, np.asarray(r.tails), -f)
    np.add.at(div, np.asarray(r.heads), f)
    assert div[s] == -2 * value and div[t] == 2 * value
    inner = np.ones(r.n, bool)
    inner[[s, t]] = False
    assert not div[inner].any(), "conservation violated at inner vertices"


@pytest.mark.parametrize("layout", ["rcsr", "bcsr"])
@pytest.mark.parametrize("mode", ["vc", "tc"])
def test_device_phase2_matches_oracle(layout, mode, rng):
    """Across modes x layouts: device and host corrections are both valid
    flows of the same value with the same min cut; with no stranded
    excess they are bit-for-bit identical."""
    for trial in range(4):
        g = _random_messy_graph(rng)
        s, t = 0, g.n - 1
        r = build_residual(g, layout)
        stats = pr.solve_impl(r, s, t, mode=mode)
        res_dev = pr.convert_preflow_to_flow(r, stats.state, s, t)
        res_host = pr.convert_preflow_to_flow(r, stats.state, s, t,
                                              reference=True)
        _assert_valid_flow(r, res_dev, s, t, stats.maxflow)
        _assert_valid_flow(r, res_host, s, t, stats.maxflow)
        e = np.asarray(stats.state.e).copy()
        e[[s, t]] = 0
        if not e.any():  # no stranded excess: correction must be a no-op
            np.testing.assert_array_equal(res_dev, res_host)
            np.testing.assert_array_equal(res_dev,
                                          np.asarray(stats.state.res))
        for res in (res_dev, res_host):
            st_corr = pr.PRState(res=res, h=np.zeros(r.n, np.int32),
                                 e=np.asarray(stats.state.e))
            cut = mincut.min_cut(r, st_corr, s, t, corrected=True)
            assert cut.value == stats.maxflow


def _random_tree(rng, n):
    """Arcs parent->child of a random tree rooted at 0: every vertex has a
    single inbound arc, so the phase-2 decomposition is unique."""
    parents = [int(rng.integers(0, i)) for i in range(1, n)]
    edges = np.array([(p, i + 1) for i, p in enumerate(parents)], np.int64)
    caps = rng.integers(1, 20, size=n - 1).astype(np.int64)
    return Graph(n, edges, caps)


def test_tree_decomposition_bit_for_bit(rng):
    """Unique decomposition (single inbound arc per vertex): the device
    result must equal the host oracle exactly."""
    for trial in range(4):  # capped for tier-1 wall clock
        n = int(rng.integers(6, 20))
        g = _random_tree(rng, n)
        s, t = 0, n - 1
        for layout in ("bcsr", "rcsr"):
            r = build_residual(g, layout)
            stats = pr.solve_impl(r, s, t)
            res_dev = pr.convert_preflow_to_flow(r, stats.state, s, t)
            res_host = pr.convert_preflow_to_flow(r, stats.state, s, t,
                                                  reference=True)
            np.testing.assert_array_equal(res_dev, res_host)
            _assert_valid_flow(r, res_dev, s, t, stats.maxflow)


@settings(max_examples=6, deadline=None)  # capped for tier-1 wall clock
@given(st.integers(0, 10**6))
def test_phase2_property(seed):
    """Property: on arbitrary random graphs (parallel arcs, self-loops)
    the device correction is a feasible flow of the solver's value and
    agrees with the host oracle's value and flows()-divergence."""
    rng = np.random.default_rng(seed)
    g = _random_messy_graph(rng, n_lo=4, n_hi=16)
    s, t = 0, g.n - 1
    r = build_residual(g, "bcsr")
    stats = pr.solve_impl(r, s, t)
    res_dev = pr.convert_preflow_to_flow(r, stats.state, s, t)
    _assert_valid_flow(r, res_dev, s, t, stats.maxflow)
    res_host = pr.convert_preflow_to_flow(r, stats.state, s, t,
                                          reference=True)
    _assert_valid_flow(r, res_host, s, t, stats.maxflow)


def test_invalid_preflow_raises_without_assert():
    """Excess that is not flow-connected to the source must raise a real
    exception from both implementations (the old host ``assert`` vanished
    under ``python -O``)."""
    g = Graph(4, np.array([[0, 1], [2, 3]], np.int64),
              np.array([5, 5], np.int64))
    r = build_residual(g, "bcsr")
    e = np.zeros(4, np.int32)
    e[2] = 3  # vertex 2 receives no flow: nothing to cancel
    bad = pr.PRState(res=r.res0.astype(np.int32).copy(),
                     h=np.zeros(4, np.int32), e=e)
    with pytest.raises(RuntimeError, match="preflow"):
        pr.convert_preflow_to_flow(r, bad, 0, 3)
    with pytest.raises(RuntimeError, match="preflow"):
        pr.convert_preflow_to_flow(r, bad, 0, 3, reference=True)


def test_batched_phase2_matches_single_device(rng):
    """One batched dispatch corrects every instance exactly as the
    single-instance device path does (padding is inert)."""
    graphs = [_random_messy_graph(rng, n_lo=5, n_hi=14) for _ in range(3)]
    insts = [(build_residual(g, "bcsr"), 0, g.n - 1) for g in graphs]
    bg, meta, res0, trivial = batched.pack_instances(insts)
    state = batched.batched_preflow(bg, meta, res0)
    out = batched.batched_resolve(bg, meta, state, trivial=trivial)
    corrected, leftover = batched.batched_phase2(bg, meta, res0, out.state)
    batched.check_phase2_leftover(leftover)
    res_np = np.asarray(corrected.res)
    e_np = np.asarray(corrected.e)
    raw_res = np.asarray(out.state.res)
    raw_e = np.asarray(out.state.e)
    for i, (r, s, t) in enumerate(insts):
        single, _ = phase2.convert_preflow_to_flow_device(
            r, pr.PRState(res=raw_res[i, : r.num_arcs],
                          h=np.zeros(r.n, np.int32),
                          e=raw_e[i, : r.n]), s, t)
        np.testing.assert_array_equal(res_np[i, : r.num_arcs], single)
        _assert_valid_flow(r, res_np[i, : r.num_arcs], s, t,
                           int(out.maxflows[i]))
        # cleaned excess: zero everywhere but the sink
        want_e = np.zeros(r.n, np.int64)
        want_e[t] = out.maxflows[i]
        np.testing.assert_array_equal(e_np[i, : r.n], want_e)


def test_scan_selector_bit_for_bit(rng):
    """The cancellation selects no arc any more, so the serving pool's
    correction (which once asked for the thread-centric scan selector)
    runs the same drain as every other caller.  On preflows of the
    thread-centric scan mode ('tc'), the pooled correction equals the
    single-instance device path bit-for-bit, row by row, and both are
    flows of the solver's value."""
    graphs = [_random_messy_graph(rng, n_lo=5, n_hi=16) for _ in range(4)]
    insts = [(build_residual(g, "bcsr"), 0, g.n - 1) for g in graphs]
    bg, meta, res0, trivial = batched.pack_instances(insts)
    state = batched.batched_preflow(bg, meta, res0)
    out = batched.batched_resolve(bg, meta, state, trivial=trivial,
                                  mode="tc")
    pooled, left = batched.batched_phase2(bg, meta, res0, out.state)
    batched.check_phase2_leftover(left)
    raw_res = np.asarray(out.state.res)
    raw_e = np.asarray(out.state.e)
    for i, (r, s, t) in enumerate(insts):
        single, _ = phase2.convert_preflow_to_flow_device(
            r, pr.PRState(res=raw_res[i, : r.num_arcs],
                          h=np.zeros(r.n, np.int32),
                          e=raw_e[i, : r.n]), s, t)
        np.testing.assert_array_equal(
            np.asarray(pooled.res)[i, : r.num_arcs], single)
        _assert_valid_flow(r, single, s, t, int(out.maxflows[i]))


def _hand_preflow(r, flows, excess):
    """A preflow state set by hand: ``flows`` maps (u, v) to the flow on
    the arc u->v, ``excess`` maps vertices to their excess."""
    res = np.asarray(r.res0, np.int64).copy()
    for (u, v), f in flows.items():
        a = batched.find_arc(r, u, v)
        res[a] -= f
        res[r.rev[a]] += f
    e = np.zeros(r.n, np.int64)
    for v, x in excess.items():
        e[v] = x
    return pr.PRState(res=batched.as_state_dtype(res),
                      h=np.zeros(r.n, np.int32),
                      e=batched.as_state_dtype(e))


def _hub(k, caps):
    """s -> a_i -> h -> t with ``caps[i]`` on both arcs of a_i and a unit
    arc h -> t: s = 0, a_i = 1 + i, h = k + 1, t = k + 2."""
    s, h, t = 0, k + 1, k + 2
    edges = [(s, 1 + i) for i in range(k)] + [(1 + i, h) for i in range(k)]
    edges.append((h, t))
    cap = np.array(list(caps) * 2 + [1], np.int64)
    return Graph(k + 3, np.array(edges, np.int64), cap), s, h, t


@pytest.mark.parametrize("layout", ["bcsr", "rcsr"])
def test_hub_drains_in_one_step(layout):
    """A hub holding k - 1 stranded units over k unit inbound arcs drains
    in one pass of depth + 1 steps (heights 2: hub -> users -> s, and
    the step that finds nothing to move), not one unit a step, and the
    result is a flow of value 1."""
    k = 40
    g, s, h, t = _hub(k, [1] * k)
    r = build_residual(g, layout)
    flows = {(s, 1 + i): 1 for i in range(k)}
    flows.update({(1 + i, h): 1 for i in range(k)})
    flows[(h, t)] = 1
    state = _hand_preflow(r, flows, {h: k - 1, t: 1})
    res, stats = phase2.convert_preflow_to_flow_device(r, state, s, t)
    _assert_valid_flow(r, res, s, t, 1)
    assert stats.passes == 1
    assert stats.steps <= 2 + 1
    # k - 1 units cross one user arc each, then one source arc each
    assert stats.cancels == 2 * (k - 1)


def test_large_capacities_drain_exactly():
    """Capacities near 2**30: each hub holds nearly 2**31 units, so the
    global prefix sum of the drain wraps past int32 several times over,
    and the per-segment difference still returns every unit exactly."""
    hubs, k = 3, 2
    caps = [2**30 - 1, 2**30 - 2]
    # hubs side by side behind one source and one sink
    n = 2 + hubs * (k + 1)
    s, t = 0, n - 1
    edges, cap, flows, excess = [], [], {}, {t: 0}
    for j in range(hubs):
        base = 1 + j * (k + 1)
        hub = base + k
        for i in range(k):
            edges += [(s, base + i), (base + i, hub)]
            cap += [caps[i], caps[i]]
            flows[(s, base + i)] = flows[(base + i, hub)] = caps[i]
        edges.append((hub, t))
        cap.append(1)
        flows[(hub, t)] = 1
        excess[hub] = sum(caps) - 1
        excess[t] += 1
    g = Graph(n, np.array(edges, np.int64), np.array(cap, np.int64))
    r = build_residual(g, "bcsr")
    state = _hand_preflow(r, flows, excess)
    assert sum(excess.values()) - hubs > 2**32  # the global sum wraps
    res, stats = phase2.convert_preflow_to_flow_device(r, state, s, t)
    _assert_valid_flow(r, res, s, t, hubs)
    assert stats.passes == 1 and stats.steps <= 3


def test_batched_phase2_flags_invalid_lane():
    g = Graph(4, np.array([[0, 1], [2, 3]], np.int64),
              np.array([5, 5], np.int64))
    r = build_residual(g, "bcsr")
    bg, meta, res0, _ = batched.pack_instances([(r, 0, 3)])
    e = np.zeros(meta.n, np.int32)
    e[2] = 3  # stranded excess with no inbound flow
    state = batched.pack_states(
        [(r.res0.astype(np.int32), np.zeros(r.n, np.int32), e[: r.n])],
        meta.n, meta.num_arcs)
    _, leftover = batched.batched_phase2(bg, meta, res0, state)
    with pytest.raises(RuntimeError, match="lanes \\[0\\]"):
        batched.check_phase2_leftover(leftover)


def test_solve_many_returns_corrected_handles(rng):
    """solve_many corrects the whole batch in one dispatch: handles come
    back already holding genuine flows, and the lazy views are free."""
    graphs = [_random_messy_graph(rng, n_lo=6, n_hi=16) for _ in range(3)]
    sols = Solver().solve_many(
        [MaxflowProblem(g, 0, g.n - 1) for g in graphs])
    for g, sol in zip(graphs, sols):
        h = sol.warm_start
        assert h.corrected  # no host work left to do
        res, e = h.arrays()
        _assert_valid_flow(h.residual, res, 0, g.n - 1, sol.value)
        assert e.sum() == e[g.n - 1] == sol.value
        assert sol.min_cut().value == sol.value


def test_single_solve_handle_lazy_device_default(rng):
    """Single solves stay lazy; the first arrays() call runs the device
    phase 2 (reference=True forces the host oracle instead)."""
    g = _random_messy_graph(rng, n_lo=8, n_hi=18)
    s, t = 0, g.n - 1
    sol = Solver().solve(MaxflowProblem(g, s, t))
    ref = Solver().solve(MaxflowProblem(g, s, t))
    assert not sol.warm_start.corrected
    res_dev, e_dev = sol.warm_start.arrays()
    res_host, e_host = ref.warm_start.arrays(reference=True)
    _assert_valid_flow(sol.warm_start.residual, res_dev, s, t, sol.value)
    _assert_valid_flow(ref.warm_start.residual, res_host, s, t, ref.value)
    np.testing.assert_array_equal(e_dev, e_host)
