"""The bucketed ``vc`` step: each cycle of the unbatched ``vc`` loop runs
at the smallest rung of ``pushrelabel.frontier_ladder`` that holds its
live work, and must give, cycle by cycle, the state the padded (A, n)
step gives.  The vmapped step and the kernel modes stay padded.

``_padded_vc_step`` below is the reference: the padded step as it was
before the ladder, kept verbatim so that the bucketed loop and the
batched program are compared with it and not with themselves.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import ir
from repro.core import batched, globalrelabel
from repro.core import pushrelabel as pr
from repro.core.csr import Graph, build_residual
from repro.graphs import generators as G

INF = pr.INF


def _padded_minh(g, meta, state, avq, q_valid):
    n, A = meta.n, meta.num_arcs
    avq_c = jnp.minimum(avq, n - 1)
    deg = jnp.where(q_valid, g.indptr[avq_c + 1] - g.indptr[avq_c], 0)
    offs = jnp.cumsum(deg)
    starts = offs - deg
    total = offs[-1]
    pos = jnp.arange(A, dtype=jnp.int32)
    row = jnp.repeat(jnp.arange(n, dtype=jnp.int32), deg,
                     total_repeat_length=A)
    fvalid = pos < total
    row = jnp.where(fvalid, row, 0)
    arc = g.indptr[avq_c[row]] + (pos - starts[row])
    arc = jnp.clip(arc, 0, A - 1)
    key = jnp.where(fvalid & (state.res[arc] > 0),
                    state.h[g.heads[arc]], INF)
    minh = jax.ops.segment_min(key, row, num_segments=n,
                               indices_are_sorted=True)
    cand = jnp.where(fvalid & (key == minh[row]), arc, jnp.int32(A))
    argarc = jax.ops.segment_min(cand, row, num_segments=n,
                                 indices_are_sorted=True)
    minh = jnp.where(q_valid & (minh < INF), minh, INF)
    argarc = jnp.where(minh < INF, argarc, jnp.int32(A))
    return minh, argarc


def _padded_vc_step(g, meta, state, s, t, minh_fn=None, rev_fn=None):
    n = meta.n
    act = pr.active_mask(state, n, s, t)
    avq = jnp.nonzero(act, size=n, fill_value=n)[0].astype(jnp.int32)
    q_valid = avq < n
    minh, argarc = _padded_minh(g, meta, state, avq, q_valid)
    return pr._decide_apply(g, meta, state, avq, q_valid, minh, argarc,
                            rev_fn)


_padded = jax.jit(_padded_vc_step, static_argnames=("meta", "s", "t"))
_bucketed = jax.jit(pr.vc_bucketed_step, static_argnames=("meta", "s", "t"))


def _same(a: pr.PRState, b: pr.PRState, what=""):
    for name, x, y in zip(pr.PRState._fields, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{what} {name}")


def _ladder(meta):
    return pr.frontier_ladder(meta.n, meta.num_arcs)


def _rung_of(ladder, nact, ftotal):
    """The smallest rung that fits, by brute force."""
    return next(i for i, (f, k) in enumerate(ladder)
                if f >= ftotal and k >= nact)


# -- the ladder and the selector ---------------------------------------------


@pytest.mark.parametrize("n,arcs", [(10, 30), (514, 2_900), (1_026, 5_854),
                                    (32_770, 188_368),
                                    (1_048_578, 6_281_676)])
def test_ladder_shape(n, arcs):
    """Top rung (A, n); below it F shrinks by at most 2 a rung down to
    the floor, K never exceeds n and never grows as F shrinks; at most
    16 rungs; a graph too small for a second rung has the top alone."""
    ladder = pr.frontier_ladder(n, arcs)
    assert ladder[-1] == (arcs, n)
    assert 1 <= len(ladder) <= 16
    lanes = [f for f, _ in ladder]
    queue = [k for _, k in ladder]
    assert lanes == sorted(set(lanes))
    assert queue == sorted(queue) and queue[-1] == n
    assert all(hi <= 2 * lo for lo, hi in zip(lanes, lanes[1:]))
    assert all(f % 128 == 0 and (k % 128 == 0 or k == n)
               for f, k in ladder[:-1])
    assert lanes[0] >= min(arcs, pr._LADDER_FLOOR)
    if arcs < pr._LADDER_FLOOR * pr._LADDER_RATIO:
        assert ladder == ((arcs, n),)


def test_ladder_rung_is_the_smallest_that_fits():
    ladder = pr.frontier_ladder(32_770, 188_368)
    probes = {0, 1}
    for f, k in ladder:
        probes |= {f - 1, f, f + 1, k - 1, k, k + 1}
    probes = sorted(p for p in probes if 0 <= p <= 188_368)
    rung = jax.jit(lambda a, b: pr.ladder_rung(ladder, a, b))
    for nact in (p for p in probes if p <= 32_770):
        for ftotal in probes:
            assert int(rung(nact, ftotal)) == _rung_of(ladder, nact,
                                                       ftotal), (nact,
                                                                 ftotal)


# -- one step at the edges of a rung -----------------------------------------


@pytest.fixture(scope="module")
def leafy():
    """A random level graph with 1,500 pendant leaves (residual degree
    1), so an active set can hit any frontier or queue size exactly."""
    rng = np.random.default_rng(3)
    core, s, t = G.washington_rlg(32, 8, seed=1)
    leaves = 1_500
    heads = np.arange(core.n, core.n + leaves)
    tails = rng.choice(np.setdiff1d(np.arange(core.n), [s, t]), leaves)
    edges = np.concatenate([core.edges, np.stack([tails, heads], 1)])
    caps = np.concatenate([core.cap, rng.integers(1, 50, size=leaves)])
    r = build_residual(Graph(core.n + leaves, edges, caps), "bcsr")
    dg, meta, res0 = pr.to_device(r)
    assert len(_ladder(meta)) >= 4
    return r, dg, meta, res0, s, t


def _state_with_active(r, res0, s, t, active, seed):
    """Heights and residuals drawn at random, excess on ``active``
    only."""
    rng = np.random.default_rng(seed)
    h = rng.integers(0, r.n, size=r.n).astype(np.int32)
    e = np.zeros(r.n, np.int32)
    e[active] = rng.integers(1, 40, size=len(active))
    h[active] = rng.integers(0, r.n - 1, size=len(active))
    res = np.asarray(res0) * rng.integers(0, 2, size=r.num_arcs)
    return pr.PRState(res=jnp.asarray(res, jnp.int32), h=jnp.asarray(h),
                      e=jnp.asarray(e))


def _pick(r, s, t, ftotal, nact_max):
    """Non-terminal vertices whose degrees sum to exactly ``ftotal``,
    at most ``nact_max`` of them: the widest first, leaves to finish."""
    deg = np.diff(r.indptr)
    inner = np.setdiff1d(np.arange(r.n), [s, t])
    wide = sorted(inner[deg[inner] > 1], key=lambda v: -deg[v])
    picked, left = [], ftotal
    for v in wide:
        if deg[v] <= left:
            picked.append(v)
            left -= deg[v]
    leaves = inner[deg[inner] == 1]
    assert left <= len(leaves)
    picked += list(leaves[:left])
    assert len(picked) <= nact_max
    return np.array(picked)


@pytest.mark.parametrize("edge", ["frontier_full", "frontier_over",
                                  "queue_full", "queue_over"])
def test_step_matches_padded_at_rung_edges(leafy, edge):
    """A cycle whose arcs (or vertices) fill a rung exactly runs at that
    rung; one more runs a rung up; both give the padded step's state."""
    r, dg, meta, res0, s, t = leafy
    ladder = _ladder(meta)
    deg = np.diff(r.indptr)
    leaves = np.setdiff1d(np.flatnonzero(deg == 1), [s, t])
    for i in range(len(ladder) - 1):
        f, k = ladder[i]
        if edge.startswith("frontier"):
            ftotal = f + (edge == "frontier_over")
            active = _pick(r, s, t, ftotal, k)
        else:
            nact = k + (edge == "queue_over")
            if nact > len(leaves) or nact > f:
                continue
            active = leaves[:nact]
        nact, ftotal = len(active), int(deg[active].sum())
        want = i + edge.endswith("_over")
        assert _rung_of(ladder, nact, ftotal) == want
        assert int(pr.ladder_rung(ladder, nact, ftotal)) == want
        state = _state_with_active(r, res0, s, t, active, seed=i)
        _same(_bucketed(dg, meta, state, s, t),
              _padded(dg, meta, state, s, t), f"{edge} rung {i}")


# -- whole loops ----------------------------------------------------------


def _wide_source(n, m, fan, seed):
    """A random sparse graph whose source also feeds ``fan`` random
    vertices, so the first cycles' frontier is wide."""
    rng = np.random.default_rng(seed)
    g, s, t = G.random_sparse(n, m, seed=seed)
    heads = rng.choice(np.setdiff1d(np.arange(n), [s, t]), fan,
                       replace=False)
    edges = np.concatenate([g.edges, np.stack([np.full(fan, s), heads], 1)])
    return Graph(n, edges, np.concatenate([g.cap, np.full(fan, 500)])), s, t


def _instances():
    yield "rlg", G.washington_rlg(128, 8, seed=0)
    yield "random", _wide_source(800, 6_000, 200, seed=5)
    yield "random_wide", _wide_source(1_200, 8_000, 300, seed=4)


@pytest.mark.parametrize("name,inst", list(_instances()),
                         ids=[n for n, _ in _instances()])
def test_run_cycles_matches_padded_cycle_by_cycle(name, inst):
    """``run_cycles`` (mode ``vc``, bucketed) against the padded
    reference step: the same ``res``, ``h``, ``e`` after every cycle and
    the same cycle count, over several rungs."""
    g, s, t = inst
    r = build_residual(g, "bcsr")
    dg, meta, res0 = pr.to_device(r)
    state = pr.preflow(dg, meta, res0, s)
    state, _, _ = globalrelabel.global_relabel(dg, meta, state, s, t)
    ladder = _ladder(meta)
    assert len(ladder) > 2, name
    deg = np.diff(r.indptr)
    one = jnp.int32(1)
    ref, got, rungs = state, state, set()
    for cycle in range(400):
        act = np.asarray(pr.active_mask(ref, meta.n, s, t))
        if not act.any():
            break
        rungs.add(_rung_of(ladder, int(act.sum()), int(deg[act].sum())))
        ref = _padded(dg, meta, ref, s, t)
        got, ran = pr.run_cycles(dg, meta, got, s, t, max_cycles=64,
                                 budget=one)
        assert int(ran) == 1
        _same(got, ref, f"{name} cycle {cycle}")
    assert len(rungs) > 1, (name, rungs)
    # and in one dispatch: the loop's own early exit, the same count
    whole, cycles = pr.run_cycles(dg, meta, state, s, t, max_cycles=512)
    ref, want = state, 0
    while want < 512 and np.asarray(pr.active_mask(ref, meta.n, s,
                                                   t)).any():
        ref, want = _padded(dg, meta, ref, s, t), want + 1
    assert int(cycles) == want
    _same(whole, ref, f"{name} whole loop")


# -- the paths that stay padded ------------------------------------------


@pytest.fixture(scope="module")
def bucket():
    """A serving-size batch whose padded shapes have several rungs."""
    insts = []
    for seed in (1, 2):
        g, s, t = G.washington_rlg(64, 8, seed=seed)
        insts.append((build_residual(g, "bcsr"), s, t))
    bg, meta, res0, _ = batched.pack_instances(insts)
    assert len(_ladder(meta)) > 2
    return bg, meta, batched.batched_preflow(bg, meta, res0)


def test_only_the_unbatched_vc_loop_switches(bucket, leafy):
    """The vmapped step has no ``cond``: a switch on a batched index
    would run every rung.  The kernel modes have none either; the
    unbatched ``vc`` loop has exactly one."""
    bg, meta, state = bucket
    for mode in ("vc", "vc_kernel"):
        assert ir.primitive_count(
            lambda st: batched.batched_run_cycles(bg, meta, st, mode=mode,
                                                  max_cycles=8),
            "cond", state) == 0, mode
    r, dg, meta1, res0, s, t = leafy
    st1 = pr.preflow(dg, meta1, res0, s)
    for mode, want in (("vc", 1), ("vc_kernel", 0),
                       ("vc_kernel_bsearch", 0), ("tc", 0)):
        assert ir.primitive_count(
            lambda st: pr.run_cycles(dg, meta1, st, s, t, mode=mode,
                                     max_cycles=8),
            "cond", st1) == want, mode


def _stripped(compiled) -> str:
    """A compiled program's optimized HLO text without its metadata and
    the debug tables after it.  An executable from the persistent cache
    gives its text through its runtime executable."""
    text = compiled.as_text() or "\n".join(
        m.to_string() for m in compiled.runtime_executable().hlo_modules())
    assert " scatter(" in text
    code = [line for line in text.splitlines()
            if line.startswith((" ", "}", "%", "ENTRY"))]
    return re.sub(r",? metadata=\{[^}]*\}", "", "\n".join(code))


def test_batched_program_is_the_padded_one(bucket, monkeypatch):
    """``batched_run_cycles``' optimized HLO, metadata stripped, is the
    one it compiles with both ``vc`` steps replaced by the padded
    reference.  (Under ``vmap`` a switch becomes selects, not a
    ``cond``: this is what would show a vmapped bucketed step.)"""
    bg, meta, state = bucket

    def hlo():
        jax.clear_caches()
        return _stripped(batched.batched_run_cycles.lower(
            bg, meta, state, mode="vc", max_cycles=32).compile())

    got = hlo()
    with monkeypatch.context() as m:
        m.setattr(pr, "vc_step", _padded_vc_step)
        m.setattr(pr, "vc_bucketed_step", _padded_vc_step)
        want = hlo()
    jax.clear_caches()
    assert got == want
