"""Batched multi-instance WBPR: advance B independent max-flow instances
per device dispatch.

The single-instance solver (``repro.core.pushrelabel``) compiles one
executable per graph shape and handles one graph per call.  Serving traffic
is many small/medium instances, so here we stack instances into padded
flat-arc arrays — one leading batch axis over the same ``DeviceGraph`` /
``PRState`` layout — and ``jax.vmap`` the unmodified per-instance step,
preflow and global-relabel functions over it.  The Pallas modes do NOT
vmap their kernels: the kernels natively carry a leading batch *grid*
dimension, so each cycle's min search is ONE launch spanning the whole
microbatch (``_kernel_batch_step``).  One compiled executable then
advances every instance of a shape bucket at once:

* ``pack_instances`` pads B ``ResidualCSR``s to a common ``(n_pad, A_pad)``
  and stacks them (padded vertices have empty arc segments; padded arcs have
  zero residual, so both are inert under push/relabel and BFS sweeps).
* ``batched_run_cycles`` runs the bulk-synchronous loop with **per-instance
  convergence flags**: converged instances are fixpoints of the step
  function, so the loop exits when every instance's AVQ is empty and each
  instance's cycle counter stops advancing the moment it converges.
* ``batched_resolve`` accepts an arbitrary valid starting state, which is
  how **warm-started re-solves** enter: apply capacity increases to a cached
  final residual, re-saturate the arcs out of the source
  (``warm_start_arrays``), and let global relabel restore exact heights —
  the prior flow is kept, so only the new capacity is routed.

Correctness note on padding: every height threshold in the per-instance code
is ``meta.n``, which here is ``n_pad``.  Push-relabel is indifferent to the
numeric value of the "unreachable" height as long as it exceeds any true
residual distance, and ``n_pad >= n`` does; the max-flow value (``e[t]`` at
convergence) is the graph's unique optimum either way, so batched and
sequential solves agree exactly.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core import globalrelabel as gr
from repro.core import pushrelabel as pr
from repro.core.csr import ResidualCSR
from repro.obs import scopes
from repro.obs import solvercounters as sc
from typing import NamedTuple

#: THE device state dtype: residual occupancies, heights and excess are
#: int32 end-to-end (the paper's integer-capacity formulation; validated
#: at the facade by ``SolverOptions.dtype``).  Host-side staging arrays may
#: be wider, but every device entry point narrows through
#: ``as_state_dtype`` — which RAISES on values that do not fit instead of
#: silently truncating.
STATE_DTYPE = np.int32


def as_state_dtype(arr, what: str = "array") -> np.ndarray:
    """``np.asarray(arr, STATE_DTYPE)`` that refuses lossy casts.

    Large-capacity instances can push host-side int64 excess/residual
    staging arrays past 2**31; a silent ``astype(np.int32)`` would wrap
    them into garbage the solver happily routes.  Raise instead."""
    a = np.asarray(arr)
    if a.dtype == STATE_DTYPE:
        return a
    info = np.iinfo(STATE_DTYPE)
    if a.size and (a.min() < info.min or a.max() > info.max):
        raise OverflowError(
            f"{what} holds values outside the int32 state dtype "
            f"(min={a.min()}, max={a.max()}); capacities this large are "
            "not representable — rescale the instance (see "
            "SolverOptions.dtype)")
    return a.astype(STATE_DTYPE)


class BatchedDeviceGraph(NamedTuple):
    """B stacked ``DeviceGraph``s padded to a common shape, plus the
    per-instance true sizes and terminals."""

    indptr: jax.Array  # (B, n_pad+1) int32
    heads: jax.Array  # (B, A_pad) int32
    tails: jax.Array  # (B, A_pad) int32
    rev: jax.Array  # (B, A_pad) int32
    n: jax.Array  # (B,) int32 — true vertex count
    num_arcs: jax.Array  # (B,) int32 — true arc count
    s: jax.Array  # (B,) int32
    t: jax.Array  # (B,) int32

    @property
    def batch(self) -> int:
        return self.s.shape[0]


class BatchedPRState(NamedTuple):
    res: jax.Array  # (B, A_pad) int32
    h: jax.Array  # (B, n_pad) int32
    e: jax.Array  # (B, n_pad) int32


@dataclasses.dataclass
class BatchedSolveResult:
    maxflows: np.ndarray  # (B,) int64
    cycles: np.ndarray  # (B,) int64 — per-instance push-relabel iterations
    rounds: np.ndarray  # (B,) int64 — chunks the instance was live for
    global_relabels: int
    converged: np.ndarray  # (B,) bool
    state: BatchedPRState  # final padded device state
    trivial: np.ndarray  # (B,) bool — s==t / empty instances, forced to 0
    corrected: bool = False  # state is phase-2 corrected (a genuine flow)
    gr_time_s: float = 0.0  # wall seconds in pooled global-relabel sweeps
    # (dispatch + sync: an upper bound that may absorb tail latency of the
    # preceding cycles dispatch — a serving-tier reporting knob, not a
    # microbenchmark)
    gr_sweeps: int = 0  # Bellman-Ford sweep total across global relabels
    # per-instance (B,) int64 device-counter totals — telemetry solves
    # only, None otherwise (repro.obs.solvercounters)
    pushes: np.ndarray | None = None
    relabels: np.ndarray | None = None
    active_sum: np.ndarray | None = None
    frontier_sum: np.ndarray | None = None
    frontier_lanes: np.ndarray | None = None  # A_pad per live cycle


def round_up_pow2(x: int, lo: int = 1) -> int:
    x = max(int(x), lo)
    return 1 << (x - 1).bit_length()


def _pad_instance(r: ResidualCSR, n_pad: int, A_pad: int, trivial: bool):
    n, A = r.n, r.num_arcs
    if n > n_pad or A > A_pad:  # not an assert: must survive python -O
        raise ValueError(
            f"instance exceeds bucket shape: (n={n}, arcs={A}) does not "
            f"fit (n_pad={n_pad}, A_pad={A_pad})")
    indptr = np.full(n_pad + 1, A, np.int32)
    indptr[: n + 1] = r.indptr
    # pad arcs: zero residual, endpoints at the last padded vertex (keeps
    # `tails` non-decreasing for the sorted segment reductions), rev = self
    heads = np.full(A_pad, n_pad - 1, np.int32)
    tails = np.full(A_pad, n_pad - 1, np.int32)
    rev = np.arange(A_pad, dtype=np.int32)
    res0 = np.zeros(A_pad, np.int32)
    heads[:A] = r.heads
    tails[:A] = r.tails
    rev[:A] = r.rev
    if not trivial:
        res0[:A] = r.res0
    return indptr, heads, tails, rev, res0


def pack_instances(instances: list[tuple[ResidualCSR, int, int]],
                   n_pad: int | None = None, A_pad: int | None = None,
                   deg_max: int | None = None):
    """Stack instances ``(ResidualCSR, s, t)`` into one padded batch.

    Returns ``(bg, meta, res0)`` where ``meta`` is the *padded* static
    ``GraphMeta`` shared by every instance and ``res0`` is ``(B, A_pad)``.
    Instances with ``s == t``, no arcs, or no edges are marked trivial and
    packed with zero capacities (they converge immediately with flow 0).

    ``meta.layout`` records whether EVERY instance has head-sorted (bcsr)
    segments — ``"batched-bcsr"`` vs plain ``"batched"`` — which is what
    licenses the binary-search reverse lookup; ``batched_run_cycles``
    rejects ``mode='vc_kernel_bsearch'`` on an unsorted pack at trace
    time, on every entry path (cold solve, warm resolve, serving flush).
    """
    assert instances, "empty batch"
    n_pad = n_pad or max(max(r.n for r, _, _ in instances), 2)
    A_pad = A_pad or max(max(r.num_arcs for r, _, _ in instances), 1)
    deg_max = deg_max or max(max(r.deg_max for r, _, _ in instances), 1)
    cols = [[] for _ in range(5)]
    ns, As, ss, ts, triv = [], [], [], [], []
    for r, s, t in instances:
        trivial = (s == t) or r.num_arcs == 0 or r.deg_max == 0
        parts = _pad_instance(r, n_pad, A_pad, trivial)
        for c, p in zip(cols, parts):
            c.append(p)
        ns.append(r.n)
        As.append(r.num_arcs)
        ss.append(min(s, n_pad - 1))
        ts.append(min(t, n_pad - 1))
        triv.append(trivial)
    indptr, heads, tails, rev, res0 = (np.stack(c) for c in cols)
    bg = BatchedDeviceGraph(
        indptr=jnp.asarray(indptr), heads=jnp.asarray(heads),
        tails=jnp.asarray(tails), rev=jnp.asarray(rev),
        n=jnp.asarray(ns, jnp.int32), num_arcs=jnp.asarray(As, jnp.int32),
        s=jnp.asarray(ss, jnp.int32), t=jnp.asarray(ts, jnp.int32))
    sorted_ok = all(r.binary_search_ready() for r, _, _ in instances)
    meta = pr.GraphMeta(n=n_pad, num_arcs=A_pad, deg_max=deg_max,
                        layout="batched-bcsr" if sorted_ok else "batched")
    return bg, meta, jnp.asarray(res0), np.asarray(triv)


def pack_states(states: list[tuple[np.ndarray, np.ndarray, np.ndarray]],
                n_pad: int, A_pad: int) -> BatchedPRState:
    """Stack per-instance ``(res, h, e)`` numpy arrays into a padded
    ``BatchedPRState`` (used to enter ``batched_resolve`` warm).

    Inputs of any integer dtype are accepted but must FIT the int32
    state dtype — a wider array with out-of-range values raises
    ``OverflowError`` (``as_state_dtype``) instead of wrapping silently.
    """
    B = len(states)
    res = np.zeros((B, A_pad), STATE_DTYPE)
    h = np.zeros((B, n_pad), STATE_DTYPE)
    e = np.zeros((B, n_pad), STATE_DTYPE)
    for i, (ri, hi, ei) in enumerate(states):
        res[i, : ri.shape[0]] = as_state_dtype(ri, f"states[{i}].res")
        h[i, : hi.shape[0]] = as_state_dtype(hi, f"states[{i}].h")
        e[i, : ei.shape[0]] = as_state_dtype(ei, f"states[{i}].e")
    return BatchedPRState(res=jnp.asarray(res), h=jnp.asarray(h),
                          e=jnp.asarray(e))


# ---------------------------------------------------------------------------
# vmapped device stages
# ---------------------------------------------------------------------------

def _rows(bg: BatchedDeviceGraph):
    return bg.indptr, bg.heads, bg.tails, bg.rev


@functools.partial(jax.jit, static_argnames=("meta",))
def batched_preflow(bg: BatchedDeviceGraph, meta, res0) -> BatchedPRState:
    """Vmapped paper Alg. 1 step 0 over the whole batch."""

    def one(indptr, heads, tails, rev, r0, s):
        st = pr.preflow(pr.DeviceGraph(indptr, heads, tails, rev), meta,
                        r0, s)
        return st.res, st.h, st.e

    res, h, e = jax.vmap(one)(*_rows(bg), res0, bg.s)
    return BatchedPRState(res=res, h=h, e=e)


@functools.partial(jax.jit, static_argnames=("meta", "minh_fn"))
def batched_global_relabel(bg: BatchedDeviceGraph, meta,
                           state: BatchedPRState, minh_fn=None):
    """Global relabel over the whole batch; returns (state, per-instance
    active counts).  ``nact == 0`` is the per-instance convergence flag.

    The distance sweeps run at batch level
    (``globalrelabel.batched_global_relabel_impl``): ``minh_fn=None``
    vmaps XLA's ``segment_min`` per row, while a kernel ``minh_fn``
    (``kernels.ops.min_neighbor_minh_fn(...)``) executes each sweep step
    as ONE ``tile_min_neighbor`` launch with grid ``(B, tiles)`` — no
    vmapped ``pallas_call``.  Results are bit-for-bit identical.

    Also returns the pooled Bellman-Ford ``sweeps`` count (shared by the
    batch: the sweep loop runs to the slowest row's fixpoint)."""
    g = pr.DeviceGraph(*_rows(bg))
    st, nact, sweeps = gr.batched_global_relabel_impl(
        g, meta, pr.PRState(*state), bg.s, bg.t, minh_fn=minh_fn)
    return BatchedPRState(res=st.res, h=st.h, e=st.e), nact, sweeps


def _mode_minh_fn(mode: str, interpret: bool | None):
    """The batched sweep hook a solver mode implies — a thin alias of the
    engine-owned resolver (``repro.core.engine.resolve_minh_fn``): kernel
    modes route their pooled sweeps (global relabel, phase 2) through the
    batch-grid tile kernel; XLA modes keep the vmapped ``segment_min``
    reference."""
    return engine.resolve_minh_fn(mode, interpret)


def _kernel_batch_step(bg: BatchedDeviceGraph, meta, state: BatchedPRState,
                       mode: str, interpret: bool | None) -> BatchedPRState:
    """One bulk-synchronous cycle over the whole batch with the min-height
    search executed by the batched Pallas tile kernel — ONE ``pallas_call``
    spanning every instance (grid ``(B, tiles)``), instead of a vmapped
    per-instance kernel.  The AVQ compaction and the decide/apply stay on
    vmapped XLA (they are scatter-bound, not search-bound).  Results are
    bit-for-bit ``vc`` (the tile kernel computes the same (min, argmin)).
    """
    from repro.kernels import ops as kops
    from repro.kernels.revsearch import bcsr_rev_search

    n, A = meta.n, meta.num_arcs

    def one_avq(h, e, s, t):
        act = pr.active_mask(pr.PRState(res=None, h=h, e=e), n, s, t)
        return jnp.nonzero(act, size=n, fill_value=n)[0].astype(jnp.int32)

    with jax.named_scope(scopes.COMPACT):
        avq = jax.vmap(one_avq)(state.h, state.e, bg.s, bg.t)  # (B, n)
        q_valid = avq < n
    # the shared minh hook (batched form): ONE launch, grid (B, tiles)
    with jax.named_scope(scopes.MINH):
        minh, argarc = kops.min_neighbor_kernel(
            pr.DeviceGraph(*_rows(bg)), meta, pr.PRState(*state), avq,
            q_valid, interpret=interpret)
    with jax.named_scope(scopes.APPLY):
        if mode == "vc_kernel_bsearch":
            # run the shared push decision up front to assemble the batch of
            # push arcs, then resolve every reverse arc in one bsearch launch
            u_c = jnp.minimum(avq, n - 1)
            arc_c = jnp.clip(argarc, 0, A - 1)
            _, do_push = jax.vmap(pr._push_decision)(state.h, u_c, q_valid,
                                                     minh)
            push_arc = jnp.where(do_push, arc_c, jnp.int32(A))
            rev_rows = bcsr_rev_search(push_arc, bg.indptr, bg.heads, bg.tails,
                                       interpret=interpret)

            def one_apply(indptr, heads, tails, rev, res, h, e, q, qv, mh, aa,
                          rr):
                g = pr.DeviceGraph(indptr, heads, tails, rev)
                st = pr._decide_apply(g, meta, pr.PRState(res, h, e), q, qv,
                                      mh, aa, rev_fn=lambda *_: rr)
                return st.res, st.h, st.e

            res, h, e = jax.vmap(one_apply)(*_rows(bg), *state, avq, q_valid,
                                            minh, argarc, rev_rows)
        else:
            def one_apply(indptr, heads, tails, rev, res, h, e, q, qv, mh, aa):
                g = pr.DeviceGraph(indptr, heads, tails, rev)
                st = pr._decide_apply(g, meta, pr.PRState(res, h, e), q, qv,
                                      mh, aa)
                return st.res, st.h, st.e

            res, h, e = jax.vmap(one_apply)(*_rows(bg), *state, avq, q_valid,
                                            minh, argarc)
        return BatchedPRState(res=res, h=h, e=e)


@functools.partial(jax.jit,
                   static_argnames=("meta", "mode", "max_cycles",
                                    "interpret", "telemetry", "chunk"))
def batched_run_cycles(bg: BatchedDeviceGraph, meta, state: BatchedPRState,
                       mode: str = "vc", max_cycles: int = 256,
                       interpret: bool | None = None,
                       telemetry: bool = False,
                       budget: jax.Array | None = None,
                       chunk: int | None = None):
    """Up to ``max_cycles`` bulk-synchronous iterations over the batch,
    run through the shared sweep engine (``repro.core.engine``): an outer
    ``while_loop`` over scan-compiled chunks of ``chunk`` cycles — the
    steady-state trace holds ONE step body regardless of ``max_cycles``.
    ``budget`` (traced, optional) tightens the cycle cap below the static
    ``max_cycles`` without recompiling; ``batched_resolve`` threads its
    remaining total-cycle allowance through it.

    A converged instance (empty AVQ) is a fixpoint of the step function, so
    stepping it is the identity; ``cycles[b]`` counts only the iterations
    instance ``b`` was still live for.  The loop exits early when every
    instance has converged *or* when an iteration moves no excess at all
    (pure relabel climb): once pushes stop, active vertices are only
    raising heights toward ``n`` — the caller's next global relabel settles
    that in one sweep instead of O(n) climb iterations.

    Every solver mode (``pushrelabel.ALL_MODES``) is batchable: 'vc'/'tc'
    vmap the XLA step, 'vc_kernel'/'vc_kernel_bsearch' run the batched
    Pallas tile kernels (one launch per cycle spanning the whole batch).

    ``telemetry=True`` (static) folds per-instance ``(B,)`` int32
    push/relabel/active/frontier/lanes totals into the carry
    (``repro.obs.solvercounters``) and returns them as a third element —
    a ``CycleTelemetry`` with ``None`` histories.  ``telemetry=False``
    traces exactly the historical two-result loop.
    """
    if mode not in pr.ALL_MODES:
        raise ValueError(
            f"batched mode must be one of {pr.ALL_MODES}, got {mode!r}")
    if mode == "vc_kernel_bsearch" and meta.layout != "batched-bcsr":
        # guard at the shared depth: every entry path (cold solve, warm
        # resolve, serving flush) passes through here, and a failed
        # binary search on unsorted segments would be scatter-DROPPED
        # silently, corrupting residuals
        raise ValueError(
            "mode 'vc_kernel_bsearch' needs head-sorted (bcsr) segments "
            f"in every packed instance; this batch is {meta.layout!r}")

    # everything here but the step phases: the cap, the condition, the
    # engine's chunk gating and carry, the telemetry counters
    with jax.named_scope(scopes.LOOP):
        def one_nact(h, e, s, t):
            st = pr.PRState(res=None, h=h, e=e)
            return jnp.sum(pr.active_mask(st, meta.n, s, t))

        vnact = jax.vmap(one_nact)

        cap = jnp.int32(max_cycles)
        if budget is not None:
            cap = jnp.minimum(cap, jnp.asarray(budget, jnp.int32))

        if mode in ("vc", "tc"):
            step_fn = pr._make_step(mode)

            def one_step(indptr, heads, tails, rev, res, h, e, s, t):
                g = pr.DeviceGraph(indptr, heads, tails, rev)
                st = step_fn(g, meta, pr.PRState(res, h, e), s, t)
                return st.res, st.h, st.e

            vstep = jax.vmap(one_step)

            def step(state):
                return BatchedPRState(*vstep(*_rows(bg), *state, bg.s, bg.t))
        else:
            def step(state):
                return _kernel_batch_step(bg, meta, state, mode, interpret)

        def cond(carry):
            nact, cycle, pushed = carry[1], carry[2], carry[4]
            return (cycle < cap) & jnp.any(nact > 0) & pushed

        def body(carry):
            state, nact, cycle, cycles_per, _ = carry[:5]
            new_state = step(state)
            pushed = jnp.any(new_state.e != state.e)  # any excess moved?
            new_nact = vnact(new_state.h, new_state.e, bg.s, bg.t)
            out = (new_state, new_nact, cycle + 1,
                   cycles_per + (nact > 0).astype(jnp.int32), pushed)
            if telemetry:
                tel = carry[5]
                # every valid active vertex pushed or relabelled exactly
                # once; relabels are the h changes
                relab = sc.count_relabels(state.h, new_state.h)
                _, fr, _ = sc.cycle_stats(pr.DeviceGraph(*_rows(bg)), meta,
                                          state, bg.s, bg.t)
                tel = sc.CycleTelemetry(
                    pushes=tel.pushes + nact - relab,
                    relabels=tel.relabels + relab,
                    active=tel.active + nact, frontier=tel.frontier + fr,
                    lanes=tel.lanes + jnp.where(nact > 0, meta.num_arcs, 0))
                out = out + (tel,)
            return out

        zero = jnp.zeros(bg.batch, jnp.int32)
        nact0 = vnact(state.h, state.e, bg.s, bg.t)
        init = (state, nact0, jnp.int32(0), zero, jnp.bool_(True))
        if telemetry:
            init = init + (sc.telemetry_init(batch=bg.batch),)
        out = engine.run_bulk_loop(body, init, cond_fn=cond,
                                   chunk=engine.normalize_chunk(chunk,
                                                                max_cycles))
        if telemetry:
            return out[0], out[3], out[5]
        return out[0], out[3]


@functools.partial(jax.jit, static_argnames=("meta", "minh_fn"))
def batched_phase2(bg: BatchedDeviceGraph, meta, res0,
                   state: BatchedPRState, minh_fn=None):
    """Device phase 2 (preflow -> flow) over the whole batch: one dispatch
    cancels every instance's stranded excess back to its source.

    ``res0`` is the packed ``(B, A_pad)`` initial-capacity array from
    ``pack_instances``.  Returns ``(corrected state, leftover)`` where
    ``leftover[b]`` is instance b's undrainable excess — zero for every
    valid preflow (callers raise otherwise).  Padded and trivial lanes
    carry no excess and are no-ops.

    The height sweeps run at batch level (``phase2.batched_phase2_impl``):
    a kernel ``minh_fn`` executes each as ONE batch-grid
    ``tile_min_neighbor`` launch instead of vmapped XLA — results
    bit-for-bit identical either way.
    """
    from repro.core import phase2 as p2

    res, e, leftover = p2.batched_phase2_impl(
        pr.DeviceGraph(*_rows(bg)), meta, res0, state.res, state.e,
        bg.s, bg.t, minh_fn=minh_fn)
    return BatchedPRState(res=res, h=state.h, e=e), leftover


def check_phase2_leftover(leftover) -> None:
    """Raise if any batch lane could not drain its excess (invalid preflow)."""
    left = np.asarray(leftover)
    if left.any():
        bad = np.nonzero(left)[0].tolist()
        raise RuntimeError(
            f"phase 2 could not drain excess on batch lanes {bad} — the "
            "states are not valid preflows (excess must be flow-connected "
            "to the source)")


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

def batched_resolve(bg: BatchedDeviceGraph, meta, state: BatchedPRState,
                    trivial: np.ndarray | None = None, mode: str = "vc",
                    cycle_chunk: int | None = None,
                    max_rounds: int = 100000,
                    interpret: bool | None = None,
                    telemetry: bool = False,
                    max_cycles: int | None = None,
                    scan_chunk: int | None = None) -> BatchedSolveResult:
    """[global relabel -> cycles]* from an arbitrary valid preflow state.

    This is the shared tail of cold solves (entered right after
    ``batched_preflow``) and warm re-solves (entered from an edited cached
    residual via ``warm_start_arrays``/``pack_states``).

    Kernel modes route the pooled global-relabel distance sweeps through
    the batch-grid tile kernel (one launch per sweep step spanning the
    whole batch) — the same ``minh_fn`` hook their cycle loops use.

    ``telemetry=True`` runs the cycle loops with the device-side workload
    counters and fills the result's per-instance ``pushes``/``relabels``/
    ``active_sum``/``frontier_sum``/``frontier_lanes`` arrays (int64,
    accumulated across rounds on the host — one extra fetch per round,
    never per cycle).

    ``max_cycles`` (optional) is an exact total bulk-synchronous cycle
    budget across rounds — threaded into every ``batched_run_cycles``
    dispatch as the traced ``budget`` scalar, so the cap is honored
    exactly even when it is not a multiple of ``cycle_chunk`` and no
    recompile happens per round.  ``scan_chunk`` sets the engine's
    scanned steps-per-chunk.
    """
    B = bg.batch
    if trivial is None:
        trivial = np.zeros(B, bool)
    chunk = cycle_chunk or max(32, min(1024, meta.n))
    gr_minh = _mode_minh_fn(mode, interpret)
    gr_time = 0.0
    gr_sweeps = 0

    def relabel(state):
        nonlocal gr_time, gr_sweeps
        t0 = time.perf_counter()
        state, nact, sweeps = batched_global_relabel(bg, meta, state,
                                                     minh_fn=gr_minh)
        nact = np.asarray(nact)  # sync: the host loop branches on it
        gr_sweeps += int(sweeps)
        gr_time += time.perf_counter() - t0
        return state, nact

    state, nact = relabel(state)
    cycles = np.zeros(B, np.int64)
    rounds = np.zeros(B, np.int64)
    # pushes, relabels, active, frontier, lanes
    counts = np.zeros((5, B), np.int64)
    grs = 1
    remaining = max_cycles  # None = unbounded; else exact total allowance
    for _ in range(max_rounds):
        live = nact > 0
        if not live.any():
            break
        budget = None if remaining is None else jnp.int32(remaining)
        if telemetry:
            state, cyc, tel = batched_run_cycles(bg, meta, state, mode=mode,
                                                 max_cycles=chunk,
                                                 interpret=interpret,
                                                 telemetry=True,
                                                 budget=budget,
                                                 chunk=scan_chunk)
            counts += np.asarray(tel[:5], np.int64)
        else:
            state, cyc = batched_run_cycles(bg, meta, state, mode=mode,
                                            max_cycles=chunk,
                                            interpret=interpret,
                                            budget=budget, chunk=scan_chunk)
        cyc = np.asarray(cyc, np.int64)
        cycles += cyc
        rounds += live
        if remaining is not None:
            # per-lane liveness is a prefix of the loop, so the max lane
            # count IS the number of bulk cycles this dispatch executed
            remaining -= int(cyc.max())
        state, nact = relabel(state)
        grs += 1
        if remaining is not None and remaining <= 0 and (nact > 0).any():
            from repro.errors import BudgetExhausted

            raise BudgetExhausted(
                f"batched push-relabel did not converge within "
                f"max_cycles={max_cycles}",
                cycles_spent=max_cycles - remaining, limit=max_cycles,
                partial=True)
    else:
        raise RuntimeError("batched push-relabel did not converge "
                           "within max_rounds")
    e = np.asarray(state.e)
    maxflows = e[np.arange(B), np.asarray(bg.t)].astype(np.int64)  # lint-ok: int64-state-cast
    maxflows[trivial] = 0
    return BatchedSolveResult(
        maxflows=maxflows, cycles=cycles, rounds=rounds, global_relabels=grs,
        converged=nact == 0, state=state,
        trivial=np.asarray(trivial), gr_time_s=gr_time, gr_sweeps=gr_sweeps,
        pushes=counts[0] if telemetry else None,
        relabels=counts[1] if telemetry else None,
        active_sum=counts[2] if telemetry else None,
        frontier_sum=counts[3] if telemetry else None,
        frontier_lanes=counts[4] if telemetry else None)


def batched_solve_impl(instances: list[tuple[ResidualCSR, int, int]],
                       mode: str = "vc", cycle_chunk: int | None = None,
                       max_rounds: int = 100000,
                       n_pad: int | None = None, A_pad: int | None = None,
                       deg_max: int | None = None,
                       phase2: bool = False,
                       interpret: bool | None = None,
                       telemetry: bool = False,
                       max_cycles: int | None = None,
                       scan_chunk: int | None = None) -> BatchedSolveResult:
    """Cold-solve B instances in one padded batch.

    Per-instance max-flow values match the single-instance solver exactly
    (the optimum is unique); one executable per ``(n_pad, A_pad, deg_max,
    mode)`` replaces one per instance shape.  This is the execution engine
    behind ``repro.api.Solver.solve_many``.

    Every mode is batchable — the Pallas modes run their kernels with a
    leading batch grid axis (one launch per cycle spanning the whole
    microbatch).  ``vc_kernel_bsearch``
    requires head-sorted (bcsr) instances.

    ``phase2=True`` additionally converts every final preflow to a genuine
    flow in one extra ``batched_phase2`` dispatch (the whole microbatch is
    corrected at once; handles built from the result skip the lazy
    correction).
    """
    if mode == "vc_kernel_bsearch":
        bad = [i for i, (r, _, _) in enumerate(instances)
               if not r.binary_search_ready()]
        if bad:
            raise ValueError(
                "mode 'vc_kernel_bsearch' needs head-sorted (bcsr) "
                f"segments; instances {bad} are not binary-search ready")
    bg, meta, res0, trivial = pack_instances(instances, n_pad=n_pad,
                                             A_pad=A_pad, deg_max=deg_max)
    state = batched_preflow(bg, meta, res0)
    out = batched_resolve(bg, meta, state, trivial=trivial, mode=mode,
                          cycle_chunk=cycle_chunk, max_rounds=max_rounds,
                          interpret=interpret, telemetry=telemetry,
                          max_cycles=max_cycles, scan_chunk=scan_chunk)
    if phase2:
        # kernel modes correct on the batch-grid tile kernel too
        out.state, leftover = batched_phase2(
            bg, meta, res0, out.state, minh_fn=_mode_minh_fn(mode,
                                                             interpret))
        check_phase2_leftover(leftover)
        out.corrected = True
    return out


# ---------------------------------------------------------------------------
# warm starts
# ---------------------------------------------------------------------------

def warm_start_arrays(r: ResidualCSR, prev_res: np.ndarray,
                      prev_e: np.ndarray, s: int,
                      budget: int | None = None):
    """Turn a cached final residual (possibly after capacity increases have
    been added to ``prev_res``) into a valid warm preflow.

    Saturates residual arcs out of the source, each by at most ``budget``
    units.  For a re-solve after capacity increases totalling ``D``, the
    max-flow gain is at most ``D`` and the optimum routes at most ``D``
    additional units through any single source arc, so ``budget = D``
    preserves optimality while bounding the injected excess to
    ``deg(s) * D`` instead of the full unsent source capacity — the excess
    that cannot route (and would otherwise bounce for many cycles before
    re-stranding) is never created.  ``budget=None`` saturates fully, which
    on a fresh residual is exactly the preflow initialisation.

    Returns host ``(res, h, e)`` ready for ``pack_states`` (heights are
    recomputed by the global relabel inside ``batched_resolve``).  The
    arithmetic stages in int64 and narrows through ``as_state_dtype`` —
    values that left the int32 state dtype raise instead of wrapping.
    """
    res = np.asarray(prev_res, np.int64).copy()
    e = np.asarray(prev_e, np.int64).copy()
    lo, hi = int(r.indptr[s]), int(r.indptr[s + 1])
    out = np.arange(lo, hi)
    d = res[out] if budget is None else np.minimum(res[out], budget)
    res[r.rev[out]] += d
    np.add.at(e, r.heads[out], d)
    res[out] -= d
    e[s] = 0
    h = np.zeros(r.n, STATE_DTYPE)
    return (as_state_dtype(res, "warm-start res"), h,
            as_state_dtype(e, "warm-start excess"))


def find_arc(r: ResidualCSR, u: int, v: int) -> int:
    """Index of the directed arc u->v; raises KeyError when the pair does
    not exist (a structural change — callers must rebuild the CSR).

    Scans only u's arc segment (O(log deg) on bcsr, whose segments are
    head-sorted; O(deg) on rcsr) — this sits on the capacity-update path
    of every warm re-solve."""
    if not 0 <= u < r.n:
        raise KeyError(f"no arc {u}->{v} in graph")
    lo, hi = int(r.indptr[u]), int(r.indptr[u + 1])
    seg = r.heads[lo:hi]
    if r.binary_search_ready():
        i = int(np.searchsorted(seg, v))
        if i < seg.size and seg[i] == v:
            return lo + i
    else:
        hit = np.nonzero(seg == v)[0]
        if hit.size:
            return lo + int(hit[0])
    raise KeyError(f"no arc {u}->{v} in graph")


def apply_capacity_increases(r: ResidualCSR, res: np.ndarray,
                             updates) -> tuple[ResidualCSR, np.ndarray]:
    """Apply ``(u, v, delta>=0)`` capacity increases to a solved residual.

    Returns ``(updated ResidualCSR, updated res)``; raises ``KeyError`` if
    ``(u, v)`` is not an existing directed pair (a structural change — the
    caller must fall back to a cold solve on a rebuilt CSR) and
    ``ValueError`` for negative deltas (not warm-startable: reducing
    capacity below routed flow creates deficits push-relabel cannot drain).
    """
    res = np.asarray(res, np.int64).copy()  # lint-ok: int64-state-cast
    res0 = r.res0.copy()
    for u, v, delta in updates:
        if delta < 0:
            raise ValueError("capacity decreases are not warm-startable")
        a = find_arc(r, u, v)
        res[a] += delta
        res0[a] += delta
    return dataclasses.replace(r, res0=res0), res
