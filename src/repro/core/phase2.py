"""Device-resident phase 2: preflow -> flow conversion (flow decomposition).

The solver (``repro.core.pushrelabel``) terminates with a maximum *preflow*:
``e[t]`` is the max-flow value, but vertices that were deactivated by the
global relabel may hold stranded excess, so ``res0 - res`` is not yet a
conservation-respecting flow.  The classic fix walks flow backwards from
each excess vertex to the source, one host-side BFS per vertex — the only
remaining O(V*E) host loop in the serving path.

Baumstark et al. (arXiv:1507.01926) observe the second phase is itself
parallelizable: every stranded unit of excess is flow-connected to ``s``
(flow decomposition of a preflow = s->excess paths + s->t paths + cycles),
so *all* excess can be drained at once by cancelling flow along arcs that
step closer to the source.  This module is the bulk-synchronous device
formulation, built from the same primitives as phase 1:

* **heights**: a reverse BFS from ``s`` over flow-carrying arcs — literally
  ``globalrelabel.residual_distances`` on the pseudo-residual
  ``fin[a] = flow(rev[a])`` (an arc is traversable v<-w iff w currently
  sends flow to v), swept to fixpoint with segmented mins;
* **cancellation**: every stranded vertex returns its excess along all
  of its height-decreasing inbound flow arcs in one step, filling them
  in arc order up to its excess (a segmented exclusive prefix of
  ``fin``) — dense work over the arcs, with no frontier and no arc
  selection, so a vertex holding k units over k unit arcs drains in one
  step, not k.  Arc ownership by the draining vertex makes the
  bulk-synchronous apply conflict-free: within a coalesced pair only
  one direction can carry positive flow, so no arc and its partner are
  ever both drained.

Cancellations are restricted to *strictly height-decreasing* arcs, so
excess can never cycle under a fixed height assignment; when the inner
loop drains no more (flow arcs it relied on were cancelled away), the
outer loop recomputes heights — the exact [cycles -> global relabel]
structure of phase 1.  Each pass with fresh heights is guaranteed
progress by the BFS property (a stranded vertex at height ``d`` has an
inbound flow arc from height ``d-1``), so the potential
``sum_v e[v] * height[v]`` strictly decreases and the loop terminates
with all excess returned to ``s``.

Everything here is jit- and vmap-compatible (``meta`` static, ``s``/``t``
traced): the batched solver corrects whole microbatches in one dispatch
(``repro.core.batched.batched_phase2``).  The host BFS survives as
``pushrelabel.convert_preflow_to_flow(..., reference=True)`` — the test
oracle and escape hatch.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core import globalrelabel as gr
from repro.core import pushrelabel as pr
from repro.core.csr import ResidualCSR
from repro.obs import scopes


def inflow(g: pr.DeviceGraph, res0: jax.Array, res: jax.Array) -> jax.Array:
    """Per-arc inbound flow: ``fin[a]`` is the flow currently carried by
    ``rev[a]``, i.e. the flow arriving at ``tails[a]`` from ``heads[a]``.
    Positive entries are exactly the arcs phase 2 may cancel along, and
    ``fin`` doubles as the pseudo-residual for the height BFS."""
    return (res0 - res)[g.rev]


def flow_heights_impl(g: pr.DeviceGraph, meta, res0, res, s,
                      minh_fn: Callable | None = None):
    """Exact distance-from-``s`` along flow-carrying arcs, by reverse BFS
    over ``inflow`` — ``residual_distances`` with the source as the sink.
    Unreachable vertices get INF (possible only for excess-free ones).
    ``minh_fn`` runs the sweeps on the Pallas tile kernel."""
    return gr.residual_distances_impl(g, meta, inflow(g, res0, res), s,
                                      minh_fn=minh_fn)


def _drain_step(g: pr.DeviceGraph, meta, res0, res, height, e, s, t):
    """One bulk-synchronous cancellation, dense over the arcs: every
    stranded vertex ``u`` returns its excess along ALL of its strictly
    height-decreasing inbound flow arcs at once, filling them in arc
    order (``height`` holds the flow heights, distance from s).

    On each arc ``a`` of ``u``'s segment the eligible weight is
    ``w[a] = fin[a]`` (clamped to ``e[u]``, which changes no ``d``) and
    ``u`` cancels ``d[a] = clip(e[u] - excl[a], 0, w[a])``, ``excl`` being
    the exclusive prefix of ``w`` within the segment.  Within a coalesced
    pair only one direction carries positive flow, so ``a`` and
    ``rev[a]`` are never both eligible and the dense apply has no
    conflicts.  Returns ``(res, e, cancels)``: ``cancels`` counts the
    arcs with ``d > 0``.
    """
    n = meta.n
    v = jnp.arange(n)
    strand = (e > 0) & (v != s) & (v != t)
    fin = inflow(g, res0, res)
    e_u = jnp.where(strand, e, 0)[g.tails]
    elig = (e_u > 0) & (fin > 0) & (height[g.heads] < height[g.tails])
    w = jnp.where(elig, jnp.minimum(fin, e_u), 0)
    # exclusive prefix within each segment as the difference of one
    # global int32 cumsum at the segment start: the global sum wraps past
    # 2**31 on large stranded totals, but the difference is exact modulo
    # 2**32, so exact while a segment's clamped prefix fits int32
    x = jnp.cumsum(w) - w
    start = jnp.take(x, g.indptr[:-1], mode="clip")
    excl = x - start[g.tails]
    d = jnp.clip(e_u - excl, 0, w)
    # cancel d on rev[a]: res[a] -= d restores the partner, res[rev[a]]
    # += d undoes the flow; u loses sum d, each head gains its share
    back = d[g.rev]
    res = res - d + back
    e = e - jax.ops.segment_sum(d - back, g.tails, num_segments=n,
                                indices_are_sorted=True)
    return res, e, jnp.sum(d > 0, dtype=jnp.int32)


class Phase2Stats(NamedTuple):
    """Iteration counts of one phase 2: ``passes`` height recomputations
    (outer passes), ``steps`` cancellation steps summed over them, each
    pass's last, no-movement step included, and ``cancels`` the arcs
    that cancelled flow, summed over the steps (``cancels / steps`` is
    how many arcs a step drains).  int32 on the device, Python ints once
    fetched."""

    passes: Any
    steps: Any
    cancels: Any


def phase2_impl(g: pr.DeviceGraph, meta, res0, res, e, s, t,
                minh_fn: Callable | None = None):
    """Drain all stranded excess at once; device-side, vmap-compatible.

    Returns ``(res, e, leftover, stats)``: the corrected residual (a
    genuine flow when ``leftover == 0``), the cleaned excess (zero
    everywhere but ``e[t] == maxflow``), the excess that could not be
    drained (non-zero only if the input was not a valid preflow —
    callers raise), and the ``Phase2Stats`` counters of the loops.
    ``meta`` must be static; ``s``/``t`` may be traced scalars.
    ``minh_fn`` runs the height sweeps on the Pallas tile kernel; the
    cancellation selects no arc, so it has no hook.
    """
    with jax.named_scope(scopes.PHASE2):
        n = meta.n
        v = jnp.arange(n)

        def stranded(e):
            return jnp.sum(jnp.where((v != s) & (v != t), e, 0))

        def outer_cond(carry):
            _, e, progressed, _ = carry
            return (stranded(e) > 0) & progressed

        def outer_body(carry):
            res, e, _, stats = carry
            e_before = e
            height, _ = flow_heights_impl(g, meta, res0, res, s,
                                          minh_fn=minh_fn)

            def inner_body(c):
                res, e, _, steps, cancels = c
                res2, e2, k = _drain_step(g, meta, res0, res, height, e,
                                          s, t)
                return res2, e2, jnp.any(e2 != e), steps + 1, cancels + k

            res, e, _, steps, cancels = engine.run_bulk_loop(
                inner_body, (res, e, jnp.bool_(True), stats.steps,
                             stats.cancels),
                cond_fn=lambda c: c[2])
            # no movement under fresh heights => invariant violated: bail out
            # instead of spinning (the host wrapper turns this into an error)
            return (res, e, jnp.any(e != e_before),
                    Phase2Stats(stats.passes + 1, steps, cancels))

        # chunk=1: one outer step is a full [heights -> cancel-to-fixpoint]
        # pass — scanning speculative passes would be pure gated waste
        zero = jnp.int32(0)
        res, e, _, stats = engine.run_bulk_loop(
            outer_body, (res, e, jnp.bool_(True),
                         Phase2Stats(zero, zero, zero)),
            cond_fn=outer_cond, chunk=1)
        leftover = stranded(e)
        # a flow: only the sink holds excess
        e = jnp.zeros_like(e).at[t].set(e[t])
        return res, e, leftover, stats


phase2_run = functools.partial(
    jax.jit, static_argnames=("meta", "minh_fn"))(phase2_impl)


# ---------------------------------------------------------------------------
# batch-level formulation (stacked (B, ...) rows, shared sweep loops)
# ---------------------------------------------------------------------------

def batched_inflow(g: pr.DeviceGraph, res0, res):
    """``inflow`` over stacked rows: per-row gather of ``(res0-res)[rev]``."""
    return jnp.take_along_axis(res0 - res, g.rev, axis=1)


def _batched_drain_step(g: pr.DeviceGraph, meta, res0, res, height, e,
                        s, t):
    """Batch-level ``_drain_step``: the same rule, one row per vmapped
    lane, so every row moves exactly what the per-instance step moves."""

    def one(indptr, heads, tails, rev, r0, res_r, h_r, e_r, s_r, t_r):
        gr_ = pr.DeviceGraph(indptr, heads, tails, rev)
        return _drain_step(gr_, meta, r0, res_r, h_r, e_r, s_r, t_r)[:2]

    return jax.vmap(one)(g.indptr, g.heads, g.tails, g.rev, res0, res,
                         height, e, s, t)


def batched_phase2_impl(g: pr.DeviceGraph, meta, res0, res, e, s, t,
                        minh_fn: Callable | None = None):
    """Batch-level :func:`phase2_impl`: drain every instance's stranded
    excess with shared [heights -> cancel-to-fixpoint] loops — each
    height sweep executes as ONE batch-grid launch per step under a
    kernel ``minh_fn``.

    Rows that finish (or stall) earlier are fixpoints of both loops, so
    the result is bit-for-bit what vmapping the per-instance
    ``phase2_impl`` produces: each row's trajectory depends only on its
    own arrays, and a stalled row's heights recompute to the same values
    whenever the batch-level outer loop runs.  Returns
    ``(res, e, leftover)`` with per-row ``leftover``, and no
    ``Phase2Stats``: the loops are shared, so their counts belong to the
    batch, not to a row.
    """
    with jax.named_scope(scopes.PHASE2):
        n = meta.n
        B = res.shape[0]
        rows = jnp.arange(B)
        v = jnp.arange(n)
        inner_m = (v[None, :] != s[:, None]) & (v[None, :] != t[:, None])

        def stranded(e):
            return jnp.sum(jnp.where(inner_m, e, 0), axis=1)

        def outer_cond(carry):
            _, e, progressed = carry
            return jnp.any((stranded(e) > 0) & progressed)

        def outer_body(carry):
            res, e, _ = carry
            e_before = e
            height, _ = gr.batched_residual_distances_impl(
                g, meta, batched_inflow(g, res0, res), s, minh_fn=minh_fn)

            def inner_body(c):
                res, e, _ = c
                res2, e2 = _batched_drain_step(g, meta, res0, res, height,
                                               e, s, t)
                return res2, e2, jnp.any(e2 != e)

            res, e, _ = engine.run_bulk_loop(
                inner_body, (res, e, jnp.bool_(True)), cond_fn=lambda c: c[2])
            # a row that moved nothing under fresh heights can never move
            # again (its state is unchanged): mark it done/stuck
            return res, e, jnp.any(e != e_before, axis=1)

        res, e, _ = engine.run_bulk_loop(
            outer_body, (res, e, jnp.ones(B, bool)), cond_fn=outer_cond,
            chunk=1)
        leftover = stranded(e)
        e = jnp.zeros_like(e).at[rows, t].set(e[rows, t])
        return res, e, leftover


def convert_preflow_to_flow_device(r: ResidualCSR, state: pr.PRState,
                                   s: int, t: int,
                                   minh_fn: Callable | None = None
                                   ) -> tuple[np.ndarray, Phase2Stats]:
    """Host entry point for a single instance: run the device phase 2 and
    return the corrected ``res`` (int64 numpy, matching the host
    reference's convention) with the loops' ``Phase2Stats``.  States with
    no stranded excess are returned untouched without a device dispatch,
    with zero counts.  ``minh_fn`` executes the height sweeps on the
    Pallas tile kernel (results are bit-for-bit identical)."""
    e = np.asarray(state.e)
    inner = np.ones(r.n, bool)
    inner[[s, t]] = False
    if not (e[inner] > 0).any():  # already a genuine flow
        return (np.asarray(state.res, np.int64).copy(),  # lint-ok: int64-state-cast
                Phase2Stats(0, 0, 0))
    g, meta, res0 = pr.to_device(r)
    res, _, leftover, stats = phase2_run(
        g, meta, res0, jnp.asarray(state.res, jnp.int32),
        jnp.asarray(e, jnp.int32), jnp.int32(s), jnp.int32(t),
        minh_fn=minh_fn)
    if int(leftover) != 0:
        raise RuntimeError(
            f"phase 2 could not drain {int(leftover)} units of excess back "
            "to the source — the state is not a valid preflow for this "
            "graph (excess must be flow-connected to s)")
    return (np.asarray(res, np.int64),  # lint-ok: int64-state-cast
            Phase2Stats(*(int(x) for x in stats)))
