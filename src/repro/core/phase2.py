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
* **cancellation**: every stranded vertex selects its minimum-height
  inbound flow arc with the same flat-frontier segmented min/argmin the
  vertex-centric push uses (``pushrelabel._flat_frontier_minh``, or any
  drop-in ``minh_fn`` such as the Pallas tile kernel
  ``repro.kernels.ops.min_neighbor_kernel``), and cancels
  ``min(e, fin)`` units on it.  Arc ownership by the selecting vertex
  makes the bulk-synchronous apply conflict-free: within a coalesced
  pair only one direction can carry positive flow, so no two vertices
  ever pick partner arcs of each other.

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


def _cancel_step(g: pr.DeviceGraph, meta, res0, state: pr.PRState, s, t,
                 minh_fn: Callable | None = None,
                 scan: bool = False) -> pr.PRState:
    """One bulk-synchronous cancellation: every stranded vertex returns
    ``min(e, fin)`` units along its minimum-height inbound flow arc,
    provided that arc is strictly height-decreasing.  ``state.h`` holds
    the flow heights (distance from s).

    Both selectors pick the *smallest arc index attaining the minimum
    height*, so their results are bit-for-bit identical; they differ only
    in execution shape (see ``phase2_impl``).
    """
    n, A = meta.n, meta.num_arcs
    res, height, e = state
    v = jnp.arange(n)
    strand = (e > 0) & (v != s) & (v != t)
    fin = inflow(g, res0, res)
    # the phase-1 min-height machinery verbatim: res := inbound flow,
    # h := flow heights -> (min height of a flow-sending neighbour, arc)
    pseudo = pr.PRState(res=fin, h=height, e=e)
    if scan:
        u_c, q_valid = v, strand
        minh, argarc = pr._tc_scan_minh(g, meta, pseudo, strand)
    else:
        avq = jnp.nonzero(strand, size=n, fill_value=n)[0].astype(jnp.int32)
        q_valid = avq < n
        u_c = jnp.minimum(avq, n - 1)
        if minh_fn is None:
            minh, argarc = pr._flat_frontier_minh(g, meta, pseudo, avq,
                                                  q_valid)
        else:
            minh, argarc = minh_fn(g, meta, pseudo, avq, q_valid)
    arc_c = jnp.clip(argarc, 0, A - 1)
    do = q_valid & (minh < height[u_c])  # strictly toward the source
    d = jnp.where(do, jnp.minimum(e[u_c], fin[arc_c]), 0).astype(jnp.int32)

    # cancel d on the inbound arc rev[arc_c]:  res[rev[arc]] += d undoes
    # the flow, res[arc] -= d restores its partner.  arc_c lies in the
    # selecting vertex's own segment, so the scattered indices are
    # distinct across the batch of stranded vertices.
    drop = jnp.int32(A)
    res = res.at[jnp.where(do, arc_c, drop)].add(-d, mode="drop")
    res = res.at[jnp.where(do, g.rev[arc_c], drop)].add(d, mode="drop")
    vdrop = jnp.int32(n)
    e = e.at[jnp.where(do, u_c, vdrop)].add(-d, mode="drop")
    e = e.at[jnp.where(do, g.heads[arc_c], vdrop)].add(d, mode="drop")
    return pr.PRState(res=res, h=height, e=e)


class Phase2Stats(NamedTuple):
    """Iteration counts of one phase 2: ``passes`` height recomputations
    (outer passes) and ``steps`` cancellation steps summed over them,
    each pass's last, no-movement step included.  int32 on the device,
    Python ints once fetched."""

    passes: Any
    steps: Any


def phase2_impl(g: pr.DeviceGraph, meta, res0, res, e, s, t,
                minh_fn: Callable | None = None, scan: bool = False):
    """Drain all stranded excess at once; device-side, vmap-compatible.

    Returns ``(res, e, leftover, stats)``: the corrected residual (a
    genuine flow when ``leftover == 0``), the cleaned excess (zero
    everywhere but ``e[t] == maxflow``), the excess that could not be
    drained (non-zero only if the input was not a valid preflow —
    callers raise), and the ``Phase2Stats`` counters of the loops.
    ``meta`` must be static; ``s``/``t`` may be traced scalars.

    ``scan=True`` (static) selects cancellation arcs with the
    thread-centric masked scan (``O(n * deg_max)`` work, but roughly half
    the compiled-program size and per-iteration cost of the flat
    frontier on small padded shapes — the serving correction pool's
    regime); the default flat frontier is workload-balanced
    (``O(sum deg(stranded))``) for large single instances.  Results are
    bit-for-bit identical either way.
    """
    with jax.named_scope(scopes.PHASE2):
        n = meta.n
        v = jnp.arange(n)

        def stranded(e):
            return jnp.sum(jnp.where((v != s) & (v != t), e, 0))

        def outer_cond(carry):
            _, e, progressed, _, _ = carry
            return (stranded(e) > 0) & progressed

        def outer_body(carry):
            res, e, _, passes, steps = carry
            e_before = e
            height, _ = flow_heights_impl(g, meta, res0, res, s,
                                          minh_fn=minh_fn)

            def inner_body(c):
                res, e, _, steps = c
                st = _cancel_step(g, meta, res0, pr.PRState(res, height, e),
                                  s, t, minh_fn, scan)
                return st.res, st.e, jnp.any(st.e != e), steps + 1

            res, e, _, steps = engine.run_bulk_loop(
                inner_body, (res, e, jnp.bool_(True), steps),
                cond_fn=lambda c: c[2])
            # no movement under fresh heights => invariant violated: bail out
            # instead of spinning (the host wrapper turns this into an error)
            return res, e, jnp.any(e != e_before), passes + 1, steps

        # chunk=1: one outer step is a full [heights -> cancel-to-fixpoint]
        # pass — scanning speculative passes would be pure gated waste
        zero = jnp.int32(0)
        res, e, _, passes, steps = engine.run_bulk_loop(
            outer_body, (res, e, jnp.bool_(True), zero, zero),
            cond_fn=outer_cond, chunk=1)
        leftover = stranded(e)
        # a flow: only the sink holds excess
        e = jnp.zeros_like(e).at[t].set(e[t])
        return res, e, leftover, Phase2Stats(passes, steps)


phase2_run = functools.partial(
    jax.jit, static_argnames=("meta", "minh_fn", "scan"))(phase2_impl)


# ---------------------------------------------------------------------------
# batch-level formulation (stacked (B, ...) rows, shared sweep loops)
# ---------------------------------------------------------------------------

def batched_inflow(g: pr.DeviceGraph, res0, res):
    """``inflow`` over stacked rows: per-row gather of ``(res0-res)[rev]``."""
    return jnp.take_along_axis(res0 - res, g.rev, axis=1)


def _batched_cancel_step(g: pr.DeviceGraph, meta, res0, res, height, e,
                         s, t, minh_fn: Callable | None = None,
                         scan: bool = False):
    """Batch-level ``_cancel_step``: one bulk-synchronous cancellation for
    every instance at once.  Under a kernel ``minh_fn`` the selection is
    ONE ``tile_min_neighbor`` launch with grid ``(B, tiles)``; otherwise
    the per-row selectors are vmapped (bit-for-bit the same choices —
    all paths pick the smallest arc index attaining the minimum)."""
    n, A = meta.n, meta.num_arcs
    B = res.shape[0]
    v = jnp.arange(n, dtype=jnp.int32)
    strand = ((e > 0) & (v[None, :] != s[:, None])
              & (v[None, :] != t[:, None]))
    fin = batched_inflow(g, res0, res)
    if scan:
        u_c = jnp.broadcast_to(v, (B, n))
        q_valid = strand

        def one_scan(indptr, heads, tails, rev, fin_r, h_r, e_r, act_r):
            gr_ = pr.DeviceGraph(indptr, heads, tails, rev)
            return pr._tc_scan_minh(gr_, meta, pr.PRState(fin_r, h_r, e_r),
                                    act_r)

        minh, argarc = jax.vmap(one_scan)(g.indptr, g.heads, g.tails,
                                          g.rev, fin, height, e, strand)
    else:
        avq = jax.vmap(
            lambda m: jnp.nonzero(m, size=n,
                                  fill_value=n)[0].astype(jnp.int32))(strand)
        q_valid = avq < n
        u_c = jnp.minimum(avq, n - 1)
        pseudo = pr.PRState(res=fin, h=height, e=e)
        if minh_fn is None:
            def one_flat(indptr, heads, tails, rev, fin_r, h_r, e_r, q, qv):
                gr_ = pr.DeviceGraph(indptr, heads, tails, rev)
                return pr._flat_frontier_minh(
                    gr_, meta, pr.PRState(fin_r, h_r, e_r), q, qv)

            minh, argarc = jax.vmap(one_flat)(g.indptr, g.heads, g.tails,
                                              g.rev, fin, height, e, avq,
                                              q_valid)
        else:
            minh, argarc = minh_fn(g, meta, pseudo, avq, q_valid)
    arc_c = jnp.clip(argarc, 0, A - 1)
    hh = jnp.take_along_axis(height, u_c, axis=1)
    do = q_valid & (minh < hh)  # strictly toward the source
    d = jnp.where(do, jnp.minimum(jnp.take_along_axis(e, u_c, axis=1),
                                  jnp.take_along_axis(fin, arc_c, axis=1)),
                  0).astype(jnp.int32)

    def one_apply(res_r, e_r, do_r, arc_r, d_r, u_r, heads_r, rev_r):
        drop = jnp.int32(A)
        res_r = res_r.at[jnp.where(do_r, arc_r, drop)].add(-d_r,
                                                           mode="drop")
        res_r = res_r.at[jnp.where(do_r, rev_r[arc_r], drop)].add(
            d_r, mode="drop")
        vdrop = jnp.int32(n)
        e_r = e_r.at[jnp.where(do_r, u_r, vdrop)].add(-d_r, mode="drop")
        e_r = e_r.at[jnp.where(do_r, heads_r[arc_r], vdrop)].add(
            d_r, mode="drop")
        return res_r, e_r

    res, e = jax.vmap(one_apply)(res, e, do, arc_c, d, u_c, g.heads, g.rev)
    return res, e


def batched_phase2_impl(g: pr.DeviceGraph, meta, res0, res, e, s, t,
                        minh_fn: Callable | None = None,
                        scan: bool = False):
    """Batch-level :func:`phase2_impl`: drain every instance's stranded
    excess with shared [heights -> cancel-to-fixpoint] loops — the height
    sweeps and (``scan=False``) cancellation selections each execute as
    ONE batch-grid launch per step under a kernel ``minh_fn``.

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
                res2, e2 = _batched_cancel_step(g, meta, res0, res, height, e,
                                                s, t, minh_fn, scan)
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
    with zero counts.  ``minh_fn`` executes the cancellation-arc
    selection on the Pallas tile kernel (results are bit-for-bit
    identical — both selectors pick the smallest arc index attaining the
    minimum height)."""
    e = np.asarray(state.e)
    inner = np.ones(r.n, bool)
    inner[[s, t]] = False
    if not (e[inner] > 0).any():  # already a genuine flow
        return (np.asarray(state.res, np.int64).copy(),  # lint-ok: int64-state-cast
                Phase2Stats(0, 0))
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
            Phase2Stats(int(stats.passes), int(stats.steps)))
