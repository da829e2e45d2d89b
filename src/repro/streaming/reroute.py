"""Device-resident flow rerouting for capacity decreases.

A capacity decrease on arc ``(u, v)`` only invalidates the routed flow
when the arc carried more than the new capacity.  Instead of
cold-solving, the overflow ``o = flow - new_cap`` is *cancelled* on the
arc, which leaves a pseudo-flow with a signed per-vertex imbalance
``b``: ``+o`` of excess at ``u`` (units it was forwarding that no longer
fit) and ``-o`` of deficit at ``v`` (units it was passing on that no
longer arrive).  Both imbalances are drained on-device with the same
height-bounded bulk-synchronous cancellation as the phase-2
preflow->flow conversion (``repro.core.phase2``); the deficit drain
selects one arc a vertex a step with the flat-frontier segmented min
behind the shared ``minh_fn`` hook — kernel modes run the reroute's
sweeps and selections on the Pallas tile kernel unchanged:

* **deficit first**, along *outbound* flow arcs toward the multi-sink
  set ``{t} ∪ {vertices with excess}``.  Heights are the exact distance
  to that set over the pseudo-residual ``fout[a] = flow(a)`` (a
  Bellman-Ford sweep identical to ``globalrelabel.residual_distances``
  but seeded at every sink).  Deficit reaching ``t`` reduces the flow
  value; deficit reaching an excess vertex annihilates against it
  (that pairing is what retires cancelled *cycle* flow, which has no
  path to ``t`` at all).  By pseudo-flow decomposition every deficit
  vertex has an outbound flow path into the sink set, so each pass with
  fresh heights makes progress.
* **excess second**, along inbound flow arcs back to ``s`` — literally
  ``phase2_impl``: once no deficits remain, every leftover excess is
  flow-connected to the source.

The result is a feasible (conservation-respecting) flow on the updated
capacities whose value is ``old_value - drained``; re-entering the
solver warm with budget ``drained + total_increases`` recovers
maximality (the new optimum exceeds the drained value by at most that
much), and a zero budget means the flow is *already* maximal — no
solver dispatch at all.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import batched
from repro.core import engine
from repro.core import globalrelabel as gr
from repro.core import phase2
from repro.core import pushrelabel as pr
from repro.core.csr import ResidualCSR
from repro.obs import counter, span

INF = gr.INF


# ---------------------------------------------------------------------------
# host side: apply signed capacity deltas, cancel overflow
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RerouteResult:
    """Outcome of applying signed updates to a corrected flow."""

    residual: ResidualCSR  # updated capacities (res0)
    res: np.ndarray  # feasible flow on the new capacities (int32)
    e: np.ndarray  # zero everywhere but e[t] == value (int32)
    value: int  # flow value after the drain (pre-re-solve)
    budget: int  # warm re-solve budget; 0 => already maximal
    overflow: int  # units cancelled on decreased arcs
    rerouted: bool  # a device drain actually ran
    ok: bool  # False => drain stalled, caller must cold-solve


@dataclasses.dataclass
class PreparedReroute:
    """Host-side outcome of ``prepare_signed``: updated capacities plus the
    cancelled-overflow imbalance, staged for a (possibly pooled) device
    drain.  ``overflow == 0`` means no drain is needed — ``finish()``
    answers directly."""

    residual: ResidualCSR  # updated capacities (res0)
    res: np.ndarray  # int64 post-cancel pseudo-flow
    b: np.ndarray  # int64 signed per-vertex imbalance
    e: np.ndarray  # int64 corrected excess of the pre-update flow
    s: int
    t: int
    old_value: int
    inc_total: int
    overflow: int


def prepare_signed(r: ResidualCSR, res: np.ndarray, e: np.ndarray,
                   s: int, t: int, ups) -> PreparedReroute:
    """The host half of ``apply_signed``: fold ``(u, v, signed_delta)``
    updates into the capacities, cancel overflow on decreased arcs and
    account the signed imbalance — NO device work.  Raises ``KeyError``
    for a missing arc and ``ValueError`` for a capacity driven below
    zero.  Preparations from many independent streams can then be pooled
    into one device drain (``drain_prepared``)."""
    res0 = np.asarray(r.res0, np.int64).copy()  # lint-ok: int64-state-cast
    res = np.asarray(res, np.int64).copy()  # lint-ok: int64-state-cast
    b = np.zeros(r.n, np.int64)
    inc_total = 0
    overflow = 0
    for u, v, delta in ups:
        a = batched.find_arc(r, u, v)
        if delta >= 0:
            res0[a] += delta
            res[a] += delta
            inc_total += delta
            continue
        c_new = res0[a] + delta
        if c_new < 0:
            raise ValueError(
                f"capacity of {u}->{v} would go negative "
                f"({int(res0[a])} {delta:+d})")
        f = res0[a] - res[a]  # current flow on the arc (negative: reverse)
        o = max(0, int(f - c_new))
        res0[a] = c_new
        res[a] += delta + o  # == c_new - min(f, c_new): never negative
        if o:
            res[r.rev[a]] -= o  # cancelled flow returns its reverse slack
            b[u] += o  # tail keeps units it can no longer forward
            b[v] -= o  # head no longer receives them
            overflow += o
    b[s] = 0  # the source absorbs/supplies freely; never an imbalance
    return PreparedReroute(
        residual=dataclasses.replace(r, res0=res0), res=res, b=b,
        e=np.asarray(e, np.int64).copy(), s=s, t=t, old_value=int(e[t]),  # lint-ok: int64-state-cast
        inc_total=inc_total, overflow=overflow)


def _finish(prep: PreparedReroute, res_j: np.ndarray, e_j: np.ndarray,
            stalled: int) -> RerouteResult:
    """Fold a drained ``(res, e)`` pair back into a ``RerouteResult``
    (counter accounting included) — shared by the single-instance and the
    pooled drain paths."""
    if stalled:
        # invariant violated (the input was not a corrected flow): loud
        # counter, graceful answer — the caller cold-solves
        counter("stream.reroute.stalls").inc()
        return RerouteResult(residual=prep.residual, res=np.asarray(res_j),
                             e=np.asarray(e_j), value=prep.old_value,
                             budget=0, overflow=prep.overflow,
                             rerouted=True, ok=False)
    value = int(np.asarray(e_j)[prep.t])
    counter("stream.reroute.drained_units").inc(
        max(0, prep.old_value - value))
    return RerouteResult(
        residual=prep.residual, res=np.asarray(res_j), e=np.asarray(e_j),
        value=value,
        budget=max(0, prep.old_value + prep.inc_total - value),
        overflow=prep.overflow, rerouted=True, ok=True)


def _no_drain_result(prep: PreparedReroute) -> RerouteResult:
    """Pure increases (or slack-only decreases): no device drain."""
    return RerouteResult(
        residual=prep.residual,
        res=batched.as_state_dtype(prep.res, "updated res"),
        e=batched.as_state_dtype(prep.e, "updated excess"),
        value=prep.old_value, budget=prep.inc_total, overflow=0,
        rerouted=False, ok=True)


def apply_signed(r: ResidualCSR, res: np.ndarray, e: np.ndarray,
                 s: int, t: int, ups, use_kernel: bool = False,
                 interpret: bool | None = None) -> RerouteResult:
    """Apply ``(u, v, signed_delta)`` updates to a phase-2-corrected
    ``(res, e)`` flow and reroute any overflowed flow on-device.

    Increases follow ``batched.apply_capacity_increases`` semantics
    (residual grows, flow untouched).  Decreases below the currently
    routed flow cancel the overflow and drain the resulting imbalance
    (module docstring); decreases that stay above the routed flow are
    free.  Raises ``KeyError`` for a missing arc and ``ValueError`` for
    a capacity driven below zero.
    """
    prep = prepare_signed(r, res, e, s, t, ups)
    if prep.overflow == 0:
        return _no_drain_result(prep)

    counter("stream.reroute.applies").inc()
    counter("stream.reroute.overflow_units").inc(prep.overflow)
    minh_fn = None
    if use_kernel:
        from repro.kernels import ops as kops
        minh_fn = kops.min_neighbor_minh_fn(interpret)
    r2 = prep.residual
    g, meta, _ = pr.to_device(r2)
    with span("stream.reroute", n=r2.n, arcs=r2.num_arcs,
              overflow=prep.overflow):
        res_j, e_j, deficit_left, excess_left = _reroute_run(
            g, meta,
            jnp.asarray(batched.as_state_dtype(r2.res0, "caps")),
            jnp.asarray(batched.as_state_dtype(prep.res, "reroute res")),
            jnp.asarray(batched.as_state_dtype(prep.b,
                                               "reroute imbalance")),
            jnp.asarray(batched.as_state_dtype(prep.e, "reroute excess")),
            jnp.int32(s), jnp.int32(t), minh_fn=minh_fn)
        stalled = int(deficit_left) + int(excess_left)
    return _finish(prep, res_j, e_j, stalled)


def drain_prepared(preps: list[PreparedReroute], use_kernel: bool = False,
                   interpret: bool | None = None) -> list[RerouteResult]:
    """Drain MANY prepared reroutes in ONE pooled device dispatch.

    The overflowed preparations are packed into stacked ``(B, ...)`` rows
    (``batched.pack_instances`` shapes; the imbalance vector rides in the
    height slot) and the whole pool runs through the batched drain
    (``_batched_reroute_run``) — one engine loop per phase for every
    stream at once, ONE batch-grid ``pallas_call`` per sweep step under
    kernel modes.  Overflow-free preparations are answered inline without
    device work.  Results are bit-for-bit what per-stream
    ``apply_signed`` produces: each row's trajectory depends only on its
    own arrays (see ``phase2.batched_phase2_impl``).
    """
    out: list[RerouteResult | None] = [None] * len(preps)
    todo = []
    for i, prep in enumerate(preps):
        if prep.overflow == 0:
            out[i] = _no_drain_result(prep)
        else:
            todo.append(i)
            counter("stream.reroute.applies").inc()
            counter("stream.reroute.overflow_units").inc(prep.overflow)
    if not todo:
        return out  # type: ignore[return-value]
    minh_fn = None
    if use_kernel:
        from repro.kernels import ops as kops
        minh_fn = kops.min_neighbor_minh_fn(interpret)
    pool = [preps[i] for i in todo]
    bg, meta, res0_p, _ = batched.pack_instances(
        [(p.residual, p.s, p.t) for p in pool])
    state = batched.pack_states(
        [(batched.as_state_dtype(p.res, "reroute res"),
          batched.as_state_dtype(p.b, "reroute imbalance"),
          batched.as_state_dtype(p.e, "reroute excess")) for p in pool],
        meta.n, meta.num_arcs)
    counter("stream.reroute.batched_dispatches").inc()
    with span("stream.reroute.pooled", streams=len(pool), n=meta.n,
              arcs=meta.num_arcs,
              overflow=sum(p.overflow for p in pool)):
        res_j, e_j, deficit_left, excess_left = _batched_reroute_run(
            pr.DeviceGraph(bg.indptr, bg.heads, bg.tails, bg.rev), meta,
            res0_p, state.res, state.h, state.e, bg.s, bg.t,
            minh_fn=minh_fn)
        res_np, e_np = np.asarray(res_j), np.asarray(e_j)
        dl, xl = np.asarray(deficit_left), np.asarray(excess_left)
    for row, i in enumerate(todo):
        p = preps[i]
        out[i] = _finish(p, res_np[row, : p.residual.num_arcs],
                         e_np[row, : p.residual.n],
                         int(dl[row]) + int(xl[row]))
    return out  # type: ignore[return-value]


def apply_signed_batched(items, use_kernel: bool = False,
                         interpret: bool | None = None
                         ) -> list[RerouteResult]:
    """``apply_signed`` over many independent streams with the overflow
    drains POOLED into one device dispatch.  ``items`` is a list of
    ``(r, res, e, s, t, ups)`` tuples; returns one ``RerouteResult`` per
    item, bit-for-bit equal to calling ``apply_signed`` per item."""
    preps = [prepare_signed(r, res, e, s, t, ups)
             for r, res, e, s, t, ups in items]
    return drain_prepared(preps, use_kernel=use_kernel,
                          interpret=interpret)


# ---------------------------------------------------------------------------
# device side: deficit drain (mirror of phase 2) + excess drain (phase 2)
# ---------------------------------------------------------------------------

def _multi_sink_distances(g, meta, fres, sink, minh_fn=None):
    """Exact distance to the nearest sink over ``fres``-positive arcs —
    ``globalrelabel.residual_distances_impl`` seeded at a whole vertex
    *set* instead of one sink (``sink`` is a boolean mask) and swept to
    fixpoint through the shared engine."""
    n = meta.n
    dist0 = jnp.where(sink, 0, INF).astype(jnp.int32)

    def sweep(dist):
        if minh_fn is None:
            dh = dist[g.heads]
            key = jnp.where((fres > 0) & (dh < INF), dh + 1, INF)
            cand = jax.ops.segment_min(key, g.tails, num_segments=n,
                                       indices_are_sorted=True)
        else:
            pseudo = pr.PRState(res=fres, h=jnp.minimum(dist + 1, INF),
                                e=None)
            cand, _ = minh_fn(g, meta, pseudo, None, None)
        return jnp.where(sink, 0, jnp.minimum(dist, cand))

    dist, _ = engine.run_to_fixpoint(sweep, dist0, cap=n)
    return dist


def _batched_multi_sink_distances(g, meta, fres, sink, minh_fn=None):
    """Batch-level :func:`_multi_sink_distances` over stacked rows:
    ``fres`` is ``(B, A)``, ``sink`` is a ``(B, n)`` mask.  One shared
    sweep loop serves the whole pool — a kernel ``minh_fn`` executes each
    sweep step as ONE batch-grid launch.  Rows that reach their fixpoint
    earlier are fixpoints of the sweep, so results equal the per-row
    loops bit-for-bit."""
    n = meta.n

    dist0 = jnp.where(sink, 0, INF).astype(jnp.int32)

    def sweep(dist):
        if minh_fn is None:
            def one(dist_r, fres_r, heads_r, tails_r):
                dh = dist_r[heads_r]
                key = jnp.where((fres_r > 0) & (dh < INF), dh + 1, INF)
                return jax.ops.segment_min(key, tails_r, num_segments=n,
                                           indices_are_sorted=True)

            cand = jax.vmap(one)(dist, fres, g.heads, g.tails)
        else:
            pseudo = pr.PRState(res=fres, h=jnp.minimum(dist + 1, INF),
                                e=None)
            cand, _ = minh_fn(g, meta, pseudo, None, None)
        return jnp.where(sink, 0, jnp.minimum(dist, cand))

    dist, _ = engine.run_to_fixpoint(sweep, dist0, cap=n)
    return dist


def _deficit_cancel_step(g, meta, res0, res, height, b, s, t,
                         minh_fn: Callable | None = None):
    """One bulk-synchronous deficit cancellation: every deficit vertex
    retires ``min(-b, flow)`` units of its minimum-height *outbound* flow
    arc, provided that arc steps strictly toward the sink set.  The
    one-arc-a-step counterpart of ``phase2._drain_step`` (which drains
    excess along all its inbound flow arcs at once): arc ownership by the
    selecting vertex keeps the scatter conflict-free — within a coalesced
    pair only one direction can carry positive flow."""
    n, A = meta.n, meta.num_arcs
    v = jnp.arange(n)
    strand = (b < 0) & (v != s) & (v != t)
    fout = res0 - res  # flow currently carried by each arc
    pseudo = pr.PRState(res=fout, h=height, e=-b)
    avq = jnp.nonzero(strand, size=n, fill_value=n)[0].astype(jnp.int32)
    q_valid = avq < n
    u_c = jnp.minimum(avq, n - 1)
    if minh_fn is None:
        minh, argarc = pr._flat_frontier_minh(g, meta, pseudo, avq, q_valid)
    else:
        minh, argarc = minh_fn(g, meta, pseudo, avq, q_valid)
    arc_c = jnp.clip(argarc, 0, A - 1)
    do = q_valid & (minh < height[u_c])  # strictly toward the sink set
    d = jnp.where(do, jnp.minimum(-b[u_c], fout[arc_c]), 0).astype(jnp.int32)

    drop = jnp.int32(A)
    res = res.at[jnp.where(do, arc_c, drop)].add(d, mode="drop")
    res = res.at[jnp.where(do, g.rev[arc_c], drop)].add(-d, mode="drop")
    vdrop = jnp.int32(n)
    b = b.at[jnp.where(do, u_c, vdrop)].add(d, mode="drop")
    b = b.at[jnp.where(do, g.heads[arc_c], vdrop)].add(-d, mode="drop")
    return res, b


def _drain_deficit(g, meta, res0, res, b, s, t,
                   minh_fn: Callable | None = None):
    """Drain every negative imbalance along outbound flow arcs into
    ``{t} ∪ {b > 0}`` with the [heights -> cancel-to-fixpoint] outer/inner
    loop structure of ``phase2_impl``.  Returns ``(res, b, leftover)``."""
    n = meta.n
    v = jnp.arange(n)

    def stranded(b):
        return jnp.sum(jnp.where((v != s) & (v != t),
                                 jnp.maximum(-b, 0), 0))

    def outer_cond(carry):
        _, b, progressed = carry
        return (stranded(b) > 0) & progressed

    def outer_body(carry):
        res, b, _ = carry
        b_before = b
        sink = (v == t) | (b > 0)
        height = _multi_sink_distances(g, meta, res0 - res, sink,
                                       minh_fn=minh_fn)

        def inner_body(c):
            res, b, _ = c
            res2, b2 = _deficit_cancel_step(g, meta, res0, res, height, b,
                                            s, t, minh_fn)
            return res2, b2, jnp.any(b2 != b)

        res, b, _ = engine.run_bulk_loop(
            inner_body, (res, b, jnp.bool_(True)), cond_fn=lambda c: c[2])
        # no movement under fresh heights => bail instead of spinning
        return res, b, jnp.any(b != b_before)

    # chunk=1: one outer step is a full [heights -> cancel-to-fixpoint]
    # pass — scanning speculative passes would be pure gated waste
    res, b, _ = engine.run_bulk_loop(outer_body, (res, b, jnp.bool_(True)),
                                     cond_fn=outer_cond, chunk=1)
    return res, b, stranded(b)


def _reroute_impl(g, meta, res0, res, b, e, s, t,
                  minh_fn: Callable | None = None):
    """The full device drain: deficit toward ``{t} ∪ {excess}``, then the
    leftover excess back to ``s`` via ``phase2_impl``.  ``e`` is the
    corrected excess of the pre-update flow (zero but ``e[t]``).  Returns
    ``(res, e, deficit_left, excess_left)`` — both leftovers zero on
    success, ``e`` again zero everywhere but ``e[t] == new value``."""
    res, b, deficit_left = _drain_deficit(g, meta, res0, res, b, s, t,
                                          minh_fn=minh_fn)
    # fold the signed imbalance into a plain excess vector: positives are
    # stranded excess, b[t] adjusts the flow value (deficit that reached
    # the sink is value lost; excess minted at t by a cancel on an
    # outbound arc of t is value regained by its returning deficit)
    e2 = jnp.maximum(b, 0).at[t].set(e[t] + b[t]).at[s].set(0)
    e2 = e2.astype(jnp.int32)
    res, e3, excess_left, _ = phase2.phase2_impl(g, meta, res0, res, e2, s,
                                                 t, minh_fn=minh_fn)
    return res, e3, deficit_left, excess_left


_reroute_run = functools.partial(
    jax.jit, static_argnames=("meta", "minh_fn"))(_reroute_impl)


# ---------------------------------------------------------------------------
# batch-level formulation: many streams' drains in one dispatch
# ---------------------------------------------------------------------------

def _batched_deficit_cancel_step(g, meta, res0, res, height, b, s, t,
                                 minh_fn: Callable | None = None):
    """Batch-level :func:`_deficit_cancel_step` over stacked ``(B, ...)``
    rows, with outbound flow (``fout = res0 - res``) as the
    pseudo-residual and the negative imbalance as the excess.  Under a
    kernel ``minh_fn`` the selection is ONE batch-grid launch; otherwise
    the per-row flat frontier is vmapped (same choices bit-for-bit)."""
    n, A = meta.n, meta.num_arcs
    v = jnp.arange(n, dtype=jnp.int32)
    strand = ((b < 0) & (v[None, :] != s[:, None])
              & (v[None, :] != t[:, None]))
    fout = res0 - res  # flow currently carried by each arc
    avq = jax.vmap(
        lambda m: jnp.nonzero(m, size=n,
                              fill_value=n)[0].astype(jnp.int32))(strand)
    q_valid = avq < n
    u_c = jnp.minimum(avq, n - 1)
    if minh_fn is None:
        def one_flat(indptr, heads, tails, rev, fout_r, h_r, b_r, q, qv):
            gr_ = pr.DeviceGraph(indptr, heads, tails, rev)
            return pr._flat_frontier_minh(
                gr_, meta, pr.PRState(fout_r, h_r, -b_r), q, qv)

        minh, argarc = jax.vmap(one_flat)(g.indptr, g.heads, g.tails,
                                          g.rev, fout, height, b, avq,
                                          q_valid)
    else:
        pseudo = pr.PRState(res=fout, h=height, e=-b)
        minh, argarc = minh_fn(g, meta, pseudo, avq, q_valid)
    arc_c = jnp.clip(argarc, 0, A - 1)
    hh = jnp.take_along_axis(height, u_c, axis=1)
    do = q_valid & (minh < hh)  # strictly toward the sink set
    d = jnp.where(do, jnp.minimum(-jnp.take_along_axis(b, u_c, axis=1),
                                  jnp.take_along_axis(fout, arc_c, axis=1)),
                  0).astype(jnp.int32)

    def one_apply(res_r, b_r, do_r, arc_r, d_r, u_r, heads_r, rev_r):
        drop = jnp.int32(A)
        res_r = res_r.at[jnp.where(do_r, arc_r, drop)].add(d_r, mode="drop")
        res_r = res_r.at[jnp.where(do_r, rev_r[arc_r], drop)].add(
            -d_r, mode="drop")
        vdrop = jnp.int32(n)
        b_r = b_r.at[jnp.where(do_r, u_r, vdrop)].add(d_r, mode="drop")
        b_r = b_r.at[jnp.where(do_r, heads_r[arc_r], vdrop)].add(
            -d_r, mode="drop")
        return res_r, b_r

    res, b = jax.vmap(one_apply)(res, b, do, arc_c, d, u_c, g.heads, g.rev)
    return res, b


def _batched_drain_deficit(g, meta, res0, res, b, s, t,
                           minh_fn: Callable | None = None):
    """Batch-level :func:`_drain_deficit`: every stream's negative
    imbalance drains at once through the shared [heights ->
    cancel-to-fixpoint] engine loops.  Rows that finish or stall earlier
    are fixpoints of both loops (same argument as
    ``phase2.batched_phase2_impl``), so results match the per-stream
    drains bit-for-bit.  Returns ``(res, b, leftover (B,))``."""
    n = meta.n
    v = jnp.arange(n)
    inner_m = (v[None, :] != s[:, None]) & (v[None, :] != t[:, None])

    def stranded(b):
        return jnp.sum(jnp.where(inner_m, jnp.maximum(-b, 0), 0), axis=1)

    def outer_cond(carry):
        _, b, progressed = carry
        return jnp.any((stranded(b) > 0) & progressed)

    def outer_body(carry):
        res, b, _ = carry
        b_before = b
        rows = jnp.arange(res.shape[0])
        sink = (b > 0).at[rows, t].set(True)
        height = _batched_multi_sink_distances(g, meta, res0 - res, sink,
                                               minh_fn=minh_fn)

        def inner_body(c):
            res, b, _ = c
            res2, b2 = _batched_deficit_cancel_step(g, meta, res0, res,
                                                    height, b, s, t,
                                                    minh_fn)
            return res2, b2, jnp.any(b2 != b)

        res, b, _ = engine.run_bulk_loop(
            inner_body, (res, b, jnp.bool_(True)), cond_fn=lambda c: c[2])
        # a row that moved nothing under fresh heights is done or stuck
        return res, b, jnp.any(b != b_before, axis=1)

    res, b, _ = engine.run_bulk_loop(
        outer_body, (res, b, jnp.ones(res.shape[0], bool)),
        cond_fn=outer_cond, chunk=1)
    return res, b, stranded(b)


def _batched_reroute_impl(g, meta, res0, res, b, e, s, t,
                          minh_fn: Callable | None = None):
    """Batch-level :func:`_reroute_impl`: the full drain for B pooled
    streams in one dispatch — deficit toward each row's ``{t} ∪
    {excess}``, then leftover excess back to each row's ``s`` via
    ``phase2.batched_phase2_impl``.  Returns ``(res, e, deficit_left,
    excess_left)`` with per-row ``(B,)`` leftovers."""
    B = res.shape[0]
    rows = jnp.arange(B)
    res, b, deficit_left = _batched_drain_deficit(g, meta, res0, res, b,
                                                  s, t, minh_fn=minh_fn)
    e2 = jnp.maximum(b, 0)
    e2 = e2.at[rows, t].set(e[rows, t] + b[rows, t])
    e2 = e2.at[rows, s].set(0).astype(jnp.int32)
    res, e3, excess_left = phase2.batched_phase2_impl(
        g, meta, res0, res, e2, s, t, minh_fn=minh_fn)
    return res, e3, deficit_left, excess_left


_batched_reroute_run = functools.partial(
    jax.jit, static_argnames=("meta", "minh_fn"))(_batched_reroute_impl)
