"""Distributed (multi-device / multi-pod) vertex-centric push-relabel.

Vertices are range-partitioned into per-device slabs; each device owns its
slab's CSR arc segment (contiguous because arcs are tail-sorted).  One BSP
superstep = each device runs the vertex-centric push/relabel decision for
its local active vertices, then the state deltas are combined collectively.

Two exchange strategies (the paper-core §Perf hillclimb):

* ``replicated`` (baseline): res/h/e replicated on every device; per-arc
  deltas are a dense (A,) ``psum`` — simple, O(A) wire bytes per superstep.
* ``sharded`` (optimized): each device keeps only its own arc-slab residuals
  (A/P per device); cross-slab reverse-arc deltas travel through a
  ``psum_scatter`` (~2x fewer wire bytes than the all-reduce, and O(A/P)
  residual memory per device).  h/e stay replicated via (V,) psums.

Heights/excess psums are the (V,)-sized control plane; the paper's
global-relabel BFS distributes as pmin sweeps over the same partition.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import engine
from repro.core.csr import ResidualCSR

INF = jnp.int32(2**30)


@dataclasses.dataclass(frozen=True)
class DistMeta:
    n: int          # global vertices (padded to P * vs)
    num_arcs: int   # global arcs (sum of padded slabs)
    vs: int         # vertices per shard
    amax: int       # arc slots per shard (padded)
    nshards: int
    s: int
    t: int
    mode: str       # replicated | sharded


class DistGraph(NamedTuple):
    """Leading dim = shard. Arc slot k of shard w has global id w*amax+k."""
    indptr: jax.Array   # (P, vs+1) int32 — local, relative offsets
    heads: jax.Array    # (P, amax) int32 — global head vertex (n = pad)
    rev: jax.Array      # (P, amax) int32 — global reverse-arc id
    tail_local: jax.Array  # (P, amax) int32 — local tail index (vs = pad)


def partition_graph(r: ResidualCSR, nshards: int, s: int, t: int,
                    mode: str = "replicated"):
    """Host-side partitioning: pad vertices to P*vs and arcs to P*amax.
    Arc global ids are re-indexed slab-major: shard w, slot k -> w*amax+k."""
    n0 = r.n
    vs = -(-n0 // nshards)
    n = vs * nshards
    deg = np.diff(r.indptr)
    slab_arcs = [int(deg[w * vs:(w + 1) * vs].sum()) for w in range(nshards)]
    amax = max(1, max(slab_arcs))
    indptr = np.zeros((nshards, vs + 1), np.int32)
    heads = np.full((nshards, amax), n, np.int32)
    tail_local = np.full((nshards, amax), vs, np.int32)
    res0 = np.zeros((nshards, amax), np.int64)
    newid = np.full(r.num_arcs, -1, np.int64)  # old arc id -> new global id
    for w in range(nshards):
        lo = w * vs
        hi = min((w + 1) * vs, n0)
        a0 = r.indptr[lo] if lo < n0 else r.indptr[-1]
        a1 = r.indptr[hi] if hi <= n0 else r.indptr[-1]
        cnt = a1 - a0
        d = np.diff(r.indptr[lo:hi + 1]) if hi > lo else np.zeros(0, int)
        indptr[w, 1:1 + len(d)] = np.cumsum(d)
        indptr[w, 1 + len(d):] = indptr[w, len(d)] if len(d) else 0
        heads[w, :cnt] = r.heads[a0:a1]
        tail_local[w, :cnt] = r.tails[a0:a1] - lo
        res0[w, :cnt] = r.res0[a0:a1]
        newid[a0:a1] = w * amax + np.arange(cnt)
    rev = np.full((nshards, amax), nshards * amax, np.int64)
    old_rev_new = newid[r.rev]
    for w in range(nshards):
        lo = w * vs
        hi = min((w + 1) * vs, n0)
        a0 = r.indptr[lo] if lo < n0 else r.indptr[-1]
        a1 = r.indptr[hi] if hi <= n0 else r.indptr[-1]
        rev[w, : a1 - a0] = old_rev_new[a0:a1]
    g = DistGraph(
        indptr=jnp.asarray(indptr),
        heads=jnp.asarray(heads, jnp.int32),
        rev=jnp.asarray(rev, jnp.int32),
        tail_local=jnp.asarray(tail_local, jnp.int32),
    )
    meta = DistMeta(n=n, num_arcs=nshards * amax, vs=vs, amax=amax,
                    nshards=nshards, s=s, t=t, mode=mode)
    return g, meta, jnp.asarray(res0, jnp.int32).reshape(-1)


# ---------------------------------------------------------------------------
# local superstep body (runs inside shard_map; arrays carry no shard dim)
# ---------------------------------------------------------------------------

def _local_decide(meta: DistMeta, indptr, heads, res_key, h, e, v0):
    """Vertex-centric decision for this slab.  ``res_key`` is the per-local-
    arc residual (length amax).  Returns (u_gl, do_push, d, k_arc, newh)."""
    vs, amax, n = meta.vs, meta.amax, meta.n
    vloc = jnp.arange(vs, dtype=jnp.int32)
    u_gl = v0 + vloc
    act = (e[u_gl] > 0) & (h[u_gl] < n) & (u_gl != meta.s) & (u_gl != meta.t)
    avq = jnp.nonzero(act, size=vs, fill_value=vs)[0].astype(jnp.int32)
    q_valid = avq < vs
    avq_c = jnp.minimum(avq, vs - 1)
    deg = jnp.where(q_valid, indptr[avq_c + 1] - indptr[avq_c], 0)
    offs = jnp.cumsum(deg)
    starts = offs - deg
    total = offs[-1]
    pos = jnp.arange(amax, dtype=jnp.int32)
    row = jnp.repeat(jnp.arange(vs, dtype=jnp.int32), deg,
                     total_repeat_length=amax)
    fvalid = pos < total
    row = jnp.where(fvalid, row, 0)
    k = jnp.clip(indptr[avq_c[row]] + (pos - starts[row]), 0, amax - 1)
    hd = jnp.minimum(heads[k], n - 1)
    key = jnp.where(fvalid & (res_key[k] > 0), h[hd], INF)
    minh = jax.ops.segment_min(key, row, num_segments=vs,
                               indices_are_sorted=True)
    cand = jnp.where(fvalid & (key == minh[row]), k, jnp.int32(amax))
    argk = jax.ops.segment_min(cand, row, num_segments=vs,
                               indices_are_sorted=True)
    minh = jnp.where(q_valid, minh, INF)
    u_q = v0 + avq_c  # global vertex per queue row
    can = q_valid & (minh < INF)
    do_push = can & (h[jnp.minimum(u_q, n - 1)] > minh)
    k_arc = jnp.clip(argk, 0, amax - 1)
    d = jnp.where(do_push,
                  jnp.minimum(e[jnp.minimum(u_q, n - 1)], res_key[k_arc]), 0)
    do_relabel = q_valid & ~do_push
    newh = jnp.where(can, minh + 1, jnp.int32(n))
    return u_q, q_valid, do_push, do_relabel, d, k_arc, newh


def make_dist_step(meta: DistMeta, axes, mesh=None):
    """One jittable BSP superstep under shard_map."""
    n, A, vs, amax = meta.n, meta.num_arcs, meta.vs, meta.amax

    def local_step(indptr, heads, rev, res, h, e):
        indptr, heads, rev = indptr[0], heads[0], rev[0]
        w = jax.lax.axis_index(axes)
        v0 = (w * vs).astype(jnp.int32)
        if meta.mode in ("sharded", "sparse"):
            res_l = res[0]
            res_key = res_l
        else:
            res_key = jax.lax.dynamic_slice_in_dim(res, w * amax, amax)
        u_q, q_valid, do_push, do_relabel, d, k_arc, newh = _local_decide(
            meta, indptr, heads, res_key, h, e, v0)

        vdrop, adrop = jnp.int32(n), jnp.int32(A)
        g_arc = jnp.where(do_push, w * amax + k_arc, adrop)
        g_rev = jnp.where(do_push, rev[k_arc], adrop)
        hd = jnp.minimum(heads[k_arc], n - 1)

        de = jnp.zeros(n, jnp.int32)
        de = de.at[jnp.where(do_push, u_q, vdrop)].add(-d, mode="drop")
        de = de.at[jnp.where(do_push, hd, vdrop)].add(d, mode="drop")
        de = jax.lax.psum(de, axes)
        e = e + de

        dh = jnp.zeros(n, jnp.int32)
        dh = dh.at[jnp.where(do_relabel, u_q, vdrop)].add(
            jnp.where(do_relabel, newh - h[jnp.minimum(u_q, n - 1)], 0),
            mode="drop")
        h = h + jax.lax.psum(dh, axes)

        if meta.mode in ("sharded", "sparse"):
            res_l = res_l.at[jnp.where(do_push, k_arc, amax)].add(
                -d, mode="drop")
            if meta.mode == "sharded":
                drev = jnp.zeros(A, jnp.int32).at[g_rev].add(d, mode="drop")
                drev_l = jax.lax.psum_scatter(drev, axes,
                                              scatter_dimension=0, tiled=True)
                res_l = res_l + drev_l
                return res_l[None], h, e
            # 'sparse': pushes are <= vs per shard, so exchange (arc, delta)
            # PAIRS through bucketed all_to_all instead of a dense (A,)
            # reduction — O(P*vs) wire instead of O(A) (§Perf iteration 2)
            P_ = meta.nshards
            dest = jnp.where(do_push, g_rev // amax, P_)  # owner shard
            order = jnp.argsort(dest)
            dest_s = dest[order]
            pos = jnp.arange(vs, dtype=jnp.int32)
            first = jnp.where(dest_s[None, :] == jnp.arange(P_)[:, None],
                              pos[None, :], vs).min(axis=1)  # (P,)
            first_s = jnp.where(dest_s < P_, first[jnp.minimum(dest_s,
                                                               P_ - 1)], 0)
            rank = pos - first_s
            buf_arc = jnp.full((P_, vs), A, jnp.int32)
            buf_d = jnp.zeros((P_, vs), jnp.int32)
            dd = jnp.where(dest_s < P_, dest_s, P_)
            buf_arc = buf_arc.at[dd, rank].set(g_rev[order], mode="drop")
            buf_d = buf_d.at[dd, rank].set(d[order], mode="drop")
            recv_arc = jax.lax.all_to_all(buf_arc, axes, split_axis=0,
                                          concat_axis=0, tiled=True)
            recv_d = jax.lax.all_to_all(buf_d, axes, split_axis=0,
                                        concat_axis=0, tiled=True)
            mine = (recv_arc >= w * amax) & (recv_arc < (w + 1) * amax)
            slot = jnp.where(mine, recv_arc - w * amax, amax)  # else dropped
            res_l = res_l.at[slot.reshape(-1)].add(recv_d.reshape(-1),
                                                   mode="drop")
            return res_l[None], h, e
        dres = jnp.zeros(A, jnp.int32)
        dres = dres.at[g_arc].add(-d, mode="drop")
        dres = dres.at[g_rev].add(d, mode="drop")
        res = res + jax.lax.psum(dres, axes)
        return res, h, e

    res_spec = P(axes) if meta.mode in ("sharded", "sparse") else P()
    return jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes), res_spec, P(), P()),
        out_specs=(res_spec, P(), P()),
        check_vma=False)


def make_dist_global_relabel(meta: DistMeta, axes, mesh=None):
    """Distributed backward BFS (pmin sweeps) + deactivation."""
    n, vs, amax = meta.n, meta.vs, meta.amax

    def local_gr(indptr, heads, rev, tail_local, res, h, e):
        indptr, heads, rev = indptr[0], heads[0], rev[0]
        tail_local = tail_local[0]
        w = jax.lax.axis_index(axes)
        v0 = (w * vs).astype(jnp.int32)
        if meta.mode in ("sharded", "sparse"):
            res_key = res[0]
        else:
            res_key = jax.lax.dynamic_slice_in_dim(res, w * amax, amax)
        tails_g = jnp.minimum(v0 + tail_local, n - 1)

        def sweep(dist):
            hd = jnp.minimum(heads, n - 1)
            dd = dist[hd]
            key = jnp.where((res_key > 0) & (dd < INF) & (tail_local < vs),
                            dd + 1, INF)
            cand = jnp.full(n, INF, jnp.int32).at[tails_g].min(key,
                                                               mode="drop")
            cand = jax.lax.pmin(cand, axes)  # combine shards' sweep fronts
            return jnp.minimum(dist, cand).at[meta.t].set(0)

        dist0 = jnp.full(n, INF, jnp.int32).at[meta.t].set(0)
        dist, _ = engine.run_to_fixpoint(sweep, dist0, cap=n)
        hn = jnp.where(dist < INF, dist, jnp.int32(n)).at[meta.s].set(n)
        v = jnp.arange(n)
        nact = jnp.sum((e > 0) & (hn < n) & (v != meta.s) & (v != meta.t))
        return hn, nact

    res_spec = P(axes) if meta.mode in ("sharded", "sparse") else P()
    return jax.shard_map(
        local_gr, mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes), P(axes), res_spec, P(), P()),
        out_specs=(P(), P()),
        check_vma=False)


def make_gr_sweep(meta: DistMeta, axes, mesh=None):
    """A single distributed Bellman sweep of the global relabel (used by the
    dry-run cost analysis — the full GR while-loop body, counted once)."""
    n, vs, amax = meta.n, meta.vs, meta.amax

    def local_sweep(indptr, heads, rev, tail_local, res, dist):
        heads, tail_local = heads[0], tail_local[0]
        w = jax.lax.axis_index(axes)
        v0 = (w * vs).astype(jnp.int32)
        if meta.mode in ("sharded", "sparse"):
            res_key = res[0]
        else:
            res_key = jax.lax.dynamic_slice_in_dim(res, w * amax, amax)
        tails_g = jnp.minimum(v0 + tail_local, n - 1)
        hd = jnp.minimum(heads, n - 1)
        dd = dist[hd]
        key = jnp.where((res_key > 0) & (dd < INF) & (tail_local < vs),
                        dd + 1, INF)
        cand = jnp.full(n, INF, jnp.int32).at[tails_g].min(key, mode="drop")
        cand = jax.lax.pmin(cand, axes)
        return jnp.minimum(dist, cand).at[meta.t].set(0)

    res_spec = P(axes) if meta.mode in ("sharded", "sparse") else P()
    return jax.shard_map(
        local_sweep, mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes), P(axes), res_spec, P()),
        out_specs=P(),
        check_vma=False)


def make_superstep(meta: DistMeta, axes, cycles: int = 64, mesh=None):
    """cycles x dist_step + one distributed global relabel, jittable —
    this is what the dry-run lowers for the wbpr-maxflow cells."""
    step = make_dist_step(meta, axes, mesh)
    gr = make_dist_global_relabel(meta, axes, mesh)

    def superstep(g: DistGraph, res, h, e):
        # counter-only cond: the historical fori_loop ran exactly
        # ``cycles`` steps with no early exit, so the engine loop must too
        def body(carry):
            res, h, e, i = carry
            res, h, e = step(g.indptr, g.heads, g.rev, res, h, e)
            return res, h, e, i + 1

        res, h, e, _ = engine.run_bulk_loop(
            body, (res, h, e, jnp.int32(0)),
            cond_fn=lambda c: c[3] < cycles,
            chunk=engine.normalize_chunk(None, cycles))
        h, nact = gr(g.indptr, g.heads, g.rev, g.tail_local, res, h, e)
        return res, h, e, nact

    return superstep


def solve_distributed(r: ResidualCSR, s: int, t: int, mesh, axes,
                      mode: str = "replicated", cycles: int = 64,
                      max_rounds: int = 10000) -> int:
    """Full distributed solve (runs on the real devices of ``mesh``)."""
    nshards = int(np.prod([mesh.shape[a] for a in
                           (axes if isinstance(axes, tuple) else (axes,))]))
    g, meta, res0 = partition_graph(r, nshards, s, t, mode)
    n = meta.n
    superstep = make_superstep(meta, axes, cycles, mesh)

    with jax.set_mesh(mesh):
        # preflow (host-side, simple)
        res = np.asarray(res0).copy()
        heads = np.asarray(g.heads).reshape(-1)
        rev = np.asarray(g.rev).reshape(-1)
        e = np.zeros(n, np.int32)
        h = np.zeros(n, np.int32)
        h[s] = n
        w0, lo = s // meta.vs, s % meta.vs
        ip = np.asarray(g.indptr)
        for k in range(ip[w0, lo], ip[w0, lo + 1]):
            a = w0 * meta.amax + k
            d = res[a]
            res[a] = 0
            res[rev[a]] += d
            e[heads[a]] += d
        e[s] = 0
        res = jnp.asarray(res)
        if meta.mode in ("sharded", "sparse"):
            res = res.reshape(meta.nshards, meta.amax)
            res = jax.device_put(
                res, jax.sharding.NamedSharding(mesh, P(axes)))
        h, e = jnp.asarray(h), jnp.asarray(e)
        jstep = jax.jit(superstep)
        for _ in range(max_rounds):
            res, h, e, nact = jstep(g, res, h, e)
            if int(nact) == 0:
                break
        else:
            raise RuntimeError("distributed push-relabel did not converge")
        return int(e[t])
