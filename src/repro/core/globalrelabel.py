"""Global-relabel heuristic (paper Alg. 1 step 2).

A backward BFS from the sink over the residual graph reassigns every height
to the exact residual distance-to-sink.  Vectorised as Bellman-Ford-style
sweeps — each sweep is one segmented min over the arc array (the same
primitive as the vertex-centric min-height search, and executable by the
same Pallas kernel) — iterated to fixpoint through the shared sweep
engine (``repro.core.engine.run_to_fixpoint``; #sweeps = residual-graph
eccentricity of t).

Vertices that cannot reach the sink get h = n and are thereby deactivated;
their stranded excess is the paper's ``Excess_total`` deduction (line 6 /
§2.2) — max-flow value is then e(t).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.obs import scopes

INF = jnp.int32(2**30)


def residual_distances_impl(g, meta, res, t, minh_fn=None):
    """Exact distance-to-t over residual arcs, via sweeps to fixpoint.

    ``t`` may be a python int or a traced scalar (the batched solver vmaps
    this with per-instance sinks); ``meta`` must be static.

    Each sweep is one segmented min over the arc array — the same primitive
    as the vertex-centric min-height search.  ``minh_fn`` (the hook shared
    with ``pushrelabel.vc_step`` and ``phase2``, e.g.
    ``repro.kernels.ops.min_neighbor_minh_fn(...)``) executes it on the
    Pallas tile kernel instead of XLA's ``segment_min``; results are
    identical (both take the exact min over each vertex's segment).
    """
    from repro.core import engine
    from repro.core import pushrelabel as pr

    n = meta.n
    dist0 = jnp.full(n, INF, jnp.int32).at[t].set(0)

    def sweep(dist):
        if minh_fn is None:
            dh = dist[g.heads]
            key = jnp.where((res > 0) & (dh < INF), dh + 1, INF)
            cand = jax.ops.segment_min(key, g.tails, num_segments=n,
                                       indices_are_sorted=True)
        else:
            # the kernel computes key = where(res > 0, h[heads], INF);
            # feeding h' = min(dist + 1, INF) reproduces the sweep's key
            # exactly (dist is INF-saturated, and INF + 1 < int32 max).
            # avq=None: the dense every-vertex kernel form — no AVQ array
            pseudo = pr.PRState(res=res, h=jnp.minimum(dist + 1, INF),
                                e=None)
            cand, _ = minh_fn(g, meta, pseudo, None, None)
        return jnp.minimum(dist, cand).at[t].set(0)

    return engine.run_to_fixpoint(sweep, dist0, cap=n)


def batched_residual_distances_impl(g, meta, res, t, minh_fn=None):
    """Batch-level form of :func:`residual_distances_impl`: ``g`` holds
    stacked ``(B, n+1)``/``(B, A)`` rows, ``res`` is ``(B, A)`` and ``t``
    is ``(B,)``.  Each sweep step is ONE segmented min over the whole
    batch: ``minh_fn=None`` vmaps XLA's ``segment_min`` per row (the
    reference), a kernel ``minh_fn`` (``kernels.ops.min_neighbor_minh_fn``)
    runs a single ``tile_min_neighbor`` launch with grid ``(B, tiles)`` —
    never a vmapped ``pallas_call``.

    The sweep loop runs until EVERY row reaches its fixpoint; rows that
    converge earlier are fixpoints of the sweep (``min`` is idempotent),
    so the result is bit-for-bit what the per-instance while-loops
    produce.  Returns ``(dist (B, n), sweeps)``.
    """
    from repro.core import engine
    from repro.core import pushrelabel as pr

    n = meta.n
    B = res.shape[0]
    rows = jnp.arange(B)
    dist0 = jnp.full((B, n), INF, jnp.int32).at[rows, t].set(0)

    def sweep(dist):
        if minh_fn is None:
            def one(dist_r, res_r, heads_r, tails_r):
                dh = dist_r[heads_r]
                key = jnp.where((res_r > 0) & (dh < INF), dh + 1, INF)
                return jax.ops.segment_min(key, tails_r, num_segments=n,
                                           indices_are_sorted=True)

            cand = jax.vmap(one)(dist, res, g.heads, g.tails)
        else:
            pseudo = pr.PRState(res=res, h=jnp.minimum(dist + 1, INF),
                                e=None)
            cand, _ = minh_fn(g, meta, pseudo, None, None)
        return jnp.minimum(dist, cand).at[rows, t].set(0)

    return engine.run_to_fixpoint(sweep, dist0, cap=n)


residual_distances = functools.partial(
    jax.jit, static_argnames=("meta", "t", "minh_fn"))(
        residual_distances_impl)


def global_relabel_impl(g, meta, state, s, t, minh_fn=None):
    """Reassign heights to exact residual distances; deactivate unreachable
    vertices.  Returns ``(new_state, active_count, sweeps)`` — ``sweeps``
    is the Bellman-Ford iteration count the distance fixpoint took (the
    residual eccentricity of ``t``), already in the device carry and free
    to report.  ``s``/``t`` may be traced scalars (vmapped by the batched
    solver); ``meta`` must be static.  ``minh_fn`` routes the distance
    sweeps through the Pallas tile kernel (see
    ``residual_distances_impl``)."""
    from repro.core import pushrelabel as pr

    with jax.named_scope(scopes.GLOBAL_RELABEL):
        n = meta.n
        dist, sweeps = residual_distances_impl(g, meta, state.res, t,
                                               minh_fn=minh_fn)
        h = jnp.where(dist < INF, dist, jnp.int32(n)).astype(jnp.int32)
        h = h.at[s].set(n)
        new_state = pr.PRState(res=state.res, h=h, e=state.e)
        nact = jnp.sum(pr.active_mask(new_state, n, s, t))
        return new_state, nact, sweeps


global_relabel = functools.partial(
    jax.jit, static_argnames=("meta", "s", "t", "minh_fn"))(
        global_relabel_impl)


def batched_global_relabel_impl(g, meta, state, s, t, minh_fn=None):
    """Batch-level global relabel over stacked rows: one distance-sweep
    loop (``batched_residual_distances_impl``) serves the whole batch —
    under a kernel ``minh_fn`` each sweep step is ONE batch-grid
    ``pallas_call``.  ``s``/``t`` are ``(B,)``; returns
    ``(new_state, nact (B,), sweeps)`` bit-for-bit equal to vmapping
    :func:`global_relabel_impl` over the batch (``sweeps`` is the shared
    fixpoint iteration count — the max over instances)."""
    from repro.core import pushrelabel as pr

    with jax.named_scope(scopes.GLOBAL_RELABEL):
        n = meta.n
        B = state.res.shape[0]
        rows = jnp.arange(B)
        dist, sweeps = batched_residual_distances_impl(
            g, meta, state.res, t, minh_fn=minh_fn)
        h = jnp.where(dist < INF, dist, jnp.int32(n)).astype(jnp.int32)
        h = h.at[rows, s].set(n)
        new_state = pr.PRState(res=state.res, h=h, e=state.e)
        v = jnp.arange(n)
        act = ((state.e > 0) & (h < n) & (v[None, :] != s[:, None])
               & (v[None, :] != t[:, None]))
        return new_state, jnp.sum(act, axis=1), sweeps
