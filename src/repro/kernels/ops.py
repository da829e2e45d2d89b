"""Public jit'd wrappers around the Pallas kernels.

``interpret=None`` everywhere: the wrappers sniff the backend
(``repro.kernels.runtime.resolve_interpret``) and run compiled on TPU,
interpreted on CPU — pass an explicit bool to override (plumbed from
``SolverOptions.interpret``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.kernels.revsearch import bcsr_rev_search
from repro.kernels.segmin import tile_min_neighbor

INF = kref.INF


def min_neighbor_kernel(g, meta, state, avq, q_valid, *, interpret=None):
    """Drop-in for ``pushrelabel._flat_frontier_minh`` backed by the
    tile-per-vertex Pallas kernel (the paper's faithful VC mode).
    Returns ``(minh, argarc)`` with ``argarc == A`` sentinel when no
    eligible arc exists — the flat path's sentinel.

    The one hook serves every caller shape: single instance (1-D state,
    ``g`` holds ``(n+1,)``/``(A,)`` rows) and batched (2-D state, ``g``
    holds stacked ``(B, n+1)``/``(B, A)`` rows — ONE launch with grid
    ``(B, tiles)``, never a vmapped ``pallas_call``).  ``avq=None`` is
    the dense every-vertex form the distance sweeps use."""
    if state.h.ndim == 2:  # batched rows: per-row gather of h[heads]
        hh = jnp.take_along_axis(state.h, jnp.clip(g.heads, 0,
                                                   meta.n - 1), axis=1)
    else:
        hh = state.h[g.heads]
    key = jnp.where(state.res > 0, hh, INF).astype(jnp.int32)
    minh, argarc = tile_min_neighbor(avq, g.indptr, key, n=meta.n,
                                     interpret=interpret)
    return minh, argarc


@functools.lru_cache(maxsize=None)
def min_neighbor_minh_fn(interpret: bool | None = None):
    """A cached ``minh_fn`` partial with a stable identity, safe to pass as
    a static jit argument (``global_relabel`` / ``phase2_run`` /
    ``batched_global_relabel`` / ``batched_phase2``) without retracing on
    every call."""
    return functools.partial(min_neighbor_kernel, interpret=interpret)


def rev_lookup_bsearch(g, meta, arcs, *, interpret=None):
    """Reverse-arc lookup via the paper's BCSR binary search kernel.
    (The batched core calls ``bcsr_rev_search`` directly, after verifying
    every packed instance is ``binary_search_ready()`` — a "batched" meta
    alone does not guarantee head-sorted segments.)"""
    if meta.layout != "bcsr":
        raise ValueError(
            f"binary search requires head-sorted (bcsr) segments, got "
            f"layout {meta.layout!r}")
    return bcsr_rev_search(arcs, g.indptr, g.heads, g.tails,
                           interpret=interpret)


def rev_lookup_table(g, meta, arcs):
    """Beyond-paper variant: precomputed rev index (O(E) ints, no search)."""
    a = g.heads.shape[0]
    valid = arcs < a
    return jnp.where(valid, g.rev[jnp.minimum(arcs, a - 1)], jnp.int32(a))
