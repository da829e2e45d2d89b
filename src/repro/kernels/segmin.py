"""Pallas TPU kernel: tile-per-active-vertex min-height neighbour search.

This is the paper's two-level parallelism hot spot (Alg. 2, second level):
the CUDA version assigns a 32-lane warp per AVQ entry and runs Harris'
parallel reduction over the vertex's CSR segment.  The TPU adaptation
walks each AVQ entry's contiguous arc window in 128-lane rows streamed
from HBM through a VMEM window (``repro.kernels.window``), reducing
(min, argmin) per row and carrying it across rows.

TPU-native structure:
* the arc *key* array (``h[heads[a]]`` masked by ``res[a] > 0``) and the
  per-entry windows ``indptr[u]:indptr[u+1]`` are computed by XLA before
  the call (gathers are XLA-native on TPU); the kernel reads the windows
  from SMEM blocks and the key rows by DMA.
* the reduction is a 128-lane vector min + iota-select argmin; no
  shared-memory tree is needed on TPU (docs/DESIGN.md §2).
* the grid carries a **leading batch dimension** — one launch serves a
  whole bucketed microbatch (docs/DESIGN.md §2.4).  The 1-D
  single-instance form is the ``B == 1`` special case.
* ``avq=None`` selects the **dense** form: every vertex is its own queue
  entry — the Bellman-Ford sweep shape used by the (batched) global
  relabel and phase 2, where an all-vertices AVQ array would be pure
  overhead (docs/DESIGN.md §2.5).

Validated against ``repro.kernels.ref.min_neighbor_ref`` (interpret mode
on CPU, compiled on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import window

INF = np.int32(2**30)  # plain numpy scalar: becomes a literal inside kernels
_BIG = np.int32(2**31 - 1)


def _row_min(v, idx, ok, u, acc):
    """Fold one key row into (min key, smallest argmin arc)."""
    m, arg = acc
    w = jnp.where(ok, v, INF)
    lm = jnp.min(w, axis=1, keepdims=True)
    la = jnp.min(jnp.where(w == lm, idx, _BIG), axis=1, keepdims=True)
    better = lm < m
    return jnp.where(better, lm, m), jnp.where(better & (lm < INF), la, arg)


def _finish(a, acc, lo):
    m, arg = acc
    # the no-eligible-arc sentinel is ``a`` — the same sentinel the
    # flat-frontier XLA path uses, so downstream consumers compare
    # against one value
    return m, jnp.where(m < INF, arg, jnp.int32(a))


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def tile_min_neighbor(avq: jax.Array | None, indptr: jax.Array,
                      key: jax.Array, *, n: int,
                      interpret: bool | None = None):
    """Per-AVQ-entry (min key, argmin arc) over CSR segments.

    Single instance::

        avq: (Q,) int32, padded with ``n`` sentinels.
        indptr: (n+1,) int32.
        key: (A,) int32 — per-arc key, INF where not eligible.

    Batched (one launch per microbatch — leading batch grid axis)::

        avq: (B, Q), indptr: (B, n+1), key: (B, A)

    ``avq=None`` is the **dense** form: every vertex is its own queue
    entry (equivalent to ``avq == arange(n)`` rows, bit-for-bit).

    Returns ``(minh, argarc)`` of shape ``(Q,)`` / ``(B, Q)`` with
    ``argarc == A`` sentinel when no eligible arc exists (the flat-frontier
    sentinel).  ``interpret=None``: compiled on TPU, interpreted on CPU.
    """
    single = key.ndim == 1
    if single:
        indptr, key = indptr[None], key[None]
        if avq is not None:
            avq = avq[None]
    if avq is None:
        lo, hi = indptr[:, :n], indptr[:, 1:n + 1]
    else:
        valid = avq < n
        u = jnp.minimum(avq, n - 1)
        lo = jnp.where(valid, jnp.take_along_axis(indptr, u, axis=1), 0)
        hi = jnp.where(valid, jnp.take_along_axis(indptr, u + 1, axis=1), 0)
    a = key.shape[1]
    minh, argarc = window.windowed_reduce(
        lo, hi, key.astype(jnp.int32), None, row_fn=_row_min,
        finish=functools.partial(_finish, a), init=(int(INF), a),
        fills=(int(INF), a), pad=int(INF), interpret=interpret)
    if single:
        minh, argarc = minh[0], argarc[0]
    return minh, argarc
