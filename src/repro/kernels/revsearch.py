"""Pallas TPU kernel: BCSR backward-arc lookup (paper §3.2).

BCSR aggregates in/out arcs per vertex sorted by head id; the reverse arc
of a push (u -> v) is the position of u inside v's head-sorted segment.
The paper binary-searches it with O(log d(v)) dependent scalar probes.
On a TPU core the dependent probes would each be a scalar load through
HBM, so the kernel computes the same lower bound as a vectorised rank
instead: it streams v's segment in 128-lane rows through the shared VMEM
window (``repro.kernels.window``) and counts the heads below u, which
in a sorted segment IS the binary search's result — a 128-lane compare
per row replaces ``log2(128) = 7`` serial probes.  A second reduction
confirms the arc exists (coalesced residuals hold one arc per direction
per vertex pair).

The grid carries a leading batch dimension — one launch resolves the
reverse arcs of a whole bucketed microbatch's pushes (docs/DESIGN.md
§6.3); the 1-D single-instance form is the ``B == 1`` special case.

Validated against the build-time ``rev`` ground truth (interpret mode on
CPU, compiled on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import window


def _row_rank(v, idx, ok, u, acc):
    """Fold one heads row into (#heads < u, #heads == u) in the window."""
    below, hit = acc
    below = below + jnp.sum(jnp.where(ok & (v < u), 1, 0), axis=1,
                            keepdims=True)
    hit = hit + jnp.sum(jnp.where(ok & (v == u), 1, 0), axis=1,
                        keepdims=True)
    return below, hit


def _finish(a, acc, lo):
    below, hit = acc
    return (jnp.where(hit > 0, lo + below, jnp.int32(a)),)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bcsr_rev_search(arcs: jax.Array, indptr: jax.Array, heads: jax.Array,
                    tails: jax.Array, *,
                    interpret: bool | None = None) -> jax.Array:
    """For each push arc a=(u->v) find the arc (v->u) in v's sorted segment.

    Single instance: ``arcs (P,)``, ``indptr (n+1,)``, ``heads``/``tails
    (A,)``.  Batched: ``arcs (B, P)`` with ``(B, ·)`` graph rows — one
    launch, leading batch grid axis.  Sentinel ``>= A`` marks inactive
    lanes; returns reverse-arc ids with sentinel ``A`` where not
    found/inactive.  ``interpret=None``: compiled on TPU, interpreted on
    CPU.
    """
    single = arcs.ndim == 1
    if single:
        arcs, indptr = arcs[None], indptr[None]
        heads, tails = heads[None], tails[None]
    a = heads.shape[1]
    valid = arcs < a
    arc_c = jnp.where(valid, arcs, 0)
    u = jnp.take_along_axis(tails, arc_c, axis=1)  # push tail
    v = jnp.take_along_axis(heads, arc_c, axis=1)  # reverse arc lives here
    lo = jnp.where(valid, jnp.take_along_axis(indptr, v, axis=1), 0)
    hi = jnp.where(valid, jnp.take_along_axis(indptr, v + 1, axis=1), 0)
    (out,) = window.windowed_reduce(
        lo, hi, heads, u, row_fn=_row_rank,
        finish=functools.partial(_finish, a), init=(0, 0), fills=(a,),
        pad=0, interpret=interpret)
    return out[0] if single else out
