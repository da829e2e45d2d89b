"""Shared Pallas TPU core: one reduction per arc window, streamed from HBM.

Both hot-spot kernels have the same shape: for every queue entry ``q``
reduce an arc array over the contiguous CSR window ``[lo[q], hi[q])`` —
the min-height search (``segmin``) reduces ``key`` to ``(min, argmin)``,
the BCSR reverse-arc lookup (``revsearch``) reduces ``heads`` to a
lower-bound position of a per-entry target.  This module owns the
Mosaic-legal machinery once (docs/DESIGN.md §2.2):

* the arc array stays in HBM (``pl.ANY``), laid out as ``(rows, 128)``
  lane rows; it is read only through ``pltpu.make_async_copy`` into a
  ``W``-row VMEM window that is refilled when an entry's row falls
  outside it.  Entries are visited in queue order, so consecutive windows
  (the dense sweep form, a compacted AVQ of neighbouring vertices) hit
  the resident window and share one DMA;
* the per-entry window bounds arrive as ``TILE_Q``-entry SMEM blocks of
  ``lo``/``hi`` (plus the optional target ``u``) — never a whole O(n)
  scalar-prefetch; the only scalar prefetch is one entry count per tile,
  so tiles past a compacted queue's valid prefix cost one store;
* all values stay vectors: each 128-lane row reduces to ``(1, 1)``
  keepdims vectors carried across the entry's rows, and results land in
  ``(8, 128)`` output blocks of ``TILE_Q = 1024`` entries — the (8, 128)
  int32 tiling.

The grid is ``(B, tiles)``: one launch serves a whole stacked batch
(docs/DESIGN.md §2.4).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

LANES = 128
#: entries per grid program.  Fixed: the 1-D SMEM entry blocks must match
#: XLA's T(1024) layout of the flattened entry arrays, and (8, 128) output
#: blocks are always legal
TILE_Q = 1024
#: lane rows per VMEM window refill (one DMA of W * 128 int32)
WINDOW_ROWS = 16


def _kernel(cnt_ref, *refs, n_in, rows_per, total_rows, row_fn, finish,
            init, fills):
    in_refs = refs[:n_in]
    src_hbm = refs[n_in]
    outs = refs[n_in + 1: n_in + 1 + len(fills)]
    buf, buf0, sem = refs[n_in + 1 + len(fills):]
    lo_ref, hi_ref = in_refs[0], in_refs[1]
    u_ref = in_refs[2] if n_in == 3 else None
    b = pl.program_id(0)
    ntile = pl.num_programs(1)
    for o, fill in zip(outs, fills):
        o[...] = jnp.full(o.shape, fill, jnp.int32)
    buf0[0] = jnp.int32(-(2**30))  # nothing resident yet
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    base = b * rows_per

    def entry(q, carry):
        lo = lo_ref[q]
        hi = hi_ref[q]
        u = None if u_ref is None else u_ref[q]

        def row(r, acc):
            g = base + r

            @pl.when((g < buf0[0]) | (g >= buf0[0] + WINDOW_ROWS))
            def _refill():
                st = jnp.minimum(g, total_rows - WINDOW_ROWS)
                cp = pltpu.make_async_copy(
                    src_hbm.at[pl.ds(st, WINDOW_ROWS)], buf, sem)
                cp.start()
                cp.wait()
                buf0[0] = st

            v = buf[pl.ds(g - buf0[0], 1), :]
            idx = r * LANES + lane
            ok = (idx >= lo) & (idx < hi)
            return row_fn(v, idx, ok, u, acc)

        acc0 = tuple(jnp.full((1, 1), c, jnp.int32) for c in init)
        acc = jax.lax.fori_loop(lo // LANES, (hi + LANES - 1) // LANES, row,
                                acc0)
        vals = finish(acc, lo)
        rr = q // LANES
        sel = lane == q % LANES
        for o, val in zip(outs, vals):
            cur = o[0, pl.ds(rr, 1), :]
            o[0, pl.ds(rr, 1), :] = jnp.where(sel, val, cur)
        return carry

    jax.lax.fori_loop(0, cnt_ref[b * ntile + pl.program_id(1)], entry, 0)


def windowed_reduce(lo: jax.Array, hi: jax.Array, src: jax.Array,
                    u: jax.Array | None, *, row_fn: Callable,
                    finish: Callable, init: tuple[int, ...],
                    fills: tuple[int, ...], pad: int,
                    interpret: bool | None = None) -> tuple[jax.Array, ...]:
    """Reduce ``src[b, lo[b, q]:hi[b, q]]`` for every entry ``(b, q)``.

    ``lo``/``hi`` (and the optional per-entry target ``u``): ``(B, Q)``
    int32, empty windows (``hi <= lo``) allowed anywhere; ``src``:
    ``(B, A)`` int32, padded with ``pad`` to whole lane rows.
    ``row_fn(v, idx, ok, u, acc) -> acc`` folds one ``(1, 128)`` row (values,
    arc indices, in-window mask) into the carried tuple of ``(1, 1)``
    vectors that starts at ``init``; ``finish(acc, lo) -> outs`` turns it
    into one ``(1, 1)`` value per output.  Entries a tile never reaches
    (past its last non-empty window) keep ``fills``.  Returns a tuple of
    ``(B, Q)`` int32 arrays.  One ``pallas_call``, grid ``(B, tiles)``.
    """
    interpret = resolve_interpret(interpret)
    bsz, q = lo.shape
    a = src.shape[1]
    tq = TILE_Q
    qp = -(-max(q, 1) // tq) * tq
    nt = qp // tq
    operands = [lo, hi] + ([] if u is None else [u])
    operands = [jnp.pad(x, ((0, 0), (0, qp - q))).reshape(-1)
                for x in operands]
    # per tile: entries up to and including the last non-empty window
    pos = jnp.arange(1, tq + 1, dtype=jnp.int32)
    live = jnp.pad(hi > lo, ((0, 0), (0, qp - q))).reshape(bsz, nt, tq)
    cnt = jnp.max(jnp.where(live, pos, 0), axis=2).reshape(-1)

    rows_per = max(1, -(-a // LANES))
    src = jnp.pad(src, ((0, 0), (0, rows_per * LANES - a)),
                  constant_values=pad).reshape(bsz * rows_per, LANES)
    if src.shape[0] < WINDOW_ROWS:  # a window refill must stay in bounds
        src = jnp.pad(src, ((0, WINDOW_ROWS - src.shape[0]), (0, 0)),
                      constant_values=pad)

    kernel = functools.partial(
        _kernel, n_in=len(operands), rows_per=rows_per,
        total_rows=src.shape[0], row_fn=row_fn, finish=finish, init=init,
        fills=fills)
    entry_spec = pl.BlockSpec((tq,), lambda b, i, c: (b * nt + i,),
                              memory_space=pltpu.SMEM)
    out_spec = pl.BlockSpec((1, tq // LANES, LANES),
                            lambda b, i, c: (b, i, 0))
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # per-tile entry counts
            grid=(bsz, nt),
            in_specs=[entry_spec] * len(operands)
            + [pl.BlockSpec(memory_space=pl.ANY)],  # src stays in HBM
            out_specs=[out_spec] * len(fills),
            scratch_shapes=[
                pltpu.VMEM((WINDOW_ROWS, LANES), jnp.int32),
                pltpu.SMEM((1,), jnp.int32),  # first resident row
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((bsz, qp // LANES, LANES),
                                        jnp.int32)] * len(fills),
        interpret=interpret,
    )(cnt, *operands, src)
    return tuple(o.reshape(bsz, qp)[:, :q] for o in outs)
