"""Device-side solver telemetry: int32 workload counters that ride the
jitted cycle loops.

The paper's workload analysis (Fig. 3) needs *per-cycle* active-vertex
and scanned-arc counts; fetching them with host round-trips per cycle
(the old ``SolveStats.frontier_history`` list-append path) serialises
the solve.  Instead the counters are folded into the existing
``while_loop`` carries of ``pushrelabel.run_cycles`` /
``batched.batched_run_cycles`` so they are computed on device and
fetched ONCE per dispatch.

Counter definitions (identical across every mode, because the state
sequences are bit-for-bit identical and every active vertex performs
exactly one push or one relabel per bulk-synchronous cycle):

* ``active``   — per-cycle count of active vertices, summed over cycles;
* ``pushes``   — cycles' push actions: ``active - relabels``;
* ``relabels`` — vertices whose height changed this cycle (a relabel
  strictly raises ``h``; a dead end deactivates to ``h = n`` — both
  count, pushes never touch ``h``);
* ``frontier`` — per-cycle sum of ``deg(u)`` over active ``u``: the flat
  arc frontier the vertex-centric approach scans;
* ``lanes``    — per-cycle frontier lanes the step ran: the rung of
  ``pushrelabel.frontier_ladder`` that the single-instance ``vc`` loop
  picked, A (the padded frontier) in every other driver and mode, so
  ``frontier / lanes`` is the share of frontier lanes that did work;
* ``*_hist``   — the per-cycle series of the three quantities above plus
  the per-cycle max active degree (the thread-centric serialisation
  term in the paper's Eq. 1), single-instance drivers only.

Overflow contract: counters are **int32 on device** like every other
state array (see the dtype contract in README).  Within one dispatch the
largest cells are ``frontier <= lanes <= max_cycles * A``; drivers
accumulate across dispatches on the host in int64, so only a single
dispatch exceeding 2**31 frontier lanes can wrap — rechunk (lower
``global_relabel_cadence``) before that point.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax.numpy as jnp

__all__ = ["CycleTelemetry", "telemetry_init", "cycle_stats",
           "count_relabels"]


class CycleTelemetry(NamedTuple):
    """Device-side counter block carried by the cycle loops.

    Totals are int32 scalars (single driver) or ``(B,)`` rows (batched
    driver).  Histories are ``(H,)`` int32 per-cycle series, present
    only when the driver allocates them (``None`` otherwise — ``None``
    is an empty pytree leaf, so the carry structure stays static).
    """

    pushes: Any
    relabels: Any
    active: Any
    frontier: Any
    lanes: Any
    active_hist: Any = None
    frontier_hist: Any = None
    maxdeg_hist: Any = None


def telemetry_init(batch: int | None = None,
                   hist: int | None = None) -> CycleTelemetry:
    """Zeroed telemetry block: scalars for the single-instance driver
    (``batch=None``), ``(batch,)`` rows otherwise; ``hist`` adds
    ``(hist,)`` per-cycle series (single-instance only)."""
    shape = () if batch is None else (batch,)
    zero = jnp.zeros(shape, jnp.int32)
    hists = (None, None, None)
    if hist is not None:
        if batch is not None:
            raise ValueError("per-cycle histories are single-instance only")
        hists = tuple(jnp.zeros(hist, jnp.int32) for _ in range(3))
    return CycleTelemetry(pushes=zero, relabels=zero, active=zero,
                          frontier=zero, lanes=zero, active_hist=hists[0],
                          frontier_hist=hists[1], maxdeg_hist=hists[2])


def cycle_stats(g, meta, state, s, t):
    """Per-cycle workload scalars of the CURRENT state: ``(active
    vertices, frontier arcs, max active degree)``, each int32.

    ``s``/``t`` may be traced scalars; with 2-D ``state`` rows (the
    batched driver) pass ``s``/``t`` as ``(B,)`` and get ``(B,)`` out.
    """
    from repro.core import pushrelabel as pr

    deg = g.indptr[..., 1:] - g.indptr[..., :-1]
    if state.h.ndim == 1:
        act = pr.active_mask(state, meta.n, s, t)
    else:
        v = jnp.arange(meta.n)
        act = ((state.e > 0) & (state.h < meta.n)
               & (v[None, :] != s[:, None]) & (v[None, :] != t[:, None]))
    adeg = jnp.where(act, deg, 0).astype(jnp.int32)
    return (jnp.sum(act, axis=-1).astype(jnp.int32),
            jnp.sum(adeg, axis=-1),
            jnp.max(adeg, axis=-1))


def count_relabels(old_h, new_h):
    """Vertices whose height changed across one bulk-synchronous cycle —
    exactly the relabel count (pushes do not write ``h``; every relabel,
    including the dead-end deactivation to ``h = n``, strictly changes
    it)."""
    return jnp.sum(new_h != old_h, axis=-1).astype(jnp.int32)
