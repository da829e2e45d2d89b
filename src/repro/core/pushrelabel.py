"""Workload-balanced push-relabel (WBPR) in JAX — the paper's core.

Implements the bulk-synchronous form of He–Hong's lock-free push-relabel
(paper Alg. 1) with both approaches from the paper:

* ``tc_step`` — **thread-centric** baseline: one lane per vertex scans its own
  residual neighbour segment sequentially (a masked ``fori_loop`` to
  ``deg_max``).  Work is O(V * deg_max) per cycle — exactly the imbalance the
  paper's cost model (Eq. 1) identifies.

* ``vc_step`` — **vertex-centric** (paper Alg. 2): compact the active
  vertices into the AVQ (prefix-sum compaction — the deterministic TPU
  analogue of the paper's ``atomic_add`` append), gather all their residual
  arcs into a flat, contiguous *frontier*, and find each vertex's
  minimum-height neighbour with a segmented min reduction (the paper's
  warp-tile parallel reduction).  Work is O(sum deg(active)) — balanced.

Each synchronous iteration applies *one* push-or-relabel per active vertex.
Pushes on distinct arcs are owned by their tail vertices (no write conflict
on ``res``), excess updates are scatter-adds (the commutative analogue of
``atomicAdd``), so this is a legal schedule of the lock-free algorithm and
inherits its correctness proof [Hong 2008].

The segmented-min hot spot can be executed by the Pallas kernel
(``repro.kernels.ops.min_neighbor``) in the faithful tile-per-vertex mode;
the pure-jnp flat mode below is the XLA fallback and the reference semantics.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core import globalrelabel
from repro.core.csr import ResidualCSR
from repro.obs import scopes
from repro.obs import solvercounters as sc
from repro.obs import span

INF = jnp.int32(2**30)


class DeviceGraph(NamedTuple):
    """Device-resident residual-graph arrays (layout-agnostic flat arc form)."""

    indptr: jax.Array  # (n+1,) int32
    heads: jax.Array  # (A,) int32
    tails: jax.Array  # (A,) int32
    rev: jax.Array  # (A,) int32


@dataclasses.dataclass(frozen=True)
class GraphMeta:
    n: int
    num_arcs: int
    deg_max: int
    layout: str


def to_device(r: ResidualCSR) -> tuple[DeviceGraph, GraphMeta, jax.Array]:
    g = DeviceGraph(
        indptr=jnp.asarray(r.indptr, jnp.int32),
        heads=jnp.asarray(r.heads, jnp.int32),
        tails=jnp.asarray(r.tails, jnp.int32),
        rev=jnp.asarray(r.rev, jnp.int32),
    )
    meta = GraphMeta(n=r.n, num_arcs=r.num_arcs, deg_max=r.deg_max,
                     layout=r.layout)
    return g, meta, jnp.asarray(r.res0, jnp.int32)


class PRState(NamedTuple):
    res: jax.Array  # (A,) int32 residual capacities
    h: jax.Array  # (n,) int32 heights
    e: jax.Array  # (n,) int32 excess


def preflow(g: DeviceGraph, meta: GraphMeta, res0: jax.Array, s: int) -> PRState:
    """Paper Alg. 1 step 0: saturate every arc out of the source."""
    n, A = meta.n, meta.num_arcs
    from_s = g.tails == s
    d = jnp.where(from_s, res0, 0)
    res = res0 - d
    res = res.at[g.rev].add(d)
    e = jax.ops.segment_sum(d, g.heads, num_segments=n)
    e = e.at[s].set(0)
    h = jnp.zeros(n, jnp.int32).at[s].set(n)
    return PRState(res=res, h=h, e=e.astype(jnp.int32))


def active_mask(state: PRState, n: int, s: int, t: int) -> jax.Array:
    v = jnp.arange(n)
    return (state.e > 0) & (state.h < n) & (v != s) & (v != t)


# ---------------------------------------------------------------------------
# min-height neighbour search
# ---------------------------------------------------------------------------

def _flat_frontier_minh(g: DeviceGraph, meta: GraphMeta, state: PRState,
                        avq: jax.Array, q_valid: jax.Array,
                        lanes: int | None = None):
    """Flat-frontier segmented min (workload-balanced: O(sum deg(active))).

    The queue ``avq`` has K = ``len(avq)`` lanes and the flat frontier
    ``lanes`` lanes (default A): the frontier of the queued vertices must
    fit, which A always does.  The bucketed step (``vc_bucketed_step``)
    runs it at the smallest (``lanes``, K) rung that holds the cycle's
    live work; every other caller runs the padded (A, n) case."""
    n, A = meta.n, meta.num_arcs
    F = A if lanes is None else lanes
    K = avq.shape[0]
    with jax.named_scope(scopes.FRONTIER):
        avq_c = jnp.minimum(avq, n - 1)
        deg = jnp.where(q_valid, g.indptr[avq_c + 1] - g.indptr[avq_c], 0)
        offs = jnp.cumsum(deg)
        starts = offs - deg
        total = offs[-1]
        pos = jnp.arange(F, dtype=jnp.int32)
        row = jnp.repeat(jnp.arange(K, dtype=jnp.int32), deg,
                         total_repeat_length=F)
        fvalid = pos < total
        row = jnp.where(fvalid, row, 0)
        arc = g.indptr[avq_c[row]] + (pos - starts[row])
        arc = jnp.clip(arc, 0, A - 1)
        key = jnp.where(fvalid & (state.res[arc] > 0),
                        state.h[g.heads[arc]], INF)
    with jax.named_scope(scopes.MINH):
        minh = jax.ops.segment_min(key, row, num_segments=K,
                                   indices_are_sorted=True)
        cand = jnp.where(fvalid & (key == minh[row]), arc, jnp.int32(A))
        argarc = jax.ops.segment_min(cand, row, num_segments=K,
                                     indices_are_sorted=True)
        # normalize the no-eligible-arc lanes (inactive row, empty
        # segment — where segment_min returns its int32-max identity — or
        # all keys INF) to the one (INF, A) sentinel pair every minh path
        # returns
        minh = jnp.where(q_valid & (minh < INF), minh, INF)
        argarc = jnp.where(minh < INF, argarc, jnp.int32(A))
    return minh, argarc


def _tc_scan_minh(g: DeviceGraph, meta: GraphMeta, state: PRState,
                  act: jax.Array):
    """Thread-centric scan: every vertex-lane walks its own segment to
    deg_max (masked) — the paper's imbalanced baseline."""
    n, A = meta.n, meta.num_arcs
    start = g.indptr[:-1]
    degv = g.indptr[1:] - g.indptr[:-1]

    def body(j, carry):
        minh, argarc = carry
        arc = jnp.clip(start + j, 0, A - 1)
        ok = (j < degv) & act & (state.res[arc] > 0)
        key = jnp.where(ok, state.h[g.heads[arc]], INF)
        better = key < minh
        return jnp.where(better, key, minh), jnp.where(better, arc, argarc)

    minh0 = jnp.full(n, INF, jnp.int32)
    arg0 = jnp.full(n, A, jnp.int32)
    return jax.lax.fori_loop(0, meta.deg_max, body, (minh0, arg0))


# ---------------------------------------------------------------------------
# push / relabel decision + bulk-synchronous apply
# ---------------------------------------------------------------------------

def _push_decision(h: jax.Array, u_c: jax.Array, q_valid: jax.Array,
                   minh: jax.Array):
    """The push-or-relabel predicate pair, shared by ``_decide_apply`` and
    the batched kernel step (which must pre-resolve reverse arcs for
    exactly the arcs ``_decide_apply`` will push on): ``can`` = an
    admissible arc exists, ``do_push`` = it is height-decreasing."""
    can = q_valid & (minh < INF)
    do_push = can & (h[u_c] > minh)
    return can, do_push


def _decide_apply(g: DeviceGraph, meta: GraphMeta, state: PRState,
                  u: jax.Array, q_valid: jax.Array,
                  minh: jax.Array, argarc: jax.Array,
                  rev_fn: Callable | None = None) -> PRState:
    n, A = meta.n, meta.num_arcs
    res, h, e = state
    u_c = jnp.minimum(u, n - 1)
    arc_c = jnp.clip(argarc, 0, A - 1)
    can, do_push = _push_decision(h, u_c, q_valid, minh)
    d = jnp.where(do_push, jnp.minimum(e[u_c], res[arc_c]), 0)

    drop = jnp.int32(A)  # out-of-range sentinel; scatter mode='drop'
    push_arc = jnp.where(do_push, arc_c, drop)
    if rev_fn is None:
        rev_arc = jnp.where(do_push, g.rev[arc_c], drop)
    else:  # paper-faithful BCSR: locate the reverse arc by binary search
        rev_arc = jnp.where(do_push, rev_fn(g, meta, push_arc), drop)
    res = res.at[push_arc].add(-d, mode="drop")
    res = res.at[rev_arc].add(d, mode="drop")

    vdrop = jnp.int32(n)
    e = e.at[jnp.where(do_push, u_c, vdrop)].add(-d, mode="drop")
    e = e.at[jnp.where(do_push, g.heads[arc_c], vdrop)].add(d, mode="drop")

    do_relabel = q_valid & ~do_push
    newh = jnp.where(can, minh + 1, jnp.int32(n))  # dead end -> deactivate
    h = h.at[jnp.where(do_relabel, u_c, vdrop)].set(
        jnp.where(do_relabel, newh, 0), mode="drop")
    return PRState(res=res, h=h, e=e)


def vc_step(g: DeviceGraph, meta: GraphMeta, state: PRState, s: int, t: int,
            minh_fn: Callable | None = None,
            rev_fn: Callable | None = None) -> PRState:
    """One vertex-centric iteration (paper Alg. 2), padded: a queue of n
    lanes and a frontier of A."""
    n = meta.n
    with jax.named_scope(scopes.COMPACT):
        act = active_mask(state, n, s, t)
        avq = jnp.nonzero(act, size=n, fill_value=n)[0].astype(jnp.int32)
    return _vc_search_apply(g, meta, state, avq, meta.num_arcs, minh_fn,
                            rev_fn)


def _vc_search_apply(g: DeviceGraph, meta: GraphMeta, state: PRState,
                     avq: jax.Array, lanes: int,
                     minh_fn: Callable | None = None,
                     rev_fn: Callable | None = None) -> PRState:
    """Min search, then push or relabel, of the queued vertices ``avq``
    over a frontier of ``lanes``, which must hold all their arcs."""
    n = meta.n
    with jax.named_scope(scopes.COMPACT):
        q_valid = avq < n
    if minh_fn is None:
        minh, argarc = _flat_frontier_minh(g, meta, state, avq, q_valid,
                                           lanes)
    else:
        with jax.named_scope(scopes.MINH):
            minh, argarc = minh_fn(g, meta, state, avq, q_valid)
    with jax.named_scope(scopes.APPLY):
        return _decide_apply(g, meta, state, avq, q_valid, minh, argarc,
                             rev_fn)


#: the frontier ladder (``frontier_ladder``): rungs shrink by this ratio
#: from A down to ``_LADDER_FLOOR`` lanes, at most ``_LADDER_RUNGS`` of
#: them, and a rung's queue holds ``_QUEUE_SLACK`` times the vertices its
#: frontier would at the graph's mean degree
_LADDER_RATIO = 2 ** 0.5
_LADDER_FLOOR = 1024
_LADDER_RUNGS = 16
_QUEUE_SLACK = 1.5


def frontier_ladder(n: int, num_arcs: int) -> tuple[tuple[int, int], ...]:
    """The (frontier lanes F, queue lanes K) rungs of the bucketed step,
    smallest first: F shrinks geometrically from A, K follows it through
    the graph's vertices per arc, both rounded up to 128 lanes.  The top
    rung is (A, n), the padded step, so every cycle fits one."""
    rungs = [(num_arcs, n)]
    f = num_arcs
    while len(rungs) < _LADDER_RUNGS:
        f = -(-math.ceil(f / _LADDER_RATIO) // 128) * 128
        if f < _LADDER_FLOOR:
            break
        k = -(-math.ceil(_QUEUE_SLACK * f * n / num_arcs) // 128) * 128
        rungs.append((f, min(n, k)))
    return tuple(reversed(rungs))


def ladder_rung(ladder: tuple[tuple[int, int], ...], nact: jax.Array,
                ftotal: jax.Array) -> jax.Array:
    """Index of the smallest rung of ``ladder`` whose queue holds ``nact``
    active vertices and whose frontier holds their ``ftotal`` arcs."""
    lanes = jnp.asarray([f for f, _ in ladder], jnp.int32)
    queue = jnp.asarray([k for _, k in ladder], jnp.int32)
    return jnp.maximum(jnp.sum(lanes < ftotal), jnp.sum(queue < nact))


def vc_bucketed_step(g: DeviceGraph, meta: GraphMeta, state: PRState,
                     s: int, t: int) -> PRState:
    """``vc_step`` at the smallest rung of ``frontier_ladder`` that holds
    this cycle's active vertices and their arcs, picked on the device by
    ``lax.switch``: the same state, bit for bit, from the live work
    instead of A frontier and n queue lanes.  Only for a step that is not
    vmapped: under ``vmap`` the switch would run every rung."""
    n = meta.n
    ladder = frontier_ladder(n, meta.num_arcs)
    with jax.named_scope(scopes.COMPACT):
        act = active_mask(state, n, s, t)
        # the compaction scans n whatever the queue's size, so it runs
        # once, and each rung takes its queue's prefix
        avq = jnp.nonzero(act, size=n, fill_value=n)[0].astype(jnp.int32)
    if len(ladder) == 1:
        return _vc_search_apply(g, meta, state, avq, meta.num_arcs)
    with jax.named_scope(scopes.COMPACT):
        deg = g.indptr[1:] - g.indptr[:-1]
        rung = ladder_rung(ladder, jnp.sum(act),
                           jnp.sum(jnp.where(act, deg, 0)))

    def branch(lanes, queue, state, avq):
        with jax.named_scope(scopes.COMPACT):
            avq = avq[:queue]
        return _vc_search_apply(g, meta, state, avq, lanes)

    branches = [functools.partial(branch, f, k) for f, k in ladder]
    return jax.lax.switch(rung, branches, state, avq)


def tc_step(g: DeviceGraph, meta: GraphMeta, state: PRState, s: int,
            t: int) -> PRState:
    """One thread-centric iteration (paper Alg. 1 inner loop)."""
    with jax.named_scope(scopes.COMPACT):
        act = active_mask(state, meta.n, s, t)
    with jax.named_scope(scopes.MINH):
        minh, argarc = _tc_scan_minh(g, meta, state, act)
        minh = jnp.where(act, minh, INF)
    with jax.named_scope(scopes.APPLY):
        u = jnp.arange(meta.n, dtype=jnp.int32)
        return _decide_apply(g, meta, state, u, act, minh, argarc)


#: modes whose hot loops execute the Pallas kernels (the min search, and
#: for 'vc_kernel_bsearch' the reverse lookup, run in the tile kernels)
KERNEL_MODES = ("vc_kernel", "vc_kernel_bsearch")

#: every step strategy — THE mode tuple; the facade (``repro.api.options``),
#: the batched core and the benchmarks all import it rather than copying it
ALL_MODES = ("vc", "tc") + KERNEL_MODES


def _make_step(mode: str, interpret: bool | None = None) -> Callable:
    """Step factory: 'vc' (flat frontier, beyond-paper), 'tc' (baseline),
    'vc_kernel' (faithful tile-per-vertex Pallas), 'vc_kernel_bsearch'
    (faithful BCSR: Pallas tiles + binary-search reverse lookup)."""
    if mode == "tc":
        return tc_step
    if mode == "vc":
        return vc_step
    from repro.kernels import ops as kops
    minh_fn = kops.min_neighbor_minh_fn(interpret)
    if mode == "vc_kernel":
        return functools.partial(vc_step, minh_fn=minh_fn)
    if mode == "vc_kernel_bsearch":
        return functools.partial(
            vc_step, minh_fn=minh_fn,
            rev_fn=lambda g, meta, arcs: kops.rev_lookup_bsearch(
                g, meta, arcs, interpret=interpret))
    raise ValueError(f"unknown mode {mode!r}")


# ---------------------------------------------------------------------------
# solver driver
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("meta", "s", "t", "mode",
                                             "max_cycles", "interpret",
                                             "telemetry", "chunk"))
def run_cycles(g: DeviceGraph, meta: GraphMeta, state: PRState, s: int, t: int,
               mode: str = "vc", max_cycles: int = 256,
               interpret: bool | None = None, telemetry: bool = False,
               budget: jax.Array | None = None, chunk: int | None = None):
    """Paper Alg. 1 step 1: up to ``max_cycles`` push-relabel iterations with
    the AVQ-empty early exit (paper §3.3), run through the shared sweep
    engine (``repro.core.engine``): an outer ``while_loop`` over
    scan-compiled chunks of ``chunk`` cycles (default
    ``engine.DEFAULT_CHUNK``) — the steady-state trace holds ONE step
    body regardless of ``max_cycles``.

    ``budget`` (traced, optional) tightens the cycle cap below the static
    ``max_cycles`` without recompiling: the loop executes exactly
    ``min(max_cycles, budget)`` cycles unless it converges first —
    ``solve_impl`` passes its remaining ``max_cycles`` allowance here so
    the total is honored exactly even when it is not a multiple of the
    per-dispatch chunk.

    ``telemetry=True`` (static) folds the workload counters of
    ``repro.obs.solvercounters`` into the loop carry and returns a third
    element, a ``CycleTelemetry`` with push/relabel/active/frontier/lanes
    totals plus per-cycle active/frontier/maxdeg histories — all device
    arrays, fetched by the caller once per call.  ``telemetry=False``
    traces exactly the historical two-result loop (no extra ops).

    Mode ``'vc'`` runs ``vc_bucketed_step``: each cycle at the smallest
    rung of ``frontier_ladder`` that holds its live work.  The other
    modes, and the vmapped step of ``repro.core.batched``, keep the
    padded (A, n) step.
    """
    # everything here but the step phases: the cap, the condition, the
    # engine's chunk gating and carry, the telemetry counters
    with jax.named_scope(scopes.LOOP):
        cap = jnp.int32(max_cycles)
        if budget is not None:
            cap = jnp.minimum(cap, jnp.asarray(budget, jnp.int32))

        def cond(carry):
            state, cycle = carry[0], carry[1]
            nact = jnp.sum(active_mask(state, meta.n, s, t))
            return (cycle < cap) & (nact > 0)

        # the one unbatched path: 'vc' runs at the live work's rung
        if mode == "vc":
            step = vc_bucketed_step
            ladder = frontier_ladder(meta.n, meta.num_arcs)
        else:
            step = _make_step(mode, interpret)
            ladder = ((meta.num_arcs, meta.n),)

        if telemetry:
            def body(carry):
                state, cycle, tel = carry
                nact, fr, md = sc.cycle_stats(g, meta, state, s, t)
                new = step(g, meta, state, s, t)
                relab = sc.count_relabels(state.h, new.h)
                lanes = jnp.asarray([f for f, _ in ladder], jnp.int32)[
                    ladder_rung(ladder, nact, fr)]
                upd = functools.partial(jax.lax.dynamic_update_slice,
                                        start_indices=(cycle,))
                tel = sc.CycleTelemetry(
                    pushes=tel.pushes + (nact - relab),
                    relabels=tel.relabels + relab,
                    active=tel.active + nact,
                    frontier=tel.frontier + fr,
                    lanes=tel.lanes + lanes,
                    active_hist=upd(tel.active_hist, nact[None]),
                    frontier_hist=upd(tel.frontier_hist, fr[None]),
                    maxdeg_hist=upd(tel.maxdeg_hist, md[None]))
                return new, cycle + 1, tel
        else:
            def body(carry):
                state, cycle = carry
                return step(g, meta, state, s, t), cycle + 1

        scan_chunk = engine.normalize_chunk(chunk, max_cycles)
        if telemetry:
            state, cycles, tel = engine.run_bulk_loop(
                body,
                (state, jnp.int32(0), sc.telemetry_init(hist=max_cycles)),
                cond_fn=cond, chunk=scan_chunk)
            return state, cycles, tel
        state, cycles = engine.run_bulk_loop(body, (state, jnp.int32(0)),
                                             cond_fn=cond, chunk=scan_chunk)
        return state, cycles


def solve_programs(n: int, mode: str = "vc", cycle_chunk: int | None = None,
                   interpret: bool | None = None,
                   scan_chunk: int | None = None) -> tuple[dict, Callable]:
    """The static arguments of the programs a solve of an ``n``-vertex
    residual dispatches: ``(run_cycles keywords, minh_fn)``, the keywords
    of every ``run_cycles`` round (the round cadence is
    ``max(32, min(1024, n))`` unless ``cycle_chunk`` pins it) and the
    ``minh_fn`` its global relabels and phase 2 run with.  ``solve_impl``
    dispatches with these, and ``repro.obs.scopes`` compiles the same
    programs from them."""
    cycles = dict(mode=mode, max_cycles=cycle_chunk or max(32, min(1024, n)),
                  interpret=interpret, chunk=scan_chunk)
    return cycles, engine.resolve_minh_fn(mode, interpret)


def _empty_hist() -> np.ndarray:
    return np.zeros(0, np.int64)


@dataclasses.dataclass
class SolveStats:
    maxflow: int
    rounds: int = 0
    cycles: int = 0
    global_relabels: int = 0
    gr_sweeps: int = 0  # Bellman-Ford sweep total across global relabels
    # device-counter workload totals (telemetry solves; 0 otherwise) —
    # int32 per dispatch, accumulated here in Python ints
    pushes: int = 0
    relabels: int = 0
    frontier_lanes: int = 0  # frontier lanes the executed cycles ran
    # per-cycle device-counter series (telemetry solves only; empty
    # otherwise): active vertices, frontier arcs, max active degree —
    # one entry per push-relabel cycle, fetched once per round
    active_history: np.ndarray = dataclasses.field(
        default_factory=_empty_hist)
    frontier_history: np.ndarray = dataclasses.field(
        default_factory=_empty_hist)
    maxdeg_history: np.ndarray = dataclasses.field(
        default_factory=_empty_hist)
    state: PRState | None = None  # final solver state (residual/heights/excess)
    residual: ResidualCSR | None = None  # the CSR the solve ran on


def solve_impl(r: ResidualCSR, s: int, t: int, mode: str = "vc",
               cycle_chunk: int | None = None, max_rounds: int = 100000,
               instrument: bool = False,
               interpret: bool | None = None,
               max_cycles: int | None = None,
               scan_chunk: int | None = None) -> SolveStats:
    """Full max-flow solve: preflow -> [cycles -> global relabel]* -> e(t).

    ``mode``: 'vc' (paper's WBPR), 'tc' (thread-centric baseline), or one
    of the Pallas ``KERNEL_MODES`` — kernel modes also route the global
    relabel's Bellman-Ford sweeps through the tile kernel.  ``interpret``
    governs Pallas execution (None = compiled on TPU, interpreted on CPU).

    ``max_cycles`` (optional) is an exact total cycle budget: the
    remaining allowance rides into every ``run_cycles`` dispatch as the
    traced ``budget`` scalar, so the solve executes exactly
    ``max_cycles`` cycles before raising — even when the budget is not a
    multiple of ``cycle_chunk`` — without a recompile per round.
    ``scan_chunk`` sets the engine's scanned steps-per-chunk
    (``repro.core.engine.DEFAULT_CHUNK`` when ``None``).

    ``instrument=True`` enables the device-side telemetry counters
    (``repro.obs.solvercounters``): the returned stats carry exact
    push/relabel totals and per-cycle active/frontier/maxdeg histories,
    computed inside the jitted loop and fetched once per round — NOT the
    old one-host-sync-per-round sampling.

    This is the single-instance execution engine behind the public facade;
    call it through ``repro.api.Solver``.
    """
    g, meta, res0 = to_device(r)
    n = meta.n
    if s == t or meta.num_arcs == 0 or meta.deg_max == 0:
        idle = PRState(res=res0, h=jnp.zeros(n, jnp.int32),
                       e=jnp.zeros(n, jnp.int32))
        return SolveStats(maxflow=0, state=idle, residual=r)
    cycle_kw, gr_minh = solve_programs(n, mode, cycle_chunk, interpret,
                                       scan_chunk)
    state = preflow(g, meta, res0, s)
    # start from exact distance labels (global relabel heuristic)
    with span("solve.global_relabel") as sp:
        state, _, sweeps = globalrelabel.global_relabel(g, meta, state, s, t,
                                                        minh_fn=gr_minh)
        sweeps = int(sweeps)
        sp.set_metadata(sweeps=sweeps)
    stats = SolveStats(maxflow=0, gr_sweeps=sweeps)
    hists: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
    remaining = max_cycles  # None = unbounded; else exact total allowance
    for _ in range(max_rounds):
        budget = None if remaining is None else jnp.int32(remaining)
        with span("solve.cycles") as sp:
            if instrument:
                state, cycles, tel = run_cycles(g, meta, state, s, t,
                                                telemetry=True, budget=budget,
                                                **cycle_kw)
                c = int(cycles)
                stats.pushes += int(tel.pushes)
                stats.relabels += int(tel.relabels)
                stats.frontier_lanes += int(tel.lanes)
                hists.append((np.asarray(tel.active_hist[:c], np.int64),
                              np.asarray(tel.frontier_hist[:c], np.int64),
                              np.asarray(tel.maxdeg_hist[:c], np.int64)))
            else:
                state, cycles = run_cycles(g, meta, state, s, t,
                                           budget=budget, **cycle_kw)
                c = int(cycles)
            sp.set_metadata(cycles=c)
        stats.cycles += c
        stats.rounds += 1
        if remaining is not None:
            remaining -= c
        with span("solve.global_relabel") as sp:
            state, nact, sweeps = globalrelabel.global_relabel(
                g, meta, state, s, t, minh_fn=gr_minh)
            sweeps, nact = int(sweeps), int(nact)
            sp.set_metadata(sweeps=sweeps)
        stats.global_relabels += 1
        stats.gr_sweeps += sweeps
        if nact == 0:
            break
        if remaining is not None and remaining <= 0:
            from repro.errors import BudgetExhausted

            # the state at this point is a valid partial preflow (cycles
            # stopped mid-solve, global relabel just ran): callers can
            # degrade — bigger budget, host fallback — instead of failing
            raise BudgetExhausted(
                f"push-relabel did not converge within max_cycles="
                f"{max_cycles}", cycles_spent=stats.cycles,
                limit=max_cycles, partial=True)
    else:
        raise RuntimeError("push-relabel did not converge within max_rounds")
    if hists:
        stats.active_history, stats.frontier_history, stats.maxdeg_history \
            = (np.concatenate(col) for col in zip(*hists))
    stats.maxflow = int(state.e[t])
    stats.state = state
    stats.residual = r
    return stats


def convert_preflow_to_flow(r: ResidualCSR, state: PRState, s: int,
                            t: int, reference: bool = False,
                            use_kernel: bool = False,
                            interpret: bool | None = None) -> np.ndarray:
    """Phase 2: the solver terminates with a maximum *preflow* (stranded
    excess at deactivated vertices).  Return that excess to the source by
    cancelling flow backwards, yielding a genuine max flow; returns the
    corrected ``res`` array (int64 numpy).

    The default runs the device-resident bulk decomposition
    (``repro.core.phase2``) — one jitted dispatch drains every stranded
    vertex at once.  ``use_kernel=True`` executes its segmented mins on
    the Pallas tile kernel (identical results; the same ``minh_fn`` hook
    the kernel solve modes use).  ``reference=True`` runs the original
    host-side per-excess-vertex BFS: the test oracle and escape hatch.
    """
    return convert_preflow_to_flow_stats(r, state, s, t, reference,
                                         use_kernel, interpret)[0]


def convert_preflow_to_flow_stats(r: ResidualCSR, state: PRState, s: int,
                                  t: int, reference: bool = False,
                                  use_kernel: bool = False,
                                  interpret: bool | None = None):
    """``convert_preflow_to_flow`` with the device loops' counters:
    ``(res, phase2.Phase2Stats)``, or ``(res, None)`` from the host
    reference, which has no such loops."""
    if not reference:
        from repro.core import phase2

        minh_fn = None
        if use_kernel:
            from repro.kernels import ops as kops

            minh_fn = kops.min_neighbor_minh_fn(interpret)
        return phase2.convert_preflow_to_flow_device(r, state, s, t,
                                                     minh_fn=minh_fn)
    return _convert_preflow_to_flow_host(r, state, s, t), None


def _convert_preflow_to_flow_host(r: ResidualCSR, state: PRState, s: int,
                                  t: int) -> np.ndarray:
    """Host-side reference phase 2: one BFS toward ``s`` per excess vertex
    over arcs currently carrying flow inward, cancelling along the found
    path.  O(V*E) worst case — kept as the oracle for the device path."""
    res = np.asarray(state.res, np.int64).copy()  # lint-ok: int64-state-cast
    res0 = np.asarray(r.res0)
    e = np.asarray(state.e, np.int64).copy()  # lint-ok: int64-state-cast
    indptr, heads, rev = r.indptr, r.heads, r.rev
    for v0 in range(r.n):
        # drain each vertex with stranded excess
        while v0 not in (s, t) and e[v0] > 0:
            # BFS back toward s over arcs currently carrying flow inward;
            # any positive excess is flow-connected to the source, so the
            # search always reaches s (greedy walks can dead-end, BFS not)
            parent = {v0: None}  # w -> (closer-to-v0 vertex, arc w->it)
            frontier = [v0]
            while frontier and s not in parent:
                nxt = []
                for v in frontier:
                    for a in range(indptr[v], indptr[v + 1]):
                        ra, w = rev[a], heads[a]  # ra: w -> v
                        if res0[ra] - res[ra] > 0 and w not in parent:
                            parent[w] = (v, ra)
                            if w == s:
                                break
                            nxt.append(w)
                    if s in parent:
                        break
                frontier = nxt
            if s not in parent:  # not an assert: must survive python -O
                raise RuntimeError(
                    f"preflow decomposition from vertex {v0} did not reach "
                    "the source — the state is not a valid preflow for this "
                    "graph (excess must be flow-connected to s)")
            path, cur = [], s
            while cur != v0:  # unwind s -> v0, collecting flow arcs
                cur, arc = parent[cur]
                path.append(arc)
            d = min(int(e[v0]), min(int(res0[a] - res[a]) for a in path))
            for a in path:  # cancel d units of flow on every path arc
                res[a] += d
                res[rev[a]] -= d
            e[v0] -= d
    return res


def flows_from_state(r: ResidualCSR, state: PRState, s: int | None = None,
                     t: int | None = None,
                     reference: bool = False) -> np.ndarray:
    """Per-coalesced-edge net flow u->v.  With (s, t) given, stranded
    preflow excess is cancelled first (exact flow decomposition)."""
    if s is not None:
        res = convert_preflow_to_flow(r, state, s, t, reference=reference)
    else:
        res = np.asarray(state.res)
    arc = np.asarray(r.pair_arc)
    return np.asarray(r.res0)[arc] - res[arc]
