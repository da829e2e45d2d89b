"""The dispatch-surface registry: every public device entry point,
abstractly traced on tiny shapes and gated on the contract rules.

A *surface* is one (callable, example-args, rules) triple — a jit
boundary the serving/solver/streaming/distributed tiers actually
dispatch through.  ``iter_surfaces()`` enumerates them all:

* ``run_cycles/<mode>/<layout>`` — the single-instance cycle loop for
  every solver mode x residual layout;
* ``batched_run_cycles/<mode>`` — the stacked ``(B, ...)`` cycle loop
  (the serving flush path), padded dummy lane included;
* ``global_relabel/{single,batched}[/kernel]`` — the Bellman-Ford
  distance sweeps, XLA reference and Pallas tile-kernel hook;
* ``phase2/{single,batched}[/kernel]`` — the preflow->flow excess
  cancellation;
* ``streaming/drain_prepared[/kernel]`` — the pooled decrease-reroute
  drain behind ``streaming.reroute.drain_prepared``;
* ``distributed/superstep`` — the shard_map superstep the dry-run
  lowers.

Tracing is ``jax.make_jaxpr`` only: no compile, no execution, no
accelerator needed — the census is a property of the traced program,
which is exactly what the paper's structural claims are about.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Iterator, Mapping

from repro.analysis import ir
from repro.analysis.rules import (
    Int32Lattice,
    LaunchBudget,
    NoHostSync,
    NoVmappedPallasCall,
    Rule,
    ScanChunkShape,
    TraceBudget,
    Violation,
    check_rules,
)

__all__ = ["Surface", "iter_surfaces", "trace_surface", "analyze_surface",
           "analyze_all", "trace_budget_for"]

#: cycles traced per surface — small; the steady-state trace shape is
#: independent of the cap (that is the point of the sweep engine)
_MAX_CYCLES = 32

#: pallas_call launches per bulk-synchronous sweep step, by mode — the
#: "one workload-balanced kernel launch per cycle" claim, per mode
#: ('vc_kernel_bsearch' adds the reverse-arc binary-search launch)
_LAUNCHES_PER_STEP = {"vc": 0, "tc": 0, "vc_kernel": 1,
                      "vc_kernel_bsearch": 2}

#: inner scan count of the cycle loop's steady state: ONE scanned chunk
#: body — except 'tc', whose per-arc masked segment walk is a
#: ``fori_loop`` that itself lowers to a second, step-internal scan
_CYCLE_SCANS = {"vc": 1, "tc": 2, "vc_kernel": 1, "vc_kernel_bsearch": 1}

#: per-surface equation-count ceilings (trace size ~= compile latency).
#: Seeded from the measured steady-state counts in BENCH_kernels.json
#: (scanned_eqns: vc 289 / tc 162 / vc_kernel 189 / vc_kernel_bsearch
#: 195 at chunk 4) plus ~2x headroom for the loop cond + driver eqns;
#: crossing one is a structural regression, not noise.  A live
#: BENCH_kernels.json re-seeds them at 2x its measured counts (see
#: :func:`trace_budget_for`).
_TRACE_CEILINGS = {
    "run_cycles": {"vc": 700, "tc": 450, "vc_kernel": 500,
                   "vc_kernel_bsearch": 520},
    "batched_run_cycles": {"vc": 800, "tc": 550, "vc_kernel": 600,
                           "vc_kernel_bsearch": 650},
    "global_relabel": 300,
    "phase2": 900,
    "streaming": 1800,
    "distributed": 700,
}


def trace_budget_for(family: str, mode: str | None = None) -> TraceBudget:
    """The family's (mode's) eqn ceiling, re-seeded from a live
    ``BENCH_kernels.json`` when one sits at the repo root (2x its
    measured steady-state count, floored at the static table) — so a
    machine that has benchmarked recently gates on its own measurements."""
    ceiling = _TRACE_CEILINGS[family]
    if isinstance(ceiling, Mapping):
        ceiling = ceiling[mode]
    measured = _bench_seeded_eqns().get(mode)
    if family in ("run_cycles", "batched_run_cycles") and measured:
        ceiling = max(ceiling, 2 * measured)
    return TraceBudget(ceiling)


@functools.lru_cache(maxsize=1)
def _bench_seeded_eqns() -> dict:
    """mode -> measured steady-state scanned_eqns from BENCH_kernels.json
    (empty when the artifact is absent, e.g. a fresh CI checkout)."""
    import json
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[3] / "BENCH_kernels.json"
    if not path.exists():
        return {}
    try:
        payload = json.loads(path.read_text())
        out = {}
        for row in payload.get("rows", []):
            for mode, st in row.get("modes", {}).items():
                if "scanned_eqns" in st:
                    out[mode] = max(out.get(mode, 0), st["scanned_eqns"])
        return out
    except (ValueError, KeyError, TypeError):
        return {}  # malformed artifact: fall back to the static table


@dataclasses.dataclass(frozen=True)
class Surface:
    """One registered dispatch surface."""

    name: str
    family: str
    tags: tuple[tuple[str, str], ...]  # sorted (key, value) pairs
    build: Callable[[], tuple[Callable, tuple]]
    rules: tuple[Rule, ...]

    def tag_dict(self) -> dict:
        return dict(self.tags)


def _tags(**kw) -> tuple[tuple[str, str], ...]:
    return tuple(sorted((k, str(v)) for k, v in kw.items()))


# ---------------------------------------------------------------------------
# tiny fixtures (host-side, cached; tracing needs shapes, not content)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _single_fixture(layout: str):
    from repro.core import globalrelabel
    from repro.core import pushrelabel as pr
    from repro.core.csr import build_residual
    from repro.graphs import generators as G

    adj, s, t = G.random_sparse(24, 96, seed=7)
    r = build_residual(adj, layout)
    g, meta, res0 = pr.to_device(r)
    state = pr.preflow(g, meta, res0, s)
    state, _, _ = globalrelabel.global_relabel(g, meta, state, s, t)
    return g, meta, state, s, t, r, res0


@functools.lru_cache(maxsize=None)
def _batched_fixture():
    from repro.core import batched
    from repro.core.csr import build_residual
    from repro.graphs import generators as G

    insts = []
    for seed in (1, 2):
        adj, s, t = G.random_sparse(20, 70, seed=seed)
        insts.append((build_residual(adj, "bcsr"), s, t))
    insts.append((insts[0][0], 0, 0))  # padded dummy lane (s == t)
    bg, meta, res0, trivial = batched.pack_instances(insts)
    state = batched.batched_preflow(bg, meta, res0)
    return bg, meta, res0, state


@functools.lru_cache(maxsize=None)
def _kernel_hook():
    from repro.kernels import ops as kops

    return kops.min_neighbor_minh_fn(None)


# ---------------------------------------------------------------------------
# surface builders
# ---------------------------------------------------------------------------

def _build_run_cycles(mode: str, layout: str):
    from repro.core import pushrelabel as pr

    g, meta, state, s, t, _, _ = _single_fixture(layout)

    def fn(res, h, e):
        return pr.run_cycles(g, meta, pr.PRState(res, h, e), s, t,
                             mode=mode, max_cycles=_MAX_CYCLES)

    return fn, (state.res, state.h, state.e)


def _build_batched_run_cycles(mode: str):
    from repro.core import batched

    bg, meta, _, state = _batched_fixture()

    def fn(res, h, e):
        return batched.batched_run_cycles(
            bg, meta, batched.BatchedPRState(res, h, e), mode=mode,
            max_cycles=_MAX_CYCLES)

    return fn, (state.res, state.h, state.e)


def _build_global_relabel(batch: bool, kernel: bool):
    hook = _kernel_hook() if kernel else None
    if batch:
        from repro.core import batched

        bg, meta, _, state = _batched_fixture()

        def fn(res, h, e):
            return batched.batched_global_relabel(
                bg, meta, batched.BatchedPRState(res, h, e), minh_fn=hook)

        return fn, (state.res, state.h, state.e)
    from repro.core import globalrelabel
    from repro.core import pushrelabel as pr

    g, meta, state, s, t, _, _ = _single_fixture("bcsr")

    def fn(res, h, e):
        return globalrelabel.global_relabel(g, meta, pr.PRState(res, h, e),
                                            s, t, minh_fn=hook)

    return fn, (state.res, state.h, state.e)


def _build_phase2(batch: bool, kernel: bool):
    hook = _kernel_hook() if kernel else None
    if batch:
        from repro.core import batched

        bg, meta, res0, state = _batched_fixture()

        def fn(res, h, e):
            return batched.batched_phase2(
                bg, meta, res0, batched.BatchedPRState(res, h, e),
                minh_fn=hook)

        return fn, (state.res, state.h, state.e)
    from repro.core import phase2
    from repro.core import pushrelabel as pr

    g, meta, state, s, t, _, res0 = _single_fixture("bcsr")

    def fn(res, e):
        return phase2.phase2_impl(g, meta, res0, res, e, s, t,
                                  minh_fn=hook)

    return fn, (state.res, state.e)


def _build_streaming_drain(kernel: bool):
    from repro.core import pushrelabel as pr
    from repro.streaming import reroute

    hook = _kernel_hook() if kernel else None
    bg, meta, res0, state = _batched_fixture()
    g = pr.DeviceGraph(bg.indptr, bg.heads, bg.tails, bg.rev)

    def fn(res, b, e):
        # the pooled decrease-reroute drain behind drain_prepared: the
        # imbalance vector rides in the height slot of the packed state
        return reroute._batched_reroute_impl(g, meta, res0, res, b, e,
                                             bg.s, bg.t, minh_fn=hook)

    return fn, (state.res, state.h, state.e)


def _build_distributed_superstep():
    import jax

    from repro import compat
    from repro.core import distributed as D
    from repro.core.csr import build_residual
    from repro.graphs import generators as G

    adj, s, t = G.random_sparse(16, 48, seed=9)
    r = build_residual(adj, "bcsr")
    mesh = compat.make_mesh((1,), ("pod",))
    g, meta, res0 = D.partition_graph(r, 1, s, t, "replicated")
    superstep = D.make_superstep(meta, ("pod",), cycles=8, mesh=mesh)

    import jax.numpy as jnp

    res = jnp.asarray(res0)
    h = jnp.zeros(meta.n, jnp.int32).at[s].set(meta.n)
    e = jnp.zeros(meta.n, jnp.int32)

    def fn(res, h, e):
        with jax.set_mesh(mesh):
            return superstep(g, res, h, e)

    return fn, (res, h, e)


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

def _base_rules() -> tuple[Rule, ...]:
    return (NoVmappedPallasCall(), NoHostSync(), Int32Lattice())


def iter_surfaces(modes: tuple[str, ...] | None = None) -> Iterator[Surface]:
    """Every registered dispatch surface, lazily built."""
    from repro.core.pushrelabel import ALL_MODES

    modes = tuple(modes) if modes is not None else ALL_MODES

    # -- run_cycles: modes x layouts ------------------------------------
    for mode in modes:
        layouts = ("bcsr",) if mode == "vc_kernel_bsearch" else ("bcsr",
                                                                 "rcsr")
        for layout in layouts:
            launches = _LAUNCHES_PER_STEP[mode]
            yield Surface(
                name=f"run_cycles/{mode}/{layout}",
                family="run_cycles",
                tags=_tags(mode=mode, layout=layout, batched=False),
                build=functools.partial(_build_run_cycles, mode, layout),
                rules=_base_rules() + (
                    ScanChunkShape(whiles=1, scans=_CYCLE_SCANS[mode],
                                   pallas_per_dispatch=launches),
                    LaunchBudget(launches),
                    trace_budget_for("run_cycles", mode),
                ))

    # -- batched_run_cycles: the serving flush path ---------------------
    for mode in modes:
        launches = _LAUNCHES_PER_STEP[mode]
        yield Surface(
            name=f"batched_run_cycles/{mode}",
            family="batched_run_cycles",
            tags=_tags(mode=mode, layout="bcsr", batched=True),
            build=functools.partial(_build_batched_run_cycles, mode),
            rules=_base_rules() + (
                ScanChunkShape(whiles=1, scans=_CYCLE_SCANS[mode],
                               pallas_per_dispatch=launches),
                LaunchBudget(launches),
                trace_budget_for("batched_run_cycles", mode),
            ))

    # -- global relabel sweeps ------------------------------------------
    for batch in (False, True):
        for kernel in (False, True):
            kind = "batched" if batch else "single"
            suffix = "/kernel" if kernel else ""
            launches = 1 if kernel else 0
            yield Surface(
                name=f"global_relabel/{kind}{suffix}",
                family="global_relabel",
                tags=_tags(batched=batch, kernel=kernel),
                build=functools.partial(_build_global_relabel, batch,
                                        kernel),
                rules=_base_rules() + (
                    ScanChunkShape(whiles=1, scans=1,
                                   pallas_per_dispatch=launches),
                    LaunchBudget(launches),
                    TraceBudget(_TRACE_CEILINGS["global_relabel"]),
                ))

    # -- phase 2: preflow -> flow ---------------------------------------
    for batch in (False, True):
        for kernel in (False, True):
            kind = "batched" if batch else "single"
            suffix = "/kernel" if kernel else ""
            # [heights-to-fixpoint -> cancel-to-fixpoint] under a chunk=1
            # outer loop: 3 whiles, 2 scanned bodies; the kernel hook
            # fires once per height sweep (the cancellation selects no arc)
            launches = 1 if kernel else 0
            yield Surface(
                name=f"phase2/{kind}{suffix}",
                family="phase2",
                tags=_tags(batched=batch, kernel=kernel),
                build=functools.partial(_build_phase2, batch, kernel),
                rules=_base_rules() + (
                    ScanChunkShape(whiles=3, scans=2,
                                   pallas_per_dispatch=launches),
                    LaunchBudget(launches),
                    TraceBudget(_TRACE_CEILINGS["phase2"]),
                ))

    # -- streaming: the pooled decrease-reroute drain -------------------
    for kernel in (False, True):
        suffix = "/kernel" if kernel else ""
        # deficit drain (height sweep + arc selection) + excess drain
        # (phase 2: height sweep only), each a phase2-shaped loop nest
        launches = 3 if kernel else 0
        yield Surface(
            name=f"streaming/drain_prepared{suffix}",
            family="streaming",
            tags=_tags(batched=True, kernel=kernel),
            build=functools.partial(_build_streaming_drain, kernel),
            rules=_base_rules() + (
                ScanChunkShape(whiles=6, scans=4,
                               pallas_per_dispatch=launches),
                LaunchBudget(launches),
                TraceBudget(_TRACE_CEILINGS["streaming"]),
            ))

    # -- distributed superstep ------------------------------------------
    yield Surface(
        name="distributed/superstep",
        family="distributed",
        tags=_tags(batched=False, kernel=False),
        build=_build_distributed_superstep,
        rules=_base_rules() + (
            ScanChunkShape(whiles=2, scans=2, pallas_per_dispatch=0),
            LaunchBudget(0),
            TraceBudget(_TRACE_CEILINGS["distributed"]),
        ))


def trace_surface(surface: Surface) -> ir.OpCensus:
    """Abstractly trace one surface and census the result."""
    fn, args = surface.build()
    return ir.census(fn, *args)


def analyze_surface(surface: Surface
                    ) -> tuple[ir.OpCensus, list[Violation]]:
    census = trace_surface(surface)
    return census, check_rules(census, surface.rules, surface.name)


def analyze_all(modes: tuple[str, ...] | None = None
                ) -> dict[str, tuple[Surface, ir.OpCensus,
                                     list[Violation]]]:
    """Trace + rule-check every registered surface; keyed by name."""
    out = {}
    for s in iter_surfaces(modes):
        census, violations = analyze_surface(s)
        out[s.name] = (s, census, violations)
    return out
