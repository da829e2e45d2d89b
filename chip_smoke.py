"""Bring-up smoke test of the max-flow main path on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the distributed backend
                                      # against the one-chip 'vc' solve

Phases (one process; each checks its answers before the next starts):

1. ``kernels``  — the Pallas kernels (``segmin`` AVQ and dense forms,
   ``revsearch``) compiled for the chip, bit-for-bit against their
   pure-jnp / build-time references on the same instance.
2. ``vc``, ``vc_kernel``, ``vc_kernel_bsearch`` — one-shot solves through
   ``repro.api.Solver`` on ``washington_rlg(4096, COLS, seed=0)``, the
   DIMACS random-level-graph family.  Uncut (``--cols 256``) it has
   1,048,578 vertices and 6,281,676 residual arcs; the default cuts it to
   8 levels (32,770 vertices) to fit the chip's time limit — see
   ``COLS``.  Each answer must pass the max-flow/min-cut duality
   certificate, checked with numpy against the graph's own capacities,
   and equal scipy's value (326,932 for any cols >= 64).
3. ``batched`` — ``Solver(backend="batched").solve_many`` on 8 instances.
4. ``serving`` — ``MaxflowService(mode="auto")`` answering a Poisson
   workload with resubmits; zero retries, demotions, host-reference
   flushes or failure disqualifications allowed.

Phases 3-4 are checked against ``scipy.sparse.csgraph.maximum_flow``, an
independent implementation.  Every phase prints one JSON line (sizes,
compile and solve seconds, value and reference, peak device bytes); the
last line is ``{"ok": true, "device": {...}}``.  The script exits
non-zero, with no such line, when the backend is not a TPU, when a kernel
would run interpreted, or when any answer is wrong.

Instances are generated from seeds here; nothing is read from disk.  The
persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` or the
checkout's ``.jax_cache`` (``repro.runtime.cache``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ROWS, SEED = 4096, 0
#: levels of the uncut instance: washington_rlg(4096, 256) has 1,048,578
#: vertices
FULL_COLS = 256
#: levels the smoke runs by default.  On a TPU v5e the 'vc' step costs
#: about 0.85 s per cycle at 10^6 vertices (every cycle gathers and
#: scatters over all 6.3M arcs) and the uncut solve needs on the order of
#: 8,500 cycles, hours in all; 8 levels (32,770 vertices, 1,024 cycles)
#: keep every phase inside a 20-minute run.  ``--cols 256`` runs the
#: uncut instance.
COLS = 8
#: scipy.sparse.csgraph.maximum_flow on washington_rlg(4096, 256, seed=0)
#: (the terminal arcs bound it, so any cols >= 64 gives the same value;
#: below that the script computes scipy's value itself)
EXPECTED_VALUE = 326_932
KERNEL_MODES = ("vc_kernel", "vc_kernel_bsearch")


class SmokeFailure(RuntimeError):
    """A phase produced a wrong answer or ran off the chip path."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# -- instances and references ------------------------------------------------

def washington(rows: int, cols: int, seed: int = SEED):
    from repro.graphs import generators as G

    return G.washington_rlg(rows, cols, seed=seed)


def scipy_maxflow(g, s: int, t: int) -> int:
    """Independent reference: scipy's max flow (Dinic, in C)."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import maximum_flow

    keep = g.edges[:, 0] != g.edges[:, 1]
    u, v = g.edges[keep, 0], g.edges[keep, 1]
    m = csr_matrix((g.cap[keep].astype(np.int32), (u, v)),
                   shape=(g.n, g.n))  # duplicates sum on conversion
    return int(maximum_flow(m, s, t).flow_value)


def _pair_caps(g):
    """Directed capacity per vertex pair, summed from the edge list:
    sorted keys ``u * n + v`` and their capacities."""
    u, v = g.edges[:, 0].astype(np.int64), g.edges[:, 1].astype(np.int64)
    keep = u != v
    keys, inv = np.unique(u[keep] * g.n + v[keep], return_inverse=True)
    cap = np.zeros(keys.size, np.int64)
    np.add.at(cap, inv, g.cap[keep].astype(np.int64))
    return keys, cap


def check_certificate(g, s: int, t: int, sol) -> int:
    """Max-flow/min-cut duality: ``sol.flows()`` must be a feasible flow
    of value ``sol.value`` under ``g``'s capacities, and ``sol.min_cut()``
    an s-t cut of the same capacity — together they prove the value is
    the maximum.  Returns the value."""
    value = sol.value
    r = sol.warm_start.residual
    f = np.asarray(sol.flows(), np.int64)
    pu = np.asarray(r.pair_u, np.int64)
    pv = np.asarray(r.heads, np.int64)[np.asarray(r.pair_arc)]
    keys, cap = _pair_caps(g)

    def cap_of(a, b):
        k = a * g.n + b
        i = np.minimum(np.searchsorted(keys, k), keys.size - 1)
        return np.where(keys[i] == k, cap[i], 0)

    _require(bool(np.all(f <= cap_of(pu, pv))), "flow exceeds capacity")
    _require(bool(np.all(-f <= cap_of(pv, pu))),
             "reverse flow exceeds capacity")
    net = np.zeros(g.n, np.int64)
    np.add.at(net, pu, f)
    np.add.at(net, pv, -f)
    _require(int(net[s]) == value, f"net flow out of s {int(net[s])} != "
             f"value {value}")
    _require(int(net[t]) == -value, f"net flow into t {-int(net[t])} != "
             f"value {value}")
    inner = np.ones(g.n, bool)
    inner[[s, t]] = False
    _require(not net[inner].any(), "flow conservation violated at "
             f"{int(np.count_nonzero(net[inner]))} vertices")
    side = np.asarray(sol.min_cut().source_side, bool)
    _require(bool(side[s]) and not bool(side[t]), "cut does not separate "
             "s from t")
    u, v = g.edges[:, 0], g.edges[:, 1]
    crossing = side[u] & ~side[v] & (u != v)
    cut_cap = int(g.cap[crossing].astype(np.int64).sum())
    _require(cut_cap == value, f"cut capacity {cut_cap} != value {value}")
    return value


# -- measurement --------------------------------------------------------------

class CompileClock:
    """Seconds JAX spends in backend compilation — XLA and Mosaic
    compiling a lowered program, or fetching it from the persistent
    compilation cache — summed from its monitoring events.  Tracing and
    lowering (Python work) count as solve time."""

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if stats is None else int(stats["peak_bytes_in_use"])


def timed(clock: CompileClock, fn):
    """``(result, wall seconds, compile seconds within it)``."""
    c0, t0 = clock.total, time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    return out, wall, clock.total - c0


def record(phase: str, **fields) -> dict:
    rec = {"phase": phase, **fields, "peak_bytes_in_use": peak_bytes()}
    print(json.dumps(rec), flush=True)
    return rec


# -- phases -------------------------------------------------------------------

def phase_kernels(clock, g, s, t) -> dict:
    """Each kernel on the real instance's arrays, bit-for-bit against its
    reference, with a Mosaic kernel (not the interpreter) in the
    compiled program whenever the backend is a TPU."""
    import jax
    import jax.numpy as jnp

    from repro.core import globalrelabel
    from repro.core import pushrelabel as pr
    from repro.core.csr import build_residual
    from repro.kernels import ref as kref
    from repro.kernels.revsearch import bcsr_rev_search
    from repro.kernels.runtime import resolve_interpret
    from repro.kernels.segmin import tile_min_neighbor

    on_tpu = jax.default_backend() == "tpu"
    _require(resolve_interpret(None) is (not on_tpu),
             "kernels would run interpreted on the chip")

    def run():
        r = build_residual(g, "bcsr")
        dg, meta, res0 = pr.to_device(r)
        n, a = meta.n, meta.num_arcs
        state = pr.preflow(dg, meta, res0, s)
        state, _, _ = globalrelabel.global_relabel(dg, meta, state, s, t)
        act = pr.active_mask(state, n, s, t)
        avq = jnp.nonzero(act, size=n, fill_value=n)[0].astype(jnp.int32)
        key = jnp.where(state.res > 0, state.h[dg.heads],
                        kref.INF).astype(jnp.int32)
        def compiled(fn, *args, **static):
            """``fn`` compiled once for ``args``, with a Mosaic kernel in
            the program whenever the backend is a TPU."""
            exe = fn.lower(*args, **static).compile()
            _require(not on_tpu or "tpu_custom_call" in exe.as_text(),
                     f"{fn.__name__}: no Mosaic kernel in the program")
            return exe

        checks = {}
        for form, q in (("avq", avq), ("dense", None)):
            got = compiled(tile_min_neighbor, q, dg.indptr, key, n=n)(
                q, dg.indptr, key)
            want = jax.jit(kref.min_neighbor_ref, static_argnames="n")(
                jnp.arange(n, dtype=jnp.int32) if q is None else q,
                dg.indptr, key, n=n)
            for x, y in zip(got, want):
                _require(bool(jnp.array_equal(x, y)),
                         f"segmin {form} differs from its reference")
            checks[f"segmin_{form}_entries"] = n
        arcs = jnp.arange(a, dtype=jnp.int32)
        got = compiled(bcsr_rev_search, arcs, dg.indptr, dg.heads,
                       dg.tails)(arcs, dg.indptr, dg.heads, dg.tails)
        want = kref.rev_search_ref(arcs, dg.rev, a)
        _require(bool(jnp.array_equal(got, want)),
                 "revsearch differs from the rev table")
        checks["revsearch_arcs"] = a
        return n, a, checks

    (n, a, checks), wall, comp = timed(clock, run)
    return record("kernels", n=n, arcs=a, compile_s=comp,
                  solve_s=wall - comp, bit_exact=True, **checks)


def phase_one_shot(clock, g, s, t, mode: str,
                   expect: int | None = None) -> dict:
    """One ``Solver.solve`` under ``mode``, certified by duality."""
    from repro.api import MaxflowProblem, Solver, SolverOptions

    problem = MaxflowProblem(g, s, t)
    solver = Solver(SolverOptions(mode=mode, layout="bcsr"))
    sol, wall, comp = timed(clock, lambda: solver.solve(problem))
    r = sol.warm_start.residual
    (value, cert_wall, _) = timed(
        clock, lambda: check_certificate(g, s, t, sol))
    if expect is not None:
        _require(value == expect, f"{mode}: value {value} != expected "
                 f"{expect}")
    return record(mode, n=r.n, arcs=r.num_arcs, compile_s=comp,
                  solve_s=wall - comp, value=value, reference=expect,
                  certificate="flow+cut", certificate_s=cert_wall,
                  rounds=sol.stats.rounds, cycles=sol.stats.cycles,
                  global_relabels=sol.stats.global_relabels,
                  gr_sweeps=sol.stats.gr_sweeps)


def batched_instances(k: int = 8):
    from repro.graphs import generators as G

    out = []
    for i in range(k):
        if i % 2:
            out.append(G.grid_road(12, 12, max_cap=10, seed=i))
        else:
            out.append(G.washington_rlg(24, 6, seed=i))
    return out


def phase_batched(clock, instances) -> dict:
    from repro.api import MaxflowProblem, Solver

    problems = [MaxflowProblem(g, s, t) for g, s, t in instances]
    sols, wall, comp = timed(
        clock, lambda: Solver(backend="batched").solve_many(problems))
    got = [sol.value for sol in sols]
    want = [scipy_maxflow(g, s, t) for g, s, t in instances]
    _require(got == want, f"batched values {got} != scipy {want}")
    return record("batched", instances=len(instances),
                  n=max(g.n for g, _, _ in instances),
                  arcs=max(sol.warm_start.residual.num_arcs for sol in sols),
                  compile_s=comp, solve_s=wall - comp, value=got,
                  reference=want)


#: robustness counters that must stay zero on a healthy chip
_ZERO_ROBUSTNESS = ("retries", "transient_demotions", "sticky_demotions",
                    "host_fallbacks", "dispatch_failed", "budget_exhausted",
                    "quarantined", "rejected", "shed")


def phase_serving(clock, num_requests: int = 32, seed: int = 0,
                  faults=None) -> dict:
    """``MaxflowService(mode="auto")`` under a Poisson workload; every
    answer equals scipy's, and the degradation ladder never engaged."""
    from repro.serving import workload
    from repro.serving.maxflow_service import MaxflowService, ServiceConfig

    items = workload.synthesize(num_requests, seed=seed, process="poisson")
    svc = MaxflowService(ServiceConfig(mode="auto"), faults=faults)
    recs, wall, comp = timed(clock, lambda: workload.drive(svc, items))
    got, want = [], []
    for item, rec in zip(items, recs):
        _require(rec["error"] is None,
                 f"{item.kind} request failed: {rec['error']!r}")
        got.append(rec["result"].maxflow)
        want.append(scipy_maxflow(*workload.resolve_item(items, item)))
    _require(got == want, f"served values {got} != scipy {want}")
    rb = svc.stats()["robustness"]
    bad = {k: rb[k] for k in _ZERO_ROBUSTNESS if rb[k]}
    _require(not bad and not rb["ladders"],
             f"the degradation ladder engaged: {bad or rb['ladders']}")
    modes = {k: v["pinned"] for k, v in svc.stats()["mode_policy"].items()}
    return record("serving", requests=num_requests,
                  resubmits=sum(i.kind == "resubmit" for i in items),
                  compile_s=comp, solve_s=wall - comp,
                  value=sum(got), reference=sum(want), pinned_modes=modes,
                  robustness={k: rb[k] for k in _ZERO_ROBUSTNESS})


def phase_distributed(clock, g, s, t, expect: int | None) -> dict:
    """``Solver(backend="distributed")`` over every visible device."""
    import jax

    from repro.api import MaxflowProblem, Solver, SolverOptions
    from repro.core import distributed
    from repro.core.csr import build_residual

    ndev = len(jax.devices())
    dg, _, res0 = distributed.partition_graph(build_residual(g, "bcsr"),
                                              ndev, s, t)
    placement = {name: sorted(str(d) for d in x.devices())
                 for name, x in dg._asdict().items()}
    placement["res0"] = sorted(str(d) for d in res0.devices())
    problem = MaxflowProblem(g, s, t)
    sol, wall, comp = timed(clock, lambda: Solver(
        SolverOptions(backend="distributed")).solve(problem))
    if expect is not None:
        _require(sol.value == expect, f"distributed value {sol.value} != "
                 f"expected {expect}")
    return record("distributed", devices=ndev, n=g.n, compile_s=comp,
                  solve_s=wall - comp, value=sol.value, reference=expect,
                  residual="replicated on every device",
                  partitioned_arrays_on=placement)


# -- entry point --------------------------------------------------------------

def _chip():
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (backend "
                         f"{devs[0].platform!r}); nothing was run")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cols", type=int, default=COLS,
                    help=f"levels of the washington_rlg instance (default "
                         f"{COLS}, cut from {FULL_COLS}: see COLS)")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit("chip_smoke: run from a checkout of the repository "
                         "(src/repro not found next to this script)")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.runtime.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    device = _chip()
    if device["count"] != args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but "
                         f"{device['count']} devices are visible")
    clock = CompileClock()
    t0 = time.perf_counter()
    g, s, t = washington(ROWS, args.cols)
    gen_s = time.perf_counter() - t0
    if args.cols >= 64:
        expect, reference = EXPECTED_VALUE, "known value (scipy)"
    else:
        expect, reference = scipy_maxflow(g, s, t), "scipy maximum_flow"
    record("instance", family="washington_rlg", rows=ROWS, cols=args.cols,
           seed=SEED, n=g.n, edges=g.m, cut=None if args.cols >= FULL_COLS
           else f"cols {FULL_COLS} -> {args.cols}", generate_s=gen_s,
           value_reference=expect, reference=reference,
           compile_cache=cache_dir)
    if args.chips == 4:
        phase_one_shot(clock, g, s, t, "vc", expect)
        phase_distributed(clock, g, s, t, expect)
    else:
        phase_kernels(clock, g, s, t)
        for mode in ("vc",) + KERNEL_MODES:
            phase_one_shot(clock, g, s, t, mode, expect)
        phase_batched(clock, batched_instances())
        phase_serving(clock)
    record("total", wall_s=time.perf_counter() - t0,
           compile_s=clock.total)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
