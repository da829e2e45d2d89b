"""Per-cycle cost of every push-relabel step mode -> BENCH_kernels.json.

Measures, for each mode in ``vc | tc | vc_kernel | vc_kernel_bsearch``
on the paper graph family:

* **us_per_cycle** — wall time of one warmed ``run_cycles`` dispatch
  divided by the cycles it executed (the solver hot-loop unit cost);
* **ops_per_cycle** — device-op count per cycle: primitive equations in
  the traced jaxpr of one bulk-synchronous step;
* **pallas_calls** — kernel launches appearing in that trace;
* **compile_ms** — wall time of the cold first ``run_cycles`` dispatch
  (trace + XLA compile + execute), the compile latency the scan-chunked
  sweep engine exists to bound;
* **scanned_eqns / unrolled_eqns** — primitive-equation counts of one
  scan-compiled engine chunk vs the same chunk Python-unrolled: the scan
  traces the step body ONCE, the unrolled form replicates it per step —
  the delta is the traced-program size the engine saves per chunk.
  These are the shared per-mode baselines from
  ``repro.analysis.baselines`` (read from a live ``ANALYSIS.json`` when
  one exists, else probed once) — NOT re-derived per benchmark graph:
  the counts are a property of the step trace, not of the graph.

``--smoke`` runs one tiny graph and asserts the launch contract (one
``pallas_call`` per cycle for ``vc_kernel``, two for
``vc_kernel_bsearch``, against a ``vc`` chain of ~10+ ops) and the engine
contract that the scan-chunked trace is strictly smaller than its
unrolled equivalent.  Times here are host wall clock on whatever backend
runs the script (CPU: XLA's CPU backend and the Pallas interpreter), not
device numbers.  Emits ``BENCH_kernels.json`` next to the repo root
(or ``--out``) so successive PRs can track the per-cycle trajectory.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro.analysis import ir
from repro.analysis.baselines import mode_baselines
from repro.core.pushrelabel import ALL_MODES as MODES
from repro.obs import REGISTRY, gauge


def _trace_counts(fn, *args):
    """(device-op count, pallas_call count) of fn's jaxpr — structural
    wrapper eqns (pjit/while/cond/scan shells) excluded, one launch
    counted as one device op (the shared census in repro.analysis.ir)."""
    census = ir.census(fn, *args)
    return census.device_op_count, census.pallas_call_count


def bench_graph(r, s, t, modes=MODES, cycles=24, repeats=3,
                graph_name: str = "anon", baselines=None):
    """Per-mode stats for one ResidualCSR instance."""
    from repro.core import globalrelabel, pushrelabel as pr

    g, meta, res0 = pr.to_device(r)
    state0 = pr.preflow(g, meta, res0, s)
    state0, _, _ = globalrelabel.global_relabel(g, meta, state0, s, t)
    out = {}
    for mode in modes:
        if mode == "vc_kernel_bsearch" and not r.binary_search_ready():
            continue

        def run():
            st, cyc = pr.run_cycles(g, meta, state0, s, t, mode=mode,
                                    max_cycles=cycles)
            return jax.block_until_ready(st.res), int(cyc)

        t0 = time.perf_counter()
        _, ncyc = run()  # warmup: trace + XLA compile + first execute
        cold_s = time.perf_counter() - t0
        best = min(_timed(run) for _ in range(repeats))
        # per-cycle device ops: one step's trace
        step = pr._make_step(mode)
        ops, pallas = _trace_counts(
            lambda st: step(g, meta, st, s, t), state0)
        ops_per_cycle = float(ops)
        out[mode] = {
            "us_per_cycle": best * 1e6 / max(ncyc, 1),
            "cycles_timed": ncyc,
            "ops_per_cycle": round(ops_per_cycle, 3),
            "pallas_calls": pallas,
            "compile_ms": round(cold_s * 1e3, 1),
        }
        if baselines and mode in baselines:
            # engine contract numbers come from the shared baseline probe
            # (repro.analysis.baselines) — graph-independent by design
            out[mode].update(baselines[mode])
        # report through the metrics registry: the JSON artifact embeds
        # REGISTRY.snapshot(), the same surface the serving tier exports
        for stat, val in out[mode].items():
            gauge(f"bench.kernel_cycles.{stat}", graph=graph_name,
                  mode=mode).set(float(val))
    return out


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def run(scale: float = 1.0, smoke: bool = False):
    from repro.core.csr import build_residual
    from repro.graphs import generators as G

    if smoke:
        graphs = {"smoke-sparse": G.random_sparse(60, 240, seed=7)}
    else:
        graphs = {
            "washington-rlg": G.washington_rlg(int(12 * scale),
                                               int(16 * scale), seed=7),
            "grid-road": G.grid_road(int(14 * scale), int(14 * scale),
                                     seed=7),
            "sparse-random": G.random_sparse(int(400 * scale),
                                             int(1800 * scale), seed=7),
        }
    # per-mode scanned/unrolled counts: one shared probe (or a live
    # ANALYSIS.json from `python -m repro.launch.analyze`), not per graph
    baselines = mode_baselines("ANALYSIS.json")
    rows = []
    for name, (g, s, t) in graphs.items():
        r = build_residual(g, "bcsr")
        per = bench_graph(r, s, t,
                          cycles=8 if smoke else 24,
                          repeats=2 if smoke else 3, graph_name=name,
                          baselines=baselines)
        rows.append({"graph": name, "n": int(g.n),
                     "arcs": int(r.num_arcs), "modes": per})
        for mode, st in per.items():
            eqns = (f"  scan={st['scanned_eqns']}/{st['unrolled_eqns']}"
                    if "scanned_eqns" in st else "")
            print(f"{name:18s} {mode:18s} {st['us_per_cycle']:10.1f} us/cyc"
                  f"  {st['ops_per_cycle']:7.2f} ops/cyc"
                  f"  pallas={st['pallas_calls']}"
                  f"  cold={st['compile_ms']:.0f}ms{eqns}")
    return rows


def main() -> None:
    from repro.runtime.cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny graph + launch-contract assertions")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--out", default="BENCH_kernels.json")
    args = ap.parse_args()

    rows = run(scale=args.scale, smoke=args.smoke)
    payload = {"bench": "kernel_cycles", "device": jax.default_backend(),
               "rows": rows,
               "metrics": REGISTRY.snapshot()["gauges"]}
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"wrote {args.out}")

    if args.smoke:
        per = rows[0]["modes"]
        vc = per["vc"]
        for mode, want in (("vc_kernel", 1), ("vc_kernel_bsearch", 2)):
            if per[mode]["pallas_calls"] != want:
                raise SystemExit(
                    f"{mode} must launch {want} pallas_call(s) per cycle, "
                    f"saw {per[mode]['pallas_calls']}")
        if vc["ops_per_cycle"] < 8:
            raise SystemExit(
                f"expected the ~10-op XLA chain in 'vc', saw "
                f"{vc['ops_per_cycle']} — the comparison baseline moved")
        for mode, st in per.items():
            if "scanned_eqns" not in st:
                continue
            if not st["scanned_eqns"] < st["unrolled_eqns"]:
                raise SystemExit(
                    f"scan-chunked trace of {mode!r} must be strictly "
                    f"smaller than its unrolled equivalent, saw "
                    f"{st['scanned_eqns']} vs {st['unrolled_eqns']}")
        print(f"smoke OK: vc {vc['ops_per_cycle']} ops/cyc; scan-chunked "
              f"vc trace {per['vc']['scanned_eqns']} eqns vs "
              f"{per['vc']['unrolled_eqns']} unrolled")


if __name__ == "__main__":
    main()
