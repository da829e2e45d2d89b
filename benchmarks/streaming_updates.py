"""Streaming updates: incremental re-solve vs cold re-solve on update
traces.

Replays generated edit-event traces (``repro.graphs.generators.
update_trace``) two ways and compares them step by step:

* **Incremental** — one long-lived ``Solver.open_stream`` session: every
  batch folds into a new version via the signed warm path (capacity
  increases re-enter with a budgeted warm start, decreases reroute the
  overflowed flow on-device, structural inserts rebuild the CSR around
  the routed flow).
* **Cold** — every batch's cumulative graph solved from scratch through
  the same ``Solver``.

Both passes replay the identical trace once untimed first, so XLA
compiles are excluded from the timed windows; values are asserted equal
at every step (the streaming tier's bit-compatibility claim).  Traces
cover random updates, high-locality updates (the warm best case) and
the adversarial frontier-toggling trace (the honest worst case).

Emits ``BENCH_streaming.json``.  ``--smoke`` shrinks the workload and
enforces the acceptance gate: incremental wall <= 0.6x cold wall on the
non-adversarial traces (per-step value equality is always asserted).
"""
from __future__ import annotations

import argparse
import json
import time

from repro.api import MaxflowProblem, Solver, SolverOptions
from repro.graphs import generators as G
from repro.obs import REGISTRY


def replay_incremental(solver, g, s, t, batches) -> dict:
    sg = solver.open_stream(MaxflowProblem(g, s, t),
                            max_versions=len(batches) + 1)
    values, wall = [], 0.0
    for batch in batches:
        t0 = time.perf_counter()
        version = sg.apply(batch)
        wall += time.perf_counter() - t0
        values.append(sg.query(version).value)
    stats = sg.stats()
    sg.close()
    return {"values": values, "wall_s": wall,
            "rebuilds": stats["structural_rebuilds"],
            "events": stats["events"]}


def replay_cold(solver, g, s, t, batches) -> dict:
    values, wall = [], 0.0
    cum = []
    for batch in batches:
        cum.append(batch)
        g2 = G.apply_events_to_graph(g, cum)
        t0 = time.perf_counter()
        values.append(solver.solve(MaxflowProblem(g2, s, t)).value)
        wall += time.perf_counter() - t0
    return {"values": values, "wall_s": wall}


def run_trace(name: str, g, s, t, batches, solver) -> dict:
    # untimed warmup replays compile every executable either pass mints
    replay_incremental(solver, g, s, t, batches)
    replay_cold(solver, g, s, t, batches)
    inc = replay_incremental(solver, g, s, t, batches)
    cold = replay_cold(solver, g, s, t, batches)
    assert inc["values"] == cold["values"], (
        f"{name}: incremental diverged from cold\n"
        f"  incremental: {inc['values']}\n  cold: {cold['values']}")
    ratio = inc["wall_s"] / cold["wall_s"] if cold["wall_s"] else 0.0
    out = {"trace": name, "steps": len(batches), "events": inc["events"],
           "rebuilds": inc["rebuilds"], "final_value": inc["values"][-1],
           "incremental_wall_s": inc["wall_s"],
           "cold_wall_s": cold["wall_s"], "ratio": ratio}
    print(f"{name:16s} steps={out['steps']:3d} events={out['events']:4d} "
          f"rebuilds={out['rebuilds']:2d} incremental="
          f"{1e3 * inc['wall_s']:7.1f}ms cold={1e3 * cold['wall_s']:7.1f}ms "
          f"ratio={ratio:.2f}")
    return out


def run(n: int = 120, m_per_n: int = 4, n_batches: int = 12,
        batch_size: int = 4, seed: int = 0, smoke: bool = False) -> dict:
    g, s, t = G.random_sparse(n, m_per_n * n, max_cap=50, seed=seed)
    solver = Solver(SolverOptions())
    traces = {
        # re-weights/deletes only: the pure warm path, no CSR rebuilds
        "reweight": G.update_trace(g, s, t, n_batches=n_batches,
                                   batch_size=batch_size, p_insert=0.0,
                                   p_delete=0.2, seed=seed + 1),
        # mixed with structural inserts (some steps pay a rebuild)
        "mixed": G.update_trace(g, s, t, n_batches=n_batches,
                                batch_size=batch_size, p_insert=0.15,
                                p_delete=0.15, seed=seed + 2),
        # high locality: updates hammer one neighbourhood
        "local": G.update_trace(g, s, t, n_batches=n_batches,
                                batch_size=batch_size, p_insert=0.0,
                                p_delete=0.2, locality=0.9, seed=seed + 3),
        # frontier toggling: repeatedly invalidates the routed flow
        "adversarial": G.update_trace(g, s, t, n_batches=max(
            2, n_batches // 3), batch_size=batch_size, adversarial=True,
            seed=seed + 4),
    }
    results = [run_trace(name, g, s, t, batches, solver)
               for name, batches in traces.items()]
    counters = {k: v for k, v in REGISTRY.snapshot()["counters"].items()
                if k.startswith("stream.")}
    out = {"graph": {"n": n, "m": m_per_n * n}, "traces": results,
           "stream_counters": counters}
    print("stream counters:",
          {k: v for k, v in sorted(counters.items())})
    if smoke:
        check_smoke(out)
    return out


def check_smoke(out: dict) -> None:
    """Acceptance gate: the incremental replay must beat cold by the
    margin the streaming tier exists for, on every non-adversarial
    trace.  (Value equality at every step is asserted inside
    ``run_trace`` unconditionally — incremental is bit-compatible with
    cold on the flow value, both capacity signs.)"""
    for rec in out["traces"]:
        if rec["trace"] == "adversarial":
            continue  # worst case is reported, not gated
        assert rec["ratio"] <= 0.6, (
            f"trace {rec['trace']}: incremental {rec['ratio']:.2f}x cold "
            "wall (> 0.6x)")
    print("SMOKE PASS: incremental <= 0.6x cold wall on "
          "reweight/mixed/local traces, values equal at every step")


def main(argv=None):
    from repro.runtime.cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=120)
    ap.add_argument("--batches", type=int, default=12)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_streaming.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small workload + assert acceptance thresholds")
    args = ap.parse_args(argv)
    if args.smoke:
        n, batches, bsize = min(args.n, 80), min(args.batches, 8), 3
    else:
        n, batches, bsize = args.n, args.batches, args.batch_size
    out = run(n=n, n_batches=batches, batch_size=bsize, seed=args.seed,
              smoke=False)
    import jax

    payload = {"bench": "streaming_updates",
               "device": jax.default_backend(), **out}
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True, default=str)
    print(f"wrote {args.out}")
    if args.smoke:  # gate AFTER the artifact exists
        check_smoke(out)


if __name__ == "__main__":
    main()
