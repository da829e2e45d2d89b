"""Serving throughput: batched microbatching vs sequential solves, and
warm-started re-solves vs cold.

Measures the two claims the serving subsystem exists for:

* **Batched vs sequential** — the same Poisson workload through
  ``MaxflowService`` (shape buckets amortize XLA compiles, one dispatch
  advances a whole microbatch) vs one single-backend ``repro.api`` solve
  per request (one executable per instance shape).  Reports requests/s and
  p50/p99 per-request latency; asserts the flows agree exactly.
* **Warm vs cold** — for every resubmit (capacity increase of a previously
  solved graph), the warm re-solve's push-relabel cycles vs a cold solve
  of the identical updated graph.

* **Phase-2 cost** — warm resubmits need genuine flows; the first
  resubmit of a flushed microbatch corrects the whole batch in one
  ``batched_phase2`` device dispatch (replacing the old host-side O(V*E)
  preflow->flow BFS).  Reported as absolute time and as a ratio to
  warm-resubmit solve latency (it must stay sub-dominant).

* **Per-bucket mode policy** — a second service runs ``mode="auto"``:
  each shape bucket trials the candidate solver modes on its first
  flushes and pins the measured winner.  Reports the per-bucket table
  (chosen mode + measured per-cycle costs), the pooled-sweep
  (global-relabel) and phase-2 time, and a steady-state wall comparison
  of the pinned-auto service vs a pinned-``vc`` service on a second
  workload (executables warm for both).

Emits ``BENCH_serving.json`` (like ``BENCH_kernels.json``) so successive
PRs can track the serving trajectory.  ``--smoke`` runs a small CPU-scale
workload and enforces the acceptance thresholds (batched >= 2x sequential
throughput, warm <= 0.5x cold cycles, phase-2 <= 0.5x of warm resubmit
latency, and the auto policy never losing to pinned ``vc`` by more than
10% on any bucket it pinned).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.api import MaxflowProblem, Solver, SolverOptions
from repro.core.pushrelabel import ALL_MODES
from repro.serving import MaxflowService, ServiceConfig
from repro.serving.workload import drive, resolve_item, synthesize


def run_sequential(items) -> dict:
    """Baseline: every request solved on arrival, no batching, no caching."""
    solver = Solver(SolverOptions(layout="bcsr"))
    lat = []
    flows = []
    t0 = time.perf_counter()
    for item in items:
        g, s, t = resolve_item(items, item)
        ta = time.perf_counter()
        flows.append(solver.solve(MaxflowProblem(g, s, t)).value)
        lat.append(time.perf_counter() - ta)
    wall = time.perf_counter() - t0
    return {"wall_s": wall, "rps": len(items) / wall, "flows": flows,
            "p50_ms": 1e3 * float(np.percentile(lat, 50)),
            "p99_ms": 1e3 * float(np.percentile(lat, 99))}


CYCLE_CHUNK = 16  # cycles between global relabels (same for warm and cold)


def run_batched(items, max_batch: int = 8, mode: str = "vc") -> dict:
    svc = MaxflowService(ServiceConfig(mode=mode, max_batch=max_batch,
                                       cycle_chunk=CYCLE_CHUNK))
    t0 = time.perf_counter()
    records = drive(svc, items)
    wall = time.perf_counter() - t0
    lat = [r["latency_s"] for r in records]
    return {"wall_s": wall, "rps": len(items) / wall,
            "flows": [r["result"].maxflow for r in records],
            "p50_ms": 1e3 * float(np.percentile(lat, 50)),
            "p99_ms": 1e3 * float(np.percentile(lat, 99)),
            "records": records, "stats": svc.stats()}


def warm_vs_cold(items, records) -> dict:
    """Per resubmit: warm cycles (measured in the serving run) vs cycles of
    a cold batch-of-1 solve of the same updated graph."""
    solver = Solver(SolverOptions(backend="batched", layout="bcsr",
                                  global_relabel_cadence=CYCLE_CHUNK))
    warm_cycles, cold_cycles = 0, 0
    n = 0
    for item, rec in zip(items, records):
        if item.kind != "resubmit" or not rec["result"].warm:
            continue
        g, s, t = resolve_item(items, item)
        cold = solver.solve(MaxflowProblem(g, s, t))
        assert cold.value == rec["result"].maxflow, \
            (cold.value, rec["result"].maxflow)
        warm_cycles += rec["result"].cycles
        cold_cycles += cold.stats.cycles
        n += 1
    ratio = warm_cycles / cold_cycles if cold_cycles else 0.0
    return {"resubmits": n, "warm_cycles": warm_cycles,
            "cold_cycles": cold_cycles, "ratio": ratio}


def run_policy(items, items2, items3, max_batch: int = 2) -> dict:
    """Measured per-bucket mode policy.  Three workloads keep the timed
    comparison honest:

    * ``items``/``items2`` — warmup for BOTH services: the auto service
      runs its trials across them (two workloads so the bucket space is
      saturated before timing) and force-pins afterwards, the vc service
      compiles the same executables;
    * ``items3`` — the timed steady-state pass, also pre-driven through a
      throwaway vc service so any shape it mints is compiled process-wide
      before EITHER timed pass (otherwise whichever runs first pays XLA
      compiles the other gets from the jit cache for free).
    """
    cfg = dict(max_batch=max_batch, cycle_chunk=CYCLE_CHUNK)
    auto = MaxflowService(ServiceConfig(mode="auto", **cfg))
    drive(auto, items)  # trials happen here...
    drive(auto, items2)  # ...and here, minting the long-tail buckets
    auto.pin_modes()  # end the measuring phase: steady state from here on
    warmer = MaxflowService(ServiceConfig(mode="vc", **cfg))
    drive(warmer, items3)
    t0 = time.perf_counter()
    drive(auto, items3)  # pinned modes, warm executables
    auto_wall = time.perf_counter() - t0
    vc = MaxflowService(ServiceConfig(mode="vc", **cfg))
    drive(vc, items)  # same warmup: compiles + result-cache population
    drive(vc, items2)
    t0 = time.perf_counter()
    drive(vc, items3)
    vc_wall = time.perf_counter() - t0
    st = auto.stats()
    return {
        "mode_policy": st["mode_policy"],
        "sweep_time_s": st["sweep_time_s"],
        "phase2_time_s": st["phase2_time_s"],
        "steady_state": {
            "auto_wall_s": auto_wall, "vc_wall_s": vc_wall,
            "auto_over_vc": auto_wall / vc_wall if vc_wall else 0.0},
    }


def check_policy_smoke(policy: dict, tolerance: float = 1.1) -> None:
    """The --smoke gate, falsifiable end to end: the pinned-auto service
    must serve the steady-state workload within ``tolerance`` x the wall
    of the pinned-``vc`` service (both warm — trial flushes and compiles
    are excluded from the timed window by construction), and at least one
    bucket must have pinned from full trials."""
    pinned = {b: e for b, e in policy["mode_policy"].items()
              if e["pinned"] is not None}
    assert pinned, "no bucket pinned a mode — not enough trial flushes"
    ratio = policy["steady_state"]["auto_over_vc"]
    assert ratio <= tolerance, (
        f"auto policy steady state is {ratio:.2f}x pinned vc wall "
        f"(> {tolerance:.2f}x): the measured mode choices lose more "
        f"than {100 * (tolerance - 1):.0f}%")


def phase2_report(items, records, stats) -> dict:
    """Device phase-2 time attributed to warm resubmits (each record
    carries the pooled-correction seconds its own admission triggered),
    as a ratio to those resubmits' queue->completion solve latency."""
    warm_lat, warm_p2 = 0.0, 0.0
    for item, rec in zip(items, records):
        if item.kind != "resubmit" or not rec["result"].warm:
            continue
        warm_lat += rec["latency_s"]
        warm_p2 += rec["result"].phase2_s
    ratio = warm_p2 / warm_lat if warm_lat else 0.0
    return {"total_s": stats["phase2_time_s"], "warm_phase2_s": warm_p2,
            "warm_latency_s": warm_lat, "warm_ratio": ratio}


def run_overload(num_requests: int = 48, seed: int = 0,
                 deadline_ms: float = 250.0, max_queue: int = 4,
                 poll_every: int = 6) -> dict:
    """Overload + chaos section: a flood arrival trace (everything lands
    at once) with per-request deadlines, bounded queues, an infrequently
    polling driver, and an injected fault plan (persistent ``vc``
    failures until a limit -> retries, ladder demotions, host fallbacks;
    every cached handle corrupted -> quarantines on reuse).

    What it certifies: under all of that, every ADMITTED request that
    completed returned the exact max-flow (checked against the host
    Dinic oracle); everything else failed typed (``Overloaded`` /
    ``DeadlineExceeded`` / ``DispatchFailed``), never silently."""
    from repro.core.ref_maxflow import dinic_maxflow
    from repro.runtime.fault import FaultPlan

    items = synthesize(num_requests, rate_hz=500.0, seed=seed,
                       process="flood", deadline_s=deadline_ms / 1e3)
    plan = FaultPlan(seed=seed, fail_modes=("vc",), fail_mode_rate=1.0,
                     fail_mode_limit=4, corrupt_handle_rate=1.0)
    svc = MaxflowService(ServiceConfig(
        mode="vc", max_batch=4, cycle_chunk=CYCLE_CHUNK,
        max_queue=max_queue, deadline_slack_s=0.01, retry_limit=1,
        retry_base_s=0.001, retry_max_s=0.01, demote_after=2),
        faults=plan)
    t0 = time.perf_counter()
    records = drive(svc, items, poll_every=poll_every)
    wall = time.perf_counter() - t0
    ok = [r for r in records if r["error"] is None]
    wrong = 0
    for item, rec in zip(items, records):
        if rec["error"] is not None:
            continue
        g, s, t = resolve_item(items, item)
        if rec["result"].maxflow != dinic_maxflow(g, s, t):
            wrong += 1
    rb = svc.stats()["robustness"]
    errors_by_type: dict[str, int] = {}
    for r in records:
        if r["error"] is not None:
            name = type(r["error"]).__name__
            errors_by_type[name] = errors_by_type.get(name, 0) + 1
    lat = [r["latency_s"] for r in ok] or [0.0]
    shed_rate = (rb["rejected"] + rb["shed"]
                 + rb["expired_at_admission"]) / max(num_requests, 1)
    return {
        "process": "flood", "requests": num_requests,
        "deadline_ms": deadline_ms, "max_queue": max_queue,
        "poll_every": poll_every, "wall_s": wall,
        "admitted": len(ok), "wrong_answers": wrong,
        "shed_rate": shed_rate, "errors_by_type": errors_by_type,
        "admitted_p50_ms": 1e3 * float(np.percentile(lat, 50)),
        "admitted_p99_ms": 1e3 * float(np.percentile(lat, 99)),
        "rejected": rb["rejected"], "shed": rb["shed"],
        "expired_at_admission": rb["expired_at_admission"],
        "retries": rb["retries"],
        "transient_demotions": rb["transient_demotions"],
        "sticky_demotions": rb["sticky_demotions"],
        "host_fallbacks": rb["host_fallbacks"],
        "quarantined": rb["quarantined"],
        "dispatch_failed": rb["dispatch_failed"],
        "faults_injected": rb["faults_injected"],
    }


def check_overload_smoke(ov: dict,
                         p99_budget_s: float = 5.0) -> None:
    """Overload acceptance gates: zero wrong answers under injected
    faults, overload actually triggered and bounded, degradation ladder
    + quarantine exercised, admitted p99 within budget."""
    assert ov["wrong_answers"] == 0, \
        f"{ov['wrong_answers']} admitted requests got a WRONG max-flow"
    assert ov["admitted"] > 0, "everything was rejected/shed"
    assert 0.0 < ov["shed_rate"] <= 0.95, \
        (f"shed rate {ov['shed_rate']:.2f} out of bounds (flood must "
         "trigger SOME rejection, but not starve the service)")
    assert ov["admitted_p99_ms"] <= 1e3 * p99_budget_s, \
        (f"admitted p99 {ov['admitted_p99_ms']:.0f}ms over the "
         f"{1e3 * p99_budget_s:.0f}ms budget")
    assert ov["retries"] >= 1, "fault plan injected but no retry recorded"
    assert ov["transient_demotions"] + ov["sticky_demotions"] >= 1, \
        "persistent mode failures caused no ladder demotion"
    assert ov["quarantined"] >= 1, \
        "corrupted handles were reused without quarantine"
    print("OVERLOAD SMOKE PASS: zero wrong answers, shed rate "
          f"{ov['shed_rate']:.2f} bounded, p99 "
          f"{ov['admitted_p99_ms']:.0f}ms within budget, "
          f"retries={ov['retries']} demotions="
          f"{ov['transient_demotions'] + ov['sticky_demotions']} "
          f"quarantined={ov['quarantined']}")


def run(num_requests: int = 64, max_batch: int = 8, mode: str = "vc",
        seed: int = 0, smoke: bool = False, policy: bool = True) -> dict:
    items = synthesize(num_requests, rate_hz=500.0, seed=seed)
    batched_out = run_batched(items, max_batch=max_batch, mode=mode)
    seq = run_sequential(items)
    assert batched_out["flows"] == seq["flows"], \
        "batched and sequential max-flow values diverged"
    wc = warm_vs_cold(items, batched_out["records"])
    p2 = phase2_report(items, batched_out["records"], batched_out["stats"])
    speedup = batched_out["rps"] / seq["rps"]
    print(f"requests={num_requests} max_batch={max_batch} mode={mode}")
    print(f"sequential: {seq['rps']:8.2f} req/s  p50={seq['p50_ms']:7.1f}ms "
          f"p99={seq['p99_ms']:7.1f}ms")
    print(f"batched:    {batched_out['rps']:8.2f} req/s  "
          f"p50={batched_out['p50_ms']:7.1f}ms "
          f"p99={batched_out['p99_ms']:7.1f}ms   "
          f"throughput {speedup:.2f}x sequential")
    st = batched_out["stats"]
    print(f"buckets={st['buckets']} batches={st['batches']} "
          f"compiles={st['executables']['compiles']} "
          f"result-cache hits={st['result_cache']['hits']}")
    print(f"warm-vs-cold: {wc['resubmits']} re-solves, "
          f"warm {wc['warm_cycles']} vs cold {wc['cold_cycles']} cycles "
          f"(ratio {wc['ratio']:.2f})")
    print(f"phase-2:    {1e3 * p2['total_s']:8.1f}ms device total; warm "
          f"resubmits triggered {1e3 * p2['warm_phase2_s']:.1f}ms vs "
          f"{1e3 * p2['warm_latency_s']:.1f}ms solve latency "
          f"(ratio {p2['warm_ratio']:.2f})")
    print(f"pooled sweeps: {1e3 * st['sweep_time_s']:.1f}ms global-relabel "
          "time inside batched dispatches")
    # device-side workload counters, folded into every solve dispatch
    # (ServiceConfig.telemetry) and fetched once per flush — not sampled
    print("per-bucket device counters:")
    for bucket, bc in sorted(st["bucket_counters"].items()):
        print(f"  {bucket:24s} pushes={bc.get('pushes', 0):7d} "
              f"relabels={bc.get('relabels', 0):7d} "
              f"cycles={bc['cycles']:6d} sweeps={bc['gr_sweeps']:5d} "
              f"({bc['flushes']} flushes)")
    out = {"sequential": seq, "batched": {k: v for k, v in
                                          batched_out.items()
                                          if k != "records"},
           "speedup": speedup, "warm_vs_cold": wc, "phase2": p2}
    if policy:
        items2 = synthesize(num_requests, rate_hz=500.0, seed=seed + 1)
        items3 = synthesize(num_requests, rate_hz=500.0, seed=seed + 2)
        pol = run_policy(items, items2, items3)
        out["policy"] = pol
        print("per-bucket mode policy (mode='auto'):")
        for bucket, entry in sorted(pol["mode_policy"].items()):
            costs = ", ".join(f"{m}={c:.2e}" for m, c in
                              sorted(entry["per_cycle_s"].items()))
            print(f"  {bucket:24s} pinned={str(entry['pinned']):18s} "
                  f"flushes={entry['flushes']:3d}  s/cycle: {costs}")
        ss = pol["steady_state"]
        print(f"  steady state: auto {ss['auto_wall_s']:.2f}s vs vc "
              f"{ss['vc_wall_s']:.2f}s ({ss['auto_over_vc']:.2f}x); pooled "
              f"sweeps {1e3 * pol['sweep_time_s']:.1f}ms")
    if smoke:
        check_smoke(out)
    return out


def check_smoke(out: dict) -> None:
    """The acceptance gates (asserted after the JSON artifact is written
    when running via ``main``, so a failed gate still leaves the data)."""
    speedup, wc, p2 = out["speedup"], out["warm_vs_cold"], out["phase2"]
    assert speedup >= 2.0, f"batched speedup {speedup:.2f}x < 2x"
    bcs = out["batched"]["stats"]["bucket_counters"]
    assert bcs and all(bc.get("pushes", 0) > 0 for bc in bcs.values()), \
        f"dead per-bucket device counters: {bcs}"
    assert wc["cold_cycles"] == 0 or wc["ratio"] <= 0.5, \
        f"warm/cold cycle ratio {wc['ratio']:.2f} > 0.5"
    assert p2["warm_ratio"] <= 0.5, \
        (f"phase-2 is {p2['warm_ratio']:.2f}x of warm resubmit "
         "solve latency (> 0.5x)")
    gates = ("batched >= 2x sequential, warm <= 0.5x cold, "
             "phase-2 sub-dominant, device counters live")
    if "policy" in out:
        check_policy_smoke(out["policy"])
        gates += ", auto policy within 10% of vc"
    print(f"SMOKE PASS: {gates}")


def main(argv=None):
    from repro.runtime.cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--mode", default="vc",
                    choices=list(ALL_MODES) + ["auto"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-policy", action="store_true",
                    help="skip the mode-policy section (auto-vs-vc)")
    ap.add_argument("--overload", action="store_true",
                    help="add the overload/chaos section: flood trace, "
                         "bounded queues, deadlines, injected faults")
    ap.add_argument("--only-overload", action="store_true",
                    help="run ONLY the overload section (CI chaos job)")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small workload + assert acceptance thresholds")
    args = ap.parse_args(argv)
    out: dict = {}
    if not args.only_overload:
        out = run(num_requests=args.requests, max_batch=args.max_batch,
                  mode=args.mode, seed=args.seed, smoke=False,
                  policy=not args.no_policy)
    if args.overload or args.only_overload:
        ov = run_overload(num_requests=min(args.requests, 48),
                          seed=args.seed)
        out["overload"] = ov
        print(f"overload: admitted {ov['admitted']}/{ov['requests']} "
              f"(shed rate {ov['shed_rate']:.2f}; "
              f"rejected={ov['rejected']} shed={ov['shed']}) "
              f"p50={ov['admitted_p50_ms']:.1f}ms "
              f"p99={ov['admitted_p99_ms']:.1f}ms")
        print(f"  ladder: retries={ov['retries']} "
              f"demotions={ov['transient_demotions']}+"
              f"{ov['sticky_demotions']} "
              f"host_fallbacks={ov['host_fallbacks']} "
              f"quarantined={ov['quarantined']} "
              f"wrong_answers={ov['wrong_answers']}")
    import jax

    payload = {"bench": "serving_throughput",
               "device": jax.default_backend(),
               "requests": args.requests, "max_batch": args.max_batch,
               "mode": args.mode,
               **{k: v for k, v in out.items()}}
    # --only-overload updates just its own section of an existing artifact
    if args.only_overload:
        try:
            with open(args.out) as f:
                payload = json.load(f)
        except (OSError, ValueError):
            pass
        payload["overload"] = out["overload"]
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True, default=str)
    print(f"wrote {args.out}")
    if args.smoke:  # gate AFTER the artifact exists
        if not args.only_overload:
            check_smoke(out)
        if "overload" in out:
            check_overload_smoke(out["overload"])


if __name__ == "__main__":
    main()
