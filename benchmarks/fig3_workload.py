"""Paper Fig. 3: workload balance across execution tiles (warps),
regenerated from LIVE device-side solver counters.

A telemetry solve (``SolverOptions(telemetry=True)``) returns exact
per-cycle series computed inside the jitted cycle loop and fetched once
per round (``repro.obs.solvercounters``): active-vertex count, total arc
frontier, and the maximum active degree.  From those, each cycle's
issued tile work is modelled:

* **TC** — 128 vertex-lanes in lockstep; ``ceil(active / 128)`` tiles,
  each serialising to the slowest lane, modelled by the cycle's max
  active degree (the divergent-scan cost the paper's Eq. 1 describes —
  a lower bound on waste: the device counter is the cycle-global max,
  so intra-cycle tiles are modelled uniform).
* **VC** — the flat arc frontier is carved into 128-slot tiles; every
  tile does 128 units except the last partial one.

The headline statistic is **lane utilization**: useful arc work (the
frontier the cycle actually scanned) over issued lockstep lane-work.
VC sits near 1 by construction — only the final partial tile idles —
while TC pays ``max_deg / mean_deg`` serialisation, the imbalance the
paper's Fig. 3 histograms visualise.  Per-tile mean/std/cv are still
reported per graph for continuity with the old host-replay version of
this benchmark (which re-sampled the active set on the host every round;
the counters now ride the solve for free).

Emits ``BENCH_fig3.json``.  ``--smoke`` additionally asserts the
counters are live (nonzero pushes/relabels, the pushes + relabels ==
sum(active) identity) and that VC utilization beats TC on every graph.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from benchmarks.common import maxflow_suite
from repro.api import MaxflowProblem, Solver, SolverOptions

LANES = 128


def tile_work_stats(g, s, t, layout="bcsr", mode="vc"):
    """(tc stats, vc stats, solve counters) for one instance, from the
    per-cycle telemetry of a single live solve."""
    sol = Solver(SolverOptions(mode=mode, layout=layout,
                               telemetry=True)).solve(
        MaxflowProblem(g, s, t))
    st = sol.stats
    act = np.asarray(st.active_history, np.int64)
    fr = np.asarray(st.frontier_history, np.int64)
    md = np.asarray(st.maxdeg_history, np.int64)
    tc_tiles, vc_tiles = [], []
    useful = tc_issued = vc_issued = 0
    for a, f, m in zip(act, fr, md):
        if a == 0:
            continue
        useful += int(f)
        # TC: lockstep vertex-lane tiles, all lanes wait for the max degree
        ntiles = -(-int(a) // LANES)
        tc_tiles.extend([int(m) * LANES] * ntiles)
        tc_issued += ntiles * int(m) * LANES  # every lane runs md deep
        # VC: flat arc frontier, 128 slots per tile; the last partial tile
        # still issues all 128 lanes (the only idle lanes VC ever has)
        full, rem = divmod(int(f), LANES)
        vc_tiles.extend([LANES] * full + ([rem] if rem else []))
        vc_issued += (full + (1 if rem else 0)) * LANES
    counters = {"pushes": st.pushes, "relabels": st.relabels,
                "cycles": st.cycles, "gr_sweeps": st.gr_sweeps,
                "active_sum": int(act.sum()), "frontier_sum": int(fr.sum())}
    return (_stats(tc_tiles, useful, tc_issued),
            _stats(vc_tiles, useful, vc_issued), counters)


def _stats(tiles, useful, issued):
    x = np.asarray(tiles, float)
    if len(x) == 0:
        return dict(mean=0.0, std=0.0, cv=0.0, tiles=0, utilization=0.0)
    return dict(mean=float(x.mean()), std=float(x.std()),
                cv=float(x.std() / (x.mean() + 1e-9)), tiles=len(x),
                utilization=useful / issued if issued else 0.0)


def run(scale: float = 0.6, verbose: bool = True):
    rows = []
    for name, (g, s, t) in maxflow_suite(scale).items():
        tc, vc, counters = tile_work_stats(g, s, t)
        row = {"graph": name,
               "tc_utilization": tc["utilization"],
               "vc_utilization": vc["utilization"],
               "tc_cv": tc["cv"], "vc_cv": vc["cv"],
               "tc_mean": tc["mean"], "vc_mean": vc["mean"],
               "tc_tiles": tc["tiles"], "vc_tiles": vc["tiles"],
               "counters": counters}
        rows.append(row)
        if verbose:
            print(f"{name:18s} TC util={tc['utilization']:5.3f} "
                  f"({tc['tiles']} tiles, mean {tc['mean']:8.1f})   "
                  f"VC util={vc['utilization']:5.3f} "
                  f"({vc['tiles']} tiles)   "
                  f"[{counters['pushes']} pushes, "
                  f"{counters['relabels']} relabels]", flush=True)
    return rows


def check_smoke(rows) -> None:
    """Falsifiable gates: the counters must be live and the balance claim
    must reproduce from them."""
    for row in rows:
        c = row["counters"]
        assert c["pushes"] > 0 and c["relabels"] > 0, \
            f"{row['graph']}: dead device counters {c}"
        assert c["pushes"] + c["relabels"] == c["active_sum"], \
            (f"{row['graph']}: push/relabel identity violated "
             f"({c['pushes']} + {c['relabels']} != {c['active_sum']})")
        assert row["vc_utilization"] > row["tc_utilization"], \
            (f"{row['graph']}: VC lane utilization "
             f"{row['vc_utilization']:.3f} not above TC "
             f"{row['tc_utilization']:.3f} — the Fig. 3 balance claim "
             "did not reproduce")
    tc_u = float(np.mean([r["tc_utilization"] for r in rows]))
    vc_u = float(np.mean([r["vc_utilization"] for r in rows]))
    print(f"SMOKE PASS: counters live, mean lane utilization "
          f"VC {vc_u:.3f} vs TC {tc_u:.3f}")


def main(argv=None):
    from repro.runtime.cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=float, default=0.6)
    ap.add_argument("--out", default="BENCH_fig3.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small suite + live-counter assertions")
    args = ap.parse_args(argv)
    rows = run(scale=0.3 if args.smoke else args.scale)
    import jax

    payload = {"bench": "fig3_workload", "device": jax.default_backend(),
               "lanes": LANES, "rows": rows}
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"wrote {args.out}")
    if args.smoke:  # gate AFTER the artifact exists
        check_smoke(rows)


if __name__ == "__main__":
    main()
