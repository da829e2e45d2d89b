"""Benchmark harness entry point — one section per paper table/figure.

Prints a human-readable section per experiment plus the machine-readable
``name,us_per_call,derived`` CSV lines at the end.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from repro.runtime.cache import enable_compile_cache

    enable_compile_cache()
    t0 = time.time()
    csv = []

    print("=" * 72)
    print("Table 1 — max-flow: {TC,VC} x {RCSR,BCSR}  (paper Table 1)")
    print("=" * 72)
    from benchmarks import table1_maxflow
    for row in table1_maxflow.run():
        for k in ("tc+rcsr", "tc+bcsr", "vc+rcsr", "vc+bcsr"):
            csv.append(f"maxflow/{row['graph']}/{k},"
                       f"{row[f'{k}_ms'] * 1e3:.1f},"
                       f"flow={row['flow']}")
        csv.append(f"maxflow/{row['graph']}/speedup_bcsr,"
                   f"{row['speedup_bcsr']:.3f},tc_over_vc")

    print()
    print("=" * 72)
    print("Table 2 — bipartite matching  (paper Table 2)")
    print("=" * 72)
    from benchmarks import table2_bipartite
    for row in table2_bipartite.run():
        for k in ("tc+rcsr", "tc+bcsr", "vc+rcsr", "vc+bcsr"):
            csv.append(f"bipartite/{row['graph']}/{k},"
                       f"{row[f'{k}_ms'] * 1e3:.1f},"
                       f"matching={row['matching']}")

    print()
    print("=" * 72)
    print("Fig 3 — per-tile workload distribution (coefficient of variation)")
    print("=" * 72)
    from benchmarks import fig3_workload
    for row in fig3_workload.run():
        csv.append(f"workload/{row['graph']}/tc_cv,{row['tc_cv']*1e6:.0f},"
                   f"x1e-6")
        csv.append(f"workload/{row['graph']}/vc_cv,{row['vc_cv']*1e6:.0f},"
                   f"x1e-6")

    print()
    print("=" * 72)
    print("Kernel cycles — per-cycle cost of every step mode "
          "(fused vs XLA chain)")
    print("=" * 72)
    from benchmarks import kernel_cycles
    for row in kernel_cycles.run(scale=0.5):
        for mode, st in row["modes"].items():
            csv.append(f"kernel/{row['graph']}/{mode},"
                       f"{st['us_per_cycle']:.1f},"
                       f"ops={st['ops_per_cycle']};"
                       f"pallas={st['pallas_calls']}")

    print()
    print("=" * 72)
    print("Memory — O(V+E) enhanced CSR vs O(V^2) adjacency (paper claim)")
    print("=" * 72)
    from benchmarks import table_memory
    for row in table_memory.run():
        csv.append(f"memory/{row['graph']}/reduction,"
                   f"{row['reduction']:.0f},adj_over_csr")

    print()
    print("name,us_per_call,derived")
    for line in csv:
        print(line)
    print(f"\ntotal benchmark wall time: {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
