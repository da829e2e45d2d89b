"""Paper Table 1: max-flow execution time, {TC, VC} x {RCSR, BCSR}.

Graphs are generator-matched stand-ins at CPU scale (DESIGN.md §6.6); the
reproduced quantity is the comparison structure — per-graph runtimes, the
VC/TC speedups per representation, and which representation wins where.
Solves run through the ``repro.api`` facade (the problem caches one
residual per layout, so construction cost stays out of the timed region
after the warmup call).
"""
from __future__ import annotations

from benchmarks.common import maxflow_suite, time_solve
from repro.api import MaxflowProblem, Solver, SolverOptions
from repro.core.ref_maxflow import dinic_maxflow


def run(scale: float = 1.0, verbose: bool = True):
    rows = []
    for name, (g, s, t) in maxflow_suite(scale).items():
        want = dinic_maxflow(g, s, t)
        problem = MaxflowProblem(g, s, t)
        row = {"graph": name, "V": g.n, "E": g.m, "flow": want}
        for layout in ("rcsr", "bcsr"):
            problem.residual(layout)  # build outside the timed region
            for mode in ("tc", "vc"):
                solver = Solver(SolverOptions(mode=mode, layout=layout))
                sol, ms = time_solve(lambda sv=solver: sv.solve(problem))
                assert sol.value == want, (name, layout, mode,
                                           sol.value, want)
                row[f"{mode}+{layout}_ms"] = ms
                row[f"{mode}+{layout}_cycles"] = sol.stats.cycles
        row["speedup_rcsr"] = row["tc+rcsr_ms"] / row["vc+rcsr_ms"]
        row["speedup_bcsr"] = row["tc+bcsr_ms"] / row["vc+bcsr_ms"]
        rows.append(row)
        if verbose:
            print(f"{name:18s} V={row['V']:7d} E={row['E']:8d} "
                  f"flow={row['flow']:8d} "
                  f"TC+R={row['tc+rcsr_ms']:8.1f}ms "
                  f"TC+B={row['tc+bcsr_ms']:8.1f}ms "
                  f"VC+R={row['vc+rcsr_ms']:8.1f}ms "
                  f"VC+B={row['vc+bcsr_ms']:8.1f}ms "
                  f"spd(R)={row['speedup_rcsr']:4.2f}x "
                  f"spd(B)={row['speedup_bcsr']:4.2f}x", flush=True)
    return rows


if __name__ == "__main__":
    from repro.runtime.cache import enable_compile_cache

    enable_compile_cache()
    run()
