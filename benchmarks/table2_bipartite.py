"""Paper Table 2: bipartite matching via unit-capacity max-flow, through
the ``repro.api`` facade."""
from __future__ import annotations

from benchmarks.common import bipartite_suite, time_solve
from repro.api import MatchingProblem, Solver, SolverOptions
from repro.core.ref_maxflow import dinic_maxflow


def run(scale: float = 1.0, verbose: bool = True):
    rows = []
    for name, bp in bipartite_suite(scale).items():
        want = dinic_maxflow(bp.graph, bp.s, bp.t)
        problem = MatchingProblem(bp)
        row = {"graph": name, "L": bp.n_left, "R": bp.n_right,
               "E": len(bp.lr_edges), "matching": want}
        for layout in ("rcsr", "bcsr"):
            problem.residual(layout)  # build outside the timed region
            for mode in ("tc", "vc"):
                solver = Solver(SolverOptions(mode=mode, layout=layout))
                sol, ms = time_solve(lambda sv=solver: sv.solve(problem))
                assert sol.value == want
                row[f"{mode}+{layout}_ms"] = ms
        row["speedup_rcsr"] = row["tc+rcsr_ms"] / row["vc+rcsr_ms"]
        row["speedup_bcsr"] = row["tc+bcsr_ms"] / row["vc+bcsr_ms"]
        rows.append(row)
        if verbose:
            print(f"{name:12s} L={row['L']:6d} R={row['R']:6d} "
                  f"E={row['E']:8d} match={row['matching']:6d} "
                  f"TC+R={row['tc+rcsr_ms']:8.1f} TC+B={row['tc+bcsr_ms']:8.1f} "
                  f"VC+R={row['vc+rcsr_ms']:8.1f} VC+B={row['vc+bcsr_ms']:8.1f} "
                  f"spd(R)={row['speedup_rcsr']:4.2f}x "
                  f"spd(B)={row['speedup_bcsr']:4.2f}x", flush=True)
    return rows


if __name__ == "__main__":
    from repro.runtime.cache import enable_compile_cache

    enable_compile_cache()
    run()
