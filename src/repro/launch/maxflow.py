"""Max-flow launcher: the paper's workload end-to-end, through the
``repro.api`` facade.

``python -m repro.launch.maxflow --generator powerlaw --n 3000 --mode vc``
``python -m repro.launch.maxflow --smoke``   (CI: small verified instance)
"""
from __future__ import annotations

import argparse
import time

from repro.api.options import MODES


def main(argv=None):
    from repro.runtime.cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--generator", default="powerlaw",
                    choices=["powerlaw", "washington", "genrmf", "grid",
                             "dimacs"])
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--layout", default="bcsr", choices=["rcsr", "bcsr"])
    ap.add_argument("--mode", default="vc", choices=list(MODES))
    ap.add_argument("--backend", default="single",
                    choices=["single", "batched", "distributed"])
    ap.add_argument("--cycle-chunk", type=int, default=None,
                    help="push-relabel cycles between global relabels")
    ap.add_argument("--dimacs-file", default=None)
    ap.add_argument("--verify", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="small instance + --verify (exercised by CI)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.n = min(args.n, 400)
        args.verify = True

    from repro.api import MaxflowProblem, Solver, SolverOptions
    from repro.graphs import generators as G

    if args.generator == "powerlaw":
        g, s, t = G.powerlaw(args.n, 4, seed=args.seed)
    elif args.generator == "washington":
        k = max(4, int(args.n ** 0.5))
        g, s, t = G.washington_rlg(k, k, seed=args.seed)
    elif args.generator == "genrmf":
        a = max(3, int((args.n / 8) ** (1 / 3)))
        g, s, t = G.genrmf(a, 8, seed=args.seed)
    elif args.generator == "grid":
        k = max(4, int(args.n ** 0.5))
        g, s, t = G.grid_road(k, k, seed=args.seed)
    else:
        from repro.graphs.dimacs import read_dimacs
        g, s, t = read_dimacs(args.dimacs_file)

    solver = Solver(SolverOptions(
        mode=args.mode, layout=args.layout, backend=args.backend,
        global_relabel_cadence=args.cycle_chunk))
    problem = MaxflowProblem(g, s, t)
    t0 = time.time()
    sol = solver.solve(problem)
    dt = time.time() - t0
    print(f"V={g.n} E={g.m} layout={args.layout} mode={args.mode} "
          f"backend={args.backend} maxflow={sol.value} "
          f"cycles={sol.stats.cycles} "
          f"global_relabels={sol.stats.global_relabels} time={dt:.3f}s")
    if args.verify:
        from repro.core.ref_maxflow import dinic_maxflow
        want = dinic_maxflow(g, s, t)
        assert sol.value == want, (sol.value, want)
        print(f"verified against Dinic oracle: {want}")
        if args.smoke:
            print("SMOKE PASS")


if __name__ == "__main__":
    main()
