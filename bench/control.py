"""Run a cell with the control (``faults.control``) in the program's place
and print what its comparison reads, one line per seed.

    python3 bench/control.py --workload rlg.cold --seconds 1 --seeds 41 42 43

Every line has to read ``"correct": false``: the numbers it prints are
the upper readings that the limits in ``PERF.md`` were set below.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import faults
import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))  # the faults patch the program
    for seed in args.seeds:
        with faults.control():
            res = run.run_cell(args.workload, seed, args.seconds, False)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": True, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
