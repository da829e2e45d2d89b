"""The phase readers on the program's own compiled programs, compiled on
the CPU for a network whose ``vc`` cycle runs at several frontier rungs:
every op the cycle loop runs, those in the branches of its
``conditional`` included, falls in one of the step's phases, so each
``step_*`` reader gives a number and not None."""
import re

import generators
import run
import trace_reduce as tr

STEP = ("compact", "frontier", "minh", "apply", "loop")
CONTAINERS = ("while", "conditional", "call")


class ProgramRun:
    """What the readers use of ``run.Run``: one traced solve of ``inst``
    whose ops each took 1 ms."""

    def __init__(self, inst, solver, ops):
        self.trace = tr.Reduction(window_s=1.0, busy_s=1.0, programs={},
                                  ops=ops, gaps={})
        self.spans = {"solve": [1.0]}
        self.driver_state = {"inst": inst, "solver": solver}


def _executed_ops(text: str) -> dict[str, str]:
    """``{instruction: opcode}`` of every computation of an HLO module
    but the fused ones and the reducers, whose instructions run inside
    the op that calls them."""
    fused = set(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", text))
    out, comp = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            comp = head.group(1)
            continue
        inst = re.match(r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(",
                        line)
        if inst and comp not in fused:
            out[inst.group(1)] = inst.group(2)
    return out


def test_step_readers_cover_the_bucketed_cycle_program():
    from repro.api import Solver
    from repro.core import pushrelabel as pr
    from repro.core.csr import build_residual

    import facade
    import op_scopes

    inst = generators.washington_rlg(128, 8, seed=0)
    r = build_residual(facade.program_graph(inst), "bcsr")
    assert len(pr.frontier_ladder(r.n, r.num_arcs)) > 1
    solver = Solver()
    texts = op_scopes.program_hlo(ProgramRun(inst, solver, {}))
    cycles = _executed_ops(texts["jit_run_cycles"])
    assert "conditional" in cycles.values()
    ops = {f"jit_run_cycles:%{name}": 1e-3 for name in cycles}
    timed = ProgramRun(inst, solver, ops)
    got = {p: run.load_module(run.BENCH / "metrics" / f"step_{p}_ms.solve.py",
                              f"bench_metric_step_{p}").read(timed)
           for p in STEP}
    assert all(v is not None and v > 0 for v in got.values()), got
    leaves = sum(op not in CONTAINERS for op in cycles.values())
    assert abs(sum(got.values()) - leaves) < 1e-6 * leaves
