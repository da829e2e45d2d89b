"""What the drivers share: instances handed to the program, answers kept
from the window, and their comparison with the reference.

An ``Answer`` keeps what the program returned for one solve
(value, per-pair flows, cut mask) together with the benchmark's own
``Instance`` it answers, so the comparison runs after the window on host
copies only.
"""
from __future__ import annotations

import dataclasses
import sys
import traceback

import numpy as np

import reference
from generators import Instance

#: the numbers a certified answer is held to; every one is an exact
#: comparison, so every limit is 0
EXACT_LIMITS = {"value_gap": 0, "flow_faults": 0, "cut_gap": 0,
                "unanswered": 0}


def program_graph(inst: Instance):
    """The program's graph type for a benchmark instance."""
    from repro.core.csr import Graph

    return Graph(inst.n, inst.edges, inst.caps)


@dataclasses.dataclass
class Answer:
    """``value`` is None for an answer that never came (the program
    raised while producing it)."""

    inst: Instance
    value: int | None
    flows: np.ndarray | None = None
    source_side: np.ndarray | None = None


def attempt(inst: Instance, produce) -> Answer:
    """``produce()``'s answer, or an answer that never came when the
    program raises: its traceback goes to standard error and the run
    carries on, to be judged not correct."""
    try:
        return produce()
    except Exception:  # the program under test failed; record, go on
        traceback.print_exc(file=sys.stderr)
        return Answer(inst, None)


def make_instance(config: dict, seed: int) -> Instance:
    """The configuration's one fixed network, timed in every run:
    ``washington_rlg(rows, cols, seed=structure_seed)`` with its edge list
    in an order drawn from ``seed``.  The residual the program builds from
    it does not depend on the order, so every seed does the same device
    work; capacities, or vertex labels, drawn per seed made one or two
    rounds of 1,024 cycles, a solve of 19 or 37 s (see ``check_instance``
    for the instance drawn from the seed)."""
    import generators

    if config["family"] != "washington_rlg":
        raise ValueError(f"no instance family {config['family']!r}")
    net = generators.washington_rlg(int(config["rows"]), int(config["cols"]),
                                    int(config["max_cap"]),
                                    seed=int(config["structure_seed"]))
    return generators.shuffle_edges(net, np.random.default_rng(seed))


def check_instance(config: dict, seed: int) -> Instance:
    """The configuration's network with every capacity drawn anew from
    ``seed``, from the family's own ranges (``1..max_cap`` inside,
    ``rows`` times that on the terminal edges).  The arcs are those of
    ``make_instance``, so the program runs it on the window's compiled
    programs; the work is not the same for every seed (one or two rounds
    of cycles), which is why only the comparison solves it."""
    net = make_instance(config, seed)
    rng = np.random.default_rng([int(seed), 1])
    caps = rng.integers(1, int(config["max_cap"]) + 1,
                        size=net.caps.shape[0]).astype(np.int64)
    terminal = (net.edges[:, 0] == net.s) | (net.edges[:, 1] == net.t)
    caps[terminal] *= int(config["rows"])
    return dataclasses.replace(net, caps=caps)


def solver_options(config: dict):
    """``SolverOptions`` as the configuration states them (none: the
    defaults a user of ``Solver()`` gets)."""
    from repro.api import SolverOptions

    return SolverOptions(**config.get("solver_options", {}))


def cold_solve(run, solver, inst: Instance):
    """One cold solve through the facade: a fresh ``MaxflowProblem`` and
    its CSR (span ``csr_build``), ``Solver.solve`` (span ``solve``) and the
    certificate views (span ``certificate``).  Returns
    ``(Answer, Solution)``."""
    from repro.api import MaxflowProblem

    with run.span("csr_build"):
        problem = MaxflowProblem(program_graph(inst), inst.s, inst.t)
        problem.residual(solver.options.layout)
    with run.span("solve"):
        sol = solver.solve(problem)
    with run.span("certificate"):  # flows() and min_cut() run phase 2
        flows = np.array(sol.flows(), copy=True)
        side = np.array(sol.min_cut().source_side, copy=True)
    run.count("cycles", sol.stats.cycles)
    return Answer(inst, int(sol.value), flows, side), sol


def compare(answers: list[Answer]) -> tuple:
    """``(checks, attempted, failed)`` for a list of answers: the widest
    value gap against the reference, ``unanswered`` (answers that never
    came) and, where answers carry a certificate, the total flow faults
    and the widest cut gap."""
    worst_value = worst_cut = faults = failed = missing = 0
    refs: dict[int, int] = {}
    for a in answers:
        if a.value is None:
            missing += 1
            continue
        key = id(a.inst)
        if key not in refs:
            refs[key] = reference.max_flow_value(a.inst)
        ref = refs[key]
        gap = abs(a.value - ref)
        bad = gap > 0
        if a.flows is not None:
            f = reference.flow_faults(a.inst, a.value, a.flows)
            c = reference.cut_gap(a.inst, ref, a.source_side)
            faults += f
            worst_cut = max(worst_cut, c)
            bad = bad or f > 0 or c > 0
        worst_value = max(worst_value, gap)
        failed += bad
    checks = {"value_gap": worst_value, "unanswered": missing}
    if any(a.flows is not None for a in answers):
        checks.update(flow_faults=faults, cut_gap=worst_cut)
    checks = {k: {"value": v, "limit": EXACT_LIMITS[k]}
              for k, v in checks.items()}
    return checks, len(answers), failed + missing
