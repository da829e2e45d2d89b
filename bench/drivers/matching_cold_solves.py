"""Driver ``matching_cold_solves``: the window repeats one cold matching
of the configuration's bipartite graph through the facade, back to back.

Each solve starts from a fresh ``MatchingProblem`` and its CSR (span
``csr_build``), runs ``Solver.solve`` (span ``solve``) and ends with the
matched pairs and the min cut in host memory (span ``certificate``:
``matching()`` and ``min_cut()``, which run phase 2).  ``solve_s`` is
all window time, up to the end of the last solve started in it, over the
solves started.  Each solve's cycles are counted, and its phase-2 cancel
steps where the program reports them (``Solution.phase2_stats``); a
program without that counter leaves the count out.

Set-up generates the graph and warms every program a solve runs on a
twin with the same arcs whose only capacity is on the source's arcs: the
same shapes, so the same compiled programs, at almost no device work.
The twin's preflow strands a unit on every left vertex, so its solve runs
phase 2 too.  A traced run traces one whole solve and, beforehand,
solves once with ``SolverOptions(telemetry=True)`` to count each cycle's
active vertices and scanned arcs for the roofline.

The timed graph is one fixed instance, so that every seed times the same
work; the seed orders its edge list.  After the window the comparison
also solves, with the same solver and its compiled programs, a graph
drawn from the seed (``check_graph``).  Its spans and counts stay out of
the per-layer metrics.

The loop is ``cold_solves``' own; that driver's window calls its
``facade.cold_solve``, which builds a ``MaxflowProblem`` and reads
``flows()``, so this one keeps a copy of the loop around its own solve.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

import bipartite
import facade
import generators
import reference
import reference_matching

#: every number is exact, so every limit is 0
LIMITS = {"value_gap": 0, "matching_faults": 0, "cut_gap": 0,
          "unanswered": 0}
#: the share of the check graph's source and sink arcs that are closed
CLOSED = 0.25


@dataclasses.dataclass
class MatchAnswer(facade.Answer):
    """``facade.Answer`` with the graph it answers and the matched
    ``(left, right)`` pairs; an answer that never came is a plain
    ``facade.Answer`` with value None."""

    bp: bipartite.Bipartite | None = None
    pairs: np.ndarray | None = None


def make_graph(config: dict, seed: int) -> bipartite.Bipartite:
    """The configuration's one fixed graph, its flow network's edge list
    in an order drawn from ``seed``."""
    if config["family"] != "bipartite_powerlaw":
        raise ValueError(f"no bipartite family {config['family']!r}")
    bp = bipartite.bipartite_powerlaw(
        int(config["n_left"]), int(config["n_right"]),
        int(config["n_edges"]), float(config["left_exp"]),
        float(config["right_exp"]), seed=int(config["structure_seed"]))
    return dataclasses.replace(bp, inst=generators.shuffle_edges(
        bp.inst, np.random.default_rng(seed)))


def check_graph(config: dict, seed: int) -> bipartite.Bipartite:
    """The comparison's extra graph, drawn from ``seed``: the timed one
    with its sides' ids permuted (``bipartite.permuted``) and a quarter
    of its source and sink arcs closed (``bipartite.closed``).  It keeps
    every arc, so it runs on the window's compiled programs, while its
    maximum matching falls below both sides' open vertices: a cut of
    every open source arc, or of every open sink arc, is no minimum
    cut."""
    rng = np.random.default_rng([int(seed), 1])
    return bipartite.closed(bipartite.permuted(make_graph(config, seed), rng),
                            rng, CLOSED)


def _problem(bp: bipartite.Bipartite):
    from repro.api import MatchingProblem
    from repro.graphs.generators import BipartiteProblem

    inst = bp.inst
    return MatchingProblem(BipartiteProblem(
        facade.program_graph(inst), inst.s, inst.t, bp.n_left, bp.n_right,
        bp.lr))


def cold_solve(run, solver, bp: bipartite.Bipartite) -> MatchAnswer:
    """One cold matching through the facade, in the three spans above."""
    with run.span("csr_build"):
        problem = _problem(bp)
        problem.residual(solver.options.layout)
    with run.span("solve"):
        sol = solver.solve(problem)
    with run.span("certificate"):  # matching() and min_cut() run phase 2
        pairs = np.array(sol.matching(), copy=True)
        side = np.array(sol.min_cut().source_side, copy=True)
    run.count("cycles", sol.stats.cycles)
    stats = getattr(sol, "phase2_stats", None)
    if stats is not None:
        run.count("phase2_steps", stats.steps)
    return MatchAnswer(bp.inst, int(sol.value), source_side=side, bp=bp,
                       pairs=pairs)


def setup(run) -> dict:
    from repro.api import Solver

    bp = make_graph(run.config, run.seed)
    solver = Solver(facade.solver_options(run.config))
    inst = bp.inst
    twin = dataclasses.replace(bp, inst=dataclasses.replace(
        inst, caps=np.where(inst.edges[:, 0] == inst.s, inst.caps, 0)))
    cold_solve(run, solver, twin)
    # the telemetry solve and the phase readers work on the flow network
    st = {"bp": bp, "inst": inst, "solver": solver, "answers": []}
    if run.tracing:
        tel = Solver(solver.options.replace(telemetry=True)).solve(
            _problem(bp))
        st["active"] = tel.stats.active_history
        st["frontier"] = tel.stats.frontier_history
    run.spans.clear()
    run.counts.clear()
    return st


def _solve(run, st) -> None:
    st["answers"].append(facade.attempt(st["inst"], lambda: cold_solve(
        run, st["solver"], st["bp"])))


def window(run, st, seconds: float) -> dict:
    t0 = time.perf_counter()
    while True:
        _solve(run, st)
        if time.perf_counter() - t0 >= seconds:
            break
    return {"solve_s": (time.perf_counter() - t0) / len(st["answers"])}


def traced(run, st, seconds: float) -> dict:
    _solve(run, st)
    return {}


def compare_answers(answers: list) -> tuple:
    """``(checks, attempted, failed)``: the widest value gap against
    scipy's matching, the total matching faults, the widest cut gap and
    the answers that never came."""
    worst_value = worst_cut = faults = failed = missing = 0
    refs: dict[int, int] = {}
    for a in answers:
        if a.value is None:
            missing += 1
            continue
        key = id(a.bp)
        if key not in refs:
            refs[key] = reference_matching.matching_value(a.bp)
        ref = refs[key]
        gap = abs(a.value - ref)
        f = reference_matching.matching_faults(a.bp, ref, a.pairs)
        c = reference.cut_gap(a.bp.inst, ref, a.source_side)
        worst_value = max(worst_value, gap)
        faults += f
        worst_cut = max(worst_cut, c)
        failed += gap > 0 or f > 0 or c > 0
    checks = {"value_gap": worst_value, "matching_faults": faults,
              "cut_gap": worst_cut, "unanswered": missing}
    checks = {k: {"value": v, "limit": LIMITS[k]} for k, v in checks.items()}
    return checks, len(answers), failed + missing


def compare(run, st) -> tuple:
    spans = {k: list(v) for k, v in run.spans.items()}
    counts = {k: list(v) for k, v in run.counts.items()}
    drawn_bp = check_graph(run.config, run.seed)
    drawn = facade.attempt(drawn_bp.inst, lambda: cold_solve(
        run, st["solver"], drawn_bp))
    run.spans, run.counts = spans, counts
    return compare_answers(st["answers"] + [drawn])
