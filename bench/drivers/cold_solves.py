"""Driver ``cold_solves``: the window repeats one cold ``Solver.solve`` of
the seed's instance through the facade, back to back.

Each solve starts from a fresh ``MaxflowProblem`` (so the CSR build
counts) and ends with ``value``, ``flows()`` and ``min_cut()`` in host
memory.  ``solve_s`` is all window time, up to the end of the last solve
started in it, over the solves started.

Set-up generates the instance and warms every program a solve runs on a
twin with the same arcs whose only capacity is on the source's arcs:
the same shapes, so the same compiled programs, at almost no device
work.  The twin's preflow strands its excess next to the source, so its
solve runs phase 2 too.  A traced run traces
one whole solve and, beforehand, solves once with
``SolverOptions(telemetry=True)`` to count each cycle's active vertices
and scanned arcs for the roofline.

The timed network is one fixed instance, so that every seed times the
same work.  After the window the comparison also solves, with the same
solver and its compiled programs, the network with capacities drawn from
the seed (``facade.check_instance``): one or two rounds of cycles, so a
run checks a graph of its own and, on some seeds, the restart between
rounds.  Its spans and counts stay out of the per-layer metrics.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

import facade


def setup(run) -> dict:
    from repro.api import MaxflowProblem, Solver

    inst = facade.make_instance(run.config, run.seed)
    solver = Solver(facade.solver_options(run.config))
    twin = dataclasses.replace(inst, caps=np.where(
        inst.edges[:, 0] == inst.s, inst.caps, 0))
    facade.cold_solve(run, solver, twin)
    st = {"inst": inst, "solver": solver, "answers": []}
    if run.tracing:
        tel = Solver(solver.options.replace(telemetry=True)).solve(
            MaxflowProblem(facade.program_graph(inst), inst.s, inst.t))
        st["active"] = tel.stats.active_history
        st["frontier"] = tel.stats.frontier_history
    run.spans.clear()
    run.counts.clear()
    return st


def _solve(run, st) -> None:
    st["answers"].append(facade.attempt(st["inst"], lambda: facade.cold_solve(
        run, st["solver"], st["inst"])[0]))


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


def compare(run, st) -> tuple:
    spans = {k: list(v) for k, v in run.spans.items()}
    counts = {k: list(v) for k, v in run.counts.items()}
    inst = facade.check_instance(run.config, run.seed)
    drawn = facade.attempt(inst, lambda: facade.cold_solve(
        run, st["solver"], inst)[0])
    run.spans, run.counts = spans, counts
    return facade.compare(st["answers"] + [drawn])
