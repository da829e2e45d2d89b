"""Device time per phase of the traced solve's programs, for the
``step_*``, ``global_relabel_ms`` and ``phase2_ms`` readers.

The program names its device phases with ``jax.named_scope``
(``repro.obs.scopes``: the cycle step's compact, frontier, minh, apply
and loop, the global relabel, phase 2).  ``repro.obs.scopes.solve_hlo``
compiles the programs one solve of the traced instance runs (a
persistent-cache hit after the run) and ``op_scopes`` maps each of their
HLO instructions to a phase.  The trace reduction keys each device op
``<program>:<hlo name>`` (``trace_reduce``), so a phase's time is the sum
over the ops of those programs that the map puts in it; containers
(``while``) hold other ops and count for no phase.

A program without the scopes (``repro.obs.scopes`` missing) or an op the
map does not hold gives None: no number rather than a wrong one.
"""
from __future__ import annotations

import weakref

#: jit names of the programs one solve and its ``flows()`` run
PROGRAMS = ("jit_run_cycles", "jit_global_relabel_impl", "jit_phase2_impl")

_maps: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()


def program_hlo(run) -> dict[str, str] | None:
    """``{jit name: optimized HLO text}`` of the traced instance's
    programs, or None where the program does not name its phases."""
    try:
        from repro.obs import scopes
    except ImportError:
        return None
    from repro.api import MaxflowProblem

    import facade

    inst = run.driver_state.get("inst")
    solver = run.driver_state.get("solver")
    if inst is None or solver is None:
        return None
    problem = MaxflowProblem(facade.program_graph(inst), inst.s, inst.t)
    return scopes.solve_hlo(problem, solver.options)


def phase_maps(run) -> dict[str, dict] | None:
    """``{jit name: {instruction: phase}}`` for the run's programs,
    computed once per run."""
    if run not in _maps:
        texts = program_hlo(run)
        maps = None
        if texts is not None:
            from repro.obs import scopes

            maps = {p: scopes.op_scopes(texts[p]) for p in PROGRAMS
                    if p in texts}
        _maps[run] = maps
    return _maps[run]


def phase_seconds(ops: dict[str, float], maps: dict[str, dict],
                  phase: str) -> float | None:
    """Device seconds of the ops in ``phase``: ``ops`` keyed
    ``<program>:<op>`` as ``trace_reduce`` keys them, ``maps`` as
    ``phase_maps`` gives them.  None when an op of a mapped program is
    not in its map, or when no op of a mapped program is in the trace."""
    total, seen = 0.0, False
    for key, secs in ops.items():
        program, _, op = key.partition(":")
        if program not in maps:
            continue
        seen = True
        name = op.removeprefix("%")
        if name not in maps[program]:
            return None
        if maps[program][name] == phase:
            total += secs
    return total if seen else None


def phase_ms(run, phase: str) -> float | None:
    """Device milliseconds per traced solve spent in ``phase``."""
    solves = len(run.spans.get("solve", ()))
    if run.trace is None or not solves:
        return None
    maps = phase_maps(run)
    if not maps:
        return None
    secs = phase_seconds(run.trace.ops, maps, phase)
    return None if secs is None else secs * 1e3 / solves
