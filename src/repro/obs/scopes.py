"""Device phases of the solver's programs: their ``jax.named_scope``
names, and the phase each instruction of a compiled program belongs to.

* ``compact`` (``wbpr.cycle/compact``): the active mask and the AVQ
  ``nonzero``;
* ``frontier`` (``wbpr.cycle/frontier``): ``deg``, ``cumsum``,
  ``repeat``, the arc and key gathers of the flat frontier;
* ``minh`` (``wbpr.cycle/minh``): the segmented mins and their
  sentinel, a kernel ``minh_fn``, the thread-centric scan;
* ``apply`` (``wbpr.cycle/apply``): the push/relabel decision and its
  scatters, ``rev_fn`` included;
* ``loop`` (``wbpr.cycle/loop``): the rest of a cycle program: cap,
  condition, the engine's chunk gating and carry, telemetry counters;
* ``global_relabel`` (``wbpr.global_relabel``) and ``phase2``
  (``wbpr.phase2``): those programs, single and batched.

The scopes sit at the call sites in ``repro.core``, so a kernel or a
rewrite put in the same place inherits them.  They change only the
``op_name`` metadata of the ops traced under them, never the compiled
code.  A program scope (global relabel, phase 2) claims everything
traced inside it, the cycle-step helpers it reuses included; inside a
cycle program the innermost cycle scope wins, so the step's phases stand
out of ``loop``.

``op_scopes`` reads a compiled program's optimized HLO text and gives
each instruction its phase; ``solve_hlo`` compiles the programs one
``Solver.solve`` and its ``flows()`` run.  A profiler trace names device
ops after HLO instructions, so their times add up per phase.
"""
from __future__ import annotations

import re

__all__ = ["COMPACT", "FRONTIER", "MINH", "APPLY", "LOOP",
           "GLOBAL_RELABEL", "PHASE2", "PHASES", "CONTAINERS",
           "phase_of", "op_scopes", "solve_hlo"]

COMPACT = "wbpr.cycle/compact"
FRONTIER = "wbpr.cycle/frontier"
MINH = "wbpr.cycle/minh"
APPLY = "wbpr.cycle/apply"
LOOP = "wbpr.cycle/loop"
GLOBAL_RELABEL = "wbpr.global_relabel"
PHASE2 = "wbpr.phase2"

#: phase -> its scope
PHASES = {"compact": COMPACT, "frontier": FRONTIER, "minh": MINH,
          "apply": APPLY, "loop": LOOP, "global_relabel": GLOBAL_RELABEL,
          "phase2": PHASE2}
_PROGRAM_PHASES = ("global_relabel", "phase2")
_SCOPE_PHASE = {scope: phase for phase, scope in PHASES.items()}
_SCOPE = re.compile("(?:^|/)(" + "|".join(map(re.escape, _SCOPE_PHASE))
                    + ")(?=/|$)")

#: opcodes whose device time overlaps that of the computations they run;
#: they belong to no phase
CONTAINERS = frozenset({"while", "conditional", "call"})

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"([\w\-]+)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
#: attributes naming the computations an instruction runs (a fusion's
#: run fused into it; a reduction's ``to_apply`` is inlined)
_CALLS = {"while": ("body", "condition"), "call": ("to_apply",),
          "conditional": ("true_computation", "false_computation"),
          "fusion": ("calls",)}
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def phase_of(op_name: str) -> str | None:
    """The phase an ``op_name`` path names, or None where it names none:
    its outermost program scope, else its innermost cycle scope."""
    found = [_SCOPE_PHASE[m.group(1)] for m in _SCOPE.finditer(op_name)]
    for f in found:
        if f in _PROGRAM_PHASES:
            return f
    return found[-1] if found else None


def _balanced(text: str, start: int) -> int:
    """Index just past the parenthesis group opening at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        depth += (text[i] == "(") - (text[i] == ")")
        if depth == 0:
            return i + 1
    return len(text)


def _parse(rest: str):
    """``(opcode, operands, attributes)`` of an instruction's text after
    ``name = ``."""
    # skip the result shape: a tuple's parentheses, else one token
    i = _balanced(rest, 0) if rest.startswith("(") else rest.index(" ")
    rest = rest[i:].lstrip()
    m = _OPCODE.match(rest)
    if m is None:
        return rest.split(" ", 1)[0], [], ""
    end = _balanced(rest, m.end() - 1)
    return m.group(1), _OPERAND.findall(rest[m.end():end - 1]), rest[end:]


def _computations(hlo_text: str) -> tuple[dict, dict, str | None]:
    """``({computation: [(name, opcode, operands, called, phase)]},
    {computation: its root's phase}, entry)`` of an HLO module's text,
    ``phase`` being what the instruction's own metadata names."""
    comps: dict[str, list] = {}
    roots: dict[str, str | None] = {}
    entry = None
    current = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m is not None:
            comp = m.group(1)
            current = comps.setdefault(comp, [])
            if line.startswith("ENTRY"):
                entry = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None or current is None:
            if line.startswith("}"):
                current = None
            continue
        opcode, operands, attrs = _parse(m.group(3))
        op = _OP_NAME.search(attrs)
        called = [c for key in _CALLS.get(opcode, ())
                  for c in re.findall(rf"\b{key}=%?([\w.\-]+)", attrs)]
        if opcode == "conditional":
            for group in _BRANCHES.findall(attrs):
                called += _OPERAND.findall(group)
        own = phase_of(op.group(1)) if op else None
        current.append((m.group(2), opcode, operands, called, own))
        if m.group(1):
            roots[comp] = own
    return comps, roots, entry


def op_scopes(hlo_text: str) -> dict[str, str | None]:
    """``{instruction name: phase}`` for every instruction of the
    computations a compiled program runs (its entry and the bodies,
    conditions and branches of its control flow; fused computations are
    part of their fusion).  Containers (``CONTAINERS``) map to None, and
    an instruction no scope can be found for is left out: a program
    compiled without the scopes gives an empty map.

    An instruction takes the phase its ``op_name`` metadata names, a
    fusion without metadata that of its fused computation's root.  One with
    neither (XLA's copies and tuples, a ``cumsum``'s pads and slices on
    the CPU) takes the phase of the first instruction of the same
    computation that uses it, else of the first it uses, else that of
    the instruction that runs the computation."""
    comps, roots, entry = _computations(hlo_text)
    phase: dict[str, str | None] = {}
    order, todo, caller = [], [entry], {}
    while todo:  # computations in the order control flow reaches them
        c = todo.pop(0)
        if c is None or c in order or c not in comps:
            continue
        order.append(c)
        for name, opcode, _, called, _ in comps[c]:
            for x in called if opcode != "fusion" else ():
                caller.setdefault(x, name)
                todo.append(x)
    for c in order:
        insts = comps[c]
        users: dict[str, list[str]] = {}
        for name, opcode, operands, called, own in insts:
            if own is None and opcode == "fusion":
                own = next(filter(None, map(roots.get, called)), None)
            phase[name] = own
            for o in operands:
                users.setdefault(o, []).append(name)
        for neighbours in (lambda i: users.get(i[0], ()), lambda i: i[2]):
            changed = True
            while changed:
                changed = False
                for inst in insts:
                    if phase[inst[0]] is None:
                        got = next((phase[x] for x in neighbours(inst)
                                    if phase.get(x) is not None), None)
                        changed |= got is not None
                        phase[inst[0]] = got
        for name, *_ in insts:
            if phase[name] is None and c in caller:
                phase[name] = phase[caller[c]]
    return {name: None if opcode in CONTAINERS else phase[name]
            for c in order for name, opcode, *_ in comps[c]
            if opcode in CONTAINERS or phase[name] is not None}


def _text(compiled) -> str:
    """A compiled program's optimized HLO text; an executable loaded from
    the persistent cache gives it through its runtime executable."""
    text = compiled.as_text()
    if not text:
        text = "\n".join(m.to_string() for m in
                         compiled.runtime_executable().hlo_modules())
    return text


def solve_hlo(problem, options=None) -> dict[str, str]:
    """Optimized HLO text of the device programs one
    ``Solver(options).solve(problem)`` and its ``flows()`` run, keyed by
    jit name: ``jit_run_cycles``, ``jit_global_relabel_impl`` and
    ``jit_phase2_impl``.  Their static arguments come from
    ``pushrelabel.solve_programs``, as the solve's own do, and
    ``run_cycles`` takes a traced ``patience`` as every round does.
    Compiles each (a persistent-cache hit where the cache holds it)."""
    import jax.numpy as jnp

    from repro.api import SolverOptions
    from repro.core import globalrelabel, phase2
    from repro.core import pushrelabel as pr

    opts = options or SolverOptions()
    if opts.backend != "single":
        raise ValueError("solve_hlo covers the single backend, got "
                         f"{opts.backend!r}")
    r = problem.residual(opts.layout)
    s, t = problem.s, problem.t
    g, meta, res0 = pr.to_device(r)
    cycle_kw, minh_fn = pr.solve_programs(
        r.n, opts.mode, opts.global_relabel_cadence, opts.interpret,
        opts.scan_chunk)
    zeros = jnp.zeros(r.n, jnp.int32)
    state = pr.PRState(res=res0, h=zeros, e=zeros)
    budget = None if opts.max_cycles is None else jnp.int32(opts.max_cycles)
    lowered = {
        "jit_run_cycles": pr.run_cycles.lower(
            g, meta, state, s, t, telemetry=opts.telemetry, budget=budget,
            patience=jnp.int32(pr.round_patience(0)), **cycle_kw),
        "jit_global_relabel_impl": globalrelabel.global_relabel.lower(
            g, meta, state, s, t, minh_fn=minh_fn),
        "jit_phase2_impl": phase2.phase2_run.lower(
            g, meta, res0, state.res, state.e, jnp.int32(s), jnp.int32(t),
            minh_fn=minh_fn),
    }
    return {name: _text(low.compile()) for name, low in lowered.items()}
