"""The ONE jaxpr IR walker: a normalized op census per traced callable.

Every structural claim this repo makes about its compiled device
programs — "one workload-balanced kernel launch per cycle", "no host
round-trips inside the bulk-synchronous loops", "state stays int32
end-to-end", "the steady-state trace is one scanned body" — used to be
asserted by ad-hoc jaxpr walkers duplicated across the test suite and
the benchmarks.  This module is their single shared replacement:

* :func:`count_eqns` — the primitive-equation counter (formerly
  ``repro.compat.count_jaxpr_eqns``), descending into pjit/while/cond/
  scan sub-jaxprs; ``enter_pallas_body=False`` treats a ``pallas_call``
  as one device op instead of recursing into its kernel body.
* :func:`iter_eqns` — the underlying generator, yielding every equation
  with its structural *context* (the tuple of enclosing structural
  primitives, e.g. ``('pjit', 'while', 'scan')``).
* :func:`census` / :func:`census_of` — an :class:`OpCensus` of one
  traced callable: op counts, every ``pallas_call`` with its grid and
  vmap-batching evidence, while/scan nesting with dead-carry counts,
  every ``convert_element_type`` with source/target dtypes, every
  host-callback/transfer primitive.

The contract rules in :mod:`repro.analysis.rules` consume the census;
the dispatch surfaces they are checked on live in
:mod:`repro.analysis.surfaces`.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Any, Callable, Iterator, Mapping

import jax

from jax.extend.core import ClosedJaxpr, Jaxpr

__all__ = [
    "STRUCTURAL_PRIMS", "HOST_CALLBACK_PRIMS", "TRANSFER_PRIMS",
    "PallasLaunch", "DtypeCast", "HostCall", "LoopShell", "OpCensus",
    "LoopCounts", "count_eqns", "iter_eqns", "trace", "census",
    "census_of", "primitive_count", "loop_counts",
]

#: wrapper primitives that own sub-jaxprs but are not device compute
STRUCTURAL_PRIMS = frozenset({
    "pjit", "jit", "xla_call", "closed_call", "core_call", "while",
    "cond", "scan", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "remat", "remat2", "checkpoint",
    "shard_map", "named_call",
})

#: primitives that round-trip through the host inside a trace — any of
#: these inside a jitted hot path is a per-dispatch host sync
HOST_CALLBACK_PRIMS = frozenset({
    "io_callback", "pure_callback", "debug_callback", "debug_print",
    "infeed", "outfeed", "host_callback_call",
})

#: explicit device/host transfer primitives — an implicit transfer
#: inside a jitted trace is the same stall by another name
TRANSFER_PRIMS = frozenset({"device_put", "copy_to_host_async"})


def _is_benign_device_put(eqn) -> bool:
    """``device_put`` of a compile-time Literal with no device target is
    constant *placement* (jnp.asarray on a python scalar inside a traced
    body) — XLA folds it; there is no runtime transfer to flag."""
    if eqn.primitive.name != "device_put":
        return False
    if any(d is not None for d in eqn.params.get("devices", [])):
        return False
    return all(type(v).__name__ == "Literal" for v in eqn.invars)


def _as_jaxpr(j):
    return j.jaxpr if isinstance(j, ClosedJaxpr) else j


def _subjaxprs(eqn) -> Iterator[Jaxpr]:
    """Every sub-jaxpr carried in ``eqn.params`` — direct values AND
    tuple/list params (``cond`` keeps its branches in a tuple, which the
    historical per-test walkers silently skipped)."""
    for v in eqn.params.values():
        if isinstance(v, (ClosedJaxpr, Jaxpr)):
            yield _as_jaxpr(v)
        elif isinstance(v, (list, tuple)):
            for w in v:
                if isinstance(w, (ClosedJaxpr, Jaxpr)):
                    yield _as_jaxpr(w)


def iter_eqns(jaxpr, *, enter_pallas_body: bool = True,
              _ctx: tuple[str, ...] = ()):
    """Yield ``(eqn, context)`` for every equation in ``jaxpr`` and its
    sub-jaxprs.  ``context`` is the tuple of enclosing primitive names
    from the outside in (``('pjit', 'while', 'scan')`` for an equation
    inside the engine's scanned chunk body)."""
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn, _ctx
        name = eqn.primitive.name
        if name == "pallas_call" and not enter_pallas_body:
            continue
        for sub in _subjaxprs(eqn):
            yield from iter_eqns(sub, enter_pallas_body=enter_pallas_body,
                                 _ctx=_ctx + (name,))


def count_eqns(jaxpr, pred, *, enter_pallas_body: bool = True) -> int:
    """Count primitive equations matching ``pred`` in ``jaxpr``,
    descending into sub-jaxprs (pjit/while/cond/scan bodies).  The one
    shared walker behind every trace-shape assertion in the repo;
    ``enter_pallas_body=False`` treats a ``pallas_call`` as a single
    device op instead of recursing into its kernel body."""
    return sum(1 for eqn, _ in
               iter_eqns(jaxpr, enter_pallas_body=enter_pallas_body)
               if pred(eqn))


# ---------------------------------------------------------------------------
# census records
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PallasLaunch:
    """One ``pallas_call`` equation: kernel name, static grid shape
    (dynamic dims as ``None``), the grid axes inserted by jax's vmap
    batching rule (non-empty == this launch was vmapped, not written
    with a native batch grid axis), and its structural context."""

    kernel: str
    grid: tuple[int | None, ...]
    vmapped_dims: tuple[int, ...]
    context: tuple[str, ...]

    @property
    def vmapped(self) -> bool:
        return bool(self.vmapped_dims)


@dataclasses.dataclass(frozen=True)
class DtypeCast:
    """One ``convert_element_type``: source/target dtype names + context."""

    src: str
    dst: str
    context: tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class HostCall:
    """One host-callback or transfer primitive inside the trace."""

    primitive: str
    context: tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class LoopShell:
    """One ``while``/``scan`` equation: kind, context, and how many of
    its carry outputs are dead (``DropVar`` — computed then discarded)."""

    kind: str  # 'while' | 'scan'
    context: tuple[str, ...]
    dead_carries: int


class LoopCounts(tuple):
    """``(while, scan, pallas_call)`` counts — the trio every
    steady-state trace-shape assertion compares against."""

    __slots__ = ()

    def __new__(cls, while_, scan, pallas):
        return super().__new__(cls, (while_, scan, pallas))

    @property
    def while_(self):
        return self[0]

    @property
    def scan(self):
        return self[1]

    @property
    def pallas(self):
        return self[2]


@dataclasses.dataclass(frozen=True)
class OpCensus:
    """Normalized op census of one traced callable.

    All counts treat a ``pallas_call`` as a single device op (the kernel
    body is summarized separately in ``kernel_eqn_count``), matching how
    every launch-count and ops-per-cycle claim in the repo is stated.
    """

    op_counts: Mapping[str, int]  # primitive name -> eqn count
    pallas_calls: tuple[PallasLaunch, ...]
    loops: tuple[LoopShell, ...]
    casts: tuple[DtypeCast, ...]
    host_calls: tuple[HostCall, ...]
    kernel_eqn_count: int  # eqns inside pallas kernel bodies

    @property
    def eqn_count(self) -> int:
        """Total equations outside pallas kernel bodies."""
        return sum(self.op_counts.values())

    @property
    def device_op_count(self) -> int:
        """Equations that are device compute (structural wrappers —
        pjit/while/cond/scan shells — excluded)."""
        return sum(n for name, n in self.op_counts.items()
                   if name not in STRUCTURAL_PRIMS)

    @property
    def while_count(self) -> int:
        return self.op_counts.get("while", 0)

    @property
    def scan_count(self) -> int:
        return self.op_counts.get("scan", 0)

    @property
    def pallas_call_count(self) -> int:
        return len(self.pallas_calls)

    @property
    def dead_carry_leaves(self) -> int:
        return sum(loop.dead_carries for loop in self.loops)

    def count(self, primitive: str) -> int:
        return self.op_counts.get(primitive, 0)

    def loop_counts(self) -> LoopCounts:
        return LoopCounts(self.while_count, self.scan_count,
                          self.pallas_call_count)


def _static_grid(grid) -> tuple[int | None, ...]:
    out = []
    for d in tuple(grid):
        try:
            out.append(int(d))
        except (TypeError, ValueError):
            out.append(None)  # dynamic grid bound
    return tuple(out)


def _pallas_launch(eqn, ctx) -> PallasLaunch:
    gm = eqn.params.get("grid_mapping")
    grid = _static_grid(getattr(gm, "grid", ())) if gm is not None else ()
    vmapped = tuple(getattr(gm, "vmapped_dims", ()) or ())
    name_info = eqn.params.get("name_and_src_info")
    kernel = getattr(name_info, "name", None) or str(
        eqn.params.get("name", "<pallas>"))
    return PallasLaunch(kernel=kernel, grid=grid, vmapped_dims=vmapped,
                        context=ctx)


def _dead_carries(eqn) -> int:
    # jax marks computed-but-unused loop outputs as DropVar; a dead carry
    # leaf is state threaded through every iteration for nothing
    return sum(1 for v in eqn.outvars
               if type(v).__name__ == "DropVar")


def census_of(jaxpr) -> OpCensus:
    """Build the :class:`OpCensus` of an already-traced (closed) jaxpr."""
    ops: Counter[str] = Counter()
    pallas: list[PallasLaunch] = []
    loops: list[LoopShell] = []
    casts: list[DtypeCast] = []
    host: list[HostCall] = []
    for eqn, ctx in iter_eqns(jaxpr, enter_pallas_body=False):
        name = eqn.primitive.name
        ops[name] += 1
        if name == "pallas_call":
            pallas.append(_pallas_launch(eqn, ctx))
        elif name in ("while", "scan"):
            loops.append(LoopShell(kind=name, context=ctx,
                                   dead_carries=_dead_carries(eqn)))
        elif name == "convert_element_type":
            casts.append(DtypeCast(
                src=str(eqn.invars[0].aval.dtype),
                dst=str(eqn.params["new_dtype"]), context=ctx))
        elif name in HOST_CALLBACK_PRIMS or name in TRANSFER_PRIMS:
            if not _is_benign_device_put(eqn):
                host.append(HostCall(primitive=name, context=ctx))
    kernel_eqns = (count_eqns(jaxpr, lambda e: True)
                   - sum(ops.values()))
    return OpCensus(op_counts=dict(ops), pallas_calls=tuple(pallas),
                    loops=tuple(loops), casts=tuple(casts),
                    host_calls=tuple(host), kernel_eqn_count=kernel_eqns)


def trace(fn: Callable, *args: Any, **kwargs: Any) -> ClosedJaxpr:
    """``jax.make_jaxpr`` with kwargs threaded — the abstract trace every
    census and rule check runs on (no compilation, no execution)."""
    return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)


def census(fn: Callable, *args: Any, **kwargs: Any) -> OpCensus:
    """Trace ``fn(*args, **kwargs)`` abstractly and census the result."""
    return census_of(trace(fn, *args, **kwargs))


def primitive_count(fn: Callable, name: str, *args: Any,
                    enter_pallas_body: bool = False, **kwargs: Any) -> int:
    """Occurrences of primitive ``name`` in the trace of ``fn(*args)``."""
    return count_eqns(trace(fn, *args, **kwargs),
                      lambda e: e.primitive.name == name,
                      enter_pallas_body=enter_pallas_body)


def loop_counts(fn: Callable, *args: Any, **kwargs: Any) -> LoopCounts:
    """``(while, scan, pallas_call)`` counts of the trace of ``fn`` —
    the steady-state shape assertion shared by the engine/kernel tests."""
    return census(fn, *args, **kwargs).loop_counts()
