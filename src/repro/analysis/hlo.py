"""The HLO backend: collective-bytes accounting over compiled modules.

The jaxpr census (:mod:`repro.analysis.ir`) sees the program *before*
XLA; communication volume only exists after SPMD partitioning, so the
distributed cost model parses the compiled HLO text instead.  Every
``all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute`` op contributes its *on-wire per-device* bytes,
derived from the result shape and the replica-group size::

    all-gather         out * (g-1)/g        (ring, out = full gathered)
    all-reduce         2 * out * (g-1)/g    (reduce-scatter + all-gather)
    reduce-scatter     out * (g-1)          (input = out * g)
    all-to-all         out * (g-1)/g
    collective-permute out

Formerly ``repro.launch.hlo_analysis`` (that module now re-exports from
here).  One behavioural fix over the historical parser: an op line whose
``replica_groups`` cannot be parsed used to silently assume a group size
of 2 — *undercounting* wire bytes for any larger group.  It now raises
:class:`ReplicaGroupParseError` carrying the unmatched line; pass
``strict=False`` to keep the old floor and get a warning instead.
"""
from __future__ import annotations

import re
import warnings
from collections import defaultdict


__all__ = ["DTYPE_BYTES", "ReplicaGroupParseError", "collective_bytes",
           "cost_summary"]

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_OP_RE = re.compile(
    r"=\s*(?P<shape>\(?[a-z0-9_\[\],{}\s]*?\)?)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<start>-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9_]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


class ReplicaGroupParseError(ValueError):
    """An HLO collective op line whose ``replica_groups`` attribute the
    parser could not read — guessing a group size would mis-state wire
    bytes, so strict mode refuses.  ``.line`` carries the offender."""

    def __init__(self, line: str):
        self.line = line
        super().__init__(
            "could not parse replica_groups from HLO collective op line "
            f"(wire-byte accounting would be wrong): {line.strip()!r}")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def _group_size(line: str, strict: bool) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    # collective-permute carries source_target_pairs, not replica_groups;
    # its wire volume does not depend on a group size anyway
    if "collective-permute" in line:
        return 2
    if strict:
        raise ReplicaGroupParseError(line)
    warnings.warn(
        "unparsed replica_groups in HLO collective op; assuming group "
        f"size 2 (may UNDERCOUNT wire bytes): {line.strip()!r}",
        stacklevel=3)
    return 2


def collective_bytes(hlo_text: str, strict: bool = True) -> dict:
    """Per-op-type on-wire bytes per device + op counts.

    ``strict=True`` (default) raises :class:`ReplicaGroupParseError` on a
    collective op whose replica groups cannot be parsed; ``strict=False``
    restores the historical assume-2 floor, with a warning."""
    out_bytes = defaultdict(float)
    counts = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m or "-done" in line:
            continue
        op = m.group("op")
        size = _shape_bytes(m.group("shape"))
        g = max(2, _group_size(line, strict))
        if op == "all-gather":
            wire = size * (g - 1) / g
        elif op == "all-reduce":
            wire = 2.0 * size * (g - 1) / g
        elif op == "reduce-scatter":
            wire = size * (g - 1)
        elif op == "all-to-all":
            wire = size * (g - 1) / g
        else:  # collective-permute
            wire = size
        out_bytes[op] += wire
        counts[op] += 1
    total = sum(out_bytes.values())
    return {"total_bytes": total, "by_op": dict(out_bytes),
            "counts": dict(counts)}


def cost_summary(compiled, strict: bool = False) -> dict:
    """flops / bytes / memory / collective summary of one compiled
    executable.  Collective parsing is lenient here by default — a cost
    *estimate* should degrade, not crash, on an exotic HLO line; the
    analyzer CLI runs :func:`collective_bytes` strictly."""
    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    mem = {}
    if ma is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            mem[f] = getattr(ma, f, None)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
        "memory": mem,
        "collectives": collective_bytes(compiled.as_text(), strict=strict),
    }
