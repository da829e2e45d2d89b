"""From a JAX profiler trace to the numbers the benchmark reports.

A TPU trace (``.xplane.pb``, read with ``jax.profiler.ProfileData``)
holds one plane per chip, ``/device:TPU:<k>``, whose line ``XLA Modules``
has one event per program execution (``jit_run_cycles(<hash>)``) and
whose line ``XLA Ops`` has one event per operation.  The host plane
``/host:CPU`` has a line ``python`` holding the benchmark's own
``TraceAnnotation`` spans (``bench.<name>``) on the same clock.

From these events, flattened to ``(plane, line, name, start_ns, dur_ns)``
tuples, ``reduce_events`` computes:

* ``window_s``: the traced window, the span ``bench.traced`` (or, without
  it, the extent of the device events);
* ``busy_s``: per chip, the union of the ``XLA Ops`` intervals inside the
  window, averaged over the chips;
* ``programs``: device seconds and executions per program, keyed by the
  module name without its hash (``jit_run_cycles``), from chip 0;
* ``ops``: device seconds per operation of chip 0, keyed
  ``<program>:<hlo name>`` (the text before `` = ``), each op credited to
  the program whose execution holds its start;
* ``gaps``: chip 0's idle time inside the window, each gap credited to
  the innermost benchmark span that holds its midpoint (``(no span)``
  when none does).
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_LINE = "python"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_HASH = re.compile(r"\(\d+\)$")


def program_name(module_event_name: str) -> str:
    """``jit_run_cycles(1362...)`` -> ``jit_run_cycles``."""
    return _HASH.sub("", module_event_name)


def op_name(op_event_name: str) -> str:
    """``%fusion.3 = s32[...] fusion(...)`` -> ``%fusion.3``."""
    return op_event_name.split(" = ", 1)[0]


def load_events(path: str | Path) -> list[tuple]:
    """The device and host-span events of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        device = _DEVICE.match(plane.name) is not None
        if not device and plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            keep = (line.name in (OPS_LINE, MODULES_LINE) if device
                    else line.name == HOST_LINE)
            if not keep:
                continue
            for ev in line.events:
                if device or ev.name.startswith("bench."):
                    out.append((plane.name, line.name, ev.name,
                                float(ev.start_ns), float(ev.duration_ns)))
    return out


def _merge(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


class Reduction:
    """The reduced trace (see the module docstring); times in seconds."""

    def __init__(self, window_s, busy_s, programs, ops, gaps):
        self.window_s = window_s
        self.busy_s = busy_s
        self.programs = programs  # name -> [seconds, executions]
        self.ops = ops  # name -> seconds
        self.gaps = gaps  # span name -> idle seconds

    def program_s(self, names) -> float | None:
        """Device seconds of the programs named, or None when the trace
        holds none of them."""
        hit = [self.programs[n][0] for n in names if n in self.programs]
        return sum(hit) if hit else None

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce_events(events: list[tuple], window: str = "bench.traced"
                  ) -> Reduction:
    spans = [(e[3], e[3] + e[4], e[2]) for e in events
             if e[1] == HOST_LINE and e[2].startswith("bench.")]
    dev = defaultdict(lambda: {"ops": [], "mods": []})
    for plane, line, name, start, dur in events:
        m = _DEVICE.match(plane)
        if m is None or line not in (OPS_LINE, MODULES_LINE):
            continue
        key = "ops" if line == OPS_LINE else "mods"
        dev[int(m.group(1))][key].append((start, start + dur, name))
    if not dev:
        raise ValueError("the trace holds no TPU device events")
    win = [s for s in spans if s[2] == window]
    if win:
        w0, w1 = win[0][0], win[0][1]
    else:
        every = [x for d in dev.values() for k in ("ops", "mods")
                 for x in d[k]]
        w0, w1 = min(x[0] for x in every), max(x[1] for x in every)
    window_ns = max(w1 - w0, 1.0)

    busy = []
    for d in dev.values():
        clipped = [(max(a, w0), min(b, w1)) for a, b, _ in d["ops"]
                   if b > w0 and a < w1]
        busy.append(sum(b - a for a, b in _merge(clipped)))
    first = dev[min(dev)]
    programs: dict[str, list] = {}
    mods = sorted(first["mods"])
    for a, b, name in mods:
        p = programs.setdefault(program_name(name), [0.0, 0])
        p[0] += (b - a) * 1e-9
        p[1] += 1
    ops: dict[str, float] = defaultdict(float)
    starts = [m[0] for m in mods]
    for a, b, name in first["ops"]:
        i = bisect.bisect_right(starts, a) - 1
        owner = (program_name(mods[i][2])
                 if i >= 0 and mods[i][1] >= a else "(no program)")
        ops[f"{owner}:{op_name(name)}"] += (b - a) * 1e-9
    gaps: dict[str, float] = defaultdict(float)
    merged = _merge([(max(a, w0), min(b, w1)) for a, b, _ in first["ops"]
                     if b > w0 and a < w1])
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        mid = 0.5 * (g0 + g1)
        holding = [s for s in spans if s[0] <= mid <= s[1]
                   and s[2] != window]
        name = (min(holding, key=lambda s: s[1] - s[0])[2] if holding
                else "(no span)")
        gaps[name] += (g1 - g0) * 1e-9
    return Reduction(window_s=window_ns * 1e-9,
                     busy_s=sum(busy) / len(busy) * 1e-9,
                     programs=programs, ops=dict(ops), gaps=dict(gaps))


def reduce_dir(log_dir: str | Path, window: str = "bench.traced"
               ) -> Reduction:
    """Reduce the one ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``log_dir``."""
    found = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return reduce_events(load_events(found[-1]), window=window)
