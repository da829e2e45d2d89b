"""Unified telemetry: metrics registry, span tracer, device scopes,
device-side solver counters.

Four layers, one export surface:

* ``repro.obs.metrics`` — process-global, label-scoped counters /
  gauges / histograms; ``REGISTRY.snapshot()`` is the JSON metrics dump
  every surface (``MaxflowService.telemetry_snapshot()``,
  ``serve_maxflow --metrics-out``, ``BENCH_*.json``) reads from.
* ``repro.obs.trace`` — nested host spans on the profiler's clock: each
  is a ``jax.profiler.TraceAnnotation``, and an enabled ``TRACER`` also
  records it for Chrome ``trace_event`` export (``TRACER.export(path)``
  opens in Perfetto).
* ``repro.obs.scopes`` — the ``jax.named_scope`` phases of the solver's
  device programs (cycle step, global relabel, phase 2) and the map from
  a compiled program's HLO instructions to them.
* ``repro.obs.solvercounters`` — int32 push/relabel/active/frontier
  counters folded into the jitted cycle loops so per-cycle workload
  numbers (the paper's Fig. 3 inputs) ride the solve for free and are
  fetched once per dispatch.

See ``docs/OBSERVABILITY.md`` for the metric catalogue and span
taxonomy.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.obs.metrics import (REGISTRY, Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry, counter, gauge, histogram)
from repro.obs.trace import TRACER, Tracer, span  # noqa: F401

__all__ = [
    "REGISTRY", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "counter", "gauge", "histogram",
    "TRACER", "Tracer", "span",
    "to_jsonable",
]


def to_jsonable(obj):
    """Recursively convert a stats tree to pure-JSON Python values.

    numpy scalars become ints/floats, numpy arrays become lists, tuples
    and sets become lists, dataclasses become dicts, non-string dict
    keys are stringified.  ``json.dumps(to_jsonable(x))`` must never
    raise for any ``stats()`` tree in the repo — that is the contract
    the telemetry snapshot (and its tests) enforce.
    """
    if isinstance(obj, (bool, int, float, str)) or obj is None:
        return obj
    if isinstance(obj, (np.bool_,)):
        return bool(obj)
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, np.floating):
        return float(obj)
    if isinstance(obj, np.ndarray):
        return [to_jsonable(v) for v in obj.tolist()] \
            if obj.dtype == object else obj.tolist()
    if isinstance(obj, dict):
        return {_key(k): to_jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple, set, frozenset)):
        return [to_jsonable(v) for v in obj]
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {f.name: to_jsonable(getattr(obj, f.name))
                for f in dataclasses.fields(obj)}
    if hasattr(obj, "tolist"):  # jax.Array and other array-likes
        return to_jsonable(np.asarray(obj))
    return repr(obj)  # last resort: loud but serializable


def _key(k) -> str:
    if isinstance(k, str):
        return k
    if isinstance(k, (bool, int, float)) or k is None:
        return str(k)
    label = getattr(k, "label", None)  # BucketKey and friends
    if isinstance(label, str):
        return label
    return str(to_jsonable(k))
