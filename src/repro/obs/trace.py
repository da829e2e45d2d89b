"""Nested host spans on the profiler's clock, with Chrome ``trace_event``
JSON export.

Every ``span()`` enters a ``jax.profiler.TraceAnnotation``: under a
``jax.profiler`` session (``jax.profiler.trace``, TensorBoard, a
benchmark's traced run) the span is an event on the ``/host:CPU`` plane,
above the device ops it dispatched; with no session running it costs
TraceMe's own "is a session active" check.

``Tracer`` additionally records the spans as Chrome events while enabled
(``TRACER.enable()``; the ``serve_maxflow --trace-out`` flag does):
begin/end (``ph: "B"``/``"E"``) pairs for the synchronous span tree
(flush -> solve -> phase 2) and complete (``ph: "X"``) events for things
whose start was recorded elsewhere (a request's enqueue -> respond
lifecycle).  Their ``ts`` is the clock the profiler stamps host events
with, ``time.time_ns()`` in microseconds, so an exported file lines up
with a device trace of the same run.  ``Tracer.export(path)`` writes the
JSON object form (``{"traceEvents": [...]}``) that ``chrome://tracing``
and https://ui.perfetto.dev open directly.

A count known only inside a span (cycles fetched from the device) is
added with ``set_metadata(**args)`` on the object the ``with`` binds:
it lands on the profiler event and on the Chrome ``E`` event.
"""
from __future__ import annotations

import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "TRACER", "span"]


def _now_us() -> float:
    """The profiler's host clock, in microseconds."""
    return time.time_ns() * 1e-3


class _Span:
    """One live span of an enabled tracer: a profiler annotation plus its
    ``B``/``E`` pair; re-entrant use is a fresh instance."""

    __slots__ = ("_tracer", "_name", "_args", "_end_args", "_ann")

    def __init__(self, tracer: Tracer, name: str, args: dict):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._end_args = None
        self._ann = TraceAnnotation(name, **args)

    def set_metadata(self, **args) -> None:
        self._ann.set_metadata(**args)
        self._end_args = args

    def __enter__(self):
        self._ann.__enter__()
        self._tracer._emit("B", self._name, _now_us(), self._args)
        return self

    def __exit__(self, *exc):
        self._tracer._emit("E", self._name, _now_us(), self._end_args)
        self._ann.__exit__(*exc)
        return False


class Tracer:
    """Collects Chrome trace events in memory until ``export``/``clear``."""

    def __init__(self, enabled: bool = False):
        self.enabled = bool(enabled)
        self._events: list[dict] = []
        self._lock = threading.Lock()
        self._pid = os.getpid()

    # -- control ------------------------------------------------------------

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events = []

    def __len__(self) -> int:
        return len(self._events)

    # -- recording ----------------------------------------------------------

    def _emit(self, ph: str, name: str, ts_us: float,
              args: dict | None = None, dur_us: float | None = None) -> None:
        ev = {"name": name, "ph": ph, "ts": ts_us, "pid": self._pid,
              "tid": threading.get_ident()}
        if dur_us is not None:
            ev["dur"] = dur_us
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def span(self, name: str, **args):
        """``with tracer.span("serve.flush", bucket=...):`` — a profiler
        annotation, and while enabled a nested ``B``/``E`` pair."""
        if not self.enabled:
            return TraceAnnotation(name, **args)
        return _Span(self, name, args)

    def complete(self, name: str, start_s: float, end_s: float,
                 **args) -> None:
        """A ``ph: "X"`` complete event from ``time.perf_counter()``
        endpoints — for lifecycles whose start predates the span (a
        request's enqueue happened turns before its flush).  The endpoints
        move to the profiler's clock here."""
        if not self.enabled:
            return
        offset_us = _now_us() - time.perf_counter() * 1e6
        self._emit("X", name, start_s * 1e6 + offset_us, args,
                   dur_us=max(end_s - start_s, 0.0) * 1e6)

    # -- export -------------------------------------------------------------

    def to_dict(self) -> dict:
        with self._lock:
            return {"traceEvents": list(self._events),
                    "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        """Write the Chrome ``trace_event`` JSON object format; returns
        ``path``.  Load in chrome://tracing or ui.perfetto.dev."""
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)
        return path


#: THE process-global tracer (disabled until a surface enables it)
TRACER = Tracer()


def span(name: str, **args):
    """Module-level shorthand for ``TRACER.span``."""
    return TRACER.span(name, **args)
