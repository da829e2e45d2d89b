"""Typed, validated solver configuration shared by every backend."""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.pushrelabel import ALL_MODES as MODES

LAYOUTS = ("bcsr", "rcsr")
BACKENDS = ("single", "batched", "distributed")


@dataclasses.dataclass(frozen=True)
class SolverOptions:
    """How to execute a solve, independent of what is being solved.

    ``mode``
        Push-relabel step strategy: ``vc`` (the paper's workload-balanced
        vertex-centric), ``tc`` (thread-centric baseline), or the faithful
        Pallas tile variants ``vc_kernel`` / ``vc_kernel_bsearch``.
    ``layout``
        Residual-graph layout, ``bcsr`` or ``rcsr`` (paper §3.2).
    ``backend``
        ``single`` (one instance per dispatch), ``batched`` (vmapped
        multi-instance core — also what ``Solver.solve_many`` uses), or
        ``distributed`` (shard_map over all local devices).
    ``global_relabel_cadence``
        The most push-relabel cycles between global relabels (the legacy
        ``cycle_chunk``).  ``None`` picks the auto heuristic
        ``max(32, min(1024, n))``.  On the single backend this is a cap:
        a round also ends once the sink's excess has not risen for
        ``max(32, 2 * sweeps)`` cycles, ``sweeps`` being the depth the
        round's opening global relabel found.  The batched backend runs
        every round to the cadence.
    ``max_cycles``
        Total push-relabel cycle budget; the solve raises ``RuntimeError``
        if it has not converged within it.  ``None`` means the legacy
        effectively-unbounded default.  The budget is exact: the core
        threads the remaining allowance into every dispatch as a traced
        scalar, so a budget that is not a multiple of the dispatch
        cadence is still honored to the cycle.
    ``scan_chunk``
        Steps per scan-compiled chunk inside the sweep engine's device
        loops (``repro.core.engine.run_bulk_loop``).  ``None`` picks
        ``engine.DEFAULT_CHUNK``; 1 disables chunking (one step per
        outer-loop iteration, the pre-engine trace shape).
    ``dtype``
        Capacity dtype.  Only ``int32`` is supported (the paper's integer
        capacities) — THE device state dtype for residuals/heights/excess
        end-to-end (``repro.core.batched.STATE_DTYPE``); validated here so
        a bad dtype fails loudly at configuration time, not inside a
        jitted kernel.  Host-side staging arrays may be wider, but every
        device entry point (``pack_states``, ``warm_start_arrays``,
        ``WarmStartHandle``) narrows through a checked cast that raises
        ``OverflowError`` on values outside int32 instead of silently
        wrapping (README "Dtype contract").
    ``interpret``
        Pallas execution for the kernel modes: ``None`` (default) sniffs
        the backend — compiled on TPU, interpreted elsewhere; an explicit
        bool overrides (e.g. force interpret mode on TPU to debug).
    ``telemetry``
        Fold the device-side workload counters
        (``repro.obs.solvercounters``) into every dispatch: the returned
        ``Solution.stats`` carries exact push/relabel totals (plus
        per-cycle active/frontier/maxdeg histories on the ``single``
        backend).  Off by default — the disabled trace is byte-identical
        to the pre-telemetry solver.
    """

    mode: str = "vc"
    layout: str = "bcsr"
    backend: str = "single"
    global_relabel_cadence: int | None = None
    max_cycles: int | None = None
    scan_chunk: int | None = None
    dtype: str | type | np.dtype = "int32"
    interpret: bool | None = None
    telemetry: bool = False

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(
                f"unknown mode {self.mode!r}; expected one of {MODES}")
        if self.layout not in LAYOUTS:
            raise ValueError(
                f"unknown layout {self.layout!r}; expected one of {LAYOUTS}")
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; "
                f"expected one of {BACKENDS}")
        if self.mode == "vc_kernel_bsearch" and self.layout != "bcsr":
            raise ValueError(
                "mode 'vc_kernel_bsearch' binary-searches head-sorted "
                f"segments and needs layout='bcsr', got {self.layout!r}")
        if self.backend == "distributed" and self.mode != "vc":
            raise ValueError(
                "backend 'distributed' is vertex-centric only (mode='vc'), "
                f"got {self.mode!r}")
        if self.interpret not in (None, True, False):
            raise ValueError(
                f"interpret must be None, True or False, got "
                f"{self.interpret!r}")
        if (self.global_relabel_cadence is not None
                and self.global_relabel_cadence < 1):
            raise ValueError("global_relabel_cadence must be >= 1 or None, "
                             f"got {self.global_relabel_cadence}")
        if self.max_cycles is not None and self.max_cycles < 1:
            raise ValueError(
                f"max_cycles must be >= 1 or None, got {self.max_cycles}")
        if self.scan_chunk is not None and self.scan_chunk < 1:
            raise ValueError(
                f"scan_chunk must be >= 1 or None, got {self.scan_chunk}")
        if np.dtype(self.dtype) != np.dtype(np.int32):
            raise ValueError(
                "capacities are int32 (the paper's integer-capacity "
                f"formulation); got dtype {self.dtype!r}")

    # -- mapping onto the legacy driver knobs -------------------------------

    def cycle_chunk(self, n: int) -> int:
        """The most cycles per device dispatch between global relabels;
        a single-backend round ends sooner when the sink's excess
        stalls (see ``global_relabel_cadence``)."""
        if self.global_relabel_cadence is not None:
            return self.global_relabel_cadence
        return max(32, min(1024, n))

    def max_rounds(self, n: int) -> int:
        """[cycles -> global relabel] rounds implied by ``max_cycles`` on
        the batched backend, whose rounds run to the cadence."""
        if self.max_cycles is None:
            return 100000
        return max(1, -(-self.max_cycles // self.cycle_chunk(n)))

    def replace(self, **changes) -> SolverOptions:
        return dataclasses.replace(self, **changes)
