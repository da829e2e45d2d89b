"""``Solver``: one execution engine over every backend.

``Solver.solve`` runs a single problem, ``Solver.solve_many`` advances a
whole batch through one vmapped dispatch (the batched core),
``Solver.resolve`` re-solves from a ``WarmStartHandle`` after signed
capacity updates — warm for *both* signs, decreases via the streaming
tier's on-device flow reroute — and ``Solver.open_stream`` opens a
long-lived ``repro.streaming.StreamingGraph`` session with versioned
incremental re-solves.
"""
from __future__ import annotations

from collections.abc import Iterable, Sequence

import numpy as np

from repro.api.options import SolverOptions
from repro.api.problem import MaxflowProblem
from repro.api.solution import (Solution, SolveStats, WarmStartHandle,
                                _normalize_updates)
from repro.core import batched
from repro.core import pushrelabel as pr
from repro.core.csr import ResidualCSR
from repro.obs import span

_DISTRIBUTED_GUIDANCE = (
    "backend='distributed' needs a multi-device runtime but only one JAX "
    "device is visible.  Expose more devices (e.g. "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8 on CPU) or use "
    "backend='single'/'batched'.  Plugging sharded solves into the serving "
    "path is the ROADMAP item 'Multi-device sharding of one giant "
    "instance'.")


class Solver:
    """Executes problems under a fixed ``SolverOptions``.

    ``Solver()`` uses the defaults; ``Solver(backend="batched", mode="tc")``
    is shorthand for ``Solver(SolverOptions(backend="batched", mode="tc"))``.
    """

    def __init__(self, options: SolverOptions | None = None, **overrides):
        if options is None:
            options = SolverOptions(**overrides)
        elif overrides:
            options = options.replace(**overrides)
        self.options = options

    # -- single problem -----------------------------------------------------

    def solve(self, problem) -> Solution:
        opts = self.options
        with span("solve", backend=opts.backend, mode=opts.mode):
            if opts.backend == "distributed":
                return self._solve_distributed(problem)
            if opts.backend == "batched":
                return self.solve_many([problem])[0]
            return self._solve_single(problem,
                                      problem.residual(opts.layout))

    def _solve_single(self, problem, r: ResidualCSR) -> Solution:
        opts = self.options
        # no max_rounds: a round ends early once e[t] stalls, so rounds
        # may outnumber max_cycles / cadence; the exact max_cycles budget
        # bounds them (every round runs at least one cycle)
        legacy = pr.solve_impl(
            r, problem.s, problem.t, mode=opts.mode,
            cycle_chunk=opts.global_relabel_cadence,
            interpret=opts.interpret, instrument=opts.telemetry,
            max_cycles=opts.max_cycles, scan_chunk=opts.scan_chunk)
        handle = WarmStartHandle(
            r, problem.s, problem.t,
            np.asarray(legacy.state.res), np.asarray(legacy.state.e),
            use_kernel=opts.mode in pr.KERNEL_MODES,
            interpret=opts.interpret)
        stats = SolveStats(
            cycles=legacy.cycles, rounds=legacy.rounds,
            global_relabels=legacy.global_relabels, backend="single",
            mode=opts.mode, layout=r.layout,
            pushes=legacy.pushes, relabels=legacy.relabels,
            gr_sweeps=legacy.gr_sweeps, frontier_lanes=legacy.frontier_lanes,
            stalled_rounds=legacy.stalled_rounds,
            active_history=legacy.active_history if opts.telemetry else None,
            frontier_history=(legacy.frontier_history if opts.telemetry
                              else None),
            maxdeg_history=legacy.maxdeg_history if opts.telemetry else None)
        return Solution(problem, legacy.maxflow, stats, handle)

    # -- batched ------------------------------------------------------------

    def solve_many(self, problems: Iterable) -> list[Solution]:
        """Solve B problems in one padded, vmapped dispatch (the batched
        core).  Per-problem values match ``solve`` exactly."""
        problems = list(problems)
        if not problems:
            return []
        opts = self.options
        if opts.backend == "distributed":
            return [self.solve(p) for p in problems]
        residuals = [p.residual(opts.layout) for p in problems]
        insts = [(r, p.s, p.t) for r, p in zip(residuals, problems)]
        n_max = max(r.n for r in residuals)
        out = batched.batched_solve_impl(
            insts, mode=opts.mode, cycle_chunk=opts.global_relabel_cadence,
            max_rounds=opts.max_rounds(n_max), phase2=True,
            interpret=opts.interpret, telemetry=opts.telemetry,
            max_cycles=opts.max_cycles, scan_chunk=opts.scan_chunk)
        return self._batched_solutions(problems, residuals, out,
                                       warm=False)

    def _batched_solutions(self, problems: Sequence,
                           residuals: Sequence[ResidualCSR],
                           out: batched.BatchedSolveResult,
                           warm: bool) -> list[Solution]:
        opts = self.options
        res_np = np.asarray(out.state.res)
        e_np = np.asarray(out.state.e)
        use_kernel = opts.mode in pr.KERNEL_MODES
        sols = []
        for i, (p, r) in enumerate(zip(problems, residuals)):
            if out.trivial[i]:
                # packed with zero capacities — the sliced state is not the
                # instance's; an idle handle (no flow) is the true answer
                handle = WarmStartHandle(
                    r, p.s, p.t, r.res0.copy(),
                    np.zeros(r.n, batched.STATE_DTYPE), corrected=True,
                    use_kernel=use_kernel, interpret=opts.interpret)
            else:
                handle = WarmStartHandle(
                    r, p.s, p.t, res_np[i, : r.num_arcs].copy(),
                    e_np[i, : r.n].copy(), corrected=out.corrected,
                    use_kernel=use_kernel, interpret=opts.interpret)
            stats = SolveStats(
                cycles=int(out.cycles[i]), rounds=int(out.rounds[i]),
                global_relabels=out.global_relabels, backend="batched",
                mode=opts.mode, layout=r.layout, warm=warm,
                batch_size=len(problems), gr_sweeps=out.gr_sweeps,
                pushes=(int(out.pushes[i]) if out.pushes is not None
                        else 0),
                relabels=(int(out.relabels[i]) if out.relabels is not None
                          else 0),
                frontier_lanes=(int(out.frontier_lanes[i])
                                if out.frontier_lanes is not None else 0))
            sols.append(Solution(p, int(out.maxflows[i]), stats, handle))
        return sols

    # -- incremental re-solves ----------------------------------------------

    def resolve(self, handle: WarmStartHandle, updates) -> Solution:
        """Re-solve after signed capacity updates, warm for both signs.

        Increases re-enter the solver from the handle's phase-2-corrected
        residual with the injected excess budgeted by the update total, so
        only the new capacity gets routed.  Decreases cancel the
        overflowed flow and drain the imbalance on-device
        (``repro.streaming.reroute``), then re-enter with the drained
        value as budget.  Either way, a warm start that injects no
        excess is answered directly — the rerouted flow is already
        maximal and no solver dispatch runs.
        """
        ups = _normalize_updates(updates)
        rerouted = any(d < 0 for _, _, d in ups)
        r2, warm = handle.apply(ups)
        problem = MaxflowProblem.from_residual(r2, handle.s, handle.t)
        if warm is None:  # reroute stalled (defensive): cold solve
            return self._solve_single(problem, r2)
        sol = self._warm_solution(problem, r2, handle, warm)
        sol.stats.rerouted = rerouted
        return sol

    def _warm_solution(self, problem, r2: ResidualCSR,
                       handle: WarmStartHandle, warm) -> Solution:
        """Finish a warm re-solve from an ``apply`` triple.  Shared by
        :meth:`resolve` and the streaming tier (which assembles its own
        residual/warm pairs for structural edits)."""
        opts = self.options
        res, _, e = warm
        inner = np.ones(r2.n, bool)
        inner[handle.t] = False  # e[s] is zero by construction
        if not (e[inner] > 0).any():
            # no injected excess: no augmenting path can exist (either
            # the budget was zero or every source arc is saturated), so
            # the warm state IS the maximum flow — skip the dispatch
            from repro.obs import counter

            counter("stream.noop_resolves").inc()
            h2 = WarmStartHandle(
                r2, handle.s, handle.t, res, e, corrected=True,
                use_kernel=opts.mode in pr.KERNEL_MODES,
                interpret=opts.interpret)
            stats = SolveStats(backend="batched", mode=opts.mode,
                               layout=r2.layout, warm=True)
            return Solution(problem, int(e[handle.t]), stats, h2)
        mode = opts.mode  # every mode is batchable
        bg, meta, _, trivial = batched.pack_instances(
            [(r2, handle.s, handle.t)])
        state0 = batched.pack_states([warm], meta.n, meta.num_arcs)
        out = batched.batched_resolve(
            bg, meta, state0, trivial=trivial, mode=mode,
            cycle_chunk=opts.global_relabel_cadence,
            max_rounds=opts.max_rounds(r2.n), interpret=opts.interpret,
            telemetry=opts.telemetry, max_cycles=opts.max_cycles,
            scan_chunk=opts.scan_chunk)
        sol = self._batched_solutions([problem], [r2], out, warm=True)[0]
        sol.stats.mode = mode
        return sol

    # -- streaming ----------------------------------------------------------

    def open_stream(self, problem, max_versions: int = 8):
        """Open a long-lived streaming session: solve ``problem`` once,
        then fold edge insert / delete / re-weight events into new
        warm-started versions via ``StreamHandle.apply(events)`` and
        answer ``query(version)`` from the retained chain.  Returns a
        ``repro.streaming.StreamHandle`` (see ``repro.streaming.stream``
        for the event vocabulary and version semantics)."""
        from repro.streaming.stream import StreamingGraph

        return StreamingGraph(problem, solver=self,
                              max_versions=max_versions)

    # -- distributed --------------------------------------------------------

    def _solve_distributed(self, problem) -> Solution:
        import jax

        ndev = len(jax.devices())
        if ndev < 2:
            raise NotImplementedError(_DISTRIBUTED_GUIDANCE)
        from repro import compat
        from repro.core import distributed

        opts = self.options
        r = problem.residual(opts.layout)
        mesh = compat.make_mesh((ndev,), ("shard",))
        flow = distributed.solve_distributed(
            r, problem.s, problem.t, mesh, "shard", mode="replicated",
            cycles=opts.global_relabel_cadence or 64)
        stats = SolveStats(backend="distributed", mode=opts.mode,
                           layout=r.layout)
        # solve_distributed reports the value only (final sharded state
        # stays on-device); no warm-start capture yet
        return Solution(problem, flow, stats, warm_start=None)
