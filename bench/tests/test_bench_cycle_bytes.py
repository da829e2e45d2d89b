"""The least-bytes count of a cycle, on an instance whose per-cycle
counts can be worked out by hand."""
import numpy as np
import pytest

import cycle_bytes


def test_least_bytes_formula():
    assert cycle_bytes.least_bytes([1], [2]) == 12 * 2 + 20 * 1
    assert cycle_bytes.least_bytes([3, 1], [10, 4]) == 12 * 14 + 20 * 4
    assert cycle_bytes.least_bytes([], []) == 0


def test_counts_of_a_path_by_hand():
    """s -> a -> t with unit capacities: the preflow leaves one unit at
    a; the global relabel gives a height 1, so the one cycle has one
    active vertex (a) scanning its two residual arcs (a->s, a->t) and
    pushes to t; the next cycle finds no active vertex."""
    from repro.api import MaxflowProblem, Solver, SolverOptions

    sol = Solver(SolverOptions(telemetry=True)).solve(
        MaxflowProblem.from_arrays(3, [[0, 1], [1, 2]], [1, 1], 0, 2))
    assert sol.value == 1
    assert list(sol.stats.active_history) == [1]
    assert list(sol.stats.frontier_history) == [2]
    assert cycle_bytes.least_bytes(sol.stats.active_history,
                                   sol.stats.frontier_history) == 44


def test_peaks_table():
    pk = cycle_bytes.peaks("TPU v5 lite")
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cycle_bytes.peaks("cpu")
    assert np.isfinite(pk["bf16_flops_per_s"])
