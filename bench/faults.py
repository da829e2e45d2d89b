"""The control and the planted faults that the comparison must catch.

Each is a context manager that patches the program underneath an
otherwise normal run (``run.run_cell``), so the run's comparison with
the reference has to come out not correct:

* ``control()``: the reference put in the program's place with one
  stated guarantee broken, the step a later change would be tempted to
  take: flows read from the solver's final preflow without phase 2 (the
  flow guarantee broken; phase 2 is what ``certificate_s`` pays for);
* ``unchanged()``: the solver's step returns its state unchanged (the
  cycle loop runs no cycle and reports convergence);
* ``altered()``: an answer altered where it is produced (its value off
  by one).

``bench/control.py`` runs the control on the chip at the cell's size;
``bench/tests/test_bench_control.py`` runs all of them at a small size.
"""
from __future__ import annotations

import contextlib
from unittest import mock

import numpy as np


@contextlib.contextmanager
def control():
    from repro.api.solution import Solution

    def preflow_flows(self):
        h = self._handle()
        arc = np.asarray(h.residual.pair_arc)
        return np.asarray(h.residual.res0)[arc] - np.asarray(h._res)[arc]

    with mock.patch.object(Solution, "flows", preflow_flows):
        yield


@contextlib.contextmanager
def unchanged():
    from repro.core import pushrelabel as pr

    def no_cycles(g, meta, state, s, t, **_):
        return state, np.int32(0)

    def no_relabel(g, meta, state, s, t, minh_fn=None):
        return state, np.int32(0), np.int32(0)

    with mock.patch.object(pr, "run_cycles", no_cycles), \
            mock.patch.object(pr.globalrelabel, "global_relabel",
                              no_relabel):
        yield


@contextlib.contextmanager
def altered():
    from repro.api.solution import Solution

    real_init = Solution.__init__

    def off_by_one(self, problem, value, stats, warm_start):
        real_init(self, problem, value + 1, stats, warm_start)

    with mock.patch.object(Solution, "__init__", off_by_one):
        yield
