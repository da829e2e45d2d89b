"""The matching cells' control, beside ``faults.control`` (whose patch of
``Solution.flows()`` a matching never calls).

``control()`` puts the program's own pair extraction in place of
``Solution.matching()``, read from the solver's final preflow without
phase 2: the matching guarantee broken, the step a later change would be
tempted to take (phase 2 is most of a ``bip.cold`` solve).  The preflow
carries a unit into a group from every user that pushed one, so groups
that held excess appear in several pairs, and the run's comparison has
to come out not correct.

``bench/control_matching.py`` runs it on the chip at the cell's size;
``bench/tests/test_bench_matching.py`` runs it at a small size.
"""
from __future__ import annotations

import contextlib
from unittest import mock

import numpy as np


@contextlib.contextmanager
def control():
    from repro.api.solution import Solution
    from repro.core import bipartite
    from repro.core import pushrelabel as pr

    def preflow_matching(self):
        h = self._handle()
        state = pr.PRState(res=h._res, h=np.zeros(h.residual.n, np.int32),
                           e=h._e)
        return bipartite.extract_matching(self.problem.bipartite,
                                          h.residual, state, corrected=True)

    with mock.patch.object(Solution, "matching", preflow_matching):
        yield
