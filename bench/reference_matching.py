"""The plain reference of the matching configurations: maximum matching
and a check of the returned pairs.

It imports nothing of the program.  It works from the benchmark's own
memberships (``bipartite.Bipartite.lr``), kept where the flow network
gives the membership, its user's source arc and its group's sink arc a
positive capacity (``open_memberships``):

* the value is scipy's ``maximum_bipartite_matching`` (Hopcroft–Karp, in
  C) on the left x right biadjacency, an independent implementation that
  knows nothing of flows;
* a returned matching is checked pair by pair: each pair must be an open
  membership, no vertex may be in two pairs, and there must be as many
  pairs as the reference value.

The cut is checked on the flow network with ``reference.cut_gap``.
Every check counts faults instead of raising, so the harness can print
each number beside its limit.
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import maximum_bipartite_matching

from bipartite import Bipartite


def open_memberships(bp: Bipartite) -> np.ndarray:
    """The ``(left, right)`` memberships a flow can use: the membership's
    own arc, its user's arc from s and its group's arc to t all have
    positive capacity."""
    inst = bp.inst
    u, v = inst.edges[:, 0], inst.edges[:, 1]
    up = inst.caps > 0
    n = inst.n
    open_v = np.zeros(n, bool)
    open_v[v[(u == inst.s) & up]] = True
    open_v[u[(v == inst.t) & up]] = True
    arcs = np.isin(bp.lr[:, 0] * n + bp.lr[:, 1], u[up] * n + v[up])
    keep = arcs & open_v[bp.lr[:, 0]] & open_v[bp.lr[:, 1]]
    return bp.lr[keep]


def matching_value(bp: Bipartite) -> int:
    """scipy's maximum matching size on the open memberships."""
    L, R = bp.n_left, bp.n_right
    lr = open_memberships(bp)
    m = csr_matrix((np.ones(len(lr), np.int8), (lr[:, 0], lr[:, 1] - L)),
                   shape=(L, R))
    return int(np.count_nonzero(
        maximum_bipartite_matching(m, perm_type="column") >= 0))


def matching_faults(bp: Bipartite, ref_value: int, pairs) -> int:
    """Faults of returned ``(left, right)`` pairs: pairs that are not open
    memberships, plus vertices used more than once (each extra use one
    fault), plus ``| len(pairs) - ref_value |``."""
    p = np.asarray(pairs, np.int64).reshape(-1, 2)
    lr = open_memberships(bp)
    n = bp.inst.n
    member = np.isin(p[:, 0] * n + p[:, 1], lr[:, 0] * n + lr[:, 1])
    faults = int(np.count_nonzero(~member))
    for side in (p[:, 0], p[:, 1]):
        faults += int(side.size - np.unique(side).size)
    return faults + abs(int(p.shape[0]) - ref_value)
