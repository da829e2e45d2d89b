"""The matching configurations' instances, in numpy alone.

``bipartite_powerlaw`` is ``repro.graphs.generators.bipartite_powerlaw``
line for line (the same draws in the same order, so a seed gives the very
instance the program's generator gives), kept here so that no change to
the program can change what the benchmark offers it.  It returns a
``Bipartite``: the flow network as the benchmark's ``Instance`` (left
vertices ``0..n_left-1``, right ``n_left..n_left+n_right-1``, then s and
t; every capacity 1) with the sides' sizes and the memberships ``lr``.
``permuted`` and ``closed`` derive the comparison's extra graph from it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from generators import Instance


@dataclasses.dataclass(frozen=True)
class Bipartite:
    inst: Instance
    n_left: int
    n_right: int
    lr: np.ndarray  # (k, 2) int64 (left, right) memberships, sorted


def bipartite_powerlaw(n_left: int, n_right: int, n_edges: int,
                       left_exp: float = 0.5, right_exp: float = 0.8,
                       seed: int = 0) -> Bipartite:
    """Affiliation graph with rank power-law weights on both sides, each
    vertex in at least one membership, ``n_edges`` distinct memberships."""
    if not n_left + n_right <= n_edges <= n_left * n_right:
        raise ValueError(f"{n_edges} memberships cannot give each of "
                         f"{n_left} x {n_right} vertices one")
    rng = np.random.default_rng(seed)
    wl = (np.arange(n_left) + 1.0) ** -left_exp
    wr = (np.arange(n_right) + 1.0) ** -right_exp
    p_left = wl[rng.permutation(n_left)] / wl.sum()
    p_right = wr[rng.permutation(n_right)] / wr.sum()
    u = np.concatenate([np.arange(n_left),
                        rng.choice(n_left, size=n_right, p=p_left)])
    v = np.concatenate([rng.choice(n_right, size=n_left, p=p_right),
                        np.arange(n_right)])
    keys = u * n_right + v
    while True:
        _, first = np.unique(keys, return_index=True)
        keys = keys[np.sort(first)]
        if keys.size >= n_edges:
            break
        k = n_edges - keys.size
        more = (rng.choice(n_left, size=k, p=p_left) * n_right
                + rng.choice(n_right, size=k, p=p_right))
        keys = np.concatenate([keys, more])
    lu, lv = np.divmod(np.sort(keys[:n_edges]), n_right)
    lr = np.stack([lu, n_left + lv], 1).astype(np.int64)
    s, t = n_left + n_right, n_left + n_right + 1
    se = np.stack([np.full(n_left, s, np.int64), np.arange(n_left)], 1)
    te = np.stack([np.arange(n_left, n_left + n_right),
                   np.full(n_right, t, np.int64)], 1)
    all_e = np.concatenate([lr, se, te])
    caps = np.ones(len(all_e), np.int64)
    return Bipartite(Instance(n_left + n_right + 2, all_e, caps, s, t),
                     n_left, n_right, lr)


def permuted(bp: Bipartite, rng: np.random.Generator) -> Bipartite:
    """An isomorph: left ids permuted among the left, right ids among the
    right, s and t kept.  It has the same vertex count, arc count and
    degrees, so the program runs it on the same compiled programs; the
    vertices it visits, and so the order of its work, differ."""
    L, R = bp.n_left, bp.n_right
    relabel = np.concatenate([rng.permutation(L), L + rng.permutation(R),
                              [L + R, L + R + 1]]).astype(np.int64)
    lr = relabel[bp.lr]
    lr = lr[np.lexsort((lr[:, 1], lr[:, 0]))]
    inst = dataclasses.replace(bp.inst, edges=relabel[bp.inst.edges])
    return dataclasses.replace(bp, inst=inst, lr=lr)


def closed(bp: Bipartite, rng: np.random.Generator,
           share: float) -> Bipartite:
    """The graph with each source and sink arc closed (capacity 0) with
    probability ``share``: users and groups that take no part, arcs
    kept.  Its maximum matching is that of the memberships between open
    users and open groups (``reference_matching.open_memberships``)."""
    inst = bp.inst
    terminal = (inst.edges[:, 0] == inst.s) | (inst.edges[:, 1] == inst.t)
    shut = terminal & (rng.random(len(inst.caps)) < share)
    caps = np.where(shut, 0, inst.caps)
    return dataclasses.replace(bp, inst=dataclasses.replace(inst, caps=caps))
