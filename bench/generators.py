"""Instance generators of the benchmark, in numpy alone.

``washington_rlg`` is ``repro.graphs.generators.washington_rlg`` line for
line (the same draws in the same order, so a seed gives the very
instance the program's generator gives), kept here so that no change to
the program can change what the benchmark offers it.

An instance is an ``Instance``: ``n`` vertices, an ``(m, 2)`` int64 edge
list of (tail, head) pairs, ``m`` int64 capacities, source and sink.
The harness wraps it in the program's graph type only at the call.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Instance:
    n: int
    edges: np.ndarray  # (m, 2) int64 (tail, head)
    caps: np.ndarray  # (m,) int64
    s: int
    t: int


def washington_rlg(rows: int, cols: int, max_cap: int = 100,
                   seed: int = 0) -> Instance:
    """DIMACS random level graph: ``cols`` levels of ``rows`` vertices,
    each vertex with 3 arcs to random vertices of the next level; s feeds
    level 0 and the last level drains to t."""
    rng = np.random.default_rng(seed)
    n = rows * cols + 2
    s, t = rows * cols, rows * cols + 1
    edges, caps = [], []
    vid = lambda r, c: c * rows + r  # noqa: E731
    for r in range(rows):
        edges.append((s, vid(r, 0)))
        caps.append(int(rng.integers(1, max_cap + 1)) * rows)
        edges.append((vid(r, cols - 1), t))
        caps.append(int(rng.integers(1, max_cap + 1)) * rows)
    for c in range(cols - 1):
        for r in range(rows):
            for tgt in rng.integers(0, rows, size=3):
                edges.append((vid(r, c), vid(int(tgt), c + 1)))
                caps.append(int(rng.integers(1, max_cap + 1)))
    return Instance(n, np.array(edges, np.int64), np.array(caps, np.int64),
                    s, t)


def shuffle_edges(inst: Instance, rng: np.random.Generator) -> Instance:
    """The same network with its edge list in a random order."""
    order = rng.permutation(inst.edges.shape[0])
    return dataclasses.replace(inst, edges=inst.edges[order],
                               caps=inst.caps[order])
