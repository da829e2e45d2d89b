"""The least bytes a push-relabel cycle has to move, and the peaks table.

One bulk-synchronous cycle gives every active vertex one push or one
relabel.  Whatever implements it, a cycle has to

* for every arc it scans (the arcs of active vertices, the ``frontier``
  counter of ``repro.obs.solvercounters``): read the arc's residual
  capacity, its head and the head's height, 3 x 4 bytes;
* for every active vertex: read its excess and height (8 bytes) and its
  segment bounds (8 bytes), and write at least one word, a height for a
  relabel or far more for a push (4 bytes).

So ``least_bytes = 12 * frontier + 20 * active`` per cycle.  Counting
only what the algorithm cannot avoid, and not what an implementation
happens to move (the flat ``vc`` step pads its frontier to every arc),
keeps a roofline share built on it at or below 100% for any step whose
time covers its work.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

BYTES_PER_ARC = 12
BYTES_PER_ACTIVE = 20


def least_bytes(active, frontier) -> int:
    """Least bytes over cycles with the given per-cycle active-vertex and
    frontier-arc counts (sequences or scalars)."""
    a = np.asarray(active, np.int64).sum()
    f = np.asarray(frontier, np.int64).sum()
    return int(BYTES_PER_ARC * f + BYTES_PER_ACTIVE * a)


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind`` from ``peaks.json``; a device that the
    table does not hold is an error, never a default."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "peaks.json") from None
