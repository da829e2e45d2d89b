"""The plain reference of every configuration: max flow and its proof.

It imports nothing of the program.  For each answer it works from the
benchmark's own ``Instance`` (the capacities the benchmark generated and
edited), never from a table the program built:

* the value is ``scipy.sparse.csgraph.maximum_flow`` on the directed
  capacities (parallel edges summed, self-loops dropped), an independent
  implementation;
* a returned flow is checked as a certificate: per unordered vertex pair
  ``(lo, hi)`` — in the order of the sorted keys ``lo * n + hi``, which is
  the order the program's ``Solution.flows()`` documents — the net flow
  ``lo -> hi`` must respect both directions' capacities, every vertex
  but s and t must conserve flow, and s must send and t receive the
  claimed value;
* a returned cut (source-side vertex mask) must hold s, not t, and have
  capacity equal to the reference value.

Every check counts faults instead of raising, so the harness can print
each number beside its limit.
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import maximum_flow

from generators import Instance


def directed_caps(inst: Instance) -> tuple[np.ndarray, np.ndarray]:
    """Sorted keys ``u * n + v`` of the directed pairs and their summed
    capacities (self-loops dropped)."""
    u, v = inst.edges[:, 0], inst.edges[:, 1]
    keep = u != v
    keys, inv = np.unique(u[keep] * inst.n + v[keep], return_inverse=True)
    cap = np.zeros(keys.size, np.int64)
    np.add.at(cap, inv, inst.caps[keep])
    return keys, cap


def max_flow_value(inst: Instance) -> int:
    """scipy's maximum flow value (Dinic, in C) on the instance."""
    keys, cap = directed_caps(inst)
    if inst.s == inst.t or keys.size == 0:
        return 0
    u, v = np.divmod(keys, inst.n)
    if cap.max(initial=0) >= 2**31:
        raise OverflowError("capacity beyond int32; scipy cannot hold it")
    m = csr_matrix((cap.astype(np.int32), (u, v)), shape=(inst.n, inst.n))
    return int(maximum_flow(m, inst.s, inst.t).flow_value)


def _cap_of(keys: np.ndarray, cap: np.ndarray, n: int, a, b) -> np.ndarray:
    k = np.asarray(a, np.int64) * n + np.asarray(b, np.int64)
    if keys.size == 0:
        return np.zeros(k.shape, np.int64)
    i = np.minimum(np.searchsorted(keys, k), keys.size - 1)
    return np.where(keys[i] == k, cap[i], 0)


def flow_faults(inst: Instance, value: int, flows) -> int:
    """Faults of a returned flow: pairs over capacity in either direction,
    vertices that do not conserve flow, a source or sink whose net flow is
    not ``value``; a flow array of the wrong length counts as one fault
    per missing or extra pair."""
    keys, cap = directed_caps(inst)
    n = inst.n
    u, v = np.divmod(keys, n)
    lo, hi = np.minimum(u, v), np.maximum(u, v)
    pairs = np.unique(lo * n + hi)
    f = np.asarray(flows, np.int64).reshape(-1)
    if f.size != pairs.size:
        return abs(int(f.size) - int(pairs.size)) + 1
    pu, pv = np.divmod(pairs, n)
    faults = int(np.count_nonzero(f > _cap_of(keys, cap, n, pu, pv)))
    faults += int(np.count_nonzero(-f > _cap_of(keys, cap, n, pv, pu)))
    net = np.zeros(n, np.int64)
    np.add.at(net, pu, f)
    np.add.at(net, pv, -f)
    inner = np.ones(n, bool)
    inner[[inst.s, inst.t]] = False
    faults += int(np.count_nonzero(net[inner]))
    faults += int(net[inst.s] != value) + int(net[inst.t] != -value)
    return faults


def cut_gap(inst: Instance, ref_value: int, source_side) -> int:
    """How far a returned cut is from a minimum cut: the capacity of the
    edges it crosses minus the reference value, or (for a mask that does
    not separate s from t, or has the wrong length) the reference value
    plus one."""
    side = np.asarray(source_side, bool).reshape(-1)
    if side.size != inst.n or not side[inst.s] or side[inst.t]:
        return ref_value + 1
    u, v = inst.edges[:, 0], inst.edges[:, 1]
    crossing = side[u] & ~side[v] & (u != v)
    return abs(int(inst.caps[crossing].sum()) - ref_value)
