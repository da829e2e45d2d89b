"""Sequential oracles used to validate the parallel push-relabel
implementations: max flow by Dinic's algorithm (O(V^2 E) worst case) and
maximum bipartite matching by Hopcroft–Karp.  Pure numpy/python —
plenty for test-scale graphs."""
from __future__ import annotations

from collections import deque

import numpy as np

from repro.core.csr import Graph, ResidualCSR, build_residual


def dinic_maxflow(g: Graph, s: int, t: int) -> int:
    r = build_residual(g, "bcsr")
    return dinic_on_residual(r, s, t)


def dinic_on_residual(r: ResidualCSR, s: int, t: int) -> int:
    return dinic_residual_flow(r, s, t)[0]


def dinic_residual_flow(r: ResidualCSR, s: int,
                        t: int) -> tuple[int, np.ndarray]:
    """Dinic's max-flow returning ``(flow, final_residual)``.

    The residual array is per-arc in ``r``'s layout, i.e. directly usable
    as the corrected residual of a ``WarmStartHandle`` (zero excess
    everywhere except ``flow`` at ``t``) — this is the host-reference
    fallback the serving degradation ladder bottoms out on.
    """
    n = r.n
    indptr, heads, rev = r.indptr, r.heads, r.rev
    res = r.res0.copy()
    if s == t:
        return 0, res

    def bfs_levels():
        level = np.full(n, -1, np.int64)
        level[s] = 0
        q = deque([s])
        while q:
            u = q.popleft()
            for a in range(indptr[u], indptr[u + 1]):
                v = heads[a]
                if res[a] > 0 and level[v] < 0:
                    level[v] = level[u] + 1
                    q.append(v)
        return level if level[t] >= 0 else None

    flow = 0
    while True:
        level = bfs_levels()
        if level is None:
            return int(flow), res
        it = indptr[:-1].copy()  # current-arc optimisation

        # iterative DFS for blocking flow
        def dfs(u, pushed):
            if u == t:
                return pushed
            while it[u] < indptr[u + 1]:
                a = it[u]
                v = heads[a]
                if res[a] > 0 and level[v] == level[u] + 1:
                    d = dfs(v, min(pushed, res[a]))
                    if d > 0:
                        res[a] -= d
                        res[rev[a]] += d
                        return d
                it[u] += 1
            return 0

        import sys
        old = sys.getrecursionlimit()
        sys.setrecursionlimit(max(old, n + 100))
        try:
            while True:
                d = dfs(s, np.iinfo(np.int64).max)
                if d == 0:
                    break
                flow += d
        finally:
            sys.setrecursionlimit(old)


def ref_matching(lr_edges, n_left: int, n_right: int) -> np.ndarray:
    """Maximum bipartite matching by Hopcroft–Karp: augmenting paths on
    the left/right adjacency itself, with no flow network, so it checks
    the solver's matching independently of both the solver and Dinic.

    ``lr_edges`` holds ``(left, right)`` pairs with left ids
    ``0..n_left-1`` and right ids ``n_left..n_left+n_right-1``, as
    ``BipartiteProblem.lr_edges`` does; returns the matched pairs in the
    same ids, sorted by left id."""
    lr = np.asarray(lr_edges, np.int64).reshape(-1, 2)
    adj: list[list[int]] = [[] for _ in range(n_left)]
    for u, v in lr.tolist():
        adj[u].append(v - n_left)
    mate_l = [-1] * n_left
    mate_r = [-1] * n_right
    while True:
        # BFS from every free left vertex, layering alternating paths
        layer = [-1] * n_left
        queue = deque(u for u in range(n_left) if mate_l[u] < 0)
        for u in queue:
            layer[u] = 0
        limit = None
        while queue:
            u = queue.popleft()
            if limit is not None and layer[u] >= limit:
                continue
            for v in adj[u]:
                w = mate_r[v]
                if w < 0:
                    limit = layer[u] + 1 if limit is None else limit
                elif layer[w] < 0:
                    layer[w] = layer[u] + 1
                    queue.append(w)
        if limit is None:
            break
        # DFS along the layers from each free left vertex, iteratively
        it = [0] * n_left
        for root in range(n_left):
            if mate_l[root] >= 0 or layer[root] != 0:
                continue
            path = [root]
            while path:
                u = path[-1]
                if it[u] == len(adj[u]):
                    layer[u] = -1  # a dead end: not again this phase
                    path.pop()
                    continue
                v = adj[u][it[u]]
                it[u] += 1
                w = mate_r[v]
                if w < 0 and layer[u] + 1 == limit:
                    # augment: flip every edge of root -> ... -> u -> v
                    for x in reversed(path):
                        taken = mate_l[x]
                        mate_l[x], mate_r[v] = v, x
                        v = taken
                    break
                if w >= 0 and layer[w] == layer[u] + 1:
                    path.append(w)
    pairs = [(u, n_left + v) for u, v in enumerate(mate_l) if v >= 0]
    return np.asarray(pairs, np.int64).reshape(-1, 2)
