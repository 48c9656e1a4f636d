"""Plain references for the benchmark's correctness checks, and their
controls. They read only the benchmark's own ``gen.Graph``: nothing of the
program under test.

* ``bfs_levels``: top-down BFS hop counts over the CSR (-1 unreached);
  with ``max_depth=k``, the k-hop ball (-1 beyond k).
* ``dijkstra``: single-source shortest paths in float64 (scipy's heap
  Dijkstra), +inf where unreached.
* ``parent_faults``: the Graph500 tree checks (spec section 5.2) of a
  parent vector against reference levels or distances.
* ``traversed_edges``: Graph500 edge accounting.
* ``components``: connected-component label and size of every vertex,
  against which the reach of every BFS or SSSP answer is checked.

The controls break one guarantee each, in the way a later change could be
tempted to: ``bfs_levels(..., max_depth=...)`` stops one level short, and
``bellman_ford(..., dtype=bfloat16)`` computes distances in the precision
below the float32 the configuration states.
"""
from __future__ import annotations

from typing import Optional

import numpy as np


def _expand(indptr, frontier):
    """CSR positions of the out-edges of ``frontier``, and their sources."""
    starts = indptr[frontier]
    counts = indptr[frontier + 1] - starts
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    ends = np.cumsum(counts)
    pos = np.arange(total, dtype=np.int64) + np.repeat(starts - ends + counts,
                                                       counts)
    return pos, np.repeat(frontier, counts)


def bfs_levels(g, root: int, *, max_depth: Optional[int] = None
               ) -> np.ndarray:
    """Hop count of every vertex from ``root`` (int32, -1 unreached)."""
    d = np.full(g.n, -1, np.int32)
    d[root] = 0
    frontier = np.asarray([root], np.int64)
    level = 0
    while frontier.size and (max_depth is None or level < max_depth):
        level += 1
        nbrs = g.indices[_expand(g.indptr, frontier)[0]]
        nbrs = np.unique(nbrs[d[nbrs] < 0])
        d[nbrs] = level
        frontier = nbrs.astype(np.int64)
    return d


def _scipy_csr(g):
    import scipy.sparse as sp
    return sp.csr_matrix((g.weights.astype(np.float64), g.indices, g.indptr),
                         shape=(g.n, g.n))


def dijkstra(g, root: int) -> np.ndarray:
    """float64 shortest-path distances from ``root`` (+inf unreached)."""
    from scipy.sparse.csgraph import dijkstra as sp_dijkstra
    return sp_dijkstra(_scipy_csr(g), directed=True, indices=int(root))


def bellman_ford(g, root: int, dtype=np.float64) -> np.ndarray:
    """Frontier Bellman-Ford with every weight and every sum rounded to
    ``dtype``; returned as float64 (+inf unreached)."""
    dtype = np.dtype(dtype)
    work = np.float64 if dtype == np.float64 else np.float32

    def rnd(x):
        return x.astype(dtype).astype(work)

    w = rnd(g.weights.astype(work))
    dist = np.full(g.n, np.inf, work)
    dist[root] = 0
    active = np.asarray([root], np.int64)
    while active.size:
        pos, src = _expand(g.indptr, active)
        v = g.indices[pos]
        cand = rnd(dist[src] + w[pos])
        best = np.full(g.n, np.inf, work)
        np.minimum.at(best, v, cand)
        active = np.nonzero(best < dist)[0]
        dist[active] = best[active]
    return dist.astype(np.float64)


def components(g):
    """(label int32[n], size of each label) of the undirected graph."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components
    adj = sp.csr_matrix((np.ones(g.nnz, np.int8), g.indices, g.indptr),
                        shape=(g.n, g.n))
    _, labels = connected_components(adj, directed=False)
    return labels, np.bincount(labels)


def traversed_edges(g, reached: np.ndarray) -> int:
    """Graph500 edge accounting: undirected edges with a reached endpoint
    (the degrees of reached vertices halved), at least 1."""
    return max(1, int(g.deg[reached].sum()) // 2)


def _edge_index(g, u: np.ndarray, v: np.ndarray):
    """Position of edge (u, v) in the CSR, and whether it exists."""
    row_start = g.indptr[u]
    row_end = g.indptr[u + 1]
    keys = np.repeat(np.arange(g.n, dtype=np.int64), g.deg) * g.n \
        + g.indices.astype(np.int64)
    q = u.astype(np.int64) * g.n + v.astype(np.int64)
    idx = np.searchsorted(keys, q)
    idx = np.minimum(idx, max(keys.size - 1, 0))
    ok = (keys.size > 0) & (keys[idx] == q) & (idx >= row_start) \
        & (idx < row_end)
    return idx, ok


def parent_faults(g, root: int, ref: np.ndarray, parents: np.ndarray, *,
                  dist: Optional[np.ndarray] = None, atol: float = 0.0,
                  rtol: float = 0.0) -> int:
    """Vertices whose parent breaks a Graph500 tree rule.

    BFS (``dist`` None, ``ref`` the reference levels): the root is its own
    parent, unreached vertices have none, every other parent is a neighbour
    one level up. SSSP (``ref`` the reference distances, ``dist`` the
    program's float32 distances): the parent is a neighbour whose
    relaxation is tight, ``|dist[p] + w(p, v) - dist[v]| <= atol + rtol *
    |dist[v]|`` in float32.
    """
    parents = np.asarray(parents, np.int64)
    reached = ref >= 0 if dist is None else np.isfinite(ref)
    faults = int(parents[root] != root)
    faults += int((parents[~reached] != -1).sum())
    v = np.nonzero(reached)[0]
    v = v[v != root]
    p = parents[v]
    has = (p >= 0) & (p < g.n)
    faults += int((~has).sum())
    v, p = v[has], p[has]
    idx, ok = _edge_index(g, p, v)
    faults += int((~ok).sum())
    v, p, idx = v[ok], p[ok], idx[ok]
    if dist is None:
        faults += int((ref[p] != ref[v] - 1).sum())
    else:
        d = np.asarray(dist, np.float32)
        slack = np.abs((d[p] + g.weights[idx]) - d[v])
        faults += int((~(slack <= atol + rtol * np.abs(d[v]))).sum())
    return faults


def level_mismatches(got: np.ndarray, ref: np.ndarray) -> int:
    """Vertices whose hop count differs from the reference."""
    return int((np.asarray(got).astype(np.int64) != ref).sum())


#: floor of the reference distance that ``distance_gap`` divides by: the
#: smallest normal float32, so a distance of 0 has to be met exactly
DIST_FLOOR = float(np.finfo(np.float32).tiny)


def distance_gap(got: np.ndarray, ref: np.ndarray) -> float:
    """Widest relative gap of shortest-path distances:
    max |got - ref| / max(ref, DIST_FLOOR) over vertices the reference
    reaches, +inf where reachability differs."""
    got = np.asarray(got, np.float64)
    fin = np.isfinite(ref)
    if (np.isfinite(got) != fin).any():
        return float("inf")
    if not fin.any():
        return 0.0
    gap = np.abs(got[fin] - ref[fin]) / np.maximum(ref[fin], DIST_FLOOR)
    return float(gap.max())
