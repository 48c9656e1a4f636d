"""The benchmark's own Graph500 data: Kronecker R-MAT edges, kernel-3 edge
weights and the undirected CSR, made on the device from seeds.

The generator follows the Graph500 reference (``kronecker_generator.m`` of
the Graph500 specification, graph500.org): for each of ``scale`` bits an
edge picks a quadrant of the adjacency matrix with probabilities
(A, B, C, D) = (a, b, c, 1 - a - b - c), the vertex labels are permuted,
and the edge list keeps its self loops and duplicates. Kernel 3 draws one
uniform weight per generated edge; a configuration without a weight range
makes kernel 2's unweighted graph.

The CSR is what a Graph500 kernel builds from that list: self loops
dropped, every undirected pair once with the least weight among its
duplicates, both directions stored, rows sorted by neighbour. The
structure comes from ``graph_seed`` alone and the weights from
``weight_seed`` alone, so one structure can carry weights from any seed;
a configuration fixes both. Edges, weights and CSR are one program on the
device (``_graph_impl``), so no edge list outlives it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Graph:
    """Host CSR of an undirected graph (both directions stored)."""
    n: int
    indptr: np.ndarray      # int64[n + 1]
    indices: np.ndarray     # int32[nnz], sorted within each row
    weights: Optional[np.ndarray]   # float32[nnz], w(u, v) == w(v, u);
    #                                 None for an unweighted graph

    @property
    def nnz(self) -> int:
        return int(self.indices.size)

    @property
    def deg(self) -> np.ndarray:
        return np.diff(self.indptr)


def _rmat_impl(key, *, scale: int, edge_factor: int, a: float, b: float,
               c: float, permute: bool):
    import jax
    import jax.numpy as jnp
    n = 1 << scale
    m = edge_factor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    kbits, kperm = jax.random.split(key)
    bit_keys = jax.random.split(kbits, 2 * scale)

    def one_bit(i, ij):
        src, dst = ij
        ii = jax.random.uniform(bit_keys[2 * i], (m,)) > ab
        thresh = jnp.where(ii, c_norm, a_norm)
        jj = jax.random.uniform(bit_keys[2 * i + 1], (m,)) > thresh
        return (src | (ii.astype(jnp.int32) << i),
                dst | (jj.astype(jnp.int32) << i))

    zero = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, one_bit, (zero, zero))
    if not permute:
        return src, dst
    perm = jax.random.permutation(kperm, n).astype(jnp.int32)
    return perm[src], perm[dst]


def _csr_impl(src, dst, w, *, n: int):
    """Sorted symmetric CSR arrays, ``(indices, weights, indptr)``, with
    ``indices`` and ``weights`` of length 2m: the CSR fills the first
    ``indptr[n]`` entries and the rest is filler.

    One sort of the m undirected pairs by (lo, hi[, w]) keeps the first of
    each run of duplicates, the one with the least weight. Row u of the
    symmetric CSR is then its neighbours below u, which are the pairs with
    hi == u in lo order, followed by those above u, which are the pairs with
    lo == u in hi order, so a second sort of the m pairs by (hi, lo) and a
    scatter of both lists into place give it without a sort of all 2m
    entries. ``w`` None builds an unweighted CSR (``weights`` None).
    """
    import jax
    import jax.numpy as jnp
    m = src.shape[0]
    lo = jnp.minimum(src, dst)
    hi = jnp.maximum(src, dst)
    loop = lo == hi
    lo = jnp.where(loop, n, lo)
    hi = jnp.where(loop, n, hi)
    extra = () if w is None else (w,)
    lo, hi, *extra = jax.lax.sort((lo, hi) + extra, num_keys=2 + len(extra))
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    first = first & (lo < n)
    # rank of each kept pair among the kept ones, and kept pairs per row:
    # fwd[u] kept pairs with lo < u, bwd[u] kept pairs with hi < u
    kept_before = jnp.cumsum(first, dtype=jnp.int32)
    kept = kept_before[-1]
    kept_before = jnp.concatenate([jnp.zeros((1,), jnp.int32), kept_before])
    rows = jnp.arange(n + 1, dtype=jnp.int32)
    fwd = kept_before[jnp.searchsorted(lo, rows, side="left")]
    hi2, lo2, *extra2 = jax.lax.sort(
        (jnp.where(first, hi, n), jnp.where(first, lo, n), *extra),
        num_keys=2)
    bwd = jnp.searchsorted(hi2, rows, side="left").astype(jnp.int32)
    indptr = fwd + bwd
    # row u holds its bwd[u + 1] - bwd[u] lower neighbours at indptr[u]
    # onwards, then its upper ones; dropped pairs fill the 2m - 2 * kept
    # entries past the CSR, so the scatter is a permutation
    idx = jnp.arange(m, dtype=jnp.int32)
    rank = kept_before[:-1]
    nnz = 2 * kept
    pos_fwd = jnp.where(first, bwd[jnp.minimum(lo, n - 1) + 1] + rank,
                        nnz + idx - rank)
    pos_bwd = jnp.where(idx < kept, fwd[jnp.minimum(hi2, n - 1)] + idx,
                        nnz + m - kept + idx - kept)

    def place(upper, lower):
        out = jnp.zeros((2 * m,), upper.dtype)
        out = out.at[pos_fwd].set(upper, unique_indices=True)
        return out.at[pos_bwd].set(lower, unique_indices=True)
    indices = place(hi, lo2)
    weights = place(extra[0], extra2[0]) if extra else None
    return indices, weights, indptr


def _graph_impl(graph_key, weight_key, *, scale: int, edge_factor: int,
                a: float, b: float, c: float, weight_range):
    """The whole graph from its seeds in one program: edges, kernel-3
    weights (``weight_range`` ``(low, high)``, or None for none) and CSR."""
    import jax
    import jax.numpy as jnp
    src, dst = _rmat_impl(graph_key, scale=scale, edge_factor=edge_factor,
                          a=a, b=b, c=c, permute=True)
    w = None
    if weight_range is not None:
        low, high = weight_range
        w = jax.random.uniform(weight_key, src.shape, jnp.float32, low, high)
    return _csr_impl(src, dst, w, n=1 << scale)


_jitted = {}


def _jit(name, impl, static):
    import jax
    if name not in _jitted:
        _jitted[name] = jax.jit(impl, static_argnames=static)
    return _jitted[name]


def rmat_edges(scale: int, edge_factor: int, a: float, b: float, c: float,
               graph_seed: int, permute: bool = True):
    """Graph500 edge list on the device: (src, dst) int32[edge_factor *
    2**scale]; ``permute=False`` keeps the unpermuted labels, whose bits
    show the quadrant draws."""
    fn = _jit("rmat", _rmat_impl,
              ("scale", "edge_factor", "a", "b", "c", "permute"))
    return fn(random_key(graph_seed), scale=scale,
              edge_factor=edge_factor, a=a, b=b, c=c, permute=permute)


def build_graph(*, scale: int, edge_factor: int, a: float, b: float,
                c: float, graph_seed: int, weight_low: Optional[float] = None,
                weight_high: Optional[float] = None,
                weight_seed: Optional[int] = None) -> Graph:
    """Generate on the device and bring the CSR to the host; without
    ``weight_low`` and ``weight_high`` the graph is unweighted."""
    import jax
    weighted = weight_low is not None or weight_high is not None
    fn = _jit("graph", _graph_impl,
              ("scale", "edge_factor", "a", "b", "c", "weight_range"))
    out = fn(random_key(graph_seed),
             random_key(weight_seed) if weighted else None,
             scale=scale, edge_factor=edge_factor, a=a, b=b, c=c,
             weight_range=(weight_low, weight_high) if weighted else None)
    indices, weights, indptr = jax.device_get(out)
    del out
    indptr = np.asarray(indptr, np.int64)
    nnz = int(indptr[-1])
    return Graph(n=1 << scale, indptr=indptr,
                 indices=np.asarray(indices[:nnz], np.int32),
                 weights=(None if weights is None
                          else np.asarray(weights[:nnz], np.float32)))


def from_config(cfg: dict) -> Graph:
    """The configuration's graph, fixed by its ``graph_seed`` and, where it
    gives ``weight_low`` and ``weight_high``, its ``weight_seed``."""
    g = cfg["graph"]
    return build_graph(scale=g["scale"], edge_factor=g["edge_factor"],
                       a=g["a"], b=g["b"], c=g["c"],
                       graph_seed=g["graph_seed"],
                       weight_low=g.get("weight_low"),
                       weight_high=g.get("weight_high"),
                       weight_seed=g.get("weight_seed"))


def random_key(seed: int):
    """``jax.random`` key for a seed of any size: its low 31 bits seed the
    key and the rest is folded in, so seeds above 2**31 stay distinct."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0x7FFFFFFF)
    high = seed >> 31
    while high:
        key = jax.random.fold_in(key, high & 0x7FFFFFFF)
        high >>= 31
    return key
