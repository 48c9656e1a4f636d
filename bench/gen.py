"""The benchmark's own Graph500 data: Kronecker R-MAT edges, kernel-3 edge
weights and the undirected CSR, made on the device from seeds.

The generator follows the Graph500 reference (``kronecker_generator.m`` of
the Graph500 specification, graph500.org): for each of ``scale`` bits an
edge picks a quadrant of the adjacency matrix with probabilities
(A, B, C, D) = (a, b, c, 1 - a - b - c), the vertex labels are permuted,
and the edge list keeps its self loops and duplicates. Kernel 3 draws one
uniform weight per generated edge.

The CSR is what a Graph500 kernel builds from that list: self loops
dropped, every undirected pair once with the least weight among its
duplicates, both directions stored, rows sorted by neighbour. The
structure comes from ``graph_seed`` alone and the weights from
``weight_seed`` alone, so one structure can carry weights from any seed;
a configuration fixes both.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Graph:
    """Host CSR of an undirected weighted graph (both directions stored)."""
    n: int
    indptr: np.ndarray      # int64[n + 1]
    indices: np.ndarray     # int32[nnz], sorted within each row
    weights: np.ndarray     # float32[nnz], w(u, v) == w(v, u)

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
    """Sorted symmetric CSR arrays with sentinel rows past the real ones."""
    import jax
    import jax.numpy as jnp
    lo = jnp.minimum(src, dst)
    hi = jnp.maximum(src, dst)
    loop = lo == hi
    lo = jnp.where(loop, n, lo)
    hi = jnp.where(loop, n, hi)
    lo, hi, w = jax.lax.sort((lo, hi, w), num_keys=3)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    first = first & (lo < n)
    lo = jnp.where(first, lo, n)
    hi = jnp.where(first, hi, n)
    u = jnp.concatenate([lo, hi])
    v = jnp.concatenate([hi, lo])
    ww = jnp.concatenate([w, w])
    u, v, ww = jax.lax.sort((u, v, ww), num_keys=2)
    indptr = jnp.searchsorted(u, jnp.arange(n + 1, dtype=jnp.int32),
                              side="left").astype(jnp.int32)
    return v, ww, indptr


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


def edge_weights(m: int, low: float, high: float, weight_seed: int):
    """Kernel-3 weights on the device: float32[m] uniform in [low, high)."""
    import jax
    import jax.numpy as jnp

    def impl(key, *, m, low, high):
        return jax.random.uniform(key, (m,), jnp.float32, low, high)

    fn = _jit("weights", impl, ("m", "low", "high"))
    return fn(random_key(weight_seed), m=m, low=low, high=high)


def build_graph(*, scale: int, edge_factor: int, a: float, b: float,
                c: float, weight_low: float, weight_high: float,
                graph_seed: int, weight_seed: int) -> Graph:
    """Generate on the device and bring the CSR to the host."""
    import jax
    n = 1 << scale
    src, dst = rmat_edges(scale, edge_factor, a, b, c, graph_seed)
    w = edge_weights(src.shape[0], weight_low, weight_high, weight_seed)
    fn = _jit("csr", _csr_impl, ("n",))
    v, ww, indptr = fn(src, dst, w, n=n)
    del src, dst, w
    indptr = np.asarray(jax.device_get(indptr)).astype(np.int64)
    nnz = int(indptr[-1])
    indices, weights = jax.device_get((v[:nnz], ww[:nnz]))
    return Graph(n=n, indptr=indptr,
                 indices=np.asarray(indices, np.int32),
                 weights=np.asarray(weights, np.float32))


def from_config(cfg: dict) -> Graph:
    """The configuration's graph, fixed by its ``graph_seed`` and
    ``weight_seed``."""
    g = cfg["graph"]
    return build_graph(scale=g["scale"], edge_factor=g["edge_factor"],
                       a=g["a"], b=g["b"], c=g["c"],
                       weight_low=g["weight_low"],
                       weight_high=g["weight_high"],
                       graph_seed=g["graph_seed"],
                       weight_seed=g["weight_seed"])


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
