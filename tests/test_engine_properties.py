"""Property tests for the workload layer (hypothesis when available,
seeded-draw fallback via ``_hypothesis_compat`` otherwise).

These are structural invariants rather than oracle matches — they hold for
*every* graph/parameter draw, so the strategy space does the exploring:

* PageRank conserves probability mass after **any** number of sweeps, not
  just at convergence (the damped update redistributes, never creates);
* betweenness on a path graph has the closed form ``bc[i] = i * (n-1-i)``
  (every s < i < t pair routes through i, uniquely);
* ``khop(k=None)`` is exactly boolean-BFS reachability;
* k-hop balls are nested: ``khop(k) ⊆ khop(k+1)`` with hop counts agreeing
  on the smaller ball;
* the bit-packed (SlimSell-B) path is bit-identical to the lane path.
"""
import numpy as np
import pytest

from repro.core.bfs import bfs
from repro.core.formats import build_csr, build_slimsell
from repro.core.khop import khop
from repro.core.pagerank import pagerank
from repro.graphs.generators import erdos_renyi, kronecker, ring_of_cliques

from _hypothesis_compat import given, settings, strategies as st


def random_layout(kind: str, seed: int):
    csr = {
        "kron": lambda: kronecker(7, 6, seed=seed),
        "er": lambda: erdos_renyi(96, 5, seed=seed),
        "ring": lambda: ring_of_cliques(4 + seed % 5, 4),
    }[kind]()
    return csr, build_slimsell(csr, C=8, L=16).to_jax()


@settings(max_examples=8, deadline=None)
@given(kind=st.sampled_from(["kron", "er", "ring"]),
       seed=st.integers(min_value=0, max_value=31),
       damping=st.floats(min_value=0.05, max_value=0.95),
       sweeps=st.integers(min_value=1, max_value=8))
def test_pagerank_conserves_mass(kind, seed, damping, sweeps):
    # tol below float32 resolution forces exactly `sweeps` iterations; the
    # rank vector must sum to 1 at every truncation point
    _, tiled = random_layout(kind, seed)
    res = pagerank(tiled, damping=float(damping), tol=1e-30,
                   max_iters=int(sweeps))
    assert abs(float(res.ranks.sum()) - 1.0) < 1e-4
    assert np.all(res.ranks >= 0)


@settings(max_examples=6, deadline=None)
@given(n=st.integers(min_value=3, max_value=40))
def test_betweenness_path_closed_form(n):
    from repro.core.betweenness import betweenness
    edges = np.stack([np.arange(n - 1), np.arange(1, n)], axis=1)
    tiled = build_slimsell(build_csr(edges, n), C=4, L=8).to_jax()
    res = betweenness(tiled)
    i = np.arange(n, dtype=np.float64)
    np.testing.assert_allclose(res.scores, i * (n - 1 - i),
                               rtol=1e-5, atol=1e-6)


@settings(max_examples=8, deadline=None)
@given(kind=st.sampled_from(["kron", "er", "ring"]),
       seed=st.integers(min_value=0, max_value=31),
       root=st.integers(min_value=0, max_value=63))
def test_khop_unbounded_is_reachability(kind, seed, root):
    _, tiled = random_layout(kind, seed)
    root = int(root) % tiled.n
    res = khop(tiled, root, None)
    d_bfs = np.asarray(bfs(tiled, root, "boolean").distances)
    np.testing.assert_array_equal(res.mask, d_bfs >= 0)
    np.testing.assert_array_equal(res.distances, d_bfs)


@settings(max_examples=8, deadline=None)
@given(kind=st.sampled_from(["kron", "er", "ring"]),
       seed=st.integers(min_value=0, max_value=31),
       root=st.integers(min_value=0, max_value=63),
       k=st.integers(min_value=0, max_value=5))
def test_khop_balls_nested(kind, seed, root, k):
    _, tiled = random_layout(kind, seed)
    root, k = int(root) % tiled.n, int(k)
    inner = khop(tiled, root, k)
    outer = khop(tiled, root, k + 1)
    assert not np.any(inner.mask & ~outer.mask)          # inner ⊆ outer
    np.testing.assert_array_equal(                       # agree on inner
        outer.distances[inner.mask], inner.distances[inner.mask])
    assert np.all(outer.distances[outer.mask & ~inner.mask] == k + 1)


@settings(max_examples=8, deadline=None)
@given(kind=st.sampled_from(["kron", "er", "ring"]),
       seed=st.integers(min_value=0, max_value=31),
       root=st.integers(min_value=0, max_value=63),
       k=st.integers(min_value=0, max_value=6))
def test_khop_packed_bit_equal(kind, seed, root, k):
    _, tiled = random_layout(kind, seed)
    root, k = int(root) % tiled.n, int(k)
    lane = khop(tiled, root, k)
    word = khop(tiled, root, k, packed=True)
    np.testing.assert_array_equal(word.distances, lane.distances)
    assert word.iterations == lane.iterations
