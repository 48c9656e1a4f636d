"""The benchmark's references agree with the program's oracles; the
controls differ from them."""
import ml_dtypes
import numpy as np
import pytest

from bench import gen, oracle
from repro.core.bfs_traditional import bfs_traditional
from repro.core.formats import CSRGraph
from repro.core.sssp import dijkstra_reference


@pytest.fixture(scope="module")
def graph():
    return gen.build_graph(scale=11, edge_factor=16, a=0.57, b=0.19, c=0.19,
                           weight_low=0.0, weight_high=1.0,
                           graph_seed=1, weight_seed=2)


@pytest.fixture(scope="module")
def csr(graph):
    return CSRGraph(n=graph.n, m_undirected=graph.nnz // 2,
                    indptr=graph.indptr, indices=graph.indices,
                    weights=graph.weights)


@pytest.fixture(scope="module")
def roots(graph):
    return [int(r) for r in np.nonzero(graph.deg > 0)[0][::97][:6]]


def test_bfs_levels_match_bfs_traditional(graph, csr, roots):
    for r in roots:
        d_ref, p_ref = bfs_traditional(csr, r)
        d = oracle.bfs_levels(graph, r)
        assert np.array_equal(d, d_ref)
        assert oracle.parent_faults(graph, r, d, p_ref) == 0


def test_khop_is_bfs_capped_at_k(graph, roots):
    for r in roots:
        full = oracle.bfs_levels(graph, r)
        for k in (1, 2, 3):
            want = np.where(full <= k, full, -1)
            got = oracle.bfs_levels(graph, r, max_depth=k)
            assert np.array_equal(got, want)


def test_dijkstra_matches_dijkstra_reference(graph, csr, roots):
    for r in roots[:3]:
        want = dijkstra_reference(csr, r).astype(np.float64)
        got = oracle.dijkstra(graph, r)
        assert oracle.distance_gap(got, want) < 1e-6


def test_bellman_ford_float64_is_dijkstra(graph, roots):
    for r in roots[:3]:
        assert oracle.distance_gap(oracle.bellman_ford(graph, r),
                                   oracle.dijkstra(graph, r)) < 1e-12


def test_bfloat16_control_is_far_from_dijkstra(graph, roots):
    for r in roots[:3]:
        gap = oracle.distance_gap(
            oracle.bellman_ford(graph, r, ml_dtypes.bfloat16),
            oracle.dijkstra(graph, r))
        assert gap > 1e-3


def test_distance_gap_is_relative_and_zero_is_exact():
    ref = np.array([0.0, 1e-6, 0.5, np.inf])
    assert oracle.distance_gap(ref.copy(), ref) == 0.0
    got = ref * (1 + 1e-7)
    assert oracle.distance_gap(got, ref) == pytest.approx(1e-7)
    got = ref.copy()
    got[0] = 1e-30
    assert oracle.distance_gap(got, ref) > 1e6
    got = ref.copy()
    got[3] = 2.0
    assert oracle.distance_gap(got, ref) == np.inf


def test_parent_faults_catch_broken_trees(graph, csr, roots):
    r = roots[0]
    d, p = bfs_traditional(csr, r)
    bad = p.copy()
    v = int(np.nonzero(d == 2)[0][0])
    bad[v] = r                              # root is not v's neighbour
    assert oracle.parent_faults(graph, r, d, bad) >= 1
    bad = p.copy()
    bad[r] = -1
    assert oracle.parent_faults(graph, r, d, bad) >= 1


def test_sssp_parent_tightness(graph, csr, roots):
    from repro.core.formats import build_slimsell
    from repro.core.sssp import sssp_parents
    r = roots[1]
    dist = oracle.dijkstra(graph, r).astype(np.float32)
    tiled = build_slimsell(csr, C=8, L=128, sigma=csr.n).to_jax()
    p = np.asarray(sssp_parents(tiled, dist, r))
    ref = oracle.dijkstra(graph, r)
    assert oracle.parent_faults(graph, r, ref, p, dist=dist, atol=1e-6,
                                rtol=1e-6) == 0
    wrong = dist.copy()
    wrong[p != r] *= 1.01
    assert oracle.parent_faults(graph, r, ref, p, dist=wrong, atol=1e-6,
                                rtol=1e-6) > 0


def test_traversed_edges_is_graph500_accounting(graph, csr, roots):
    r = roots[0]
    d = oracle.bfs_levels(graph, r)
    assert oracle.traversed_edges(graph, d >= 0) == \
        max(1, int(csr.deg[d >= 0].sum()) // 2)


def test_components_size_bfs_reach(graph, roots):
    labels, sizes = oracle.components(graph)
    for r in roots:
        assert (oracle.bfs_levels(graph, r) >= 0).sum() == sizes[labels[r]]
