"""The benchmark's Graph500 generator: deterministic, Graph500's quadrant
probabilities, symmetric CSR with the least weight of each pair."""
import numpy as np
import pytest

from bench import gen

SPEC = dict(a=0.57, b=0.19, c=0.19)
WEIGHTS = dict(weight_low=0.0, weight_high=1.0)


def build(scale=11, graph_seed=1, weight_seed=2, **abc):
    return gen.build_graph(scale=scale, edge_factor=16, **(abc or SPEC),
                           **WEIGHTS, graph_seed=graph_seed,
                           weight_seed=weight_seed)


def test_deterministic_per_seed():
    a, b = build(), build()
    assert np.array_equal(a.indptr, b.indptr)
    assert np.array_equal(a.indices, b.indices)
    assert np.array_equal(a.weights, b.weights)


def test_weight_seed_changes_weights_not_structure():
    a, b = build(weight_seed=2), build(weight_seed=3)
    assert np.array_equal(a.indices, b.indices)
    assert not np.array_equal(a.weights, b.weights)
    c = build(graph_seed=9)
    assert not np.array_equal(a.indices, c.indices)


def test_large_seeds_are_distinct():
    a = build(weight_seed=2**33 + 5)
    b = build(weight_seed=2**34 + 5)
    assert not np.array_equal(a.weights, b.weights)


def test_csr_is_simple_symmetric_and_sorted():
    g = build()
    u = np.repeat(np.arange(g.n), g.deg)
    v = g.indices.astype(np.int64)
    assert (u != v).all()
    key = u * g.n + v
    assert (np.diff(key) > 0).all()            # sorted, no duplicates
    rev = v * g.n + u
    idx = np.searchsorted(key, rev)
    assert np.array_equal(key[idx], rev)       # (v, u) for every (u, v)
    assert np.array_equal(g.weights[idx], g.weights)
    assert g.weights.min() >= 0.0 and g.weights.max() < 1.0


def test_quadrant_probabilities_are_graph500():
    src, dst = map(np.asarray, gen.rmat_edges(12, 16, **SPEC, graph_seed=3,
                                              permute=False))
    top = 1 << 11
    hi_s, hi_d = src >= top, dst >= top
    freq = [np.mean(~hi_s & ~hi_d), np.mean(~hi_s & hi_d),
            np.mean(hi_s & ~hi_d), np.mean(hi_s & hi_d)]
    assert freq == pytest.approx([0.57, 0.19, 0.19, 0.05], abs=0.003)


def _degree_profile(deg):
    return np.array([(deg == 0).mean(), np.log1p(deg).mean(),
                     np.log1p(deg.max())])


def test_host_kronecker_matches_this_generator_with_c_and_d_swapped():
    """The program's host ``kronecker`` draws the C and D quadrants the
    other way round (D = 0.19, C = 0.05): its degree profile matches this
    generator with c = 0.05, not the Graph500 one."""
    from repro.graphs.generators import kronecker
    host = np.mean([_degree_profile(kronecker(12, 16, seed=s).deg)
                    for s in range(3)], axis=0)
    swapped = np.mean([_degree_profile(build(12, graph_seed=s, a=0.57,
                                             b=0.19, c=0.05).deg)
                       for s in range(3)], axis=0)
    spec = np.mean([_degree_profile(build(12, graph_seed=s).deg)
                    for s in range(3)], axis=0)
    assert swapped == pytest.approx(host, rel=0.05, abs=0.005)
    assert abs(spec[0] - host[0]) > 0.1        # isolated share differs
