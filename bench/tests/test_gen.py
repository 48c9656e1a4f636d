"""The benchmark's Graph500 generator: deterministic, Graph500's quadrant
probabilities, symmetric CSR with the least weight of each pair, the same
graph as the one-sort CSR program it replaced, weighted or not."""
import jax
import jax.numpy as jnp
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


def _csr_before(src, dst, w, *, n):
    """The CSR program before it became two sorts of the m pairs and a
    scatter: one sort of the m pairs, then one of all 2m directed entries.
    The reference that every configuration's graph has to equal."""
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


@pytest.mark.parametrize("weighted", (True, False))
@pytest.mark.parametrize("scale,graph_seed", ((4, 1), (7, 5), (10, 1),
                                              (12, 2**33 + 3)))
def test_csr_is_bit_identical_to_the_one_sort_program(scale, graph_seed,
                                                      weighted):
    """Edges, weights and CSR in one program give the arrays that the
    separate edge, weight and one-sort CSR programs gave; without a weight
    range the same structure, with no weights."""
    edges = gen.rmat_edges(scale, 16, **SPEC, graph_seed=graph_seed)
    w = jax.random.uniform(gen.random_key(2), edges[0].shape, jnp.float32,
                           0.0, 1.0)
    v, ww, indptr = jax.jit(_csr_before, static_argnames="n")(
        *edges, w, n=1 << scale)
    nnz = int(indptr[-1])
    g = (build(scale, graph_seed=graph_seed, weight_seed=2) if weighted
         else gen.build_graph(scale=scale, edge_factor=16, **SPEC,
                              graph_seed=graph_seed))
    assert g.indptr.dtype == np.int64 and g.indices.dtype == np.int32
    np.testing.assert_array_equal(g.indptr, np.asarray(indptr))
    np.testing.assert_array_equal(g.indices, np.asarray(v[:nnz]))
    if weighted:
        assert g.weights.dtype == np.float32
        np.testing.assert_array_equal(g.weights, np.asarray(ww[:nnz]))
    else:
        assert g.weights is None


def test_a_configuration_without_a_weight_range_is_unweighted():
    cfg = {"graph": dict(scale=8, edge_factor=16, **SPEC, graph_seed=1)}
    plain = gen.from_config(cfg)
    cfg["graph"].update(WEIGHTS, weight_seed=2)
    weighted = gen.from_config(cfg)
    assert plain.weights is None and weighted.weights is not None
    np.testing.assert_array_equal(plain.indptr, weighted.indptr)
    np.testing.assert_array_equal(plain.indices, weighted.indices)
