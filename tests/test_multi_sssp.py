"""Batched multi-source SSSP vs per-root delta-stepping vs scipy Dijkstra.

Covers the batched tentpole contract: ``multi_source_sssp`` rows are
bit-identical to the per-root ``sssp`` engine (distances AND per-column
sweep/bucket counts — the full-weight-operand scheduling argument in
``core/multi_sssp.py`` is exact, not approximate) and match the scipy
Dijkstra oracle across graph families × both backends × both engine modes;
per-column delta invariance; non-128-divisible batch widths through the
SpMM kernel's lane padding; batch splitting/padding; parent
validation; the batched Graph500 harness; and boundary errors.
"""
import numpy as np
import pytest

from repro.core.formats import build_csr, build_slimsell
from repro.core.multi_sssp import multi_source_sssp
from repro.core.sssp import sssp
from repro.graph500 import run_graph500_sssp, sample_roots, validate_sssp_tree
from repro.graphs.generators import (erdos_renyi, kronecker, ring_of_cliques,
                                     star, two_components, with_random_weights)

scipy_graph = pytest.importorskip("scipy.sparse.csgraph")
from scipy.sparse import csr_matrix  # noqa: E402

BACKENDS = ["jnp", "pallas"]
MODES = ["fused", "hostloop"]


def weighted_path(n: int, seed: int = 0):
    edges = np.stack([np.arange(n - 1), np.arange(1, n)], axis=1)
    return with_random_weights(build_csr(edges, n), low=0.5, high=3.0,
                               seed=seed)


FAMILIES = {
    "kron": lambda: with_random_weights(kronecker(8, 8, seed=3), seed=5),
    "er": lambda: with_random_weights(erdos_renyi(256, 4, seed=1), seed=2),
    "ring": lambda: with_random_weights(ring_of_cliques(10, 5), low=0.25,
                                        high=4.0, seed=7),
    "star": lambda: with_random_weights(star(100), seed=4),
    "path": lambda: weighted_path(64),
    "disconnected": lambda: with_random_weights(two_components(6, 6, seed=0),
                                                seed=9),
}


def scipy_dijkstra(csr, root):
    A = csr_matrix((csr.weights, csr.indices, csr.indptr),
                   shape=(csr.n, csr.n))
    return scipy_graph.dijkstra(A, indices=root, directed=True)


def layout(csr, L=32):
    return build_slimsell(csr, C=8, L=L).to_jax()


def roots_of(csr, k=3, seed=11):
    return sample_roots(csr, k, seed=seed)


def check_dist(d, d_ref):
    assert np.all(np.isfinite(d) == np.isfinite(d_ref))
    f = np.isfinite(d_ref)
    np.testing.assert_allclose(d[f], d_ref[f], rtol=1e-4, atol=1e-5)


# ------------------------------------------------- oracle + per-root parity


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("mode", MODES)
def test_matches_per_root_and_dijkstra(family, backend, mode):
    csr = FAMILIES[family]()
    tiled = layout(csr)
    roots = roots_of(csr)
    res = multi_source_sssp(tiled, roots, mode=mode, backend=backend)
    for i, r in enumerate(roots):
        per = sssp(tiled, int(r))
        # bit-identical to the per-root engine: distances AND schedule
        assert np.array_equal(res.distances[i], per.distances), (family, i)
        assert res.sweeps[i] == per.sweeps, (family, i)
        assert res.buckets[i] == per.buckets, (family, i)
        check_dist(res.distances[i], scipy_dijkstra(csr, int(r)))


def test_parents_are_tight_relaxations():
    csr = FAMILIES["kron"]()
    tiled = layout(csr)
    roots = roots_of(csr, k=3)
    res = multi_source_sssp(tiled, roots, need_parents=True)
    for i, r in enumerate(roots):
        validate_sssp_tree(csr, int(r), res.distances[i], res.parents[i],
                           d_ref=scipy_dijkstra(csr, int(r)))


# ----------------------------------------------------- per-column delta knob


@pytest.mark.parametrize("delta", [0.3, 1.0, np.inf])
def test_delta_invariance_per_column(delta):
    csr = FAMILIES["kron"]()
    tiled = layout(csr)
    roots = roots_of(csr, k=4)
    for mode in MODES:
        res = multi_source_sssp(tiled, roots, delta=delta, mode=mode)
        for i, r in enumerate(roots):
            per = sssp(tiled, int(r), delta=delta)
            assert np.array_equal(res.distances[i], per.distances), (mode, i)
            assert res.sweeps[i] == per.sweeps, (mode, delta, i)
            assert res.buckets[i] == per.buckets, (mode, delta, i)
            check_dist(res.distances[i], scipy_dijkstra(csr, int(r)))


def test_bellman_ford_single_bucket_every_column():
    tiled = layout(FAMILIES["er"]())
    res = multi_source_sssp(tiled, [0, 5, 17], delta=np.inf)
    assert (res.buckets == 1).all()


# -------------------------------------------------- batch widths / batching


def test_non_lane_divisible_batch_width_pallas():
    """B = 5 runs through the SpMM kernel as one full-width lane tile."""
    csr = FAMILIES["er"]()
    tiled = layout(csr)
    roots = roots_of(csr, k=5, seed=3)
    res = multi_source_sssp(tiled, roots, backend="pallas")
    for i, r in enumerate(roots):
        assert np.array_equal(res.distances[i],
                              sssp(tiled, int(r), backend="pallas").distances)


def test_batch_split_and_padding():
    """batch_size smaller than the root count splits into padded batches;
    padded columns (repeat-last-root) are dropped from the result."""
    csr = FAMILIES["kron"]()
    tiled = layout(csr)
    roots = roots_of(csr, k=5, seed=7)
    whole = multi_source_sssp(tiled, roots)
    split = multi_source_sssp(tiled, roots, batch_size=2)
    assert np.array_equal(whole.distances, split.distances)
    assert np.array_equal(whole.sweeps, split.sweeps)
    assert np.array_equal(whole.buckets, split.buckets)
    assert split.iterations.shape == (3,)  # ceil(5 / 2) batches


def test_duplicate_roots_allowed():
    csr = FAMILIES["kron"]()
    tiled = layout(csr)
    res = multi_source_sssp(tiled, [7, 7, 11])
    assert np.array_equal(res.distances[0], res.distances[1])


def test_work_log_shapes():
    csr = FAMILIES["kron"]()
    tiled = layout(csr)
    res = multi_source_sssp(tiled, roots_of(csr), log_work=True,
                            batch_size=2)
    assert res.work_log is not None and res.work_log.ndim == 2
    assert res.work_log.shape[0] == res.iterations.shape[0]


def test_hostloop_union_masks_match_fused():
    """The hostloop's unioned SlimWork tile gathering computes the same
    per-column schedule as the fused union masks."""
    csr = FAMILIES["ring"]()
    tiled = layout(csr)
    roots = roots_of(csr, k=4, seed=5)
    fused = multi_source_sssp(tiled, roots, mode="fused")
    host = multi_source_sssp(tiled, roots, mode="hostloop")
    assert np.array_equal(fused.distances, host.distances)
    assert np.array_equal(fused.sweeps, host.sweeps)
    assert np.array_equal(fused.buckets, host.buckets)
    assert host.iterations[0] == fused.iterations[0]


# --------------------------------------------------------------- harness


def test_graph500_sssp_batched_harness_validates():
    rep = run_graph500_sssp(scale=8, edge_factor=8, n_roots=6, seed=3,
                            batched=True, batch_size=3)
    assert rep.validated == 6 and rep.batched and rep.batch_size == 3
    assert np.isfinite(rep.teps).all() and (rep.teps > 0).all()
    assert "batch=3" in rep.summary()
    # per-root schedule metrics are preserved through the batched harness
    per = run_graph500_sssp(scale=8, edge_factor=8, n_roots=6, seed=3)
    assert np.array_equal(rep.sweeps, per.sweeps)
    assert np.array_equal(rep.buckets, per.buckets)


# ------------------------------------------------------------- boundaries


def test_unweighted_layout_rejected():
    tiled = build_slimsell(kronecker(6, 4, seed=0), C=8, L=32).to_jax()
    with pytest.raises(ValueError, match="weighted"):
        multi_source_sssp(tiled, [0, 1])


def test_negative_weights_rejected():
    csr = weighted_path(8)
    csr.weights = csr.weights.copy()
    csr.weights[0] = -1.0
    with pytest.raises(ValueError, match="non-negative"):
        multi_source_sssp(layout(csr), [0, 1])


def test_empty_roots_rejected():
    with pytest.raises(ValueError, match="at least one root"):
        multi_source_sssp(layout(weighted_path(8)), [])


def test_root_out_of_range_rejected():
    with pytest.raises(ValueError, match="out of range"):
        multi_source_sssp(layout(weighted_path(8)), [0, 99])


def test_bad_mode_and_batch_size_rejected():
    tiled = layout(weighted_path(8))
    with pytest.raises(ValueError, match="unknown mode"):
        multi_source_sssp(tiled, [0], mode="warp")
    with pytest.raises(ValueError, match="batch_size"):
        multi_source_sssp(tiled, [0, 1], batch_size=0)
