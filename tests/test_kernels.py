"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs the jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import build_slimsell
from repro.graphs.generators import erdos_renyi, kronecker, star
from repro.kernels import ops, ref

SEMIRINGS = ["tropical", "real", "boolean", "selmax"]


def _frontier(sr, n, rng):
    x = jnp.asarray(rng.random(n), jnp.float32)
    if sr == "tropical":
        return jnp.where(jnp.asarray(rng.random(n)) < 0.2, x * 3, jnp.inf)
    if sr == "boolean":
        return (x > 0.5).astype(jnp.int32)
    return x


@pytest.mark.parametrize("semiring", SEMIRINGS)
@pytest.mark.parametrize("gen,C,L", [
    ("kron", 8, 32), ("kron", 8, 128), ("er", 16, 8), ("star", 8, 16),
])
def test_spmv_kernel_sweep(semiring, gen, C, L, rng):
    csr = {"kron": lambda: kronecker(8, 8, seed=4),
           "er": lambda: erdos_renyi(200, 6, seed=4),
           "star": lambda: star(100)}[gen]()
    tiled = build_slimsell(csr, C=C, L=L).to_jax()
    x = _frontier(semiring, csr.n, rng)
    y_k = ops.spmv(semiring, tiled, x)
    y_r = ref.spmv_ref(semiring, tiled, x)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-6)


@pytest.mark.parametrize("semiring", ["tropical", "real"])
def test_spmv_kernel_slimwork_mask(semiring, rng):
    csr = kronecker(8, 8, seed=6)
    tiled = build_slimsell(csr, C=8, L=32).to_jax()
    x = _frontier(semiring, csr.n, rng)
    for frac in (0.0, 0.3, 0.9, 1.0):
        tm = jnp.asarray(rng.random(tiled.n_tiles) >= frac)
        y_k = ops.spmv(semiring, tiled, x, tile_mask=tm)
        y_r = ref.spmv_ref(semiring, tiled, x, tile_mask=tm)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-6)


@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spmm_kernel_sweep(d, dtype, rng):
    csr = erdos_renyi(128, 6, seed=9)
    tiled = build_slimsell(csr, C=8, L=16).to_jax()
    X = jnp.asarray(rng.standard_normal((csr.n, d)), dtype)
    y_k = ops.spmm("real", tiled, X)
    y_r = ref.spmm_ref("real", tiled, X.astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_k, np.float32), np.asarray(y_r),
                               rtol=tol, atol=tol)


def test_spmm_kernel_weighted_gcn(rng):
    csr = erdos_renyi(96, 5, seed=10)
    tiled = build_slimsell(csr, C=8, L=16).to_jax()
    X = jnp.asarray(rng.standard_normal((csr.n, 128)), jnp.float32)
    deg = jnp.asarray(csr.deg, jnp.float32)
    y_k = ops.spmm("real", tiled, X, deg=deg, weighted=True)
    y_r = ref.spmm_ref("real", tiled, X, edge_weight=ref.gcn_edge_weight(deg))
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("V,d,B,K", [(500, 128, 16, 1), (1000, 128, 32, 8),
                                     (200, 256, 8, 4)])
@pytest.mark.parametrize("mode", ["sum", "mean"])
def test_embedding_bag_sweep(V, d, B, K, mode, rng):
    table = jnp.asarray(rng.standard_normal((V, d)), jnp.float32)
    bags = rng.integers(-1, V, size=(B, K)).astype(np.int32)
    bags[0, :] = -1  # fully-empty bag
    y_k = ops.embedding_bag(table, jnp.asarray(bags), mode=mode)
    y_r = ref.embedding_bag_ref(table, jnp.asarray(bags), mode=mode)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-6, atol=1e-6)


def test_spmv_kernel_grid_indirection_matches_dense_grid(rng):
    """SlimWork compaction must be a pure reordering: all-active mask ==
    no mask."""
    csr = kronecker(7, 8, seed=11)
    tiled = build_slimsell(csr, C=8, L=16).to_jax()
    x = _frontier("tropical", csr.n, rng)
    y0 = ops.spmv("tropical", tiled, x)
    y1 = ops.spmv("tropical", tiled, x,
                  tile_mask=jnp.ones(tiled.n_tiles, bool))
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))


@pytest.mark.parametrize("kernel", ["spmv", "pull", "spmm", "pull_mm",
                                    "spmv_packed", "spmm_packed"])
def test_segmented_grid_matches_single_call(kernel, rng, monkeypatch):
    """Tile lists longer than one pallas_call's grid run in segments that
    accumulate through the aliased output: a chunk straddling a segment
    boundary keeps its partial, and segments with no active step are
    skipped. Segmenting must not change a single bit."""
    from repro.core import packing
    from repro.kernels import sweep
    csr = kronecker(7, 8, seed=12)
    tiled = build_slimsell(csr, C=8, L=16).to_jax()
    n = csr.n
    x = _frontier("tropical", n, rng)
    X = jnp.stack([x, jnp.roll(x, 3)], axis=1)
    bits = jnp.asarray(rng.random(n) < 0.3)
    mask = jnp.asarray(rng.random(tiled.n_tiles) < 0.6)
    call = {
        "spmv": lambda: ops.spmv("tropical", tiled, x, tile_mask=mask),
        "pull": lambda: ops.pull("tropical", tiled, x, bits, tile_mask=mask),
        "spmm": lambda: ops.spmm("tropical", tiled, X, tile_mask=mask),
        "pull_mm": lambda: ops.pull_mm("tropical", tiled, X,
                                       jnp.stack([bits, ~bits], axis=1),
                                       tile_mask=mask),
        "spmv_packed": lambda: ops.spmv_packed(
            tiled, packing.pack_bits(bits), tile_mask=mask),
        "spmm_packed": lambda: ops.spmm_packed(
            tiled, packing.pack_bits(jnp.stack([bits, ~bits], axis=1)),
            tile_mask=mask),
    }[kernel]
    jax.clear_caches()
    whole = np.asarray(call())
    monkeypatch.setattr(sweep, "MAX_GRID_STEPS", 7)
    assert -(-tiled.n_tiles // sweep.segment_length(tiled.n_tiles)) > 2
    jax.clear_caches()
    try:
        segmented = np.asarray(call())
    finally:
        jax.clear_caches()
    np.testing.assert_array_equal(segmented, whole)
