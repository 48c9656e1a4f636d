"""The batched parent transform (``bfs.dp_transform``): every column of
a batch in one tile-grouped sweep.

Its parents must be bit-identical to the per-column formulas it replaced
(``_levels_parents`` and ``_tight_parents`` below, one whole-graph gather
per column), at widths 1 to 16, on graphs with padding slots, unreachable
vertices, isolated roots and zero-weight edges; and a served batch of the
same width must reuse one compiled program.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bfs import dp_transform
from repro.core.formats import build_csr, build_slimsell
from repro.core.sssp import sssp_parents
from repro.graphs.generators import kronecker, two_components, with_random_weights
from repro.serving import GraphSession

scipy_graph = pytest.importorskip("scipy.sparse.csgraph")
from scipy.sparse import csr_matrix  # noqa: E402

#: isolated vertices appended to every family
ISOLATED = 2


def _levels_parents(tiled, d, root):
    """One column of the BFS DP transform, as a whole-graph gather."""
    pad = tiled.cols < 0
    safe = jnp.where(pad, 0, tiled.cols)
    d_nbr = jnp.take(d, safe, axis=0)
    rv_tile = jnp.take(tiled.row_vertex, tiled.row_block, axis=0)
    d_row = jnp.take(d, jnp.where(rv_tile < 0, 0, rv_tile), axis=0)[:, :, None]
    ok = (~pad) & (d_row > 0) & (d_nbr == d_row - 1) & (d_nbr >= 0)
    tile_red = jnp.where(ok, safe + 1, 0).max(axis=-1)
    y_blocks = jax.ops.segment_max(tile_red, tiled.row_block,
                                   num_segments=tiled.n_chunks)
    rv = tiled.row_vertex.reshape(-1)
    p1 = jax.ops.segment_max(y_blocks.reshape(-1), jnp.where(rv < 0, tiled.n, rv),
                             num_segments=tiled.n + 1)[: tiled.n]
    return (p1.astype(jnp.int32) - 1).at[root].set(root)


def _tight_parents(tiled, dist, root, rtol=1e-6, atol=1e-6):
    """One column of the weighted DP transform, as a whole-graph gather."""
    n = tiled.n
    pad = tiled.cols < 0
    safe = jnp.where(pad, 0, tiled.cols)
    d_nbr = jnp.take(dist, safe, axis=0) + tiled.wts
    rv_tile = jnp.take(tiled.row_vertex, tiled.row_block, axis=0)
    d_row = jnp.take(dist, jnp.where(rv_tile < 0, 0, rv_tile), axis=0)[:, :, None]
    tight = (~pad) & jnp.isfinite(d_row) \
        & (jnp.abs(d_nbr - d_row) <= atol + rtol * jnp.abs(d_row))
    score = jnp.where(tight, (safe + 1).astype(jnp.float32)
                      + jnp.where(tiled.wts > 0, float(n), 0.0), 0.0)
    y_blocks = jax.ops.segment_max(score.max(axis=-1), tiled.row_block,
                                   num_segments=tiled.n_chunks)
    rv = tiled.row_vertex.reshape(-1)
    p1 = jax.ops.segment_max(y_blocks.reshape(-1), jnp.where(rv < 0, n, rv),
                             num_segments=n + 1)[:n]
    p1 = jnp.where(p1 > n, p1 - n, p1)
    return (p1.astype(jnp.int32) - 1).at[root].set(root)


def _with_isolated(csr):
    """The same edges over ``ISOLATED`` more vertices, which have none."""
    u = np.repeat(np.arange(csr.n, dtype=np.int64), np.diff(csr.indptr))
    edges = np.stack([u, csr.indices.astype(np.int64)], axis=1)
    return build_csr(edges, csr.n + ISOLATED)


def _path(n):
    return build_csr(np.stack([np.arange(n - 1), np.arange(1, n)], axis=1), n)


FAMILIES = {
    "kron": lambda: kronecker(7, 8, seed=3),
    "path": lambda: _path(50),
    "disconnected": lambda: two_components(5, 6, seed=0),
}


def _graph(family, weighted):
    csr = _with_isolated(FAMILIES[family]())
    if weighted:
        # integer weights in {0, 1, 2}: zero-weight edges and tied paths
        csr = with_random_weights(csr, low=0.0, high=3.0, integer=True,
                                  seed=7)
    # L = 32 leaves padding slots in most tiles
    return csr, build_slimsell(csr, C=8, L=32).to_jax()


def _distances(csr, roots, weighted):
    A = csr_matrix((csr.weights if weighted
                    else np.ones(csr.indices.size), csr.indices, csr.indptr),
                   shape=(csr.n, csr.n))
    D = scipy_graph.dijkstra(A, indices=roots, directed=True,
                             unweighted=not weighted).T        # [n, B]
    if weighted:
        return D.astype(np.float32)
    return np.where(np.isfinite(D), D, -1).astype(np.int32)


@pytest.mark.parametrize("width", [1, 3, 16])
@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("weighted", [False, True], ids=["bfs", "sssp"])
def test_batched_parents_match_the_per_column_formula(weighted, family, width):
    csr, tiled = _graph(family, weighted)
    rng = np.random.default_rng(width)
    roots = rng.integers(0, csr.n - ISOLATED, width).astype(np.int32)
    roots[-1] = csr.n - 1                  # an isolated root in every batch
    D = _distances(csr, roots, weighted)
    assert (D == (np.inf if weighted else -1)).any()   # unreachable vertices
    assert bool((tiled.cols < 0).any())                # padding slots
    if weighted:
        assert (csr.weights == 0).any()
        batched, column = sssp_parents, _tight_parents
    else:
        batched, column = dp_transform, _levels_parents

    got = np.asarray(batched(tiled, jnp.asarray(D), roots))
    want = np.stack([np.asarray(column(tiled, jnp.asarray(D[:, b]), roots[b]))
                     for b in range(width)])
    assert got.shape == (width, csr.n) and got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    # one column passed alone goes through the same code
    np.testing.assert_array_equal(
        np.asarray(batched(tiled, jnp.asarray(D[:, 0]), roots[0])), want[0])


@contextlib.contextmanager
def _compiles():
    """Executables JAX builds or loads inside the block."""
    events = []

    def on_event(name, secs, **kwargs):
        if name == "/jax/core/compile/backend_compile_duration":
            events.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield events
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


@pytest.mark.parametrize("algorithm", ["bfs", "sssp"])
def test_harvests_of_one_width_reuse_one_transform_program(algorithm):
    # a vertex count no other test uses, so the first harvest traces anew
    csr = with_random_weights(_with_isolated(kronecker(6, 4, seed=5)), seed=1)
    sess = GraphSession(csr, max_batch=4)
    batches = ([1, 2, 3, 4], [5, 6, 7, 8])

    def harvest(roots):
        if algorithm == "bfs":
            return sess.bfs_many(roots, need_parents=True)
        return sess.sssp(roots, need_parents=True)

    before = dp_transform._cache_size()
    first = harvest(batches[0])
    traced = dp_transform._cache_size()
    with _compiles() as compiled:
        second = harvest(batches[1])
    assert traced == before + 1
    assert dp_transform._cache_size() == traced
    assert compiled == []
    for roots, results in zip(batches, (first, second)):
        for r, res in zip(roots, results):
            assert res.parents is not None and res.parents[r] == r
