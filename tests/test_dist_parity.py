"""Distributed-vs-single-device parity for the engine's 2D strategy.

Every FixpointSpec that runs on one device must produce identical results
over the 2D partition: 4 semirings × push/pull/auto × single- and
multi-source BFS, plus SSSP and CC, on small (data × model) meshes and the
repo's test graph families. Subprocesses force host devices so the main
pytest process keeps its single-device view.
"""
from conftest import run_multidevice

_PRELUDE = """
import numpy as np, jax, jax.numpy as jnp
from repro.core.dist_bfs import make_mesh
from repro.graphs.generators import (erdos_renyi, kronecker, star,
                                     two_components, with_random_weights)
from repro.core.dist_bfs import (partition_slimsell, make_dist_bfs,
                                 make_dist_multi_bfs, make_dist_sssp,
                                 make_dist_multi_sssp, make_dist_cc)
from repro.core.bfs_traditional import bfs_traditional
from repro.core.formats import build_slimsell
"""


def test_dist_bfs_parity_semirings_x_directions():
    """4 semirings x 3 directions on a 4x2 mesh match the queue oracle."""
    run_multidevice(_PRELUDE + """
csr = kronecker(8, 8, seed=3)
root = int(np.argmax(csr.deg))
d_ref, _ = bfs_traditional(csr, root)
mesh = make_mesh((4, 2), ("data", "model"))
dist = partition_slimsell(csr, R=4, Co=2, C=8, L=16)
deg = jnp.asarray(dist.deg, jnp.int32)
for srn in ["tropical", "real", "boolean", "selmax"]:
    for dirn in ["push", "pull", "auto"]:
        fn = make_dist_bfs(mesh, dist, srn, max_iters=64, direction=dirn)
        args = (dist.cols, dist.row_block, dist.row_vertex)
        if dirn == "auto":
            args += (deg,)
        d, it = fn(*args, np.int32(root))
        assert np.array_equal(np.asarray(d), d_ref), (srn, dirn)
print("PASS")
""")


def test_dist_multi_source_parity():
    """Batched distributed BFS: every column matches its own single-source
    oracle, for all semirings and directions."""
    run_multidevice(_PRELUDE + """
csr = erdos_renyi(128, 6, seed=1)
roots = np.asarray([0, 5, 17, 101], np.int32)
refs = np.stack([bfs_traditional(csr, int(r))[0] for r in roots])
mesh = make_mesh((2, 2), ("data", "model"))
dist = partition_slimsell(csr, R=2, Co=2, C=4, L=8)
deg = jnp.asarray(dist.deg, jnp.int32)
for srn in ["tropical", "real", "boolean", "selmax"]:
    for dirn in ["push", "pull", "auto"]:
        fn = make_dist_multi_bfs(mesh, dist, srn, max_iters=64,
                                 direction=dirn)
        args = (dist.cols, dist.row_block, dist.row_vertex)
        if dirn == "auto":
            args += (deg,)
        d, it = fn(*args, roots)
        assert np.array_equal(np.asarray(d), refs), (srn, dirn)
print("PASS")
""")


def test_dist_sssp_parity():
    """Distributed delta-stepping matches Dijkstra and the single-device
    engine (same sweeps/buckets — the flattened phase machine is shared)."""
    run_multidevice(_PRELUDE + """
from repro.core.sssp import sssp, dijkstra_reference, default_delta
for seed, fam in [(3, "kron"), (1, "er")]:
    csr = with_random_weights(
        kronecker(8, 8, seed=seed) if fam == "kron"
        else erdos_renyi(128, 6, seed=seed), seed=seed + 10)
    root = int(np.argmax(csr.deg))
    d_ref = dijkstra_reference(csr, root)
    tiled = build_slimsell(csr, C=8, L=16).to_jax()
    single = sssp(tiled, root)
    mesh = make_mesh((4, 2), ("data", "model"))
    dist = partition_slimsell(csr, R=4, Co=2, C=8, L=16)
    fn = make_dist_sssp(mesh, dist, max_iters=512)
    d, sweeps, buckets = fn(dist.cols, dist.row_block, dist.row_vertex,
                            dist.wts, np.int32(root),
                            np.float32(single.delta))
    assert np.allclose(np.asarray(d), d_ref, rtol=1e-5, atol=1e-5), fam
    assert int(sweeps) == single.sweeps and int(buckets) == single.buckets
    # Bellman-Ford degeneration on the mesh
    d, sweeps, buckets = fn(dist.cols, dist.row_block, dist.row_vertex,
                            dist.wts, np.int32(root), np.float32(np.inf))
    assert np.allclose(np.asarray(d), d_ref, rtol=1e-5, atol=1e-5)
    assert int(buckets) == 1
print("PASS")
""")


def test_dist_multi_sssp_parity():
    """Distributed batched multi-source SSSP over the column-sharded
    distance matrix: every column matches Dijkstra and the single-device
    batched engine (same per-column sweeps/buckets — the per-column phase
    machines are shared), on both local-sweep backends, with a batch width
    the 128-lane tile does not divide."""
    run_multidevice(_PRELUDE + """
from repro.core.sssp import dijkstra_reference
from repro.core.multi_sssp import multi_source_sssp
csr = with_random_weights(kronecker(8, 8, seed=3), seed=13)
tiled = build_slimsell(csr, C=8, L=16).to_jax()
roots = np.asarray([0, 5, 17, 101, 33], np.int32)   # 5: odd batch width
single = multi_source_sssp(tiled, roots)
mesh = make_mesh((4, 2), ("data", "model"))
dist = partition_slimsell(csr, R=4, Co=2, C=8, L=16)
for backend in ["jnp", "pallas"]:
    fn = make_dist_multi_sssp(mesh, dist, max_iters=512, backend=backend)
    d, it, sweeps, buckets = fn(dist.cols, dist.row_block, dist.row_vertex,
                                dist.wts, roots, np.float32(single.delta))
    assert np.array_equal(np.asarray(d), single.distances), backend
    assert np.array_equal(np.asarray(sweeps), single.sweeps), backend
    assert np.array_equal(np.asarray(buckets), single.buckets), backend
for i, r in enumerate(roots):
    d_ref = dijkstra_reference(csr, int(r))
    f = np.isfinite(d_ref)
    assert np.allclose(single.distances[i][f], d_ref[f], rtol=1e-5,
                       atol=1e-5)
    assert (np.isfinite(single.distances[i]) == f).all()
# batched Bellman-Ford degeneration on the mesh
fn = make_dist_multi_sssp(mesh, dist, max_iters=512)
d, it, sweeps, buckets = fn(dist.cols, dist.row_block, dist.row_vertex,
                            dist.wts, roots, np.float32(np.inf))
bf = multi_source_sssp(tiled, roots, delta=np.inf)
assert np.array_equal(np.asarray(d), bf.distances)
assert (np.asarray(buckets) == 1).all()
print("PASS")
""")


def test_dist_cc_parity():
    """Distributed label propagation: same canonical labels as the
    single-device engine, including across disconnected components."""
    run_multidevice(_PRELUDE + """
from repro.core.cc import cc
for csr in [two_components(6, 6, seed=5), star(64),
            erdos_renyi(96, 2, seed=4)]:
    ref = cc(build_slimsell(csr, C=4, L=8).to_jax())
    mesh = make_mesh((2, 2), ("data", "model"))
    dist = partition_slimsell(csr, R=2, Co=2, C=4, L=8)
    fn = make_dist_cc(mesh, dist)
    lab, it = fn(dist.cols, dist.row_block, dist.row_vertex)
    assert np.array_equal(np.asarray(lab), ref.labels)
print("PASS")
""")


def test_dist_comm_modes_and_multipod_axes():
    """reduce_gather comm and 3D (pod, data, model) axes stay exact, and the
    pallas local-sweep backend agrees with jnp on the mesh."""
    run_multidevice(_PRELUDE + """
csr = erdos_renyi(128, 6, seed=1)
d_ref, _ = bfs_traditional(csr, 0)
mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
dist = partition_slimsell(csr, R=4, Co=2, C=4, L=8)
for comm in ["allreduce", "reduce_gather"]:
    fn = make_dist_bfs(mesh3, dist, "tropical", row_axes=("pod", "data"),
                       col_axes=("model",), max_iters=64, comm=comm)
    d, it = fn(dist.cols, dist.row_block, dist.row_vertex, np.int32(0))
    assert np.array_equal(np.asarray(d), d_ref), comm
mesh = make_mesh((4, 2), ("data", "model"))
for dirn in ["push", "pull"]:
    fn = make_dist_bfs(mesh, dist, "tropical", max_iters=64,
                       backend="pallas", direction=dirn)
    d, it = fn(dist.cols, dist.row_block, dist.row_vertex, np.int32(0))
    assert np.array_equal(np.asarray(d), d_ref), ("pallas", dirn)
# odd batch width: the kernels run it as one full-width lane tile
roots = np.asarray([0, 3, 9, 22, 41], np.int32)
refs = np.stack([bfs_traditional(csr, int(r))[0] for r in roots])
for dirn in ["push", "pull"]:
    fn = make_dist_multi_bfs(mesh, dist, "tropical", max_iters=64,
                             backend="pallas", direction=dirn)
    d, it = fn(dist.cols, dist.row_block, dist.row_vertex, roots)
    assert np.array_equal(np.asarray(d), refs), ("pallas multi", dirn)
print("PASS")
""")


def test_dist_packed_multi_bfs_parity():
    """SlimSell-B on the mesh: packed word planes shard along the batch
    axis, so the distributed packed multi-BFS must match both the lane
    distributed path and the single-device oracle — including a B=33 batch
    (half-empty second plane) on an n % 32 != 0 graph."""
    run_multidevice(_PRELUDE + """
csr = erdos_renyi(140, 5, seed=7)                      # tail word: 140 % 32
roots = np.asarray(sorted(np.random.default_rng(2).choice(
    csr.n, 33, replace=False)), np.int32)              # 33 -> 2 word planes
refs = np.stack([bfs_traditional(csr, int(r))[0] for r in roots])
mesh = make_mesh((2, 2), ("data", "model"))
dist = partition_slimsell(csr, R=2, Co=2, C=4, L=8)
lane = make_dist_multi_bfs(mesh, dist, "boolean", max_iters=64,
                           direction="push")
d_lane, it_lane = lane(dist.cols, dist.row_block, dist.row_vertex, roots)
packed = make_dist_multi_bfs(mesh, dist, "boolean", max_iters=64,
                             direction="push", packed=True,
                             batch_width=len(roots))
d_pk, it_pk = packed(dist.cols, dist.row_block, dist.row_vertex, roots)
assert np.array_equal(np.asarray(d_lane), refs)
assert np.array_equal(np.asarray(d_pk), np.asarray(d_lane))
assert int(it_pk) == int(it_lane)
print("PASS")
""")


def test_dist_pagerank_parity():
    """Distributed PageRank matches the single-device engine: same rank
    vector (up to tile-sum reassociation) and the same per-sweep L1
    residual history out of the resid_log ring, for two dampings through
    one traced compilation."""
    run_multidevice(_PRELUDE + """
from repro.core.dist_bfs import make_dist_pagerank
from repro.core.pagerank import pagerank
csr = kronecker(8, 8, seed=3)
tiled = build_slimsell(csr, C=4, L=8).to_jax()
mesh = make_mesh((2, 2), ("data", "model"))
dist = partition_slimsell(csr, R=2, Co=2, C=4, L=8)
fn = make_dist_pagerank(mesh, dist)
for damping in [0.85, 0.3]:
    single = pagerank(tiled, damping=damping, tol=1e-6)
    r, it, resid_log = fn(dist.cols, dist.row_block, dist.row_vertex,
                          np.float32(damping), np.float32(1e-6))
    assert int(it) == single.iterations, damping
    assert np.allclose(np.asarray(r), single.ranks, rtol=1e-5,
                       atol=1e-7), damping
    assert np.allclose(np.asarray(resid_log)[:int(it)], single.residuals,
                       rtol=1e-3, atol=1e-7), damping
print("PASS")
""")


def test_dist_brandes_parity():
    """Distributed Brandes (forward sigma/depth batch + dependency
    back-propagation) folds to the same betweenness scores as the
    single-device front door restricted to the same sources."""
    run_multidevice(_PRELUDE + """
from repro.core.dist_bfs import make_dist_brandes
from repro.core.betweenness import betweenness, brandes_accumulate
csr = erdos_renyi(96, 5, seed=2)
tiled = build_slimsell(csr, C=4, L=8).to_jax()
roots = np.asarray([0, 7, 23, 55, 80], np.int32)
single = betweenness(tiled, sources=roots)
mesh = make_mesh((2, 2), ("data", "model"))
dist = partition_slimsell(csr, R=2, Co=2, C=4, L=8)
fn = make_dist_brandes(mesh, dist)
delta, d, it_f, it_b = fn(dist.cols, dist.row_block, dist.row_vertex, roots)
scores = brandes_accumulate(np.asarray(delta), roots) / 2.0
assert np.allclose(scores, single.scores, rtol=1e-5, atol=1e-6)
print("PASS")
""")


def test_dist_khop_parity():
    """Distributed k-hop: the depth-capped boolean batch matches the
    single-device khop_many ball exactly, lane and packed."""
    run_multidevice(_PRELUDE + """
from repro.core.dist_bfs import make_dist_khop
from repro.core.khop import khop_many
csr = erdos_renyi(140, 5, seed=7)
tiled = build_slimsell(csr, C=4, L=8).to_jax()
roots = np.asarray([0, 9, 41, 77, 130], np.int32)
single = khop_many(tiled, roots, 2)
mesh = make_mesh((2, 2), ("data", "model"))
dist = partition_slimsell(csr, R=2, Co=2, C=4, L=8)
for packed in [False, True]:
    fn = make_dist_khop(mesh, dist, 2, packed=packed,
                        batch_width=len(roots) if packed else None)
    d, it = fn(dist.cols, dist.row_block, dist.row_vertex, roots)
    assert np.array_equal(np.asarray(d), single.distances), packed
    assert np.array_equal(np.asarray(d) >= 0, single.mask), packed
print("PASS")
""")


def test_dist_slimwork_push_mask_parity():
    """The per-shard push index (inc_src/inc_tile) must not change any
    result: masked push sweeps equal unmasked ones for single- and
    multi-source BFS, and compose with the packed boolean path."""
    run_multidevice(_PRELUDE + """
csr = kronecker(7, 8, seed=5)
root = int(np.argmax(csr.deg))
d_ref, _ = bfs_traditional(csr, root)
mesh = make_mesh((2, 2), ("data", "model"))
dist = partition_slimsell(csr, R=2, Co=2, C=4, L=8)
assert dist.inc_src is not None and dist.inc_tile is not None
sw_args = (dist.cols, dist.row_block, dist.row_vertex,
           dist.inc_src, dist.inc_tile)
for srn in ["tropical", "boolean"]:
    fn = make_dist_bfs(mesh, dist, srn, max_iters=64, direction="push",
                       slimwork=True)
    d, it = fn(*sw_args, np.int32(root))
    assert np.array_equal(np.asarray(d), d_ref), srn
roots = np.asarray([0, 9, 41, 77], np.int32)
refs = np.stack([bfs_traditional(csr, int(r))[0] for r in roots])
fn = make_dist_multi_bfs(mesh, dist, "boolean", max_iters=64,
                         direction="push", slimwork=True)
d, it = fn(*sw_args, roots)
assert np.array_equal(np.asarray(d), refs)
fn = make_dist_multi_bfs(mesh, dist, "boolean", max_iters=64,
                         direction="push", slimwork=True, packed=True,
                         batch_width=len(roots))
d, it = fn(*sw_args, roots)
assert np.array_equal(np.asarray(d), refs)
print("PASS")
""")
