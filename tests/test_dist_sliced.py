"""Optimized (slot-space sliced) distributed BFS vs oracle — the §Perf
BFS hillclimb implementation must stay exact."""
from conftest import run_multidevice


def test_sliced_bfs_matches_oracle_2d_and_3d():
    run_multidevice("""
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.core.dist_bfs import make_mesh
from repro.graphs.generators import kronecker
from repro.core.formats import sellcs_order
from repro.core.dist_bfs import partition_slimsell, make_dist_bfs_sliced
from repro.core.bfs_traditional import bfs_traditional

csr = kronecker(8, 8, seed=3)
root = int(np.argmax(csr.deg))
d_ref, _ = bfs_traditional(csr, root)
perm = sellcs_order(csr.deg, csr.n)
root_slot = int(np.nonzero(perm == root)[0][0])

mesh = make_mesh((2, 2), ("data", "model"))
dist = partition_slimsell(csr, R=2, Co=2, C=8, L=16, slot_space=True)
for dt in (jnp.float32, jnp.int16):
    fn = make_dist_bfs_sliced(mesh, dist, frontier_dtype=dt)
    d_slots, _ = fn(dist.cols, dist.row_block, np.int32(root_slot))
    d = np.full(csr.n, -1, np.int32)
    d[perm[:csr.n]] = np.asarray(d_slots).reshape(-1)[:csr.n]
    assert np.array_equal(d, d_ref), dt

# 3D: edges split over pods
mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
T = dist.t_max
half = (T + 1) // 2
cols3 = np.full((2, 2, 2, half, 8, 16), -1, np.int32)
rb3 = np.zeros((2, 2, 2, half), np.int32)
cols3[0, :, :, :T - T//2] = dist.cols[:, :, 0::2]
rb3[0, :, :, :T - T//2] = dist.row_block[:, :, 0::2]
cols3[1, :, :, :T//2] = dist.cols[:, :, 1::2]
rb3[1, :, :, :T//2] = dist.row_block[:, :, 1::2]
dist3 = dataclasses.replace(dist, cols=cols3, row_block=rb3, t_max=half)
fn = make_dist_bfs_sliced(mesh3, dist3, pod_axis="pod")
d_slots, _ = fn(dist3.cols, dist3.row_block, np.int32(root_slot))
d = np.full(csr.n, -1, np.int32)
d[perm[:csr.n]] = np.asarray(d_slots).reshape(-1)[:csr.n]
assert np.array_equal(d, d_ref)
print("PASS")
""")
