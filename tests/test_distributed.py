"""Multi-device tests (subprocess with forced host devices): 2D BFS, MoE EP,
sharded LM train step on a small mesh."""
import pytest

from conftest import run_multidevice


def test_dist_bfs_all_semirings_8dev():
    run_multidevice("""
import numpy as np, jax
from repro.core.dist_bfs import make_mesh
from repro.graphs.generators import kronecker
from repro.core.dist_bfs import partition_slimsell, make_dist_bfs
from repro.core.bfs_traditional import bfs_traditional
csr = kronecker(8, 8, seed=3)
root = int(np.argmax(csr.deg))
d_ref, _ = bfs_traditional(csr, root)
mesh = make_mesh((4, 2), ("data", "model"))
dist = partition_slimsell(csr, R=4, Co=2, C=8, L=16)
for srn in ["tropical", "real", "boolean", "selmax"]:
    fn = make_dist_bfs(mesh, dist, srn, max_iters=64)
    d, it = fn(dist.cols, dist.row_block, dist.row_vertex, np.int32(root))
    assert np.array_equal(np.asarray(d), d_ref), srn
print("PASS")
""")


def test_dist_bfs_multipod_axes():
    run_multidevice("""
import numpy as np, jax
from repro.core.dist_bfs import make_mesh
from repro.graphs.generators import erdos_renyi
from repro.core.dist_bfs import partition_slimsell, make_dist_bfs
from repro.core.bfs_traditional import bfs_traditional
csr = erdos_renyi(128, 6, seed=1)
d_ref, _ = bfs_traditional(csr, 0)
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
dist = partition_slimsell(csr, R=4, Co=2, C=4, L=8)
fn = make_dist_bfs(mesh, dist, "tropical", row_axes=("pod", "data"),
                   col_axes=("model",), max_iters=64)
d, it = fn(dist.cols, dist.row_block, dist.row_vertex, np.int32(0))
assert np.array_equal(np.asarray(d), d_ref)
print("PASS")
""")


def test_moe_ep_matches_reference_4dev():
    run_multidevice("""
import jax, jax.numpy as jnp
from repro.core.dist_bfs import make_mesh
from repro.models import moe as moe_lib
mesh = make_mesh((2, 2), ("data", "model"))
dims = moe_lib.MoEDims(n_experts=8, top_k=2, d_model=16, d_ff=32,
                       cap_factor=4.0)
ks = jax.random.split(jax.random.PRNGKey(0), 5)
x = jax.random.normal(ks[0], (4, 8, 16))
wr = jax.random.normal(ks[1], (16, 8)) * 0.1
wig = jax.random.normal(ks[2], (8, 16, 32)) * 0.1
wiu = jax.random.normal(ks[3], (8, 16, 32)) * 0.1
wo = jax.random.normal(ks[4], (8, 32, 16)) * 0.1
y_ref = moe_lib.moe_reference(x, wr, wig, wiu, wo, dims)
with jax.set_mesh(mesh):
    y_ep = moe_lib.moe_ep_train(x, wr, wig, wiu, wo, dims, mesh,
                                dp=("data",), tp="model", fsdp=("data",))
    y_dec = moe_lib.moe_ep_decode(x[:, :1], wr, wig, wiu, wo, dims, mesh,
                                  dp=("data",), tp="model", fsdp=("data",))
assert jnp.allclose(y_ep, y_ref, atol=1e-4)
assert jnp.allclose(y_dec, moe_lib.moe_reference(x[:, :1], wr, wig, wiu, wo,
                                                 dims), atol=1e-4)
print("PASS")
""", n_devices=4)


def test_sharded_lm_train_step_matches_single_device():
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.dist_bfs import make_mesh
from repro.models import transformer as tf
from repro.models.sharding import AxisRules
cfg = tf.LMConfig(name="t", n_layers=2, d_model=32, n_heads=4, n_kv=2,
                  d_head=8, d_ff=64, vocab=128, dtype=jnp.float32,
                  q_chunk=16, kv_chunk=16)
params = tf.init_params(cfg, jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
l0 = tf.loss_fn(params, batch, cfg, None)
mesh = make_mesh((2, 2), ("data", "model"))
ctx = tf.ShardCtx(mesh=mesh, rules=AxisRules.for_mesh(mesh))
with jax.set_mesh(mesh):
    l1 = jax.jit(lambda p, b: tf.loss_fn(p, b, cfg, ctx))(params, batch)
assert abs(float(l0) - float(l1)) < 1e-3, (float(l0), float(l1))
print("PASS")
""", n_devices=4)


def test_context_parallel_attention_matches_single_device():
    """Arch with heads not divisible by tp -> context-parallel path."""
    run_multidevice("""
import jax, jax.numpy as jnp
from repro.core.dist_bfs import make_mesh
from repro.models import transformer as tf
from repro.models.sharding import AxisRules
cfg = tf.LMConfig(name="t", n_layers=2, d_model=30, n_heads=3, n_kv=3,
                  d_head=10, d_ff=64, vocab=128, dtype=jnp.float32,
                  q_chunk=16, kv_chunk=16)
params = tf.init_params(cfg, jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
l0 = tf.loss_fn(params, batch, cfg, None)
mesh = make_mesh((2, 2), ("data", "model"))
ctx = tf.ShardCtx(mesh=mesh, rules=AxisRules.for_mesh(mesh))
assert tf._attn_mode(cfg, ctx) == "context"
with jax.set_mesh(mesh):
    l1 = jax.jit(lambda p, b: tf.loss_fn(p, b, cfg, ctx))(params, batch)
assert abs(float(l0) - float(l1)) < 1e-3, (float(l0), float(l1))
print("PASS")
""", n_devices=4)
