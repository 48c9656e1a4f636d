"""Fault tolerance: atomic checkpoints, resume equivalence, elastic reshard."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.core.dist_bfs import make_mesh
from repro.launch import train as train_launch


def test_save_restore_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": [jnp.ones((2,)), {"c": jnp.zeros((5,), jnp.int32)}]}
    checkpoint.save(str(tmp_path), 3, tree, metadata={"step": 3})
    out, meta = checkpoint.restore(str(tmp_path), 3, tree)
    assert meta["step"] == 3
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_gc_and_latest(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    for s in (1, 2, 3, 4, 5):
        checkpoint.save(str(tmp_path), s, tree, keep=2)
    assert checkpoint.latest_step(str(tmp_path)) == 5
    assert sorted(os.listdir(tmp_path)) == ["step_0000000004",
                                            "step_0000000005"]


def test_interrupted_write_is_invisible(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    checkpoint.save(str(tmp_path), 1, tree)
    # simulate a crash mid-write: tmp dir exists without manifest
    os.makedirs(tmp_path / "step_0000000002.tmp")
    (tmp_path / "step_0000000002.tmp" / "x.npy").write_bytes(b"junk")
    assert checkpoint.latest_step(str(tmp_path)) == 1
    out, _ = checkpoint.restore(str(tmp_path), 1, tree)
    assert np.asarray(out["x"]).shape == (2,)


def test_train_resume_equivalence(tmp_path):
    """train 6 steps == train 3 + kill + resume 3 (same data stream)."""
    args = ["--arch", "smollm-135m", "--reduced", "--batch", "2",
            "--seq", "16", "--log-every", "100"]
    full = train_launch.main(args + ["--steps", "6"])
    part1 = train_launch.main(args + ["--steps", "3", "--ckpt-dir",
                                      str(tmp_path), "--ckpt-every", "3"])
    part2 = train_launch.main(args + ["--steps", "6", "--ckpt-dir",
                                      str(tmp_path), "--ckpt-every", "100",
                                      "--resume"])
    np.testing.assert_allclose(full[3:], part2, rtol=1e-4)


def test_elastic_reshard_roundtrip(tmp_path):
    """Checkpoint written replicated, restored under a different sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    checkpoint.save(str(tmp_path), 1, tree)
    mesh = make_mesh((1,), ("data",))
    shardings = {"w": NamedSharding(mesh, P("data", None))}
    out, _ = checkpoint.reshard(str(tmp_path), 1, tree, shardings)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.arange(16.0).reshape(4, 4))
    assert out["w"].sharding == shardings["w"]
