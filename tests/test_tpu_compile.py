"""Compile the main path for one TPU v5e chip, without the chip.

The TPU compiler is installed here and compiles for a described v5e chip:
what it refuses (unaligned blocks, ops Mosaic cannot lower, VMEM or HBM
overruns) it would refuse on the chip, and nothing has to run. Interpret
mode, which every other kernel test uses, sees none of that.

Shapes are those of the Graph500 scale-22 layout of
``examples/graph500_driver.py`` (C=8, L=128, sigma=n). The frontier of the
batched kernels is held in VMEM, so they compile at the largest frontier
their VMEM bound admits; past it every wrapper raises ``VmemBoundError``.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core import engine as eng
from repro.core.bfs import bfs_spec, dp_transform
from repro.core.cc import CC_SPEC
from repro.core.formats import SlimSellTiled
from repro.core.multi_bfs import multi_bfs_spec
from repro.core.multi_sssp import MULTI_SSSP_SPEC
from repro.core.sssp import sssp_parents
from repro.kernels import sweep
from repro.kernels.slimsell_packed import (slimsell_spmm_packed_pallas,
                                           slimsell_spmv_packed_pallas)
from repro.kernels.slimsell_pull import (slimsell_pull_mm_pallas,
                                         slimsell_pull_pallas)
from repro.kernels.slimsell_spmm import slimsell_spmm_pallas
from repro.kernels.slimsell_spmv import slimsell_spmv_pallas

SCALE = 22
N = 1 << SCALE
C, L, CB = 8, 128, 8
N_CHUNKS = N // C
#: tiles of the scale-22 layout (Kronecker seed 1, edge factor 16),
#: extrapolated from the layouts built on the chip runs: 35,935 tiles at
#: scale 18 and 144,593 at scale 20. 18 grid segments of at most
#: sweep.MAX_GRID_STEPS
T = 580_000
#: push-index (column, tile) pairs of that layout (31.2M at scale 20)
K = 125_000_000
#: widest frontier the batched (matrix-RHS) kernels admit: scale 17
N_BATCHED = 1 << 17
B = 8
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _grid_args(sharding):
    return (_shape(sharding, (T, C, L), jnp.int32),
            _shape(sharding, (T,), jnp.int32),
            _shape(sharding, (T,), jnp.int32),
            _shape(sharding, (1,), jnp.int32))


KERNELS = {
    # name: (wrapper, static kwargs, operand shapes after the grid
    # operands, keyword operand shapes)
    "spmv-tropical": (slimsell_spmv_pallas, dict(sr_name="tropical"),
                      [((N,), jnp.float32)], {}),
    "spmv-boolean": (slimsell_spmv_pallas, dict(sr_name="boolean"),
                     [((N,), jnp.int32)], {}),
    "spmv-minplus-weighted": (slimsell_spmv_pallas, dict(sr_name="minplus"),
                              [((N,), jnp.float32)],
                              {"wts": ((T, C, L), jnp.float32)}),
    "pull-tropical": (slimsell_pull_pallas, dict(sr_name="tropical"),
                      [((N_CHUNKS, C), jnp.int32), ((N,), jnp.float32)], {}),
    "spmm-tropical": (slimsell_spmm_pallas, dict(sr_name="tropical"),
                      [((N_BATCHED, B), jnp.float32)], {}),
    "spmm-minplus-wide": (slimsell_spmm_pallas, dict(sr_name="minplus"),
                          [((N_BATCHED // 2, 192), jnp.float32)],
                          {"wts": ((T, C, L), jnp.float32)}),
    "pull-mm-tropical": (slimsell_pull_mm_pallas, dict(sr_name="tropical"),
                         [((N_CHUNKS, C, B), jnp.int32),
                          ((N_BATCHED, B), jnp.float32)], {}),
    "spmv-packed": (slimsell_spmv_packed_pallas, {},
                    [((N // 32,), jnp.uint32)], {}),
    "spmm-packed": (slimsell_spmm_packed_pallas, {},
                    [((N_BATCHED, 1), jnp.uint32)], {}),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, kwargs, extra, kw_shapes = KERNELS[name]
    args = _grid_args(one_chip) + tuple(
        _shape(one_chip, s, d) for s, d in extra)
    kw = {k: _shape(one_chip, s, d) for k, (s, d) in kw_shapes.items()}
    compiled = jax.jit(lambda *a, **k: fn(
        *a, **k, n_chunks=N_CHUNKS, interpret=False, **kwargs)
    ).lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _scale22_layout(sharding, weighted: bool):
    s = lambda shape, dtype: _shape(sharding, shape, dtype)  # noqa: E731
    return SlimSellTiled(
        n=N, m_undirected=8 * N, C=C, L=L, sigma=N, n_chunks=N_CHUNKS,
        n_tiles=T, cols=s((T, C, L), jnp.int32), row_block=s((T,), jnp.int32),
        row_vertex=s((N_CHUNKS, C), jnp.int32), cl=s((N_CHUNKS,), jnp.int32),
        deg=s((N,), jnp.int32), inc_src=s((K,), jnp.int32),
        inc_tile=s((K,), jnp.int32), inc_ptr=s((N + 1,), jnp.int32),
        wts=s((T, C, L), jnp.float32) if weighted else None)


def _fits_hbm(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used


ENGINE_RUNS = {
    # name: (spec, batch width or None, ctx_args shapes) — single-source
    # BFS, one Graph500 batch of 8 roots, and the served SSSP and CC queries
    "bfs": (lambda: bfs_spec("tropical"), None, ()),
    "graph500-batch": (lambda: multi_bfs_spec("tropical"), B, ()),
    "sssp-batch": (lambda: MULTI_SSSP_SPEC, 2, ((),)),
    "cc": (lambda: CC_SPEC, None, ()),
}


@pytest.mark.parametrize("name", sorted(ENGINE_RUNS))
def test_jnp_engine_handle_compiles_for_v5e(one_chip, name):
    """The program the serving layer (and so ``run_graph500``) runs: the
    jnp engine's persistent fixpoint handle over the resident scale-22
    weighted layout fits one chip's HBM."""
    spec, width, ctx_shapes = ENGINE_RUNS[name]
    spec = spec()
    tiled = _scale22_layout(one_chip, weighted=True)
    direction = "auto" if "auto" in spec.directions else "push"
    handle = eng.fixpoint_handle(spec, max_iters=N, backend="jnp",
                                 direction=direction, batch_width=width,
                                 donate=True)
    ctx_args = tuple(_shape(one_chip, s, jnp.float32) for s in ctx_shapes)
    arg = _shape(one_chip, () if width is None else (width,), jnp.int32)
    ctx, state = jax.eval_shape(
        lambda t, a, c: (lambda ctx: (ctx, handle.init_state(t, a, ctx)))(
            handle.setup(t, c)), tiled, arg, ctx_args)
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: _shape(one_chip, x.shape, x.dtype), tree)
    _fits_hbm(handle._jitted.lower(tiled, on_chip(ctx),
                                   on_chip(state)).compile())


PARENT_TRANSFORMS = {
    # name: (transform, dtype of the distances)
    "bfs": (dp_transform, jnp.int32),
    "sssp": (sssp_parents, jnp.float32),
}


@pytest.mark.parametrize("name", sorted(PARENT_TRANSFORMS))
def test_parent_transform_compiles_for_v5e(one_chip, name):
    """Graph500 validation's parents: the batched transform of 8 columns
    at scale 22 (levels, or weighted distances) fits one chip's HBM."""
    fn, dtype = PARENT_TRANSFORMS[name]
    tiled = _scale22_layout(one_chip, weighted=True)
    _fits_hbm(jax.jit(fn).lower(
        tiled, _shape(one_chip, (N, B), dtype),
        _shape(one_chip, (B,), jnp.int32)).compile())


BOUND_CASES = {
    "spmv": (slimsell_spmv_pallas, dict(sr_name="tropical"),
             lambda n: [((n,), jnp.float32)]),
    "pull": (slimsell_pull_pallas, dict(sr_name="tropical"),
             lambda n: [((8, C), jnp.int32), ((n,), jnp.float32)]),
    "spmm": (slimsell_spmm_pallas, dict(sr_name="tropical"),
             lambda n: [((n, B), jnp.float32)]),
    "pull-mm": (slimsell_pull_mm_pallas, dict(sr_name="tropical"),
                lambda n: [((8, C, B), jnp.int32), ((n, B), jnp.float32)]),
    "spmv-packed": (slimsell_spmv_packed_pallas, {},
                    lambda n: [((n // 32,), jnp.uint32)]),
    "spmm-packed": (slimsell_spmm_packed_pallas, {},
                    lambda n: [((n, 1), jnp.uint32)]),
}

#: largest Graph500 scale each wrapper admits under the VMEM bound (8
#: roots for the batched kernels)
ADMITTED = {"spmv": 24, "pull": 24, "spmm": 17, "pull-mm": 17,
            "spmv-packed": 29, "spmm-packed": 17}


@pytest.mark.parametrize("name", sorted(BOUND_CASES))
def test_vmem_bound_error(name):
    fn, kwargs, extra = BOUND_CASES[name]

    def trace(scale):
        n = 1 << scale
        grid = [((8, C, L), jnp.int32), ((8,), jnp.int32),
                ((8,), jnp.int32), ((1,), jnp.int32)]
        args = [jax.ShapeDtypeStruct(s, d) for s, d in grid + extra(n)]
        return jax.eval_shape(lambda *a: fn(*a, n_chunks=8, interpret=False,
                                            **kwargs), *args)

    trace(ADMITTED[name])
    with pytest.raises(sweep.VmemBoundError,
                       match=f"VMEM bound of {sweep.VMEM_BOUND_BYTES} bytes"):
        trace(ADMITTED[name] + 1)
