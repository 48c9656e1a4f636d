"""Pallas TPU kernel: embedding-bag over a SlimSell-style padded bag layout.

DLRM's hot path (kernel_taxonomy §RecSys). JAX has no native EmbeddingBag;
this kernel implements it TPU-natively: the bag index matrix uses SlimSell's
-1-padding convention, indices live in SMEM, and each table row slice is
pulled HBM -> VMEM with an explicit ``make_async_copy`` (the table never fits
VMEM: MLPerf tables reach 40M rows). The jnp oracle is ref.embedding_bag_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.registry import KernelCase, kernel_contract
from repro.core.options import resolve_interpret


def _bag_kernel(bags_ref, table_ref, out_ref, scratch_ref, sem, *,
                b_blk: int, K: int, d_tile: int, mode: str):
    dt = pl.program_id(1)

    def one_bag(b, _):
        def one_slot(k, acc_cnt):
            acc, cnt = acc_cnt
            idx = bags_ref[b, k]
            safe = jnp.maximum(idx, 0)
            cp = pltpu.make_async_copy(
                table_ref.at[pl.ds(safe, 1), pl.ds(dt * d_tile, d_tile)],
                scratch_ref, sem)
            cp.start()
            cp.wait()
            row = scratch_ref[0]
            valid = idx >= 0
            acc = acc + jnp.where(valid, row, jnp.zeros_like(row))
            return acc, cnt + valid.astype(jnp.float32)

        acc, cnt = jax.lax.fori_loop(
            0, K, one_slot, (jnp.zeros((d_tile,), out_ref.dtype),
                             jnp.zeros((), jnp.float32)))
        if mode == "mean":
            acc = acc / jnp.maximum(cnt, 1.0)
        out_ref[pl.ds(b, 1), :] = acc[None]
        return 0

    jax.lax.fori_loop(0, b_blk, one_bag, 0)


def bag_grid_spec(B, K, d, b_blk, d_tile, dtype):
    """The embedding-bag grid contract, shared by the wrapper and its
    registered contract cases. The table operand lives in ANY memory space
    (pulled HBM -> VMEM manually) and has no block map."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(B // b_blk, d // d_tile),
        in_specs=[
            pl.BlockSpec((b_blk, K), lambda b, dt: (b, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((b_blk, d_tile), lambda b, dt: (b, dt)),
        scratch_shapes=[
            pltpu.VMEM((1, d_tile), dtype),
            pltpu.SemaphoreType.DMA,
        ],
    )


def _bag_cases():
    B, K, d, b_blk, d_tile = 16, 3, 8, 8, 4
    return [KernelCase(
        name="embedding_bag/demo",
        grid_spec=bag_grid_spec(B, K, d, b_blk, d_tile, jnp.float32),
        scalar_args=(),
        in_shapes=[(B, K), None],   # table block is ANY-space: no index map
        out_shapes=[(B, d)],
        chunked_out=[("out", 0)],   # visited once each — trivially contiguous
    )]


@kernel_contract(_bag_cases)
@functools.partial(jax.jit, static_argnames=("mode", "b_blk", "d_tile",
                                             "interpret"))
def embedding_bag_pallas(table, bags, *, mode: str = "sum", b_blk: int = 8,
                         d_tile: int = 128, interpret=None):
    """table f32[V, d], bags int32[B, K] (-1 pads) -> [B, d]."""
    interpret = resolve_interpret(interpret)
    V, d = table.shape
    B, K = bags.shape
    d_tile = min(d_tile, d)
    assert d % d_tile == 0 and B % b_blk == 0, (d, d_tile, B, b_blk)
    grid_spec = bag_grid_spec(B, K, d, b_blk, d_tile, table.dtype)
    kernel = functools.partial(_bag_kernel, b_blk=b_blk, K=K, d_tile=d_tile,
                               mode=mode)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, d), table.dtype),
        interpret=interpret,
    )(bags, table)
