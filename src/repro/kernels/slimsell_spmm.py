"""Pallas TPU kernel: SlimSell SpMM — the matrix-RHS generalization.

Beyond-paper extension (DESIGN.md §2): the paper's SpMV gathers scalars
``x[col]``; SpMM gathers *rows* ``X[col, :]``. The SlimSell tile stays the
(C, L) column-index block, read slot by slot as scalars from SMEM; each
slot loads the frontier row ``X[col, lane tile]`` with a dynamic sublane
slice (the lane dimension is the RHS column axis, d_tile = 128 or the
whole width below it; wider widths are padded to whole lane tiles), and the
(C, d_tile) output accumulates in VMEM across the SlimChunk tiles of a
chunk. The workloads sharing the kernel:

* GNN aggregation — d = feature width, real semiring == sum aggregation;
  ``ops.spmm(weighted=True)`` derives GCN's sym-norm slot weight
  rsqrt(deg[row]) * rsqrt(deg[col]) and sweeps it as the stored weight.
* batched multi-source BFS (Graph500) — d = number of concurrent roots, any
  of the four semirings; one kernel sweep advances every root's frontier.
* batched multi-source SSSP — d = number of concurrent roots under min-plus
  with a *stored* weight block (``wts``, SlimSell-W) riding the cols block's
  scalar-prefetch indirection; one kernel sweep relaxes every root's
  distance column.

**SlimWork** is the same scalar-prefetch grid *indirection* as the SpMV
kernel: the wrapper compacts active tile ids into ``tile_ids`` (inactive tail
repeats the last active id); repeated ids map to the same blocks, so skipped
steps issue no DMA and ``pl.when`` skips their compute.

Per-device use at scale: the mesh partitions vertices into column ranges
(core/dist_bfs.py), so the VMEM-resident X block is the local column shard.
The X block is lane-padded to 128 in VMEM, so the VMEM bound admits about
2^17 rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.registry import KernelCase, demo_layout, kernel_contract
from repro.core.options import resolve_interpret
from . import sweep
from .slimsell_spmv import semiring_ops, _weighted_contrib


def _spmm_kernel(steps_ref, chunks_ref, live_ref, cols_ref, *refs,
                 sr_name: str, chunk_blk: int, stored: bool):
    """One grid step = one SlimSell tile of the SpMM: each column slot
    loads the frontier row ``X[col, lane tile]`` and the chunk row
    accumulates it. When ``stored`` (SlimSell-W), ``refs`` leads with the
    slot-weight block — mapped in lockstep with ``cols``, so SlimWork's
    grid indirection also skips the weight DMA — and each edge contributes
    ``mul(w, X[col, :])`` (``w + X[col, :]`` under min-plus, one batched
    relaxation).
    """
    wts_ref = refs[0] if stored else None
    x_ref, acc_ref, out_ref = refs[-3:]
    add, contrib_fn, zero = semiring_ops(sr_name)
    t = pl.program_id(1)
    row = sweep.open_block(t, chunks_ref, acc_ref, out_ref, chunk_blk)
    _, C, L = cols_ref.shape
    dt = x_ref.shape[-1]

    @pl.when(t < live_ref[0])
    def _work():
        def slot_value(col, c, l):
            v = x_ref[pl.ds(jnp.maximum(col, 0), 1), :]          # [1, dt]
            v = _weighted_contrib(sr_name, wts_ref[0, c, l].astype(v.dtype),
                                  v) if stored else contrib_fn(v)
            return jnp.where(col >= 0, v, jnp.asarray(zero, v.dtype))

        accs = sweep.reduce_slots(cols_ref, C, L, slot_value, add, zero, dt,
                                  x_ref.dtype)
        red = jnp.concatenate(accs, axis=0)                      # [C, dt]
        cur = out_ref[pl.ds(row, 1)]
        out_ref[pl.ds(row, 1)] = add(cur, red[None])


def spmm_grid_spec(S, C, L, n, d, d_tile, chunk_blk, stored):
    """The SpMM grid contract, shared by the wrapper and its registered
    contract cases. Grid is (d // d_tile, S): the tile axis is LAST (varies
    fastest), so SlimChunk revisits stay contiguous within each lane tile."""
    tile = sweep.tile_spec(C, L, lambda dt, t, st, ch, lv: (st[t], 0, 0))
    block = pl.BlockSpec(
        (chunk_blk, C, d_tile),
        lambda dt, t, st, ch, lv: (ch[t] // chunk_blk, 0, dt))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(d // d_tile, S),
        in_specs=[tile] + ([tile] if stored else []) + [
            sweep.resident_spec((n, d_tile),
                                lambda dt, t, st, ch, lv: (0, dt)),
            block,
        ],
        out_specs=block,
    )


def _spmm_cases():
    d = demo_layout()
    T, C, L, cb = d["T"], d["C"], d["L"], d["chunk_blk"]
    n, width, d_tile = d["n_pad"], 8, 4  # 2 lane tiles: exercises the revisit
    out_shape = (d["n_blk"] * cb, C, width)
    cases = []
    for scen, ids, n_active in d["scenarios"]:
        for stored in (False, True):
            in_shapes = [(T, C, L)] + ([(T, C, L)] if stored else []) \
                + [(n, width), out_shape]
            lock = [(("in", 0), ("in", 1))] if stored else []
            cases.append(KernelCase(
                name=f"spmm/{scen}" + ("/wts" if stored else ""),
                grid_spec=spmm_grid_spec(T, C, L, n, width, d_tile, cb, stored),
                scalar_args=(ids, d["row_block"][ids], n_active),
                in_shapes=in_shapes,
                out_shapes=[out_shape],
                lockstep=lock + [(("in", len(in_shapes) - 1), ("out", 0))],
                chunked_out=[("out", 0)],
            ))
    return cases


@kernel_contract(_spmm_cases)
@functools.partial(jax.jit, static_argnames=("sr_name", "chunk_blk", "n_chunks",
                                             "interpret"))
def slimsell_spmm_pallas(cols, tile_ids, row_block, n_active, X, *,
                         sr_name: str, n_chunks: int, chunk_blk: int = 8,
                         interpret=None, wts=None):
    """Tile-level SpMM.  Returns y_blocks [n_chunks_pad, C, d] (chunk-row
    space; chunks no active tile visits hold the semiring zero).

    cols:      int32[T, C, L]
    tile_ids:  int32[T]  grid order (SlimWork compaction; tail repeats last)
    row_block: int32[T]  owning chunk per tile
    n_active:  int32[1]  number of live grid steps
    X:         RHS [n_pad, d]
    wts:       optional float32[T, C, L] stored slot weights (SlimSell-W),
               block-mapped in lockstep with ``cols`` — the same tile
               indirection as the weighted SpMV kernel, so SlimWork
               skipping also skips the weight DMA
    """
    interpret = resolve_interpret(interpret)
    T, C, L = cols.shape
    n, d = X.shape
    stored = wts is not None
    zero = semiring_ops(sr_name)[2]
    d_pad, d_tile = sweep.lane_tile(d)
    X = jnp.pad(X, ((0, 0), (0, d_pad - d)),
                constant_values=jnp.asarray(zero, X.dtype))
    n_blk = -(-n_chunks // chunk_blk)
    out_shape = (n_blk * chunk_blk, C, d_pad)
    block = (chunk_blk, C, d_tile)
    limit = sweep.vmem_limit("slimsell_spmm_pallas",
                             [((n, d_tile), X.dtype)],
                             [(block, X.dtype)] * 2)
    operands = (cols,) + ((wts,) if stored else ()) + (X,)
    call = pl.pallas_call(
        functools.partial(_spmm_kernel, sr_name=sr_name,
                          chunk_blk=chunk_blk, stored=stored),
        grid_spec=spmm_grid_spec(sweep.segment_length(T), C, L, n, d_pad,
                                 d_tile, chunk_blk, stored),
        out_shape=jax.ShapeDtypeStruct(out_shape, X.dtype),
        input_output_aliases={3 + len(operands): 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit),
        interpret=interpret,
    )
    init = jnp.full(out_shape, zero, X.dtype)
    out = sweep.run_segments(call, tile_ids, row_block, n_active, init,
                             operands)
    return out[..., :d]
