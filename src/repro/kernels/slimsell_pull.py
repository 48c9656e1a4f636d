"""Pallas TPU kernel: SlimSell bottom-up (pull) semiring sweep.

The direction-optimizing counterpart of ``slimsell_spmv.py`` (Beamer et al.,
paper §V discussion): instead of expanding the frontier outward, every *not
yet finalized* chunk row scans its own neighbor slots for a frontier member.
Two things distinguish it from the push kernel:

* a per-chunk-row ``nf`` (not-final) bitmap rides along with the output block;
  finalized rows are never recomputed — their slot stays at the semiring zero;
* **per-row early exit**: SlimChunk tiles of one chunk are visited in grid
  order and accumulate into the same output block, so before doing any work
  the kernel checks which rows are still *pending* (not final AND no hit
  accumulated from an earlier tile). Once every row of the chunk has found a
  parent, the remaining tiles of that chunk skip their gather+reduce entirely
  (``pl.when``). This is the algebraic analogue of bottom-up BFS's "stop
  scanning once a parent is found" — at tile rather than scalar granularity,
  matching the paper's vectorized framing.

Exactness contract: the early exit returns *a* semiring contribution per
pending row, not necessarily the full reduction. For BFS frontiers this is
exact-for-distances because frontier payloads are level-homogeneous (every
finite/nonzero input maps to the same distance); for sel-max it returns a
valid (possibly different) parent. The jnp path in ``core.spmv.slimsell_pull``
computes the full reduction and is the oracle for that contract.

SlimWork composes unchanged: the wrapper compacts active tile ids into
``tile_ids`` (scalar-prefetch grid indirection; inactive tail repeats the
last active id, so skipped steps issue no DMA). Frontier reads, segments
and the VMEM bound are the SpMV kernel's (``kernels/sweep.py``).

``slimsell_pull_mm_pallas`` is the **batched** (matrix-RHS) variant for
multi-source traversal: the frontier is [n, B], the not-final bitmap gains
a per-column axis, and the early exit happens per (chunk row, batch
column) — a (row, b) pair that has accumulated a hit stops contributing,
and a whole tile is skipped only once every pair it covers is final (the
batched analogue of "stop scanning once a parent is found"). The lane
dimension carries the batch (d_tile = 128), matching the SpMM kernel.
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
from .slimsell_spmv import _reduce_l, semiring_ops


def _pull_kernel(steps_ref, chunks_ref, live_ref, cols_ref, nf_ref, x_ref,
                 acc_ref, out_ref, *, sr_name: str, chunk_blk: int):
    add, contrib_fn, zero = semiring_ops(sr_name)
    t = pl.program_id(0)
    row = sweep.open_block(t, chunks_ref, acc_ref, out_ref, chunk_blk)
    _, C, L = cols_ref.shape
    cur = out_ref[pl.ds(row, 1), :]                           # [1, C]
    nf = nf_ref[pl.ds(row, 1), :]                             # [1, C] int32
    # pending == still needs a parent: not final and no hit from earlier tiles
    pending = (nf > 0) & (cur == jnp.asarray(zero, cur.dtype))

    @pl.when((t < live_ref[0]) & jnp.any(pending))
    def _work():
        accs = sweep.reduce_slots(
            cols_ref, C, L,
            lambda col, c, l: sweep.lane_select(x_ref, col, zero, contrib_fn),
            add, zero, sweep.LANES, x_ref.dtype)
        red = sweep.pack_rows([_reduce_l(sr_name, a)[:, None] for a in accs],
                              C, x_ref.dtype)
        out_ref[pl.ds(row, 1), :] = jnp.where(pending, add(cur, red), cur)


def pull_grid_spec(S, C, L, x_shape, chunk_blk):
    """The pull-sweep grid contract, shared by the wrapper and its
    registered contract cases. The not-final bitmap block and the
    running-partial input are mapped in lockstep with the output block
    (same chunk-row space)."""
    block = pl.BlockSpec((chunk_blk, C),
                         lambda t, st, ch, lv: (ch[t] // chunk_blk, 0))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[
            sweep.tile_spec(C, L, lambda t, st, ch, lv: (st[t], 0, 0)),
            block,
            sweep.resident_spec(x_shape, lambda t, st, ch, lv: (0, 0)),
            block,
        ],
        out_specs=block,
    )


def _pull_cases():
    d = demo_layout()
    T, C, L, cb = d["T"], d["C"], d["L"], d["chunk_blk"]
    nf_rows = d["n_blk"] * cb
    return [KernelCase(
        name=f"pull/{scen}",
        grid_spec=pull_grid_spec(T, C, L, d["x_rows"], cb),
        scalar_args=(ids, d["row_block"][ids], n_active),
        in_shapes=[(T, C, L), (nf_rows, C), d["x_rows"], (nf_rows, C)],
        out_shapes=[(nf_rows, C)],
        lockstep=[(("in", 1), ("out", 0)), (("in", 3), ("out", 0))],
        chunked_out=[("out", 0)],
    ) for scen, ids, n_active in d["scenarios"]]


@kernel_contract(_pull_cases)
@functools.partial(jax.jit, static_argnames=("sr_name", "chunk_blk", "n_chunks",
                                             "interpret"))
def slimsell_pull_pallas(cols, tile_ids, row_block, n_active, nf, x, *,
                         sr_name: str, n_chunks: int, chunk_blk: int = 8,
                         interpret=None):
    """Tile-level pull sweep.  Returns y_blocks [n_chunks_pad, C] (chunk-row
    space; chunks no active tile visits hold the semiring zero).

    cols:      int32[T, C, L]
    tile_ids:  int32[T]  grid order (SlimWork compaction; tail repeats last)
    row_block: int32[T]  owning chunk per tile
    n_active:  int32[1]  number of live grid steps
    nf:        int32[n_chunks, C]  1 where the row still needs a value
    x:         frontier [n_pad]
    """
    interpret = resolve_interpret(interpret)
    T, C, L = cols.shape
    n_blk = -(-n_chunks // chunk_blk)
    zero = semiring_ops(sr_name)[2]
    out_shape = (n_blk * chunk_blk, C)
    nf = jnp.pad(nf.astype(jnp.int32),
                 ((0, n_blk * chunk_blk - n_chunks), (0, 0)))
    x_rows = sweep.as_rows(x, zero)
    limit = sweep.vmem_limit(
        "slimsell_pull_pallas", [(x_rows.shape, x.dtype)],
        [((chunk_blk, C), jnp.int32)] + [((chunk_blk, C), x.dtype)] * 2)
    call = pl.pallas_call(
        functools.partial(_pull_kernel, sr_name=sr_name, chunk_blk=chunk_blk),
        grid_spec=pull_grid_spec(sweep.segment_length(T), C, L, x_rows.shape,
                                 chunk_blk),
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit),
        interpret=interpret,
    )
    init = jnp.full(out_shape, zero, x.dtype)
    return sweep.run_segments(call, tile_ids, row_block, n_active, init,
                              (cols, nf, x_rows))


# ----------------------------------------------------------- batched variant


def _pull_mm_kernel(steps_ref, chunks_ref, live_ref, cols_ref, nf_ref, x_ref,
                    acc_ref, out_ref, *, sr_name: str, chunk_blk: int):
    add, contrib_fn, zero = semiring_ops(sr_name)
    t = pl.program_id(1)
    row = sweep.open_block(t, chunks_ref, acc_ref, out_ref, chunk_blk)
    _, C, L = cols_ref.shape
    dt = x_ref.shape[-1]
    cur = out_ref[pl.ds(row, 1)]                         # [1, C, dt]
    nf = nf_ref[pl.ds(row, 1)]                           # [1, C, dt] int32
    # pending per (row, column): not final and no hit from earlier tiles
    pending = (nf > 0) & (cur == jnp.asarray(zero, cur.dtype))

    @pl.when((t < live_ref[0]) & jnp.any(pending))
    def _work():
        def slot_value(col, c, l):
            v = contrib_fn(x_ref[pl.ds(jnp.maximum(col, 0), 1), :])
            return jnp.where(col >= 0, v, jnp.asarray(zero, v.dtype))

        accs = sweep.reduce_slots(cols_ref, C, L, slot_value, add, zero, dt,
                                  x_ref.dtype)
        red = jnp.concatenate(accs, axis=0)[None]       # [1, C, dt]
        out_ref[pl.ds(row, 1)] = jnp.where(pending, add(cur, red), cur)


def pull_mm_grid_spec(S, C, L, n, B, d_tile, chunk_blk):
    """The batched pull-sweep grid contract, shared by the wrapper and its
    registered contract cases. As in the SpMM, the tile axis is the LAST
    grid dim; the per-column not-final block and the running partial ride
    the output block."""
    block = pl.BlockSpec(
        (chunk_blk, C, d_tile),
        lambda dt, t, st, ch, lv: (ch[t] // chunk_blk, 0, dt))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B // d_tile, S),
        in_specs=[
            sweep.tile_spec(C, L, lambda dt, t, st, ch, lv: (st[t], 0, 0)),
            block,
            sweep.resident_spec((n, d_tile),
                                lambda dt, t, st, ch, lv: (0, dt)),
            block,
        ],
        out_specs=block,
    )


def _pull_mm_cases():
    d = demo_layout()
    T, C, L, cb = d["T"], d["C"], d["L"], d["chunk_blk"]
    n, B, d_tile = d["n_pad"], 8, 4  # 2 lane tiles: exercises the revisit
    nf_rows = d["n_blk"] * cb
    return [KernelCase(
        name=f"pull_mm/{scen}",
        grid_spec=pull_mm_grid_spec(T, C, L, n, B, d_tile, cb),
        scalar_args=(ids, d["row_block"][ids], n_active),
        in_shapes=[(T, C, L), (nf_rows, C, B), (n, B), (nf_rows, C, B)],
        out_shapes=[(nf_rows, C, B)],
        lockstep=[(("in", 1), ("out", 0)), (("in", 3), ("out", 0))],
        chunked_out=[("out", 0)],
    ) for scen, ids, n_active in d["scenarios"]]


@kernel_contract(_pull_mm_cases)
@functools.partial(jax.jit, static_argnames=("sr_name", "chunk_blk",
                                             "n_chunks", "interpret"))
def slimsell_pull_mm_pallas(cols, tile_ids, row_block, n_active, nf, X, *,
                            sr_name: str, n_chunks: int, chunk_blk: int = 8,
                            interpret=None):
    """Batched tile-level pull sweep.  Returns [n_chunks_pad, C, B]
    (chunk-row space; chunks no active tile visits hold the semiring zero).

    cols:      int32[T, C, L]
    tile_ids:  int32[T]  grid order (SlimWork compaction; tail repeats last)
    row_block: int32[T]  owning chunk per tile
    n_active:  int32[1]  number of live grid steps
    nf:        int32[n_chunks, C, B]  1 where the (row, column) still needs
               a value
    X:         frontier matrix [n_pad, B]
    """
    interpret = resolve_interpret(interpret)
    T, C, L = cols.shape
    n, B = X.shape
    zero = semiring_ops(sr_name)[2]
    B_pad, d_tile = sweep.lane_tile(B)
    X = jnp.pad(X, ((0, 0), (0, B_pad - B)),
                constant_values=jnp.asarray(zero, X.dtype))
    n_blk = -(-n_chunks // chunk_blk)
    nf = jnp.pad(nf.astype(jnp.int32),
                 ((0, n_blk * chunk_blk - n_chunks), (0, 0), (0, B_pad - B)))
    out_shape = (n_blk * chunk_blk, C, B_pad)
    block = (chunk_blk, C, d_tile)
    limit = sweep.vmem_limit(
        "slimsell_pull_mm_pallas", [((n, d_tile), X.dtype)],
        [(block, jnp.int32)] + [(block, X.dtype)] * 2)
    call = pl.pallas_call(
        functools.partial(_pull_mm_kernel, sr_name=sr_name,
                          chunk_blk=chunk_blk),
        grid_spec=pull_mm_grid_spec(sweep.segment_length(T), C, L, n, B_pad,
                                    d_tile, chunk_blk),
        out_shape=jax.ShapeDtypeStruct(out_shape, X.dtype),
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit),
        interpret=interpret,
    )
    init = jnp.full(out_shape, zero, X.dtype)
    out = sweep.run_segments(call, tile_ids, row_block, n_active, init,
                             (cols, nf, X))
    return out[..., :B]
