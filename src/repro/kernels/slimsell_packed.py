"""Pallas TPU kernels: SlimSell-B word-wise packed-boolean sweeps.

The boolean semiring moves one reachability *bit* per 32-bit lane element;
SlimSell-B packs 32 of them into each uint32 word (``core.packing``) and
sweeps word-wise, so the memory traffic of a boolean sweep shrinks by the
packing factor. Two kernels share the SlimSell tile structure (SlimChunk
revisit accumulation + SlimWork scalar-prefetch grid indirection) with the
scalar kernels:

* ``slimsell_spmv_packed_pallas`` — single-source: the frontier is a packed
  bitmap ``uint32[ceil(n/32)]`` pinned in VMEM as ``[rows, 128]`` words
  (32x smaller than the lane frontier, DMA'd once per call). Each column
  slot loads the row of words holding its bit, selects the word's lane and
  extracts the bit in-register — the packed twin of the paper's CMP+BLEND
  implicit-``val`` derivation; still nothing stored per edge.
  The per-row OR over column slots lands in the usual [chunk_blk, C]
  output block; the wrapper re-packs vertex space.
* ``slimsell_spmm_packed_pallas`` — multi-source: B roots become
  ``ceil(B/32)`` packed *planes*; the RHS is ``uint32[n, Wb]`` and one
  sweep ORs whole words (32 roots per lane element) instead of 32 separate
  lane columns. add = word-wise OR, mul = word-wise AND with the all-ones
  implicit edge word (a no-op, derived in-register).

Both kernels register their grid contracts (``@kernel_contract``) over the
same demo layout as the scalar kernels, so the contract checker proves the
index maps, lockstep and SlimChunk-contiguity properties of the packed
grids too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.registry import KernelCase, demo_layout, kernel_contract
from repro.core import packing
from repro.core.options import resolve_interpret
from . import sweep


def _spmv_packed_kernel(steps_ref, chunks_ref, live_ref, cols_ref, x_ref,
                        acc_ref, out_ref, *, chunk_blk: int):
    """One grid step = one SlimSell tile over the packed frontier bitmap:
    each column slot loads the row of words holding its bit, picks the
    word's lane and extracts the bit in-register."""
    t = pl.program_id(0)
    row = sweep.open_block(t, chunks_ref, acc_ref, out_ref, chunk_blk)
    _, C, L = cols_ref.shape

    @pl.when(t < live_ref[0])
    def _work():
        def slot_value(col, c, l):
            safe = jnp.maximum(col, 0)
            bit = packing.bit_of(safe).astype(jnp.uint32)
            word = jnp.where(col >= 0, packing.word_of(safe), -1)
            return sweep.lane_select(
                x_ref, word, 0,
                lambda w: ((w >> bit) & jnp.uint32(1)).astype(jnp.int32))

        accs = sweep.reduce_slots(cols_ref, C, L, slot_value, jnp.maximum,
                                  0, sweep.LANES, jnp.int32)
        red = sweep.pack_rows([a.max(axis=-1, keepdims=True) for a in accs],
                              C, jnp.int32)              # OR of 0/1 bits
        cur = out_ref[pl.ds(row, 1), :]
        out_ref[pl.ds(row, 1), :] = jnp.maximum(cur, red)


def spmv_packed_grid_spec(S, C, L, w_shape, chunk_blk):
    """The packed-SpMV grid contract, shared by the wrapper and its
    registered cases. Identical tile/output structure to the scalar SpMV;
    only the frontier operand shrinks to the packed word rows."""
    block = pl.BlockSpec((chunk_blk, C),
                         lambda t, st, ch, lv: (ch[t] // chunk_blk, 0))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[sweep.tile_spec(C, L, lambda t, st, ch, lv: (st[t], 0, 0)),
                  sweep.resident_spec(w_shape, lambda t, st, ch, lv: (0, 0)),
                  block],
        out_specs=block,
    )


def _spmv_packed_cases():
    d = demo_layout()
    T, C, L, cb = d["T"], d["C"], d["L"], d["chunk_blk"]
    out_shape = (d["n_blk"] * cb, C)
    return [KernelCase(
        name=f"spmv_packed/{scen}",
        grid_spec=spmv_packed_grid_spec(T, C, L, d["x_rows"], cb),
        scalar_args=(ids, d["row_block"][ids], n_active),
        in_shapes=[(T, C, L), d["x_rows"], out_shape],
        out_shapes=[out_shape],
        lockstep=[(("in", 2), ("out", 0))],
        chunked_out=[("out", 0)],
    ) for scen, ids, n_active in d["scenarios"]]


@kernel_contract(_spmv_packed_cases)
@functools.partial(jax.jit, static_argnames=("chunk_blk", "n_chunks",
                                             "interpret"))
def slimsell_spmv_packed_pallas(cols, tile_ids, row_block, n_active, x_words,
                                *, n_chunks: int, chunk_blk: int = 8,
                                interpret=None):
    """Tile-level packed-boolean SpMV. Returns y_blocks int32[n_chunks_pad, C]
    (chunk-row space, 0/1 hits; the ops wrapper re-packs vertex space).

    cols:      int32[T, C, L]
    tile_ids:  int32[T]  grid order (SlimWork compaction; tail repeats last)
    row_block: int32[T]  owning chunk per tile
    n_active:  int32[1]  number of live grid steps
    x_words:   uint32[ceil(n/32)] packed frontier bitmap
    """
    interpret = resolve_interpret(interpret)
    T, C, L = cols.shape
    n_blk = -(-n_chunks // chunk_blk)
    out_shape = (n_blk * chunk_blk, C)
    w_rows = sweep.as_rows(x_words, 0)
    limit = sweep.vmem_limit(
        "slimsell_spmv_packed_pallas", [(w_rows.shape, jnp.uint32)],
        [((chunk_blk, C), jnp.int32)] * 2)
    call = pl.pallas_call(
        functools.partial(_spmv_packed_kernel, chunk_blk=chunk_blk),
        grid_spec=spmv_packed_grid_spec(sweep.segment_length(T), C, L,
                                        w_rows.shape, chunk_blk),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.int32),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit),
        interpret=interpret,
    )
    init = jnp.zeros(out_shape, jnp.int32)
    return sweep.run_segments(call, tile_ids, row_block, n_active, init,
                              (cols, w_rows))


def _spmm_packed_kernel(steps_ref, chunks_ref, live_ref, cols_ref, x_ref,
                        acc_ref, out_ref, *, chunk_blk: int):
    """One grid step = one SlimSell tile of the packed-plane SpMM: the RHS
    rows are uint32 words (32 roots each); OR accumulates whole words.
    The implicit edge value is the all-ones word, so mul (AND) is a no-op,
    derived in-register — the packed CMP+BLEND analogue."""
    t = pl.program_id(1)
    row = sweep.open_block(t, chunks_ref, acc_ref, out_ref, chunk_blk)
    _, C, L = cols_ref.shape
    dt = x_ref.shape[-1]

    @pl.when(t < live_ref[0])
    def _work():
        def slot_value(col, c, l):
            v = x_ref[pl.ds(jnp.maximum(col, 0), 1), :]         # [1, dt]
            return jnp.where(col >= 0, v, jnp.uint32(0))

        accs = sweep.reduce_slots(cols_ref, C, L, slot_value,
                                  jnp.bitwise_or, 0, dt, jnp.uint32)
        red = jnp.concatenate(accs, axis=0)[None]              # [1, C, dt]
        cur = out_ref[pl.ds(row, 1)]
        out_ref[pl.ds(row, 1)] = jnp.bitwise_or(cur, red)


def spmm_packed_grid_spec(S, C, L, n, d, d_tile, chunk_blk):
    """The packed-SpMM grid contract. Grid (d // d_tile, S): the tile axis
    is LAST so SlimChunk revisits stay contiguous within each word tile."""
    block = pl.BlockSpec(
        (chunk_blk, C, d_tile),
        lambda dt, t, st, ch, lv: (ch[t] // chunk_blk, 0, dt))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(d // d_tile, S),
        in_specs=[sweep.tile_spec(C, L,
                                  lambda dt, t, st, ch, lv: (st[t], 0, 0)),
                  sweep.resident_spec((n, d_tile),
                                      lambda dt, t, st, ch, lv: (0, dt)),
                  block],
        out_specs=block,
    )


def _spmm_packed_cases():
    d = demo_layout()
    T, C, L, cb = d["T"], d["C"], d["L"], d["chunk_blk"]
    n, width, d_tile = d["n_pad"], 2, 1   # 2 word planes: exercises revisit
    out_shape = (d["n_blk"] * cb, C, width)
    return [KernelCase(
        name=f"spmm_packed/{scen}",
        grid_spec=spmm_packed_grid_spec(T, C, L, n, width, d_tile, cb),
        scalar_args=(ids, d["row_block"][ids], n_active),
        in_shapes=[(T, C, L), (n, width), out_shape],
        out_shapes=[out_shape],
        lockstep=[(("in", 2), ("out", 0))],
        chunked_out=[("out", 0)],
    ) for scen, ids, n_active in d["scenarios"]]


@kernel_contract(_spmm_packed_cases)
@functools.partial(jax.jit, static_argnames=("chunk_blk", "n_chunks",
                                             "interpret"))
def slimsell_spmm_packed_pallas(cols, tile_ids, row_block, n_active, X_words,
                                *, n_chunks: int, chunk_blk: int = 8,
                                interpret=None):
    """Tile-level packed-plane SpMM. Returns y_blocks uint32[n_chunks_pad,
    C, Wb] (chunk-row space).

    cols:      int32[T, C, L]
    tile_ids:  int32[T]  grid order (SlimWork compaction; tail repeats last)
    row_block: int32[T]  owning chunk per tile
    n_active:  int32[1]  number of live grid steps
    X_words:   uint32[n_pad, Wb] packed frontier planes (Wb = ceil(B/32))
    """
    interpret = resolve_interpret(interpret)
    T, C, L = cols.shape
    n, d = X_words.shape
    d_pad, d_tile = sweep.lane_tile(d)
    X_words = jnp.pad(X_words, ((0, 0), (0, d_pad - d)))
    n_blk = -(-n_chunks // chunk_blk)
    out_shape = (n_blk * chunk_blk, C, d_pad)
    block = (chunk_blk, C, d_tile)
    limit = sweep.vmem_limit(
        "slimsell_spmm_packed_pallas", [((n, d_tile), jnp.uint32)],
        [(block, jnp.uint32)] * 2)
    call = pl.pallas_call(
        functools.partial(_spmm_packed_kernel, chunk_blk=chunk_blk),
        grid_spec=spmm_packed_grid_spec(sweep.segment_length(T), C, L, n,
                                        d_pad, d_tile, chunk_blk),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.uint32),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit),
        interpret=interpret,
    )
    init = jnp.zeros(out_shape, jnp.uint32)
    out = sweep.run_segments(call, tile_ids, row_block, n_active, init,
                             (cols, X_words))
    return out[..., :d]
