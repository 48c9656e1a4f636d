"""Shared machinery of the SlimSell tile-sweep kernels.

Every SlimSell kernel (SpMV, SpMM, pull, pull-MM and the two packed ones)
sweeps the same grid: one step per SlimSell tile, output blocks in
chunk-row space, SlimChunk tiles of one chunk accumulating into the same
output block, SlimWork tile skipping by scalar-prefetch grid indirection.
This module holds what they share with each other and with the chip:

* **Segments.** Scalar-prefetch operands live in SMEM (1 MiB on v5e), so one
  ``pallas_call`` carries at most ``MAX_GRID_STEPS`` grid steps. Longer tile
  lists run as several calls over equal segments of the (compacted) step
  list. Each call aliases its output to the running result and a block's
  first visit in a call re-reads the running partial (``open_block``), so a
  chunk whose tiles straddle two segments accumulates across them. The
  running result starts at the semiring zero, so chunk blocks that no
  active tile visits come out as zero with no epilogue. Segments with no
  active step are skipped entirely.
* **Gather.** Mosaic lowers no general gather, so a tile reads its column
  ids as scalars from an SMEM block and loads the addressed frontier row
  with a dynamic sublane slice. A scalar frontier is laid out as
  ``[rows, 128]``: the row holds the value and a lane compare picks it out,
  accumulating per chunk row in a ``[1, 128]`` register that is
  lane-reduced once per tile (``reduce_slots``).
* **VMEM bound.** The frontier is VMEM-resident (DMA'd once per call, single
  buffered). ``vmem_limit`` computes a kernel's VMEM bytes from its buffer
  shapes, raises ``VmemBoundError`` past ``VMEM_BOUND_BYTES`` and otherwise
  returns the ``vmem_limit_bytes`` the kernel is compiled with.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8

#: grid steps per pallas_call: two int32[MAX_GRID_STEPS] scalar-prefetch
#: operands (tile id and chunk per step) take 256 KiB of v5e's 1 MiB SMEM
MAX_GRID_STEPS = 32768

#: v5e VMEM per TensorCore; a kernel's buffers may use up to the bound, the
#: rest is left to the compiler's own scratch
VMEM_CAPACITY_BYTES = 128 * 2**20
VMEM_BOUND_BYTES = 120 * 2**20


class VmemBoundError(ValueError):
    """A kernel's VMEM-resident buffers exceed ``VMEM_BOUND_BYTES``."""


def padded_bytes(shape, dtype) -> int:
    """VMEM footprint of an array: the last two dims padded to the
    (8, 128) tile of 32-bit words."""
    shape = (1,) * max(0, 2 - len(shape)) + tuple(int(s) for s in shape)
    *lead, a, b = shape
    itemsize = max(jnp.dtype(dtype).itemsize, 4)
    return (math.prod(lead) * -(-a // SUBLANES) * SUBLANES
            * -(-b // LANES) * LANES * itemsize)


def vmem_limit(kernel: str, resident, blocks) -> int:
    """``vmem_limit_bytes`` for a kernel: ``resident`` (shape, dtype) pairs
    are single-buffered, ``blocks`` pairs are double-buffered pipeline
    blocks. Raises ``VmemBoundError`` naming the bound when they do not
    fit; there is no fallback path."""
    need = sum(padded_bytes(s, d) for s, d in resident) \
        + 2 * sum(padded_bytes(s, d) for s, d in blocks)
    if need > VMEM_BOUND_BYTES:
        raise VmemBoundError(
            f"{kernel}: the VMEM-resident frontier and blocks need {need} "
            f"bytes, over the VMEM bound of {VMEM_BOUND_BYTES} bytes "
            f"(v5e has {VMEM_CAPACITY_BYTES}); this graph is too large for "
            f"the frontier-in-VMEM kernels — use backend='jnp'")
    return min(VMEM_CAPACITY_BYTES, max(need + 8 * 2**20, 32 * 2**20))


def resident_spec(shape, index_map):
    """BlockSpec of an operand held whole in VMEM for the whole call."""
    return pl.BlockSpec(shape, index_map, pipeline_mode=pl.Buffered(1))


def tile_spec(C: int, L: int, index_map):
    """BlockSpec of one SlimSell tile of scalars (column ids or slot
    weights), read slot by slot from SMEM."""
    return pl.BlockSpec((1, C, L), index_map, memory_space=pltpu.SMEM)


def lane_tile(d: int):
    """(padded width, lane tile) for a matrix RHS of width ``d``: widths up
    to one 128-lane tile run whole, wider ones are padded to a multiple of
    it (a block's minor dim must be the full width or a multiple of 128)."""
    if d <= LANES:
        return d, d
    return -(-d // LANES) * LANES, LANES


def as_rows(x, zero):
    """Lay a 1-D vector out as ``[rows, 128]`` (rows a multiple of 8),
    padded with ``zero`` — the layout ``reduce_slots`` gathers from."""
    n = x.shape[0]
    tile = SUBLANES * LANES
    x = jnp.pad(x, (0, -(-n // tile) * tile - n),
                constant_values=jnp.asarray(zero, x.dtype))
    return x.reshape(-1, LANES)


def open_block(t, chunks_ref, acc_ref, out_ref, chunk_blk: int):
    """Grid-step prologue shared by every tile kernel: on the first step of
    a run of steps over one output block, load the running partial of that
    block. Returns the chunk row of this step inside the block."""
    chunk = chunks_ref[t]
    prev = chunks_ref[jnp.maximum(t - 1, 0)]
    first_visit = (t == 0) | (chunk // chunk_blk != prev // chunk_blk)

    @pl.when(first_visit)
    def _init():
        out_ref[...] = acc_ref[...]

    return chunk % chunk_blk


def reduce_slots(cols_ref, C: int, L: int, slot_value, add, zero, width: int,
                 dtype):
    """Semiring-reduce the L column slots of each of the C chunk rows of
    the current tile. ``slot_value(col, c, l)`` returns the ``[1, width]``
    contribution of slot (c, l) — already the semiring zero where the slot
    is padding. Returns one ``[1, width]`` accumulator per chunk row."""
    unroll = math.gcd(L, 8)
    accs = []
    for c in range(C):
        def body(j, acc, c=c):
            for u in range(unroll):
                l = j * unroll + u
                acc = add(acc, slot_value(cols_ref[0, c, l], c, l))
            return acc
        accs.append(jax.lax.fori_loop(
            0, L // unroll, body,
            jnp.full((1, width), zero, dtype)))
    return accs


def lane_select(x_ref, col, zero, value=lambda v: v):
    """Frontier value of vertex ``col`` from a ``[rows, 128]`` VMEM ref, as
    a ``[1, 128]`` row that is ``value(x[col])`` in lane ``col % 128`` and
    the semiring zero elsewhere (and everywhere for a padding slot)."""
    safe = jnp.maximum(col, 0)
    row = x_ref[pl.ds(safe // LANES, 1), :]
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    hit = (lane == safe % LANES) & (col >= 0)
    v = value(row)
    return jnp.where(hit, v, jnp.asarray(zero, v.dtype))


def pack_rows(vals, C: int, dtype):
    """Assemble C per-row ``[1, 1]`` values into one ``[1, C]`` row."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    out = jnp.zeros((1, C), dtype)
    for c, v in enumerate(vals):
        out = jnp.where(lane == c, v, out)
    return out


def run_segments(call, tile_ids, row_block, n_active, init, operands):
    """Run ``call(steps, chunks, live, *operands, acc)`` over the grid
    steps in segments of at most ``MAX_GRID_STEPS``; returns the final
    accumulated output. ``tile_ids`` is the grid order (SlimWork
    compaction: active ids first, the tail repeats the last one);
    ``n_active`` int32[1] counts the live steps. ``call`` is a
    ``pallas_call`` whose grid has ``segment_length(T)`` steps."""
    T = tile_ids.shape[0]
    seg = segment_length(T)
    n_seg = -(-T // seg)
    ids = jnp.concatenate(
        [tile_ids, jnp.broadcast_to(tile_ids[-1:], (n_seg * seg - T,))])
    chunks = jnp.take(row_block, ids, axis=0)
    out = init
    for k in range(n_seg):
        steps = ids[k * seg:(k + 1) * seg]
        chunk = chunks[k * seg:(k + 1) * seg]
        live = jnp.clip(n_active - k * seg, 0, seg).astype(jnp.int32)
        if n_seg == 1:
            out = call(steps, chunk, live, *operands, out)
        else:
            out = jax.lax.cond(
                live[0] > 0,
                lambda acc, s=steps, ch=chunk, lv=live:
                    call(s, ch, lv, *operands, acc),
                lambda acc: acc, out)
    return out


def segment_length(T: int) -> int:
    """Grid steps per ``pallas_call``: the tile list split into the fewest
    equal segments of at most ``MAX_GRID_STEPS``."""
    n_seg = -(-T // MAX_GRID_STEPS)
    return -(-T // n_seg)
