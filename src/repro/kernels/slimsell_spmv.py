"""Pallas TPU kernel: SlimSell semiring SpMV (paper Listing 5, §III-B..D).

TPU-native realization of the paper's AVX kernel (DESIGN.md §2):

* one grid step processes one SlimSell tile — a (C, L) block of column
  indices, read slot by slot as scalars from SMEM; each slot loads the
  frontier row holding ``x[col]`` and a lane compare selects it (Mosaic
  lowers no general gather; ``kernels/sweep.py``);
* ``val`` is derived in-register from ``cols`` (compare + select), never
  loaded from HBM — the SlimSell storage/bandwidth saving;
* the frontier ``x`` is pinned in VMEM as ``[rows, 128]`` (single-buffered,
  DMA'd once per call; past the VMEM bound the wrapper raises);
* **SlimChunk** is the 2D tiling itself: tiles of one chunk revisit the same
  output block and accumulate with the semiring add (split-K analogue);
* **SlimWork** is scalar-prefetch grid *indirection*: the wrapper compacts
  active tile ids into ``tile_ids`` (inactive tail repeats the last active
  id); repeated ids map to the same blocks, so skipped steps issue no DMA and
  `pl.when` skips their compute. On a fixed TPU grid this — not predication —
  is what removes the memory traffic of finished chunks. Grids longer than
  ``sweep.MAX_GRID_STEPS`` run as segments that accumulate through the
  aliased output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.registry import KernelCase, demo_layout, kernel_contract
from repro.core import semiring as _sm
from repro.core.options import resolve_interpret
from . import sweep


def semiring_ops(name: str):
    """(add, edge_contrib, zero), derived from ``core.semiring`` — the
    single source of truth (``repro.analysis.laws`` cross-checks the
    derivation behaviorally, so a future hand-specialization cannot drift).

    The edge value is the semiring's implicit SlimSell contribution
    (``sr.edge_value``, derived in-register, never stored): the numeric 1
    for the scalar semirings — ``sr.mul(1, x)`` is ``x + 1`` under
    tropical/min-plus (one hop), ``x`` under real/boolean/selmax — and the
    all-ones word for the packed boolean domain (the weighted kernel
    replaces it with the stored slot weight).
    """
    try:
        sr = _sm.get(name)
    except (KeyError, ValueError):
        raise ValueError(name) from None
    return (sr.add,
            (lambda x: sr.mul(jnp.asarray(sr.edge_value, x.dtype), x)),
            sr.zero)


def _reduce_l(sr_name: str, contrib):
    """Semiring-add reduction over the last (column-slot) axis, folded in
    halves: a fixed pairwise order on every backend (real-semiring sums
    round the same in interpret mode and on the chip, and lose less than a
    sequential sum)."""
    add = _sm.get(sr_name).add
    w = contrib.shape[-1]
    while w % 2 == 0 and w > 1:
        w //= 2
        contrib = add(contrib[..., :w], contrib[..., w:])
    return _sm.get(sr_name).reduce_last(contrib)


def _weighted_contrib(sr_name: str, w, g):
    """Combine a stored slot weight with a gathered frontier value:
    ``sr.mul(w, x)`` — ``w + x`` under tropical/min-plus (one relaxation),
    ``w * x`` otherwise."""
    return _sm.get(sr_name).mul(w, g)


def _spmv_kernel(steps_ref, chunks_ref, live_ref, cols_ref, *refs,
                 sr_name: str, chunk_blk: int, weighted: bool):
    """One grid step = one SlimSell tile; shared by the unweighted and the
    weighted (SlimSell-W) SpMV. When ``weighted``, ``refs`` leads with the
    slot-weight block (mapped in lockstep with ``cols``) and the stored
    weight replaces the derived implicit 1 — under min-plus the contribution
    becomes ``w + x[col]`` (one relaxation).
    """
    wts_ref = refs[0] if weighted else None
    x_ref, acc_ref, out_ref = refs[-3:]
    add, contrib_fn, zero = semiring_ops(sr_name)
    t = pl.program_id(0)
    row = sweep.open_block(t, chunks_ref, acc_ref, out_ref, chunk_blk)
    _, C, L = cols_ref.shape

    @pl.when(t < live_ref[0])
    def _work():
        def slot_value(col, c, l):
            if weighted:
                w = wts_ref[0, c, l]
                return sweep.lane_select(
                    x_ref, col, zero,
                    lambda v: _weighted_contrib(sr_name, w.astype(v.dtype), v))
            return sweep.lane_select(x_ref, col, zero, contrib_fn)

        accs = sweep.reduce_slots(cols_ref, C, L, slot_value, add, zero,
                                  sweep.LANES, x_ref.dtype)
        red = sweep.pack_rows([_reduce_l(sr_name, a)[:, None] for a in accs],
                              C, x_ref.dtype)
        cur = out_ref[pl.ds(row, 1), :]
        out_ref[pl.ds(row, 1), :] = add(cur, red)


def spmv_grid_spec(S, C, L, x_shape, chunk_blk, weighted):
    """The SpMV grid contract, shared by the wrapper and its registered
    contract cases (so the checker always sees the real index maps). The
    scalar-prefetch operands are the segment's tile id and chunk per grid
    step and its live-step count; the running-partial input rides in
    lockstep with the output block."""
    tile = sweep.tile_spec(C, L, lambda t, st, ch, lv: (st[t], 0, 0))
    block = pl.BlockSpec((chunk_blk, C),
                         lambda t, st, ch, lv: (ch[t] // chunk_blk, 0))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[tile] + ([tile] if weighted else []) + [
            sweep.resident_spec(x_shape, lambda t, st, ch, lv: (0, 0)),
            block,
        ],
        out_specs=block,
    )


def _spmv_cases():
    d = demo_layout()
    T, C, L, cb = d["T"], d["C"], d["L"], d["chunk_blk"]
    out_shape = (d["n_blk"] * cb, C)
    cases = []
    for scen, ids, n_active in d["scenarios"]:
        for weighted in (False, True):
            in_shapes = [(T, C, L)] + ([(T, C, L)] if weighted else []) \
                + [d["x_rows"], out_shape]
            acc = len(in_shapes) - 1
            cases.append(KernelCase(
                name=f"spmv/{scen}" + ("/wts" if weighted else ""),
                grid_spec=spmv_grid_spec(T, C, L, d["x_rows"], cb, weighted),
                scalar_args=(ids, d["row_block"][ids], n_active),
                in_shapes=in_shapes,
                out_shapes=[out_shape],
                lockstep=([(("in", 0), ("in", 1))] if weighted else [])
                + [(("in", acc), ("out", 0))],
                chunked_out=[("out", 0)],
            ))
    return cases


@kernel_contract(_spmv_cases)
@functools.partial(jax.jit, static_argnames=("sr_name", "chunk_blk", "n_chunks",
                                             "interpret"))
def slimsell_spmv_pallas(cols, tile_ids, row_block, n_active, x, *,
                         sr_name: str, n_chunks: int, chunk_blk: int = 8,
                         interpret=None, wts=None):
    """Tile-level SpMV.  Returns y_blocks [n_chunks_pad, C] (chunk-row
    space; chunks no active tile visits hold the semiring zero).

    cols:      int32[T, C, L]
    tile_ids:  int32[T]  grid order (SlimWork compaction; tail repeats last)
    row_block: int32[T]  owning chunk per tile
    n_active:  int32[1]  number of live grid steps
    x:         frontier [n_pad]
    wts:       optional float32[T, C, L] stored slot weights (SlimSell-W),
               block-mapped in lockstep with ``cols`` — the same tile
               indirection, so SlimWork skipping also skips the weight DMA
    """
    interpret = resolve_interpret(interpret)
    T, C, L = cols.shape
    n_blk = -(-n_chunks // chunk_blk)
    weighted = wts is not None
    zero = semiring_ops(sr_name)[2]
    x_rows = sweep.as_rows(x, zero)
    out_shape = (n_blk * chunk_blk, C)
    limit = sweep.vmem_limit(
        "slimsell_spmv_pallas", [(x_rows.shape, x.dtype)],
        [((chunk_blk, C), x.dtype)] * 2)
    operands = (cols,) + ((wts,) if weighted else ()) + (x_rows,)
    call = pl.pallas_call(
        functools.partial(_spmv_kernel, sr_name=sr_name,
                          chunk_blk=chunk_blk, weighted=weighted),
        grid_spec=spmv_grid_spec(sweep.segment_length(T), C, L,
                                 x_rows.shape, chunk_blk, weighted),
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        input_output_aliases={3 + len(operands): 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit),
        interpret=interpret,
    )
    init = jnp.full(out_shape, zero, x.dtype)
    return sweep.run_segments(call, tile_ids, row_block, n_active, init,
                              operands)
