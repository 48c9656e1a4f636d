"""Jitted wrappers around the Pallas kernels.

The wrappers own everything outside the hot loop: SlimWork tile-id
compaction, chunk-row -> vertex-space scatter, GCN slot weights, and the
interpret-mode switch (True on CPU so the kernels are validated everywhere;
compiled on a TPU, where interpret mode is an error).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core import semiring as sm
from repro.core.options import check_choice, resolve_interpret
from .slimsell_spmv import slimsell_spmv_pallas
from .slimsell_spmm import slimsell_spmm_pallas
from .slimsell_packed import (slimsell_spmm_packed_pallas,
                              slimsell_spmv_packed_pallas)
from .slimsell_pull import slimsell_pull_mm_pallas, slimsell_pull_pallas
from .embedding_bag import embedding_bag_pallas


def compact_tile_ids(tile_mask):
    """SlimWork compaction: active tile ids first, tail repeats the last one.

    Repeated trailing ids revisit the same blocks -> no DMA on skipped steps.
    """
    T = tile_mask.shape[0]
    idx = jnp.arange(T, dtype=jnp.int32)
    order = jnp.argsort(~tile_mask, stable=True).astype(jnp.int32)
    n_active = tile_mask.sum(dtype=jnp.int32)
    last = order[jnp.maximum(n_active - 1, 0)]
    ids = jnp.where(idx < n_active, order, last)
    return ids, n_active.reshape(1)


def _grid_order(tiled, tile_mask):
    """Grid order of the tiles and its live-step count: every tile, or the
    SlimWork compaction of ``tile_mask``."""
    if tile_mask is None:
        T = tiled.cols.shape[0]
        return jnp.arange(T, dtype=jnp.int32), jnp.asarray([T], jnp.int32)
    return compact_tile_ids(tile_mask)


def _scatter_blocks(sr, tiled, y_blocks):
    """Shared kernel epilogue: scatter chunk-row blocks to vertex space.

    ``y_blocks`` is [n_chunks, C] (spmv/pull) or [n_chunks, C, d] (spmm);
    chunk blocks no active tile visited already hold the semiring zero.
    """
    rv = tiled.row_vertex.reshape(-1)
    ids = jnp.where(rv < 0, tiled.n, rv)
    flat = y_blocks.reshape(-1) if y_blocks.ndim == 2 \
        else y_blocks.reshape(-1, y_blocks.shape[-1])
    y = sr.segment_reduce(flat, ids, num_segments=tiled.n + 1)
    return y[: tiled.n]


@functools.partial(jax.jit, static_argnames=("sr_name", "interpret"))
def spmv(sr_name: str, tiled, x, tile_mask=None, weights=None, interpret=None):
    """SlimSell SpMV via the Pallas kernel; returns y [n] in vertex space.

    weights: optional stored per-slot weights float32[T, C, L] (SlimSell-W);
    routes to the weighted kernel, whose weight block shares the cols block's
    tile indirection.
    """
    interpret = resolve_interpret(interpret)
    sr = sm.get(sr_name)
    tile_ids, n_active = _grid_order(tiled, tile_mask)
    x = x.astype(sr.dtype)
    y_blocks = slimsell_spmv_pallas(
        tiled.cols, tile_ids, tiled.row_block, n_active, x,
        sr_name=sr_name, n_chunks=tiled.n_chunks, interpret=interpret,
        wts=weights)
    return _scatter_blocks(sr, tiled, y_blocks[: tiled.n_chunks])


@functools.partial(jax.jit, static_argnames=("interpret",))
def spmv_packed(tiled, x_words, tile_mask=None, interpret=None):
    """SlimSell-B packed-boolean SpMV via the word-wise Pallas kernel.

    x_words: uint32[ceil(n/32)] packed frontier bitmap; returns the packed
    result bitmap of the same shape. The kernel produces 0/1 hits in
    chunk-row space; the shared scatter epilogue (boolean semiring — the
    packed domain's per-bit algebra) lands them in vertex space, where each
    vertex appears exactly once, and ``pack_bits`` re-packs.
    """
    interpret = resolve_interpret(interpret)
    tile_ids, n_active = _grid_order(tiled, tile_mask)
    y_blocks = slimsell_spmv_packed_pallas(
        tiled.cols, tile_ids, tiled.row_block, n_active,
        x_words.astype(jnp.uint32),
        n_chunks=tiled.n_chunks, interpret=interpret)
    bits = _scatter_blocks(sm.get("boolean"), tiled,
                           y_blocks[: tiled.n_chunks])
    return packing.pack_bits(bits > 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spmm_packed(tiled, X_words, tile_mask=None, interpret=None):
    """SlimSell-B packed-plane SpMM via the word-wise Pallas kernel.

    X_words: uint32[n, Wb] packed frontier planes (32 roots per word);
    returns Y uint32[n, Wb]. Chunk-row blocks scatter to vertex space with
    the packed semiring's segment-OR.
    """
    interpret = resolve_interpret(interpret)
    sr = sm.get("boolean_packed")
    tile_ids, n_active = _grid_order(tiled, tile_mask)
    y_blocks = slimsell_spmm_packed_pallas(
        tiled.cols, tile_ids, tiled.row_block, n_active,
        X_words.astype(jnp.uint32),
        n_chunks=tiled.n_chunks, interpret=interpret)
    return _scatter_blocks(sr, tiled, y_blocks[: tiled.n_chunks])


@functools.partial(jax.jit, static_argnames=("sr_name", "interpret"))
def pull(sr_name: str, tiled, x, row_mask, tile_mask=None, interpret=None):
    """Bottom-up SlimSell sweep via the Pallas pull kernel; y [n] vertex space.

    row_mask: bool[n] — rows still needing a value (not-final); masked-out
    rows return the semiring zero. The kernel early-exits per chunk row (see
    slimsell_pull.py for the exactness contract vs. the jnp oracle).
    """
    interpret = resolve_interpret(interpret)
    sr = sm.get(sr_name)
    tile_ids, n_active = _grid_order(tiled, tile_mask)
    x = x.astype(sr.dtype)
    # not-final bits in chunk-row space (padding rows are never pending)
    rv = tiled.row_vertex                                  # [n_chunks, C]
    safe = jnp.where(rv < 0, 0, rv)
    nf = jnp.where(rv < 0, False, jnp.take(row_mask, safe, axis=0))
    y_blocks = slimsell_pull_pallas(
        tiled.cols, tile_ids, tiled.row_block, n_active, nf, x,
        sr_name=sr_name, n_chunks=tiled.n_chunks, interpret=interpret)
    return _scatter_blocks(sr, tiled, y_blocks[: tiled.n_chunks])


@functools.partial(jax.jit, static_argnames=("sr_name", "interpret"))
def pull_mm(sr_name: str, tiled, X, row_mask, tile_mask=None, interpret=None):
    """Batched bottom-up sweep via the Pallas pull-MM kernel; Y [n, B].

    row_mask: bool[n, B] — (row, column) pairs still needing a value. The
    kernel early-exits per (chunk row, column); same exactness contract as
    ``pull``, per batch column (core.spmv.slimsell_pull_mm is the oracle).
    """
    interpret = resolve_interpret(interpret)
    sr = sm.get(sr_name)
    tile_ids, n_active = _grid_order(tiled, tile_mask)
    X = X.astype(sr.dtype)
    # per-column not-final bits in chunk-row space; padding rows never pend
    rv = tiled.row_vertex                                  # [n_chunks, C]
    safe = jnp.where(rv < 0, 0, rv)
    nf = jnp.take(row_mask, safe, axis=0)                  # [n_chunks, C, B]
    nf = jnp.where((rv < 0)[..., None], False, nf)
    y_blocks = slimsell_pull_mm_pallas(
        tiled.cols, tile_ids, tiled.row_block, n_active, nf, X,
        sr_name=sr_name, n_chunks=tiled.n_chunks, interpret=interpret)
    return _scatter_blocks(sr, tiled, y_blocks[: tiled.n_chunks])


@functools.partial(jax.jit, static_argnames=("sr_name", "weighted", "interpret"))
def spmm(sr_name: str, tiled, X, deg=None, weighted=False, tile_mask=None,
         weights=None, interpret=None):
    """SlimSell SpMM (feature aggregation / multi-source BFS/SSSP); Y [n, d].

    weights: optional stored per-slot weights float32[T, C, L] (SlimSell-W);
    routes to the stored-weight kernel, whose weight block shares the cols
    block's tile indirection — the batched min-plus (multi-source SSSP)
    operand. ``weighted=True`` is GCN's sym-norm aggregation: the slot
    weight rsqrt(deg[row]) * rsqrt(deg[col]) is derived from ``deg`` here
    and swept as the stored weight. The two are mutually exclusive.
    """
    interpret = resolve_interpret(interpret)
    sr = sm.get(sr_name)
    if weighted:
        if weights is not None:
            raise ValueError("pass stored weights= or the derived GCN "
                             "weighted= path, not both")
        weights = _gcn_slot_weights(tiled, deg)
    tile_ids, n_active = _grid_order(tiled, tile_mask)
    y_blocks = slimsell_spmm_pallas(
        tiled.cols, tile_ids, tiled.row_block, n_active, X.astype(sr.dtype),
        sr_name=sr_name, n_chunks=tiled.n_chunks, interpret=interpret,
        wts=weights)
    return _scatter_blocks(sr, tiled, y_blocks[: tiled.n_chunks])


def _gcn_slot_weights(tiled, deg):
    """GCN's sym-norm weight of every SlimSell slot (padding slots get 0)."""
    degv = deg.astype(jnp.float32)
    rv = jnp.take(tiled.row_vertex, tiled.row_block, axis=0)     # [T, C]
    safe = jnp.maximum(tiled.cols, 0)
    w = (jax.lax.rsqrt(jnp.take(degv, jnp.maximum(rv, 0)))[..., None]
         * jax.lax.rsqrt(jnp.take(degv, safe)))
    return jnp.where(tiled.cols < 0, 0.0, w)


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def embedding_bag(table, bags, mode: str = "sum", interpret=None):
    """SlimSell-layout embedding bag; bags int32[B, K], -1 pads; -> [B, d]."""
    check_choice("embedding_bag mode", mode, ("sum", "mean"))
    interpret = resolve_interpret(interpret)
    return embedding_bag_pallas(table, bags, mode=mode, interpret=interpret)
