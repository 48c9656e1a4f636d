"""Semiring SpMV/SpMM/pull over the tiled SlimSell layout — the backend engine.

Three primitives, shared by every algorithm in ``repro.core`` (BFS,
multi-source BFS, delta-stepping SSSP, connected components):

* ``slimsell_spmv`` — one frontier expansion / relaxation sweep (top-down /
  push). BFS runs it under a BFS semiring with the implicit edge value 1;
  SSSP runs it under ``minplus`` with the stored per-slot weights.
* ``slimsell_pull`` — bottom-up sweep over not-final rows, the direction-
  optimizing counterpart of ``slimsell_spmv``.
* ``slimsell_spmm`` — matrix RHS: GNN aggregation and batched multi-source
  BFS (the frontier becomes an [n, B] matrix).

Two interchangeable backends compute the same function:

* ``backend="jnp"`` — the pure-JAX reference path in this module (gather +
  segment reductions). Always available; this is the correctness oracle.
* ``backend="pallas"`` — the Pallas TPU kernels in ``repro.kernels``
  (``slimsell_spmv.py`` / ``slimsell_spmm.py``) with explicit VMEM tiling and
  SlimWork scalar-prefetch grid indirection; interpret-mode on non-TPU
  backends, compiled on real TPUs. The algorithm engines (``bfs.py``,
  ``multi_bfs.py``, ``dist_bfs.py``, ``sssp.py``, ``cc.py``) thread
  ``backend=`` down to here.

``val`` is never materialized for the unweighted semirings: an edge
contributes ``mul(one, x[col]) == x[col]`` (``one`` is the multiplicative
identity) and a padding slot (col == -1) contributes the additive identity
``zero`` (paper §III-B, Listing 5's CMP+BLEND pair).

Per-edge weights come in two flavors:

* **stored** (``weights=`` — SlimSell-W): a [T, C, L] float array aligned
  with ``cols`` (``SlimSellTiled.wts``); the edge contributes
  ``mul(w, x[col])`` — ``w + x[col]`` under min-plus. Supported on both
  backends and on both RHS shapes: the SpMV form is the SSSP operand, the
  SpMM form (weights broadcast over the RHS columns) is the batched
  multi-source SSSP operand.
* **derived** (``edge_weight=`` callable): computed in-register from the
  (row, col) vertex ids, keeping the Slim no-``val`` property for weights
  that are functions of vertex state, e.g. GCN's D^-1/2 A D^-1/2. Derived
  weights are a jnp-path feature; the Pallas SpMM kernel supports the
  degree-derived GCN weight through ``repro.kernels.ops.spmm(weighted=True)``
  instead.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from . import packing
from .semiring import Semiring
from .options import BACKENDS, DEFAULT_BACKEND  # noqa: F401 (canonical home)

Array = jax.Array


def resolve_backend(backend: Optional[str]) -> str:
    """Map None -> the module default; validate explicit choices."""
    b = DEFAULT_BACKEND if backend is None else backend
    if b not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; available: {BACKENDS}")
    return b


#: bytes of frontier rows a jnp tile-grouped gather takes at once (see
#: gather_group)
SPMM_GATHER_BYTES = 256 * 2**20


def gather_group(cols_shape, width: int, itemsize: int) -> int:
    """Tiles per group of a tile-grouped gather of ``width``-wide rows.

    The gathered [tiles, C, L, width] block is ``width`` times the size of
    cols, so tiles are swept in groups whose gather stays near
    SPMM_GATHER_BYTES (a whole-graph gather of a Graph500 scale-22 batch of
    8 is 19 GB). A TPU lays the gather out [slots, width] with the width
    padded to 128 lanes, so the group is sized for the padded width.
    """
    _, C, L = cols_shape
    lanes = -(-width // 128) * 128
    return max(1, SPMM_GATHER_BYTES // (C * L * lanes * max(itemsize, 4)))


def tile_contributions(sr: Semiring, cols: Array, x: Array,
                       row_vertex_of_tile: Optional[Array] = None,
                       edge_weight: Optional[Callable] = None,
                       weights: Optional[Array] = None) -> Array:
    """[T, C, L] semiring contributions of each column slot.

    ``weights`` (stored, [T, C, L]) and ``edge_weight`` (derived, callable)
    are mutually exclusive; with neither, the edge value is the implicit 1.
    """
    pad = cols < 0
    safe = jnp.where(pad, 0, cols)
    gathered = jnp.take(x, safe, axis=0)  # [T, C, L]
    if weights is not None:
        if edge_weight is not None:
            raise ValueError("pass stored weights= or derived edge_weight=, not both")
        contrib = sr.mul(weights.astype(gathered.dtype), gathered)
    elif edge_weight is not None:
        w = edge_weight(row_vertex_of_tile, safe)  # [T, C, L]
        contrib = sr.mul(w, gathered)
    else:
        # implicit edge value: tropical -> x+1 (hop), real/boolean/selmax ->
        # x (the number 1), boolean_packed -> x (the all-ones word). Derived
        # in-register, never loaded (SlimSell).
        contrib = sr.mul(jnp.asarray(sr.edge_value, gathered.dtype), gathered)
    return jnp.where(pad, jnp.asarray(sr.zero, contrib.dtype), contrib)


def reduce_tiles(sr: Semiring, contrib: Array) -> Array:
    """Reduce the L (column-slot) axis with the semiring add. [T,C,L] -> [T,C]."""
    return sr.reduce_last(contrib)


def _combine_and_scatter(sr: Semiring, tiled, tile_red: Array,
                         tile_mask: Optional[Array]) -> Array:
    """Shared sweep tail: SlimWork mask, combine SlimChunk tiles of the same
    chunk, scatter chunk rows back to original vertex ids (-1 pad -> bucket n).

    ``tile_red`` is [T, C] (SpMV/pull) or [T, C, d] (SpMM).
    """
    if tile_mask is not None:
        mask = tile_mask.reshape((-1,) + (1,) * (tile_red.ndim - 1))
        tile_red = jnp.where(mask, tile_red,
                             jnp.asarray(sr.zero, tile_red.dtype))
    y_blocks = sr.segment_reduce(tile_red, tiled.row_block,
                                 num_segments=tiled.n_chunks)  # [n_chunks, C(, d)]
    rv = tiled.row_vertex.reshape(-1)
    ids = jnp.where(rv < 0, tiled.n, rv)
    flat = y_blocks.reshape(-1) if y_blocks.ndim == 2 \
        else y_blocks.reshape(-1, y_blocks.shape[-1])
    y = sr.segment_reduce(flat, ids, num_segments=tiled.n + 1)
    return y[: tiled.n]


def slimsell_spmv(sr: Semiring, tiled, x: Array, *,
                  edge_weight: Optional[Callable] = None,
                  weights: Optional[Array] = None,
                  tile_mask: Optional[Array] = None,
                  backend: Optional[str] = None) -> Array:
    """y = A (x) over semiring ``sr``; returns y in original vertex space [n].

    tile_mask: optional bool[T]; masked-out tiles contribute ``zero``
    (SlimWork's skip criterion — a mask on the jnp backend, scalar-prefetch
    grid indirection on the pallas backend).
    weights: optional stored per-slot weights [T, C, L] (SlimSell-W) — the
    min-plus SSSP operand; supported on both backends.
    backend: "jnp" (reference) or "pallas" (TPU kernel); None -> default.
    """
    if sr.name == "minplus" and weights is None:
        # minplus without stored weights is tropical; requiring weights keeps
        # the weighted operator from silently degrading to hop counts
        raise ValueError("the minplus semiring needs stored weights "
                         "(weights=tiled.wts); for the implicit-1 edge value "
                         "use the tropical semiring")
    if resolve_backend(backend) == "pallas":
        if edge_weight is not None:
            raise NotImplementedError(
                "derived edge weights are jnp-only for SpMV; use "
                "repro.kernels.ops.spmm(weighted=True) for SlimSell-W")
        from repro.kernels import ops  # deferred: kernels import this module
        return ops.spmv(sr.name, tiled, x, tile_mask=tile_mask,
                        weights=weights)
    cols = tiled.cols
    rv_tile = None
    if edge_weight is not None:
        rv_tile = jnp.take(tiled.row_vertex, tiled.row_block, axis=0)  # [T, C]
        rv_tile = rv_tile[:, :, None]
    contrib = tile_contributions(sr, cols, x, rv_tile, edge_weight, weights)
    tile_red = reduce_tiles(sr, contrib)  # [T, C]
    return _combine_and_scatter(sr, tiled, tile_red, tile_mask)


def slimsell_pull(sr: Semiring, tiled, x: Array, *, row_mask: Array,
                  tile_mask: Optional[Array] = None,
                  backend: Optional[str] = None) -> Array:
    """Bottom-up (pull) sweep: y[v] = ⊕_u A[v,u] ⊗ x[u] for rows with
    ``row_mask[v]`` True; masked-out rows return the semiring ``zero``.

    The algebraic counterpart of Beamer's bottom-up BFS step: work is keyed
    on the *not-yet-finalized* rows (row_mask) rather than on the frontier.
    The jnp path computes the full reduction and is the oracle; the pallas
    path (kernels/slimsell_pull.py) additionally early-exits per chunk row
    once a hit is accumulated — exact for level-homogeneous BFS frontiers
    (every finite/nonzero payload maps to the same distance), and a valid
    (possibly different) parent choice under sel-max.
    """
    if resolve_backend(backend) == "pallas":
        from repro.kernels import ops  # deferred: kernels import this module
        return ops.pull(sr.name, tiled, x, row_mask, tile_mask=tile_mask)
    contrib = tile_contributions(sr, tiled.cols, x)
    tile_red = reduce_tiles(sr, contrib)                       # [T, C]
    rv_tile = jnp.take(tiled.row_vertex, tiled.row_block, axis=0)  # [T, C]
    rv_safe = jnp.where(rv_tile < 0, 0, rv_tile)
    live = jnp.where(rv_tile < 0, False, jnp.take(row_mask, rv_safe, axis=0))
    tile_red = jnp.where(live, tile_red, jnp.asarray(sr.zero, tile_red.dtype))
    return _combine_and_scatter(sr, tiled, tile_red, tile_mask)


def slimsell_pull_mm(sr: Semiring, tiled, X: Array, *, row_mask: Array,
                     tile_mask: Optional[Array] = None,
                     backend: Optional[str] = None) -> Array:
    """Batched bottom-up sweep: the matrix-RHS generalization of
    ``slimsell_pull`` for multi-source traversal.

    X is [n, B]; ``row_mask`` is bool[n, B] — (row, column) pairs whose
    output is already final are masked to the semiring ``zero``. The jnp
    path computes the full SpMM reduction and masks afterwards (the
    oracle); the pallas path (``kernels/slimsell_pull.py``,
    ``slimsell_pull_mm_pallas``) additionally early-exits per (chunk row,
    column tile) once every pending (row, column) pair has accumulated a
    hit — the same exactness contract as the single-source pull kernel,
    per batch column.
    """
    if resolve_backend(backend) == "pallas":
        from repro.kernels import ops  # deferred: kernels import this module
        return ops.pull_mm(sr.name, tiled, X, row_mask, tile_mask=tile_mask)
    y = slimsell_spmm(sr, tiled, X, tile_mask=tile_mask, backend="jnp")
    return jnp.where(row_mask, y, jnp.asarray(sr.zero, y.dtype))


def slimsell_spmm(sr: Semiring, tiled, X: Array, *,
                  edge_weight: Optional[Callable] = None,
                  weights: Optional[Array] = None,
                  tile_mask: Optional[Array] = None,
                  backend: Optional[str] = None) -> Array:
    """Matrix RHS generalization: X is [n, d]; returns [n, d] (DESIGN.md §2).

    The GNN aggregation backend (real semiring == sum aggregation), the
    multi-source BFS engine (d == number of concurrent roots, any semiring)
    and — with ``weights=`` — the batched multi-source SSSP engine, where
    one min-plus sweep relaxes B distance columns at once.
    ``weights``: optional stored per-slot weights [T, C, L] (SlimSell-W),
    broadcast over the RHS columns: each edge contributes
    ``mul(w, X[col, :])`` — ``w + X[col, :]`` under min-plus. Supported on
    both backends, like the SpMV's stored-weight path.
    ``tile_mask`` applies SlimWork to the whole RHS batch at once.
    """
    if sr.name == "minplus" and weights is None:
        # same guard as the SpMV: minplus without stored weights would
        # silently degrade the weighted operator to hop counts
        raise ValueError("the minplus semiring needs stored weights "
                         "(weights=tiled.wts); for the implicit-1 edge value "
                         "use the tropical semiring")
    if weights is not None and edge_weight is not None:
        raise ValueError("pass stored weights= or derived edge_weight=, not both")
    if resolve_backend(backend) == "pallas":
        if edge_weight is not None:
            raise NotImplementedError(
                "callable edge weights are jnp-only; the pallas backend "
                "derives the GCN weight via repro.kernels.ops.spmm(weighted=True)")
        from repro.kernels import ops  # deferred: kernels import this module
        if sr.reduction == "or":
            # packed word planes take the dedicated word-wise kernel
            return ops.spmm_packed(tiled, X, tile_mask=tile_mask)
        return ops.spmm(sr.name, tiled, X, tile_mask=tile_mask,
                        weights=weights)
    rv_tiles = jnp.take(tiled.row_vertex, tiled.row_block, axis=0) \
        if edge_weight is not None else None

    def tile_red_of(tile):
        cols, w, rv = tile                        # [C, L], [C, L], [C]
        pad = cols < 0
        safe = jnp.where(pad, 0, cols)
        gathered = jnp.take(X, safe, axis=0)      # [C, L, d]
        if w is not None:
            gathered = sr.mul(w.astype(gathered.dtype)[..., None], gathered)
        elif edge_weight is not None:
            gathered = sr.mul(edge_weight(rv[:, None], safe)[..., None],
                              gathered)
        else:
            gathered = sr.mul(jnp.asarray(sr.edge_value, gathered.dtype),
                              gathered)
        contrib = jnp.where(pad[..., None],
                            jnp.asarray(sr.zero, gathered.dtype), gathered)
        if sr.reduction == "min":
            return contrib.min(axis=1)
        if sr.reduction == "max":
            return contrib.max(axis=1)
        if sr.reduction == "or":
            return packing.or_reduce(contrib, (1,))
        return contrib.sum(axis=1)                # [C, d]

    group = gather_group(tiled.cols.shape, X.shape[-1], X.dtype.itemsize)
    tile_red = jax.lax.map(tile_red_of, (tiled.cols, weights, rv_tiles),
                           batch_size=group)      # [T, C, d]
    return _combine_and_scatter(sr, tiled, tile_red, tile_mask)


def slimsell_spmv_packed(tiled, x_packed: Array, *,
                         tile_mask: Optional[Array] = None,
                         backend: Optional[str] = None) -> Array:
    """SlimSell-B single-source sweep: packed frontier in, packed result out.

    ``x_packed`` is ``uint32[ceil(n/32)]`` — bit ``v`` set iff vertex ``v``
    is in the frontier (``core.packing`` geometry). One sweep computes the
    packed reachability ``y[v] = OR_u A[v,u] & x_bit[u]``: gather the
    *word* holding each column's bit, extract the bit in-register (the
    packed twin of the implicit-1 CMP+BLEND derivation — still no stored
    ``val``), OR-reduce the column slots, combine SlimChunk tiles, scatter
    to vertex space, and re-pack. Returns ``uint32[ceil(n/32)]`` with all
    tail padding bits zero.

    The jnp path is the oracle; ``backend="pallas"`` routes to the
    word-wise kernel in ``kernels/slimsell_packed.py``.
    """
    if resolve_backend(backend) == "pallas":
        from repro.kernels import ops  # deferred: kernels import this module
        return ops.spmv_packed(tiled, x_packed, tile_mask=tile_mask)
    from .semiring import BOOLEAN  # deferred: import-order freedom only
    sr = BOOLEAN
    cols = tiled.cols
    pad = cols < 0
    safe = jnp.where(pad, 0, cols)
    bit = packing.gather_bits(x_packed, safe)           # [T, C, L] 0/1
    hit = jnp.where(pad, 0, bit.astype(jnp.int32))
    tile_red = hit.max(axis=-1)                         # [T, C] OR of 0/1
    y_bits = _combine_and_scatter(sr, tiled, tile_red, tile_mask)
    return packing.pack_bits(y_bits > 0)
