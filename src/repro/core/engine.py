"""The shared algebraic fixpoint engine (paper §III, generalized).

The paper's central claim is that one SpMV-with-a-semiring abstraction
carries a whole family of graph algorithms. This module cashes that claim
structurally: an algorithm is a small **spec** (``FixpointSpec``) — initial
state, how to read the sweep operands off the state (frontier payload, push
source bits, not-final rows, per-sweep weights), and a semiring-update-style
state merge that also decides convergence — and the *engine* owns every
execution strategy:

* ``run_fused``    — the whole fixpoint is one ``lax.while_loop`` on device;
  under ``direction="auto"`` the Beamer heuristic runs inside the carry and
  a ``lax.cond`` picks the push SpMV or the pull sweep each iteration.
* ``run_hostloop`` — the loop runs on host; each iteration builds the
  SlimWork mask in numpy (frontier-walk over the push-index incidence
  ranges), gathers only the active tiles (bucketed to powers of two to
  bound retracing) and invokes one jitted subset step.
* ``dist_step``    — one iteration of the same spec over a 2D-partitioned
  layout *inside* ``shard_map``: the local sweep is the ordinary
  ``slimsell_spmv``/``pull``/``spmm`` over the device's localized tiles,
  followed by a semiring all-reduce; the state update is the spec's own,
  replicated. ``core.dist_bfs`` owns the mesh plumbing around this.

``core.bfs``, ``core.multi_bfs``, ``core.sssp`` and ``core.cc`` are specs
over this engine — none of them carries its own while_loop or hostloop
anymore. Delta-stepping's nested bucket/fixpoint loops flatten into a
single fixpoint by carrying the phase (light-relaxation vs heavy-settle) in
the state; the spec's update does the phase transitions.

Spec callables and their shapes (B = batch width for ``batched`` specs):

  ================= ==========================================================
  ``init_state``    (n, arg, ctx) -> state dict (pytree of [n] / [n, B])
  ``frontier``      (ctx, state, k) -> sweep payload [n] / [n, B]
  ``source_bits``   (ctx, state, k) -> bool[n] / [n, B] push sources
  ``not_final``     (ctx, state) -> bool[n] / [n, B] rows that can change
  ``update``        (ctx, state, y, k) -> (state, continue?)
  ``setup``         (tiled, *ctx_args) -> ctx (per-run constants; leaves with
                    a leading tile axis are gathered by the hostloop subset)
  ``weights``       (ctx, state) -> stored per-slot weights [T, C, L] or None
  ``host_bits``     (state, k, need_sb, need_nf) -> numpy (sb, nf)
  ================= ==========================================================
"""
from __future__ import annotations

import dataclasses
import math
import re
import threading
from functools import lru_cache, partial
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import debug
from . import direction as dm
from . import packing
from . import semiring as sm
from .options import (BACKENDS, DIRECTIONS,  # noqa: F401 (home is options)
                      check_choice)
from .spmv import (slimsell_pull, slimsell_pull_mm, slimsell_spmm,
                   slimsell_spmv, slimsell_spmv_packed)

Array = jax.Array
WORK_LOG = 512  # max logged iterations


@dataclasses.dataclass(frozen=True, eq=False)
class FixpointSpec:
    """One algorithm as data. Frozen and hashed by identity so module-level
    spec instances key the engine's jit caches stably."""
    name: str
    sr_name: str
    init_state: Callable[..., dict]
    frontier: Callable[..., Array]
    update: Callable[..., tuple]
    source_bits: Optional[Callable[..., Array]] = None
    not_final: Optional[Callable[..., Array]] = None
    setup: Optional[Callable[..., Any]] = None
    weights: Optional[Callable[..., Array]] = None
    host_bits: Optional[Callable[..., tuple]] = None
    batched: bool = False
    directions: tuple = ("push",)
    # SlimSell-B: the spec's sweep payload is bit-packed uint32 words
    # (``core.packing``). Non-batched packed specs sweep a packed frontier
    # bitmap uint32[ceil(n/32)] through the word-gather SpMV; batched packed
    # specs sweep packed root *planes* [n, ceil(B/32)] through the word-wise
    # SpMM. Packed specs are push-only (their payload carries no per-row
    # ordering for the pull early-exit) — front doors enforce it.
    packed: bool = False


@dataclasses.dataclass
class EngineResult:
    """What every strategy returns, before algorithm-specific post-processing."""
    state: dict
    iterations: int
    work_log: Optional[np.ndarray] = None       # active tiles per iteration
    dirs_log: Optional[np.ndarray] = None       # 0=push 1=pull per iteration
    pull_cols_log: Optional[np.ndarray] = None  # batched: pull columns/iter


# ------------------------------------------------------------------- helpers


def _chunk_active_from(nf: Array, row_vertex: Array) -> Array:
    """bool[n_chunks] from not-final bits (SlimWork §III-C; the pull
    direction's tile criterion). ``nf`` is bool[n] in vertex space."""
    safe = jnp.where(row_vertex < 0, 0, row_vertex)
    per_row = jnp.where(row_vertex < 0, False, jnp.take(nf, safe, axis=0))
    return per_row.any(axis=1)


def _pull_tile_mask(tiled, nf_rows: Array) -> Array:
    active = _chunk_active_from(nf_rows, tiled.row_vertex)
    return jnp.take(active, tiled.row_block, axis=0)


def _sweep(spec: FixpointSpec, tiled, x, w, tile_mask, rows, backend: str,
           *, pull: bool, n_bits: Optional[int] = None):
    """One semiring sweep: the spec's shape (vector/matrix) and direction
    select between the core primitives.

    ``n_bits`` is the live-bit count of packed sweeps (n for the packed
    bitmap SpMV, the batch width B for packed planes) — threaded to the
    sanitizer's tail-word check; None skips it.
    """
    sr = sm.get(spec.sr_name)
    if pull:
        if spec.batched:
            y = slimsell_pull_mm(sr, tiled, x, row_mask=rows,
                                 tile_mask=tile_mask, backend=backend)
        else:
            y = slimsell_pull(sr, tiled, x, row_mask=rows,
                              tile_mask=tile_mask, backend=backend)
        debug.check_sweep(sr, y)
        return y
    if spec.packed and not spec.batched:
        if n_bits is None:
            n_bits = tiled.n
        y = slimsell_spmv_packed(tiled, x, tile_mask=tile_mask,
                                 backend=backend)
        debug.check_sweep(sr, y, n_bits=n_bits)
        return y
    if spec.batched:
        y = slimsell_spmm(sr, tiled, x, weights=w, tile_mask=tile_mask,
                          backend=backend)
    else:
        y = slimsell_spmv(sr, tiled, x, weights=w, tile_mask=tile_mask,
                          backend=backend)
    debug.check_sweep(sr, y, n_bits=n_bits if spec.packed else None)
    return y


def _tile_counts(mask: Optional[Array], n_tiles: Array, backend: str):
    """(tiles active, tiles read) by one sweep under SlimWork ``mask``.

    Active: the tiles the mask selects, or every tile where there is no
    mask. Read: the Pallas kernels walk only the selected tiles (the mask
    becomes their grid), while the jnp gather reads every tile and masks
    the reduced rows afterwards.
    """
    if mask is None:
        return n_tiles, n_tiles
    active = mask.sum(dtype=jnp.int32)
    return active, active if backend == "pallas" else n_tiles


def _subset_ctx(ctx, ids: Array, n_tiles: int):
    """Gather the tile-space leaves of a spec ctx down to the active tiles;
    scalars and non-tile leaves pass through untouched."""
    if ctx is None:
        return None
    return jax.tree_util.tree_map(
        lambda a: jnp.take(a, ids, axis=0)
        if (hasattr(a, "ndim") and a.ndim >= 1 and a.shape[0] == n_tiles)
        else a, ctx)


# -------------------------------------------------------------------- fused


_FUSED_STATICS = ("spec", "slimwork", "max_iters", "log_work", "backend",
                  "direction")


def _fixpoint_loop(spec: FixpointSpec, tiled, ctx, state, *,
                   slimwork: bool, max_iters: int, log_work: bool,
                   backend: str, direction: str,
                   batch_width: Optional[int] = None):
    """The fused strategy's ``lax.while_loop``, factored out of
    ``_run_fused_impl`` so the serving layer's persistent jitted handles
    (``fixpoint_handle``) trace the exact same loop body.

    ``batch_width`` is the batch axis B for batched specs (callers with the
    init arg in hand derive it; handles bake it in statically). Returns
    ``(state, iterations, work, dirs, plog, tiles)`` — the raw device
    values; ``tiles`` is the int32 pair (tiles active, tiles read) summed
    over the sweeps (``_tile_counts``).
    """
    n = tiled.n
    log_n = WORK_LOG if log_work else 1
    work = jnp.zeros((log_n,), jnp.int32)
    dirs = jnp.full((log_n,), -1, jnp.int32)
    plog = jnp.zeros((log_n,), jnp.int32)
    use_push = direction in ("push", "auto")
    n_tiles_c = jnp.asarray(tiled.cols.shape[0], jnp.int32)
    if spec.batched:
        B = batch_width
        d0 = jnp.full((B,), dm.PULL if direction == "pull" else dm.PUSH,
                      jnp.int32)
    else:
        d0 = jnp.asarray(dm.PULL if direction == "pull" else dm.PUSH,
                         jnp.int32)

    def cond(carry):
        _, k, cont, _, _, _, _, _ = carry
        return cont & (k <= max_iters)

    def body(carry):
        state, k, _, work, dcur, dirs, plog, tiles = carry
        nf = spec.not_final(ctx, state) if direction != "push" else None
        sb = spec.source_bits(ctx, state, k) if use_push else None
        if direction == "auto":
            mf, mu, nnz_f = dm.edge_counts(tiled.deg, sb, nf)
            dnext = dm.choose_direction(dcur, mf, mu, nnz_f, n)
        else:
            dnext = dcur
        x = spec.frontier(ctx, state, k)
        w = spec.weights(ctx, state) if spec.weights is not None else None

        if spec.batched:
            # one SpMM/pull-MM sweep advances every column, so per-column
            # directions compose into a single *union* tile mask
            if direction == "pull":
                mask = _pull_tile_mask(tiled, nf.any(axis=-1)) \
                    if slimwork else None
                y = _sweep(spec, tiled, x, w, mask, nf, backend, pull=True)
            else:
                mask = None
                if slimwork:
                    if direction == "push":
                        mask = dm.push_tile_mask(tiled, sb)
                    else:
                        push_rows = (sb & (dnext == dm.PUSH)[None, :]).any(axis=1)
                        pull_rows = (nf & (dnext == dm.PULL)[None, :]).any(axis=1)
                        mask = dm.push_tile_mask(tiled, push_rows) \
                            | _pull_tile_mask(tiled, pull_rows)
                y = _sweep(spec, tiled, x, w, mask, None, backend,
                           pull=False,
                           n_bits=batch_width if spec.packed else None)
            state, cont = spec.update(ctx, state, y, k)
            used, read = _tile_counts(mask, n_tiles_c, backend)
        else:
            # the tile masks are built INSIDE the branches so the untaken
            # direction's mask is never materialized (lax.cond operands
            # would be evaluated eagerly every iteration otherwise)
            def push_fn(state):
                mask = dm.push_tile_mask(tiled, sb) if slimwork else None
                y = _sweep(spec, tiled, x, w, mask, None, backend, pull=False)
                st, cont = spec.update(ctx, state, y, k)
                return (st, cont) + _tile_counts(mask, n_tiles_c, backend)

            def pull_fn(state):
                mask = _pull_tile_mask(tiled, nf) if slimwork else None
                y = _sweep(spec, tiled, x, w, mask, nf, backend, pull=True)
                st, cont = spec.update(ctx, state, y, k)
                return (st, cont) + _tile_counts(mask, n_tiles_c, backend)

            if direction == "push":
                state, cont, used, read = push_fn(state)
            elif direction == "pull":
                state, cont, used, read = pull_fn(state)
            else:
                state, cont, used, read = jax.lax.cond(
                    dnext == dm.PUSH, push_fn, pull_fn, state)
        if log_work:
            idx = jnp.minimum(k - 1, WORK_LOG - 1)
            if slimwork:
                work = work.at[idx].set(used)
            if spec.batched:
                plog = plog.at[idx].set(jnp.sum(dnext == dm.PULL,
                                                dtype=jnp.int32))
            else:
                dirs = dirs.at[idx].set(dnext)
        tiles = (tiles[0] + used, tiles[1] + read)
        return state, k + 1, cont, work, dnext, dirs, plog, tiles

    zero = jnp.asarray(0, jnp.int32)
    state, k, _, work, _, dirs, plog, tiles = jax.lax.while_loop(
        cond, body, (state, jnp.asarray(1, jnp.int32), jnp.asarray(True),
                     work, d0, dirs, plog, (zero, zero)))
    return state, k - 1, work, dirs, plog, tiles


def _run_fused_impl(spec: FixpointSpec, tiled, arg, ctx_args, *,
                    slimwork: bool, max_iters: int, log_work: bool,
                    backend: str, direction: str):
    debug.check_layout(tiled)
    ctx = spec.setup(tiled, *ctx_args) if spec.setup is not None else None
    state = spec.init_state(tiled.n, arg, ctx)
    width = arg.shape[0] if spec.batched else None
    return _fixpoint_loop(spec, tiled, ctx, state, slimwork=slimwork,
                          max_iters=max_iters, log_work=log_work,
                          backend=backend, direction=direction,
                          batch_width=width)[:5]


_run_fused = partial(jax.jit, static_argnames=_FUSED_STATICS)(_run_fused_impl)


def run_fused(spec: FixpointSpec, tiled, arg, *, ctx_args=(),
              slimwork: bool = True, max_iters: int, log_work: bool = False,
              backend: str = "jnp", direction: str = "push") -> EngineResult:
    """Run a spec to its fixpoint as one on-device ``lax.while_loop``.

    Under ``debug.checked()`` the whole loop runs through a checkified twin
    (layout bounds once, per-sweep NaN/inf checks in the carry).
    """
    check_choice("direction", direction, DIRECTIONS)
    check_choice("backend", backend, BACKENDS)
    runner = partial(debug.call_checked, _run_fused_impl,
                     static_argnames=_FUSED_STATICS,
                     pallas=backend == "pallas") \
        if debug.enabled() else _run_fused
    state, iters, work, dirs, plog = runner(
        spec, tiled, arg, tuple(ctx_args), slimwork=slimwork,
        max_iters=max_iters, log_work=log_work, backend=backend,
        direction=direction)
    iters = int(iters)
    dirs_out = plog_out = wl = None
    if log_work:
        if spec.batched:
            # batched callers stack logs across batches, so both logs keep
            # the fixed WORK_LOG length instead of truncating to iters
            wl = np.asarray(work)
            plog_out = np.asarray(plog)
        else:
            wl = np.asarray(work)[:iters]
            dirs_out = np.asarray(dirs)[:iters]
    elif direction != "auto" and not spec.batched:
        dirs_out = np.full(iters, dm.PULL if direction == "pull" else dm.PUSH,
                           np.int32)
    return EngineResult(state=state, iterations=iters, work_log=wl,
                        dirs_log=dirs_out, pull_cols_log=plog_out)


# ---------------------------------------------------------- fixpoint handles


@dataclasses.dataclass(eq=False)
class FixpointHandle:
    """A persistent, re-entrant jitted fixpoint runner for one bucket
    signature (spec, slimwork, max_iters, backend, direction, batch width).

    The serving layer's unit of compilation reuse: the handle's jitted
    function takes ``(tiled, ctx, state)`` as *traced* pytree arguments —
    nothing graph-sized is closed over — so one handle serves every layout
    with matching shapes, and ``run`` re-dispatches without retracing.
    ``donate=True`` donates the state pytree's buffers to the sweep loop
    (distance/frontier buffers are reused in place on TPU/GPU; donation is
    auto-disabled on CPU where XLA ignores it with a warning).

    ``run`` returns ``(state, iterations, tiles)`` as *device* values
    without blocking — JAX's async dispatch lets the caller overlap
    host-side request handling with the device sweeps and harvest one step
    late. ``tiles`` is the int32 pair (tiles active, tiles read) summed over
    the sweeps (``_tile_counts``); read it after ``iterations``, which has
    already waited for the program. The jitted function is named
    ``fixpoint_<spec name>``, so its XLA program is
    ``jit_fixpoint_<spec name>`` (``/`` becomes ``_``). Under
    ``debug.checked()`` the call routes through a checkified twin.
    """
    spec: FixpointSpec
    slimwork: bool
    max_iters: int
    backend: str
    direction: str
    batch_width: Optional[int]
    donate: bool
    _impl: Callable = dataclasses.field(repr=False, default=None)
    _jitted: Callable = dataclasses.field(repr=False, default=None)

    def setup(self, tiled, ctx_args=()):
        """The spec's per-run constants (weight views etc.), or None."""
        if self.spec.setup is None:
            return None
        return self.spec.setup(tiled, *tuple(ctx_args))

    def init_state(self, tiled, arg, ctx):
        """Fresh state pytree for one run (device-ready, donatable)."""
        return self.spec.init_state(tiled.n, arg, ctx)

    def run(self, tiled, ctx, state):
        """Drive ``state`` to the fixpoint; async ``(state, iterations,
        tiles)``."""
        if debug.enabled():
            return debug.call_checked(self._impl, tiled, ctx, state,
                                      pallas=self.backend == "pallas")
        return self._jitted(tiled, ctx, state)


# fixpoint_handle's concurrent-first-call guard: CPython's lru_cache is
# internally consistent but does NOT deduplicate concurrent misses — two
# serving threads asking for the same brand-new signature would both build
# (and trace) a handle, and one trace would be thrown away. One lock per
# signature serializes construction exactly once per key; hits never touch
# the guard map after the first call.
_HANDLE_ONCE_GUARD = threading.Lock()
_HANDLE_BUILD_LOCKS: dict = {}


@lru_cache(maxsize=None)
def _fixpoint_handle_cached(spec: FixpointSpec, slimwork: bool,
                            max_iters: int, backend: str, direction: str,
                            batch_width: Optional[int],
                            donate: bool) -> FixpointHandle:
    def impl(tiled, ctx, state):
        state, iters, _, _, _, tiles = _fixpoint_loop(
            spec, tiled, ctx, state, slimwork=slimwork, max_iters=max_iters,
            log_work=False, backend=backend, direction=direction,
            batch_width=batch_width)
        return state, iters, tiles

    # the program's name says which spec ran (jit_fixpoint_<spec name>)
    impl.__name__ = impl.__qualname__ = \
        "fixpoint_" + re.sub(r"\W", "_", spec.name)
    jitted = jax.jit(impl, donate_argnums=(2,) if donate else ())
    return FixpointHandle(spec=spec, slimwork=slimwork, max_iters=max_iters,
                          backend=backend, direction=direction,
                          batch_width=batch_width, donate=donate,
                          _impl=impl, _jitted=jitted)


def fixpoint_handle(spec: FixpointSpec, *, slimwork: bool = True,
                    max_iters: int, backend: str = "jnp",
                    direction: str = "push",
                    batch_width: Optional[int] = None,
                    donate: Optional[bool] = None) -> FixpointHandle:
    """Get (or build) the process-wide ``FixpointHandle`` for a bucket
    signature. Handles are cached forever — like the engine's jit caches —
    so repeated sessions over same-shaped layouts reuse both the handle
    object and its compiled executables.

    ``batch_width`` is required for batched specs (it is part of the
    signature; serving buckets pad to power-of-two widths so the set of
    live signatures stays small). ``donate=None`` enables buffer donation
    exactly where XLA honors it (not on CPU).

    Thread-safe: a per-signature once-guard serializes the first call for
    each new signature, so concurrent serving threads missing on the same
    key get one handle (one trace), never two.
    """
    check_choice("direction", direction, DIRECTIONS)
    check_choice("backend", backend, BACKENDS)
    if spec.batched and batch_width is None:
        raise ValueError(f"{spec.name}: batched specs need batch_width")
    if donate is None:
        donate = jax.default_backend() != "cpu"
    key = (spec, bool(slimwork), int(max_iters), backend, direction,
           None if batch_width is None else int(batch_width), bool(donate))
    with _HANDLE_ONCE_GUARD:
        build_lock = _HANDLE_BUILD_LOCKS.setdefault(key, threading.Lock())
    with build_lock:
        return _fixpoint_handle_cached(*key)


# ------------------------------------------------------------------ hostloop


@dataclasses.dataclass
class _SubsetTiled:
    """Duck-typed SlimSellTiled view over a compacted (or shard-local) tile
    set. ``wts`` rides along only for weighted (SSSP) steps; ``inc_src`` /
    ``inc_tile`` only when the shard carries its own push index (the
    distributed SlimWork push masks) — entries padded past a shard's real
    pair count point at tile id ``n_tiles`` so segment ops drop them."""
    cols: Array
    row_block: Array
    row_vertex: Array
    n: int
    n_chunks: int
    wts: Optional[Array] = None
    inc_src: Optional[Array] = None
    inc_tile: Optional[Array] = None

    @property
    def n_tiles(self) -> int:
        return self.cols.shape[0]


jax.tree_util.register_pytree_node(
    _SubsetTiled,
    lambda t: ((t.cols, t.row_block, t.row_vertex, t.wts,
                t.inc_src, t.inc_tile), (t.n, t.n_chunks)),
    lambda aux, ch: _SubsetTiled(cols=ch[0], row_block=ch[1],
                                 row_vertex=ch[2], n=aux[0], n_chunks=aux[1],
                                 wts=ch[3], inc_src=ch[4], inc_tile=ch[5]),
)


def _bucket(x: int) -> int:
    return 1 if x <= 1 else 2 ** math.ceil(math.log2(x))


def _pad_tile_ids(ids: np.ndarray, n_tiles: int):
    """SlimWork hostloop compaction: bucket the active-tile count to a power
    of two (bounds jit retracing) and pad with repeats of the LAST id — the
    tail then stays on the final output block, so the pallas kernel's
    first-visit re-init never revisits an earlier block."""
    bucket = min(_bucket(ids.size), n_tiles)
    ids_p = np.zeros(bucket, np.int32)
    ids_p[: ids.size] = ids
    if ids.size < bucket:
        ids_p[ids.size:] = ids[-1]
    return ids_p, bucket


def _push_tile_mask_host(active: np.ndarray, inc_ptr: np.ndarray,
                         inc_tile: np.ndarray, n_tiles: int) -> np.ndarray:
    """Host twin of ``direction.push_tile_mask``: bool[T] of the tiles
    holding ≥1 active column.

    Walks only the *active columns'* incidence ranges (``inc_ptr`` is the
    CSR-style offset vector over the vertex-sorted push index), so the cost
    is O(frontier incidence), not O(K) over the whole index — the frontier-
    restricted mask build of ROADMAP's hostloop perf item.
    """
    tmask = np.zeros(n_tiles, bool)
    verts = np.nonzero(active)[0]
    if verts.size == 0:
        return tmask
    starts = inc_ptr[verts]
    counts = inc_ptr[verts + 1] - starts
    total = int(counts.sum())
    if total == 0:
        return tmask
    # ragged range gather: concatenate [starts_i, starts_i + counts_i)
    ofs = np.repeat(starts - np.concatenate(([0], np.cumsum(counts)[:-1])),
                    counts)
    tmask[inc_tile[ofs + np.arange(total)]] = True
    return tmask


def _host_inc_ptr(tiled) -> np.ndarray:
    """inc_ptr for layouts that predate the field (duck-typed tests)."""
    ptr = getattr(tiled, "inc_ptr", None)
    if ptr is not None:
        return np.asarray(ptr)
    inc_src = np.asarray(tiled.inc_src)
    return np.searchsorted(inc_src, np.arange(tiled.n + 1)).astype(np.int64)


_SUBSET_STATICS = ("spec", "n", "n_chunks", "n_active", "pull", "backend")


def _subset_step_impl(spec: FixpointSpec, cols, row_block, row_vertex, n: int,
                      n_chunks: int, ctx, tile_ids, n_active: int, state, k,
                      pull: bool, backend: str):
    """Gather the active tiles (bucketed size) and run one step on them only."""
    ids = tile_ids[:n_active]
    sub = _SubsetTiled(
        cols=jnp.take(cols, ids, axis=0),
        row_block=jnp.take(row_block, ids, axis=0),
        row_vertex=row_vertex, n=n, n_chunks=n_chunks,
    )
    x = spec.frontier(ctx, state, k)
    w = None
    if spec.weights is not None:
        w = spec.weights(_subset_ctx(ctx, ids, cols.shape[0]), state)
    rows = spec.not_final(ctx, state) if pull else None
    y = _sweep(spec, sub, x, w, None, rows, backend, pull=pull)
    return spec.update(ctx, state, y, k)


_subset_step = partial(jax.jit,
                       static_argnames=_SUBSET_STATICS)(_subset_step_impl)

_FULLSTEP_STATICS = ("spec", "pull", "backend")


def _full_step_impl(spec: FixpointSpec, tiled, ctx, state, k, pull: bool,
                    backend: str):
    x = spec.frontier(ctx, state, k)
    w = spec.weights(ctx, state) if spec.weights is not None else None
    rows = spec.not_final(ctx, state) if pull else None
    y = _sweep(spec, tiled, x, w, None, rows, backend, pull=pull)
    return spec.update(ctx, state, y, k)


_full_step = partial(jax.jit,
                     static_argnames=_FULLSTEP_STATICS)(_full_step_impl)

_ZEROSTEP_STATICS = ("spec", "n", "width")


def _zero_step_impl(spec: FixpointSpec, n: int, ctx, state, k,
                    width: Optional[int] = None):
    """Update against an all-zero sweep result: what an empty tile set
    computes. BFS-style specs report no change and terminate; phase-carrying
    specs (delta-stepping) still advance their phase. ``width`` is the batch
    width for batched specs (their sweep result is [n, B]; packed batched
    specs sweep word planes [n, ceil(B/32)], packed single-source specs a
    word bitmap [ceil(n/32)])."""
    sr = sm.get(spec.sr_name)
    if spec.packed:
        shape = (packing.packed_words(n),) if width is None \
            else (n, packing.packed_words(width))
    else:
        shape = (n,) if width is None else (n, width)
    y = jnp.full(shape, sr.zero, sr.dtype)
    return spec.update(ctx, state, y, k)


_zero_step = partial(jax.jit,
                     static_argnames=_ZEROSTEP_STATICS)(_zero_step_impl)


def run_hostloop(spec: FixpointSpec, tiled, arg, *, ctx_args=(),
                 slimwork: bool = True, max_iters: int,
                 backend: str = "jnp",
                 direction: str = "push") -> EngineResult:
    """Run a spec with the loop on host, gathering only the active tiles
    per iteration (real work-skipping on any backend).

    All mask and heuristic math happens in numpy via the spec's
    ``host_bits`` twin — one device sync per state field per iteration
    instead of ~20 dispatches.

    Batched specs run push-only: their ``host_bits`` source matrix
    [n, B] is unioned over columns into the shared SlimWork tile set
    (mirroring the fused strategy's union masks); per-column pull/auto
    state is a fused-strategy feature.
    """
    check_choice("direction", direction, DIRECTIONS)
    check_choice("backend", backend, BACKENDS)
    if spec.batched and direction != "push":
        raise NotImplementedError(
            f"{spec.name}: batched hostloop is push-only "
            "(per-column pull/auto state needs the fused strategy)")
    if debug.enabled():
        # eager twin of check_layout, then checkified per-step twins so the
        # in-sweep checks ride inside each jitted step
        debug.validate_layout_host(tiled)
        pallas = backend == "pallas"
        zero_step = partial(debug.call_checked, _zero_step_impl,
                            static_argnames=_ZEROSTEP_STATICS, pallas=pallas)
        subset_step = partial(debug.call_checked, _subset_step_impl,
                              static_argnames=_SUBSET_STATICS, pallas=pallas)
        full_step = partial(debug.call_checked, _full_step_impl,
                            static_argnames=_FULLSTEP_STATICS, pallas=pallas)
    else:
        zero_step, subset_step, full_step = _zero_step, _subset_step, _full_step
    width = int(np.asarray(arg).shape[0]) if spec.batched else None
    n = tiled.n
    ctx = spec.setup(tiled, *ctx_args) if spec.setup is not None else None
    state = spec.init_state(n, arg, ctx)
    n_tiles = int(tiled.n_tiles)
    dcur = dm.PULL if direction == "pull" else dm.PUSH
    use_push = direction in ("push", "auto")
    # host copies of the layout metadata the per-iteration masks need
    rv_np = np.asarray(tiled.row_vertex)
    rv_safe_np = np.where(rv_np < 0, 0, rv_np)
    rb_np = np.asarray(tiled.row_block)
    deg_np = np.asarray(tiled.deg, np.float64) if direction == "auto" else None
    if use_push and slimwork:
        inc_ptr_np = _host_inc_ptr(tiled)
        inc_tile_np = np.asarray(tiled.inc_tile)
    k, iters = 1, 0
    work_list, dir_list = [], []
    while k <= max_iters:
        sb, nf = spec.host_bits(state, k, use_push, direction != "push")
        if sb is not None and sb.ndim > 1:
            # batched spec: one shared tile set — the union of the
            # per-column source sets (the SpMM advances every column)
            sb = sb.any(axis=1)
        if direction == "auto":
            dcur = dm.choose_direction_host(
                dcur, float(deg_np[sb].sum()), float(deg_np[nf].sum()),
                float(sb.sum()), n)
        kdev = jnp.asarray(k, jnp.int32)
        if slimwork:
            if dcur == dm.PUSH:
                tmask = _push_tile_mask_host(sb, inc_ptr_np, inc_tile_np,
                                             n_tiles)
            else:
                chunk_act = (nf[rv_safe_np] & (rv_np >= 0)).any(axis=1)
                tmask = chunk_act[rb_np]
            ids = np.nonzero(tmask)[0]
            if ids.size == 0:
                # empty tile set: the sweep would return all-zero; the
                # zero-step lets phase-carrying specs advance anyway. It
                # still counts as an iteration (0 tiles) so sweep counts
                # and work logs match the fused strategy, whose while_loop
                # body runs the all-masked sweep.
                state, cont = zero_step(spec, n, ctx, state, kdev, width)
                work_list.append(0)
                dir_list.append(dcur)
                iters = k
                k += 1
                if not bool(cont):
                    break
                continue
            work_list.append(ids.size)
            dir_list.append(dcur)
            ids_p, bucket = _pad_tile_ids(ids, n_tiles)
            state, cont = subset_step(
                spec, tiled.cols, tiled.row_block, tiled.row_vertex, n,
                tiled.n_chunks, ctx, jnp.asarray(ids_p), bucket, state,
                kdev, dcur == dm.PULL, backend)
        else:
            work_list.append(n_tiles)
            dir_list.append(dcur)
            state, cont = full_step(spec, tiled, ctx, state, kdev,
                                    dcur == dm.PULL, backend)
        iters = k
        k += 1
        if not bool(cont):
            break
    return EngineResult(state=state, iterations=iters,
                        work_log=np.asarray(work_list, np.int32),
                        dirs_log=np.asarray(dir_list, np.int32))


# --------------------------------------------------------------- distributed


def dist_step(spec: FixpointSpec, ctx, local, state, k, dnow, *,
              n: int, Co: int, n_col: int,
              row_axes: Sequence[str], col_axes: Sequence[str],
              comm: str = "allreduce", backend: str = "jnp",
              direction: str = "push"):
    """One fixpoint iteration over the 2D partition, inside ``shard_map``.

    ``local`` is a ``_SubsetTiled`` view of this device's tiles: localized
    column ids, *global* ``row_vertex`` ids (so the ordinary sweep
    primitives scatter straight into full vertex space), ``n_chunks`` = the
    row shard's chunk count. State is replicated; the semiring all-reduce
    combines the per-device partial sweeps (each edge lives in exactly one
    (row, column) block, so the combine is exact for every semiring).

    push — local SpMV/SpMM over the frontier's column slice, SlimWork-masked
    to the tiles holding a frontier column when the shard carries its own
    push index (``local.inc_src`` / ``local.inc_tile``);
    pull — row sweep over the shard's own not-final rows only (SlimWork's
    tile criterion on the local ``row_vertex``), which is the "local row
    sweep + row-axis gather" decomposition: other shards' rows contribute
    the semiring zero, so the same collectives double as the gather.
    """
    sr = sm.get(spec.sr_name)
    x_full = spec.frontier(ctx, state, k)
    j = jax.lax.axis_index(col_axes[0]) if col_axes else 0
    pad = ((0, Co * n_col - n),) + ((0, 0),) * (x_full.ndim - 1)
    x_pad = jnp.pad(x_full, pad, constant_values=sr.zero)
    x_local = jax.lax.dynamic_slice_in_dim(x_pad, j * n_col, n_col, axis=0)
    w = spec.weights(ctx, state) if spec.weights is not None else None

    def push_fn(state):
        # per-shard SlimWork push mask: the partition's own (localized
        # column, tile) incidence pairs select the tiles holding >=1
        # frontier column of THIS shard's column range
        mask = None
        if local.inc_src is not None:
            sb = spec.source_bits(ctx, state, k)
            sb_pad = ((0, Co * n_col - n),) + ((0, 0),) * (sb.ndim - 1)
            sb_local = jax.lax.dynamic_slice_in_dim(
                jnp.pad(sb, sb_pad), j * n_col, n_col, axis=0)
            mask = dm.push_tile_mask(local, sb_local)
        return _sweep(spec, local, x_local, w, mask, None, backend,
                      pull=False)

    def pull_fn(state):
        nf = spec.not_final(ctx, state)
        nf_rows = nf.any(axis=-1) if nf.ndim > 1 else nf
        mask = _pull_tile_mask(local, nf_rows)
        return _sweep(spec, local, x_local, w, mask, nf, backend, pull=True)

    if direction == "push":
        y = push_fn(state)
    elif direction == "pull":
        y = pull_fn(state)
    else:
        y = jax.lax.cond(dnow == dm.PUSH, push_fn, pull_fn, state)

    axes = tuple(col_axes) + tuple(row_axes)
    if comm == "allreduce":
        y = sr.pall(y, axes)
    else:  # "reduce_gather": semiring-reduce over columns, gather over rows
        y = sr.pall(y, tuple(col_axes))
        y = sr.pall(y, tuple(row_axes))
    return spec.update(ctx, state, y, k)


def dist_choose_direction(spec: FixpointSpec, ctx, deg, state, k, dcur, n: int):
    """Replicated Beamer α/β choice for the distributed strategy.

    Batched specs collapse to ONE direction for the whole batch (mean of the
    per-column statistics): one SpMM sweep advances every column on each
    active tile, so the union tile mask is the only one that matters — the
    batch-level switch keeps the introspection meaningful while every
    column stays exact.
    """
    sb = spec.source_bits(ctx, state, k)
    nf = spec.not_final(ctx, state)
    mf, mu, nnz_f = dm.edge_counts(deg, sb, nf)
    if spec.batched:
        mf, mu, nnz_f = mf.mean(), mu.mean(), nnz_f.mean()
    return dm.choose_direction(dcur, mf, mu, nnz_f, n)
