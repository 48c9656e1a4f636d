"""Algebraic BFS over SlimSell (paper §III): four semirings, SlimWork, DP,
and direction-optimizing (push/pull/auto) traversal.

One BFS iteration is one semiring sweep (``core.spmv``) plus a semiring-
specific state update. What the sweep's payload carries — and what auxiliary
state the update therefore needs — is the paper's storage/work tradeoff
(§III-A, Table I; the full table lives in ``core.semiring``):

  ================ ========================== =============================
  semiring         payload / frontier         auxiliary state per vertex
  ================ ========================== =============================
  ``tropical``     float distances in-band    none (inf == unvisited)
  ``real``         float path counts          visited bitmap + d
  ``boolean``      int32 reachability bits    visited bitmap + d
  ``selmax``       float 1-based parent ids   parent array p + d
  ================ ========================== =============================

tropical needs no filtering step but pays a float frontier; boolean has the
narrowest payload but filters through the bitmap each iteration; sel-max is
the only semiring whose result *is* the BFS tree (no DP post-pass), at the
cost of two float vectors. The other three get parents from one sel-max DP
sweep (``dp_transform``).

This module owns only the BFS *state algebra* — init, frontier/not-final
bits, the per-semiring update, and the DP transform. The iteration itself
(fused ``lax.while_loop``, hostloop with SlimWork tile gathering, or the
2D-distributed strategy) lives in ``core.engine``; BFS is the spec
``bfs_spec(semiring)`` over it, exactly like ``multi_bfs``/``sssp``/``cc``.
The engine knobs (``backend``, ``mode``, ``direction``, ``slimwork``) mean
the same thing everywhere.

Directions (core.direction, paper §V / Beamer et al.):

* ``direction="push"``  — top-down: tiles selected through the frontier-column
  push index; work ∝ edges out of the frontier.
* ``direction="pull"``  — bottom-up: ``slimsell_pull`` over not-final rows
  (SlimWork's own criterion), per-row early exit on the pallas backend; work
  ∝ edges of the unexplored rows.
* ``direction="auto"``  — per-iteration alpha/beta switch between the two.

All three give identical distances and valid (possibly different) parents.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import direction as dm
from . import engine as eng
from . import packing
from . import semiring as sm
from . import spmv
from .engine import DIRECTIONS, WORK_LOG, FixpointSpec  # noqa: F401 (re-export)
from .options import EngineConfig, MODES, check_choice, resolve_config

Array = jax.Array


@dataclasses.dataclass
class BFSResult:
    """What ``bfs`` returns, all in original (pre-σ-sort) vertex space.

    ``work_log``/``directions`` are populated when ``log_work=True`` or
    ``mode="hostloop"``; both are introspection, not part of the answer.
    """
    distances: np.ndarray          # int32[n]; -1 unreachable
    parents: Optional[np.ndarray]  # int32[n]; parent in BFS tree; root -> root
    iterations: int
    work_log: Optional[np.ndarray] = None  # active tiles per iteration
    directions: Optional[np.ndarray] = None  # int32 per iteration; 0=push 1=pull


# ------------------------------------------------------------------ state ops


def _init_state(sr_name: str, n: int, root):
    d = jnp.full((n,), -1, jnp.int32).at[root].set(0)
    if sr_name == "tropical":
        f = jnp.full((n,), jnp.inf, jnp.float32).at[root].set(0.0)
        return {"d": d, "f": f}
    if sr_name == "real":
        f = jnp.zeros((n,), jnp.float32).at[root].set(1.0)
        return {"d": d, "f": f, "visited": jnp.zeros((n,), bool).at[root].set(True)}
    if sr_name == "boolean":
        f = jnp.zeros((n,), jnp.int32).at[root].set(1)
        return {"d": d, "f": f, "visited": jnp.zeros((n,), bool).at[root].set(True)}
    if sr_name == "selmax":
        x = jnp.zeros((n,), jnp.float32).at[root].set(jnp.asarray(root, jnp.float32) + 1.0)
        p = jnp.zeros((n,), jnp.float32).at[root].set(jnp.asarray(root, jnp.float32) + 1.0)
        return {"d": d, "x": x, "p": p}
    raise ValueError(sr_name)


def _not_final(sr_name: str, state) -> Array:
    """bool[n]: True where the output value can still change (SlimWork §III-C)."""
    if sr_name == "tropical":
        return jnp.isinf(state["f"])
    if sr_name in ("real", "boolean"):
        return ~state["visited"]
    return state["p"] == 0.0


def _frontier_payload(sr_name: str, state) -> Array:
    return state["x"] if sr_name == "selmax" else state["f"]


def _ids1(y: Array) -> Array:
    """1-based vertex ids shaped like the sweep result (sel-max payload)."""
    ids = jnp.arange(y.shape[0], dtype=jnp.float32) + 1.0
    return ids[:, None] if y.ndim == 2 else ids


def semiring_update(sr_name: str, state, y: Array, k: Array, ids1: Array):
    """Per-semiring state update given the SpMV/SpMM result ``y``.

    Shape-agnostic: shared by the single-source engine (y [n], ids1 [n]),
    the batched multi-source engine (y [n, B], ids1 [n, 1]) and the
    distributed engine (replicated y [n]).
    """
    if sr_name == "tropical":
        f_new = jnp.minimum(state["f"], y)  # accumulator init == implicit diagonal
        changed = jnp.any(f_new < state["f"])
        d = jnp.where(jnp.isfinite(f_new), f_new.astype(jnp.int32), -1)
        return {"d": d, "f": f_new}, changed
    if sr_name in ("real", "boolean"):
        new = (y > 0) & ~state["visited"]
        d = jnp.where(new, k.astype(jnp.int32), state["d"])
        visited = state["visited"] | new
        f = new.astype(state["f"].dtype)
        return {"d": d, "f": f, "visited": visited}, jnp.any(new)
    if sr_name == "selmax":
        new = (y > 0) & (state["p"] == 0.0)
        p = jnp.where(new, y, state["p"])
        d = jnp.where(new, k.astype(jnp.int32), state["d"])
        x = jnp.where(new, ids1, 0.0)
        return {"d": d, "x": x, "p": p}, jnp.any(new)
    raise ValueError(sr_name)


# host-side (numpy) twin of the bit extractors: the hostloop engine decides
# direction and gathers active tiles on host, so doing this math in numpy
# avoids ~20 device dispatches per BFS iteration


def _host_direction_bits(sr_name: str, state, k: int, *, need_nf: bool,
                         need_fb: bool):
    """(not_final, frontier) numpy bit vectors, each None unless requested.

    One np.asarray per state field: for tropical both vectors derive from
    the same device->host transfer of ``f``.
    """
    nf = fb = None
    if sr_name == "tropical":
        f = np.asarray(state["f"]) if (need_nf or need_fb) else None
        nf = np.isinf(f) if need_nf else None
        fb = (f == (k - 1)) if need_fb else None
    elif sr_name in ("real", "boolean"):
        nf = ~np.asarray(state["visited"]) if need_nf else None
        fb = (np.asarray(state["f"]) > 0) if need_fb else None
    else:
        nf = (np.asarray(state["p"]) == 0.0) if need_nf else None
        fb = (np.asarray(state["x"]) > 0) if need_fb else None
    return nf, fb


# ----------------------------------------------------------------------- spec


@functools.lru_cache(maxsize=None)
def bfs_spec(sr_name: str) -> FixpointSpec:
    """Single-source BFS as a fixpoint spec (one spec per semiring; cached
    so the engine's jit caches key on a stable object)."""

    def host_bits(state, k, need_sb, need_nf):
        nf, fb = _host_direction_bits(sr_name, state, int(k),
                                      need_nf=need_nf, need_fb=need_sb)
        return fb, nf

    return FixpointSpec(
        name=f"bfs/{sr_name}",
        sr_name=sr_name,
        directions=DIRECTIONS,
        init_state=lambda n, root, ctx: _init_state(sr_name, n, root),
        frontier=lambda ctx, state, k: _frontier_payload(sr_name, state),
        source_bits=lambda ctx, state, k: dm.frontier_bits(sr_name, state, k),
        not_final=lambda ctx, state: _not_final(sr_name, state),
        update=lambda ctx, state, y, k: semiring_update(sr_name, state, y, k,
                                                        _ids1(y)),
        host_bits=host_bits,
    )


@functools.lru_cache(maxsize=None)
def packed_bfs_spec(n: int) -> FixpointSpec:
    """SlimSell-B single-source BFS: the boolean BFS with its frontier and
    visited bitmaps bit-packed to ``uint32[ceil(n/32)]`` words.

    Same recurrence as ``bfs_spec("boolean")`` — reach, mask off visited,
    stamp distances — but the mask math is word-wise (OR/AND-NOT on packed
    words) and the sweep is the word-gather packed SpMV. Only the distance
    stamp unpacks (32x less state traffic per iteration). Cached per ``n``:
    the packed geometry (word count, live-bit slice) must be static inside
    the jitted loop, so it is closed over rather than carried in ctx.
    Push-only — see ``FixpointSpec.packed``.
    """

    def init_state(n_, root, ctx):
        d = jnp.full((n,), -1, jnp.int32).at[root].set(0)
        f = packing.pack_bits(jnp.zeros((n,), bool).at[root].set(True))
        return {"d": d, "f": f, "visited": f}

    def update(ctx, state, y, k):
        # y: packed reach bitmap. Word-wise newly-visited mask; tail bits
        # stay zero (y's are zero, AND preserves zero).
        new_w = y & ~state["visited"]
        visited = state["visited"] | new_w
        d = jnp.where(packing.unpack_bits(new_w, n), k.astype(jnp.int32),
                      state["d"])
        return ({"d": d, "f": new_w, "visited": visited},
                jnp.any(new_w != jnp.asarray(0, jnp.uint32)))

    def host_bits(state, k, need_sb, need_nf):
        # push-only spec: the hostloop only ever asks for source bits
        sb = packing.unpack_bits_np(np.asarray(state["f"]), n) \
            if need_sb else None
        return sb, None

    return FixpointSpec(
        name="bfs/boolean_packed",
        sr_name="boolean_packed",
        directions=("push",),
        packed=True,
        init_state=init_state,
        frontier=lambda ctx, state, k: state["f"],
        source_bits=lambda ctx, state, k: packing.unpack_bits(state["f"], n),
        update=update,
        host_bits=host_bits,
    )


# ---------------------------------------------------------------- DP transform


@functools.partial(jax.jit, static_argnames=("weighted", "rtol", "atol"))
def dp_transform(tiled, D: Array, roots, *, weighted: bool = False,
                 rtol: float = 1e-6, atol: float = 1e-6) -> Array:
    """p = DP(d): for each v pick a neighbor w with d[w] == d[v]-1 (paper §II-C),
    for every column of ``D`` in one sel-max SlimSell sweep.

    ``D`` is [n, B]: int32 BFS levels (-1 unreachable), or float32 distances
    (+inf unreachable) when ``weighted``; ``roots`` is int32[B]. Returns
    int32 parents [B, n], each root its own parent. A 1-D ``D`` [n] with a
    scalar root is one column and returns [n].

    Each slot (chunk row v, column u) gathers the row ``D[u, :]`` once and
    scores it against the chunk row's own ``D[v, :]``:

    * levels: ``u + 1`` where ``D[u] == D[v] - 1`` and v is reached and not
      a root;
    * ``weighted`` (``sssp.sssp_parents``): ``u + 1`` where the relaxation
      is tight, ``|D[u] + w - D[v]| <= atol + rtol * |D[v]|``, plus ``n``
      where ``w > 0``, so a positive-weight parent wins over a zero-weight
      one.

    The largest score over the slots, the SlimChunk tiles of a chunk and
    the chunk rows of a vertex names the parent. Tiles are swept in groups
    sized by ``spmv.gather_group``, as ``spmv.slimsell_spmm`` sweeps them,
    so no [T, C, L, B] block is built whole. O(m+n) work per column, O(1)
    depth. One program, cached on the layout's shapes, the width B and the
    dtype of ``D``.
    """
    single = D.ndim == 1
    if single:
        D = D[:, None]
    roots = jnp.reshape(roots, (-1,)).astype(jnp.int32)
    n, width = tiled.n, D.shape[1]
    if weighted and tiled.wts is None:
        raise ValueError("the weighted parent transform needs stored weights"
                         " (a layout built from a weighted CSR)")
    wts = tiled.wts if weighted else None

    def best_of(tile):
        cols, w, rb = tile                           # [C, L], [C, L], []
        pad = (cols < 0)[..., None]
        safe = jnp.where(cols < 0, 0, cols)
        rv = tiled.row_vertex[rb]                    # [C]
        d_row = jnp.take(D, jnp.where(rv < 0, 0, rv), axis=0)[:, None]
        d_nbr = jnp.take(D, safe, axis=0)            # [C, L, B]
        if not weighted:
            ok = ~pad & (d_row > 0) & (d_nbr == d_row - 1) & (d_nbr >= 0)
            best = jnp.where(ok, (safe + 1)[..., None], 0).max(axis=1)
        else:
            d_nbr = d_nbr + w[..., None]
            tight = ~pad & jnp.isfinite(d_row) \
                & (jnp.abs(d_nbr - d_row) <= atol + rtol * jnp.abs(d_row))
            score = (safe + 1).astype(jnp.float32) \
                + jnp.where(w > 0, float(n), 0.0)
            best = jnp.where(tight, score[..., None], 0.0).max(axis=1)
        return best.reshape(-1)                      # [C * B]

    group = spmv.gather_group(tiled.cols.shape, width, D.dtype.itemsize)
    tile_best = jax.lax.map(best_of, (tiled.cols, wts, tiled.row_block),
                            batch_size=group)        # [T, C * B]
    y_blocks = jax.ops.segment_max(tile_best, tiled.row_block,
                                   num_segments=tiled.n_chunks)
    rv = tiled.row_vertex.reshape(-1)
    ids = jnp.where(rv < 0, n, rv)
    p1 = jax.ops.segment_max(y_blocks.reshape(-1, width), ids,
                             num_segments=n + 1)[:n]
    if weighted:
        p1 = jnp.where(p1 > n, p1 - n, p1)  # strip the positive-weight bonus
    p = (p1.astype(jnp.int32) - 1).T
    p = p.at[jnp.arange(width), roots].set(roots)
    return p[0] if single else p


# ----------------------------------------------------------------- public API


def _check_bfs_options(fn_name: str, semiring: str, direction: str,
                       mode: Optional[str] = None):
    """Shared entry validation for the BFS-family front doors."""
    if semiring not in sm.BFS_SEMIRINGS:
        raise KeyError(f"{fn_name} supports {sm.BFS_SEMIRINGS}, got "
                       f"{semiring!r} (minplus is the weighted operator — "
                       "see core.sssp)")
    check_choice("direction", direction, DIRECTIONS)
    if mode is not None:
        check_choice("mode", mode, MODES)


def _check_packed(fn_name: str, semiring: str, direction: str):
    """Shared validation of the SlimSell-B ``packed=True`` flag: the packed
    path is the *boolean* recurrence over packed words, push-only."""
    if semiring != "boolean":
        raise ValueError(f"{fn_name}: packed=True is the bit-packed boolean "
                         f"path; got semiring={semiring!r}")
    if direction != "push":
        raise ValueError(f"{fn_name}: packed=True is push-only (packed "
                         "payloads carry no per-row ordering for the pull "
                         f"early-exit); got direction={direction!r}")


def bfs(tiled, root: int, semiring: str = "tropical", *,
        need_parents: bool = False, slimwork: bool = True,
        packed: bool = False,
        mode: Optional[str] = None, max_iters: Optional[int] = None,
        log_work: bool = False, backend: Optional[str] = None,
        direction: Optional[str] = None,
        config: Optional[EngineConfig] = None) -> BFSResult:
    """Run BFS from ``root``; returns distances (+parents) in vertex space.

    semiring: one of ``semiring.BFS_SEMIRINGS`` — see the module docstring
    for the storage/work tradeoff between them. All four produce identical
    distances; ``selmax`` also produces parents in-band, the others derive
    them with one DP sweep when ``need_parents=True``.
    config: the engine knobs as one validated ``EngineConfig`` record —
    mode "fused" (whole BFS is one ``lax.while_loop`` on device) or
    "hostloop" (host loop gathering only the active tiles per iteration);
    backend "jnp" (reference) or "pallas" (SlimSell TPU kernel engine);
    direction "push" (top-down SpMV), "pull" (bottom-up sweep over not-final
    rows), or "auto" (per-iteration Beamer alpha/beta switch — the direction
    trace is returned in ``BFSResult.directions`` when ``log_work`` is set or
    mode is "hostloop"). The per-call ``mode``/``backend``/``direction``
    kwargs are a deprecated spelling of the same knobs.
    slimwork: skip tiles that can no longer change the output (paper §III-C).
    packed: SlimSell-B — run the boolean recurrence over bit-packed
    ``uint32[ceil(n/32)]`` frontier/visited bitmaps and the word-wise sweep
    (requires ``semiring="boolean"``, push direction); bit-identical
    distances, 32x smaller frontier state.
    """
    cfg = resolve_config("bfs", config, mode=mode, backend=backend,
                         direction=direction)
    _check_bfs_options("bfs", semiring, cfg.direction)
    if packed:
        _check_packed("bfs", semiring, cfg.direction)
    if cfg.direction in ("push", "auto") and slimwork \
            and getattr(tiled, "inc_src", None) is None:
        raise ValueError("direction-optimizing push masks need the push index;"
                         " rebuild the layout with formats.build_slimsell")
    n = tiled.n
    if semiring == "selmax" and n > (1 << 24):
        # sel-max carries 1-based vertex ids in its float32 payload; ids
        # above 2^24 would round (same guard as core.cc)
        raise ValueError("selmax BFS carries vertex ids in float32 (exact "
                         f"up to 2^24); use another semiring for n={n}")
    max_iters = int(max_iters) if max_iters is not None else n
    root = jnp.asarray(root, jnp.int32)
    spec = packed_bfs_spec(n) if packed else bfs_spec(semiring)

    with cfg.applied():
        if cfg.mode == "fused":
            res = eng.run_fused(spec, tiled, root, slimwork=slimwork,
                                max_iters=max_iters, log_work=log_work,
                                backend=cfg.backend, direction=cfg.direction)
        else:
            res = eng.run_hostloop(spec, tiled, root, slimwork=slimwork,
                                   max_iters=max_iters, backend=cfg.backend,
                                   direction=cfg.direction)

    state, iters = res.state, res.iterations
    d = np.asarray(state["d"])
    parents = None
    if need_parents:
        if semiring == "selmax":
            parents = np.array(state["p"].astype(jnp.int32) - 1)
            parents[int(root)] = int(root)
        else:
            parents = np.asarray(dp_transform(tiled, jnp.asarray(d), root))
    wl = res.work_log if (log_work or cfg.mode == "hostloop") else None
    return BFSResult(distances=d, parents=parents, iterations=iters,
                     work_log=wl, directions=res.dirs_log)
