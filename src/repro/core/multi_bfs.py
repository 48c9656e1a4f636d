"""Batched multi-source algebraic BFS: many roots as one semiring SpMM.

Graph500 runs BFS from 64 sampled roots over the same graph. Running them
one at a time leaves the vector units underfilled — each SpMV gathers one
scalar per edge. Batching B roots turns the frontier vector [n] into a
frontier *matrix* [n, B] and every iteration into a semiring SpMM
(matrix-centric traversal, cf. Graph Traversal on Tensor Cores /
Bit-GraphBLAS): one gather of ``X[col, :]`` now advances B traversals, the
adjacency structure is read once per iteration instead of once per root, and
on TPU the B axis maps onto the lane dimension of the SlimSell SpMM kernel.

All four paper semirings are supported; the per-column math is identical to
single-source BFS (it shares ``bfs.semiring_update`` verbatim). The module
is the *batched spec* over ``core.engine`` — the iteration machinery
(fused while_loop, union SlimWork masks, per-column direction state) is the
engine's; this file owns only the [n, B] state algebra.

SlimWork generalizes column-wise: a chunk is active if ANY root can still
improve one of its rows, so the batch shares one tile mask (the union of
per-root masks — batching trades some work-skipping for structure reuse;
the crossover is measured by benchmarks/bench_multisource.py).

Iterations run to the max depth over the batch: converged columns simply stop
changing (their frontier no longer produces new vertices), which is exact for
every semiring.

Direction optimization is **per column**: each root carries its own
push/pull state in the while_loop carry (``direction="auto"`` runs Beamer's
alpha/beta heuristic on per-column frontier statistics), and the per-column
directions compose into a single *union* tile mask. ``direction="pull"``
runs the true batched bottom-up sweep (``slimsell_pull_mm``): the jnp path
is the row-masked SpMM oracle; the pallas path early-exits per (chunk row,
batch column).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import direction as dm
from . import engine as eng
from . import packing
from . import semiring as sm
from .bfs import (_check_bfs_options, _check_packed, _frontier_payload,
                  _host_direction_bits, _ids1, _not_final, dp_transform,
                  semiring_update)
from .engine import DIRECTIONS, WORK_LOG, FixpointSpec  # noqa: F401
from .options import EngineConfig, resolve_config

Array = jax.Array


@dataclasses.dataclass
class MultiBFSResult:
    """What ``multi_source_bfs`` returns: one row per root, vertex space.

    Semantically ``distances[i]`` equals ``bfs(tiled, roots[i]).distances``
    — batching changes the schedule (one SpMM advances every root), never
    the answer. The per-semiring storage/work tradeoff is the single-source
    one (see ``core.bfs`` / ``core.semiring``) scaled by the batch width B.
    """
    distances: np.ndarray          # int32[n_roots, n]; -1 unreachable
    parents: Optional[np.ndarray]  # int32[n_roots, n]; root -> root
    iterations: np.ndarray         # int32[n_batches] while-loop trips per batch
    roots: np.ndarray              # int32[n_roots]
    work_log: Optional[np.ndarray] = None  # int32[n_batches, WORK_LOG]
    pull_cols_log: Optional[np.ndarray] = None  # int32[n_batches, WORK_LOG]:
    # columns running pull per iteration (direction="auto" introspection)


# ------------------------------------------------------------------ state ops


def _init_state_multi(sr_name: str, n: int, roots: Array):
    """Batched ``bfs._init_state``: every field gains a trailing B axis."""
    B = roots.shape[0]
    cols = jnp.arange(B)
    d = jnp.full((n, B), -1, jnp.int32).at[roots, cols].set(0)
    if sr_name == "tropical":
        f = jnp.full((n, B), jnp.inf, jnp.float32).at[roots, cols].set(0.0)
        return {"d": d, "f": f}
    if sr_name == "real":
        f = jnp.zeros((n, B), jnp.float32).at[roots, cols].set(1.0)
        v = jnp.zeros((n, B), bool).at[roots, cols].set(True)
        return {"d": d, "f": f, "visited": v}
    if sr_name == "boolean":
        f = jnp.zeros((n, B), jnp.int32).at[roots, cols].set(1)
        v = jnp.zeros((n, B), bool).at[roots, cols].set(True)
        return {"d": d, "f": f, "visited": v}
    if sr_name == "selmax":
        r1 = roots.astype(jnp.float32) + 1.0
        x = jnp.zeros((n, B), jnp.float32).at[roots, cols].set(r1)
        p = jnp.zeros((n, B), jnp.float32).at[roots, cols].set(r1)
        return {"d": d, "x": x, "p": p}
    raise ValueError(sr_name)


def _iter_batches(roots: np.ndarray, batch_size: Optional[int]):
    """Resolve the device batch width and yield ``(start, batch, padded)``
    slices — the batching scaffold shared by the multi-source BFS and SSSP
    front doors.

    The width defaults to all roots in one batch. The final partial batch
    is padded by repeating its last root — callers drop the padded columns.
    """
    B = int(batch_size) if batch_size is not None else roots.size
    if B <= 0:
        raise ValueError(f"batch_size must be positive, got {batch_size}")
    for start in range(0, roots.size, B):
        batch = roots[start:start + B]
        pad = B - batch.size
        batch_p = np.concatenate([batch, np.repeat(batch[-1:], pad)]) \
            if pad else batch
        yield start, batch, batch_p


# ----------------------------------------------------------------------- spec


@functools.lru_cache(maxsize=None)
def multi_bfs_spec(sr_name: str) -> FixpointSpec:
    """Multi-source BFS as a batched fixpoint spec: the single-source state
    algebra with a trailing B axis (``bfs``'s extractors are shape-agnostic
    and are reused verbatim); the engine supplies the union-mask SpMM loop
    and the per-column direction carry."""
    def host_bits(state, k, need_sb, need_nf):
        # the single-source host twin is shape-agnostic ([n, B] matrices in,
        # [n, B] bits out); run_hostloop unions the columns into the shared
        # tile set
        nf, fb = _host_direction_bits(sr_name, state, int(k),
                                      need_nf=need_nf, need_fb=need_sb)
        return fb, nf

    return FixpointSpec(
        name=f"multi_bfs/{sr_name}",
        sr_name=sr_name,
        batched=True,
        directions=DIRECTIONS,
        init_state=lambda n, roots, ctx: _init_state_multi(sr_name, n, roots),
        frontier=lambda ctx, state, k: _frontier_payload(sr_name, state),
        source_bits=lambda ctx, state, k: dm.frontier_bits(sr_name, state, k),
        not_final=lambda ctx, state: _not_final(sr_name, state),
        update=lambda ctx, state, y, k: semiring_update(sr_name, state, y, k,
                                                        _ids1(y)),
        host_bits=host_bits,
    )


@functools.lru_cache(maxsize=None)
def packed_multi_bfs_spec(B: int) -> FixpointSpec:
    """SlimSell-B multi-source BFS: ``B`` Graph500 roots become
    ``ceil(B/32)`` packed *planes* — frontier/visited are ``uint32[n,
    ceil(B/32)]`` words (roots packed along axis 1) and one word-wise SpMM
    advances 32 traversals per lane element.

    Same per-column recurrence as ``multi_bfs_spec("boolean")``; the mask
    math is word-wise and only the distance stamp unpacks. Cached per batch
    width ``B`` (the plane geometry must be static in the jitted loop).
    Push-only — see ``FixpointSpec.packed``.
    """

    def init_state(n, roots, ctx):
        cols = jnp.arange(B)
        d = jnp.full((n, B), -1, jnp.int32).at[roots, cols].set(0)
        bits = jnp.zeros((n, B), bool).at[roots, cols].set(True)
        f = packing.pack_bits(bits, axis=1)          # [n, ceil(B/32)]
        return {"d": d, "f": f, "visited": f}

    def update(ctx, state, y, k):
        new_w = y & ~state["visited"]
        visited = state["visited"] | new_w
        new_bits = packing.unpack_bits(new_w, B, axis=1)   # [n, B]
        d = jnp.where(new_bits, k.astype(jnp.int32), state["d"])
        return ({"d": d, "f": new_w, "visited": visited},
                jnp.any(new_w != jnp.asarray(0, jnp.uint32)))

    def host_bits(state, k, need_sb, need_nf):
        # push-only: only source bits are ever requested; run_hostloop
        # unions the unpacked columns into the shared tile set
        sb = packing.unpack_bits_np(np.asarray(state["f"]), B, axis=1) \
            if need_sb else None
        return sb, None

    return FixpointSpec(
        name="multi_bfs/boolean_packed",
        sr_name="boolean_packed",
        batched=True,
        directions=("push",),
        packed=True,
        init_state=init_state,
        frontier=lambda ctx, state, k: state["f"],
        source_bits=lambda ctx, state, k: packing.unpack_bits(
            state["f"], B, axis=1),
        update=update,
        host_bits=host_bits,
    )


# ----------------------------------------------------------------- public API


def multi_source_bfs(tiled, roots: Sequence[int],
                     semiring: str = "tropical", *,
                     need_parents: bool = False, slimwork: bool = True,
                     packed: bool = False,
                     batch_size: Optional[int] = None,
                     max_iters: Optional[int] = None,
                     log_work: bool = False,
                     backend: Optional[str] = None,
                     direction: Optional[str] = None,
                     mode: Optional[str] = None,
                     config: Optional[EngineConfig] = None) -> MultiBFSResult:
    """BFS from every root in ``roots``; one fused SpMM loop per batch.

    batch_size: roots per device batch (None -> all roots in one batch). The
    final partial batch is padded by repeating its last root; padded columns
    are dropped before returning.
    config: the engine knobs as one ``EngineConfig`` — backend "jnp"
    (reference) or "pallas" (SlimSell TPU SpMM kernel); direction "push" |
    "pull" | "auto" (with "auto" every column carries its own Beamer
    direction state; ``pull_cols_log`` under ``log_work`` reports how many
    columns ran pull per iteration); mode "fused" or "hostloop" (the batched
    hostloop is push-only — union tile masks, one host sweep per level).
    The per-call ``backend``/``direction``/``mode`` kwargs are the
    deprecated spelling.
    packed: SlimSell-B — pack the B root columns into ``ceil(B/32)`` uint32
    word planes and sweep word-wise (requires ``semiring="boolean"``, push
    direction); bit-identical distances, 32x narrower frontier state.
    """
    cfg = resolve_config("multi_source_bfs", config, mode=mode,
                         backend=backend, direction=direction)
    _check_bfs_options("multi_source_bfs", semiring, cfg.direction)
    if packed:
        _check_packed("multi_source_bfs", semiring, cfg.direction)
    if cfg.direction in ("push", "auto") and slimwork \
            and getattr(tiled, "inc_src", None) is None:
        raise ValueError("direction-optimizing push masks need the push index;"
                         " rebuild the layout with formats.build_slimsell")
    roots = np.asarray(roots, np.int32).reshape(-1)
    if roots.size == 0:
        raise ValueError("multi_source_bfs needs at least one root")
    n = tiled.n
    max_iters = int(max_iters) if max_iters is not None else n

    d_out = np.empty((roots.size, n), np.int32)
    p_out = np.empty((roots.size, n), np.int32) if need_parents else None
    iters, work_rows, plog_rows = [], [], []
    for start, batch, batch_p in _iter_batches(roots, batch_size):
        # the packed spec's plane geometry is static per batch width
        spec = packed_multi_bfs_spec(batch_p.size) if packed \
            else multi_bfs_spec(semiring)
        with cfg.applied():
            if cfg.mode == "fused":
                res = eng.run_fused(spec, tiled, jnp.asarray(batch_p),
                                    slimwork=slimwork, max_iters=max_iters,
                                    log_work=log_work, backend=cfg.backend,
                                    direction=cfg.direction)
            else:
                res = eng.run_hostloop(spec, tiled, jnp.asarray(batch_p),
                                       slimwork=slimwork, max_iters=max_iters,
                                       backend=cfg.backend,
                                       direction=cfg.direction)
        state = res.state
        d = np.asarray(state["d"]).T          # [B, n]
        d_out[start:start + batch.size] = d[: batch.size]
        if need_parents:
            if semiring == "selmax":
                p = np.asarray(state["p"].astype(jnp.int32) - 1).T
            else:
                p = np.asarray(dp_transform(tiled, state["d"], batch_p))
            p_out[start:start + batch.size] = p[: batch.size]
            for b, r in enumerate(batch):
                p_out[start + b, int(r)] = int(r)
        iters.append(res.iterations)
        if log_work:
            work_rows.append(np.asarray(res.work_log, np.int32))
            plog_rows.append(
                None if res.pull_cols_log is None
                else np.asarray(res.pull_cols_log, np.int32))
    wl = plog = None
    if log_work:
        # fused rows are fixed WORK_LOG length; hostloop rows are one entry
        # per executed level — pad to the longest so batches stack
        width = max(w.size for w in work_rows)
        wl = np.zeros((len(work_rows), width), np.int32)
        plog = np.zeros((len(work_rows), width), np.int32)
        for i, w in enumerate(work_rows):
            wl[i, : w.size] = w
            p = plog_rows[i]
            if p is not None:
                plog[i, : p.size] = p
    return MultiBFSResult(
        distances=d_out, parents=p_out, iterations=np.asarray(iters, np.int32),
        roots=roots, work_log=wl, pull_cols_log=plog)
