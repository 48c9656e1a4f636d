"""Batched multi-source delta-stepping SSSP: many roots as one min-plus SpMM.

The Graph500 SSSP kernel is inherently 64-root, and running delta-stepping
once per root leaves the same vectorization on the table that per-root BFS
did before ``core.multi_bfs``: every relaxation sweep gathers one scalar per
edge. Batching B roots turns the distance vector [n] into a distance
*matrix* [n, B] and every relaxation into a **weighted min-plus SpMM** over
SlimSell-W,

    Y[v, r] = min_u ( w(v, u) + X[u, r] ),

so one sweep reads the adjacency (and the weight slots) once and relaxes B
shortest-path trees at once — the matrix-centric batching win of
Bit-GraphBLAS, applied to the weighted kernel. On TPU the root axis maps
onto the lane dimension of the stored-weight SpMM kernel
(``kernels/slimsell_spmm.py``), whose ``wts`` block rides the cols block's
scalar-prefetch indirection.

This module is the *batched spec* over ``core.engine``, mirroring
``multi_bfs``: the engine supplies the fused while_loop, the union SlimWork
masks and the hostloop tile gathering; this file owns only the [n, B] state
algebra. Delta buckets are **per column**: each root carries its own phase
(light fixpoint vs heavy settle), bucket index, bucket count and done flag
in the state, exactly like ``multi_bfs``'s per-column direction state, and
the per-column source sets union into one shared tile mask.

**One sweep operand for mixed phases.** The per-root spec (``core.sssp``)
switches between light/heavy +inf-masked weight views with a scalar
``lax.cond`` on the phase — but batched columns occupy *different* phases
at the same time, and one SpMM sweep carries one weight operand. The
batched spec therefore sweeps with the **full** weight array and lets the
per-column phase machine gate only the *source sets*. This is exact, not an
approximation, and it reproduces the per-root schedule sweep-for-sweep:

* a heavy edge (w > delta) relaxed early from a bucket-b source lands at
  ``dist + w > (b+1)*delta`` — strictly past bucket b — so it can never
  enter the current bucket's active set and never perturbs the light
  fixpoint's iteration count;
* committing such an improvement early is harmless: it is a valid path
  length, merged with min, and the heavy-phase sweep re-relaxes from the
  bucket's *final* values anyway, so the distances at every bucket jump are
  identical to the light/heavy-view engine's;
* light edges from the settled bucket are already at their fixpoint when
  the heavy phase fires, so the full-weight heavy sweep produces exactly
  the heavy-view improvements.

Hence ``multi_source_sssp(...).distances[i]``, ``.sweeps[i]`` and
``.buckets[i]`` all equal the per-root ``sssp(tiled, roots[i], ...)``
results — batching changes the schedule, never the answer (asserted by
``tests/test_multi_sssp.py``).

Columns converge independently: a finished column's source set is empty
(its frontier contributes only +inf) and its phase/bucket counters freeze;
the batch terminates when every column is done.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import engine as eng
from .engine import FixpointSpec
from .multi_bfs import _iter_batches
from .options import EngineConfig, MODES, check_choice, resolve_config
from .sssp import (_HEAVY, _LIGHT, _require_weighted, _resolve_delta,
                   sssp_parents)

Array = jax.Array


@dataclasses.dataclass
class MultiSSSPResult:
    """What ``multi_source_sssp`` returns: one row per root, vertex space.

    Semantically row i equals ``sssp(tiled, roots[i]).distances`` (and the
    per-root ``sweeps``/``buckets`` match too) — batching changes the
    schedule, never the answer.
    """
    distances: np.ndarray          # float32[n_roots, n]; +inf unreachable
    parents: Optional[np.ndarray]  # int32[n_roots, n]; root -> root
    sweeps: np.ndarray             # int32[n_roots] relaxation sweeps per root
    buckets: np.ndarray            # int32[n_roots] delta buckets per root
    iterations: np.ndarray         # int32[n_batches] engine trips per batch
    delta: float                   # bucket width actually used
    roots: np.ndarray              # int32[n_roots]
    work_log: Optional[np.ndarray] = None  # int32[n_batches, WORK_LOG]


# ----------------------------------------------------------------------- spec


def _begin_bucket_cols(dist: Array, settled: Array, delta: Array):
    """Per-column ``sssp._begin_bucket``: (bucket index [B], members [n, B],
    any live? [B]) — the jump to each column's next non-empty bucket."""
    live = ~settled & jnp.isfinite(dist)                       # [n, B]
    b = jnp.floor(jnp.min(jnp.where(live, dist, jnp.inf), axis=0) / delta)
    active = live & (jnp.floor(dist / delta) == b[None, :])
    return b, active, jnp.any(live, axis=0)


def _msssp_setup(tiled, delta):
    """Per-run constants: the full weight slots (one operand serves every
    column's phase — see the module docstring) and the bucket width.

    ``wts`` is a tile-space leaf ([T, C, L]), so the engine's hostloop
    subset step gathers it alongside ``cols``.
    """
    return {"wts": tiled.wts, "delta": jnp.asarray(delta, jnp.float32)}


def _msssp_init(n: int, roots, ctx):
    B = roots.shape[0]
    cols = jnp.arange(B)
    dist = jnp.full((n, B), jnp.inf, jnp.float32).at[roots, cols].set(0.0)
    settled = jnp.zeros((n, B), bool)
    b, active, live = _begin_bucket_cols(dist, settled, ctx["delta"])
    return {"dist": dist, "settled": settled,
            "removed": jnp.zeros((n, B), bool), "active": active,
            "phase": jnp.full((B,), _LIGHT, jnp.int32), "b": b,
            "buckets": jnp.zeros((B,), jnp.int32),
            "sweeps": jnp.zeros((B,), jnp.int32),
            "done": ~live}


def _msssp_sources(ctx, state, k) -> Array:
    """Per-column source sets: the bucket's light-fixpoint frontier for
    columns in the light phase, everything the bucket processed for columns
    firing their heavy shot, nothing for finished columns."""
    src = jnp.where((state["phase"] == _LIGHT)[None, :], state["active"],
                    state["removed"])
    return src & ~state["done"][None, :]


def _msssp_frontier(ctx, state, k) -> Array:
    return jnp.where(_msssp_sources(ctx, state, k), state["dist"], jnp.inf)


def _msssp_update(ctx, state, y: Array, k):
    """One batched relaxation merge + B independent phase machines.

    The light and heavy outcomes are both computed (they are cheap [n, B]
    masks) and selected per column — the vectorized counterpart of the
    per-root spec's ``lax.cond``; finished columns keep their state
    verbatim so their counters stay comparable to the per-root runs.
    """
    delta = ctx["delta"]
    is_light = state["phase"] == _LIGHT                        # [B]
    done = state["done"]                                       # [B]
    nd = jnp.minimum(state["dist"], y)
    nd = jnp.where(done[None, :], state["dist"], nd)
    improved = nd < state["dist"]

    # light outcome: re-enter the within-bucket fixpoint with improvements
    # that landed back in bucket b; once none do, switch to the heavy phase
    removed_l = state["removed"] | state["active"]
    active_l = improved & (jnp.floor(nd / delta) == state["b"][None, :])
    has_more = jnp.any(active_l, axis=0)
    phase_l = jnp.where(has_more, _LIGHT, _HEAVY).astype(jnp.int32)

    # heavy outcome: commit the settled bucket, jump to the next non-empty
    settled_h = state["settled"] | state["removed"]
    b_h, active_h, live_h = _begin_bucket_cols(nd, settled_h, delta)

    def sel(light_val, heavy_val, old):
        """Per-column light/heavy select, frozen where the column is done."""
        m, d = (is_light, done) if light_val.ndim == 1 \
            else (is_light[None, :], done[None, :])
        return jnp.where(d, old, jnp.where(m, light_val, heavy_val))

    new = {
        "dist": nd,
        "settled": sel(state["settled"], settled_h, state["settled"]),
        "removed": sel(removed_l, jnp.zeros_like(state["removed"]),
                       state["removed"]),
        "active": sel(active_l, active_h, state["active"]),
        "phase": sel(phase_l, jnp.full_like(state["phase"], _LIGHT),
                     state["phase"]),
        "b": sel(state["b"], b_h, state["b"]),
        "buckets": sel(state["buckets"], state["buckets"] + 1,
                       state["buckets"]),
        "sweeps": jnp.where(done, state["sweeps"], state["sweeps"] + 1),
    }
    new["done"] = done | (~is_light & ~live_h)
    return new, jnp.any(~new["done"])


def _msssp_host_bits(state, k, need_sb, need_nf):
    """Host twin: the per-column source matrix [n, B] (the engine unions it
    over columns for the shared SlimWork tile set)."""
    phase = np.asarray(state["phase"])
    done = np.asarray(state["done"])
    sb = np.where((phase == _LIGHT)[None, :], np.asarray(state["active"]),
                  np.asarray(state["removed"])) & ~done[None, :]
    return sb, None


MULTI_SSSP_SPEC = FixpointSpec(
    name="multi_sssp",
    sr_name="minplus",
    batched=True,
    directions=("push",),
    init_state=_msssp_init,
    frontier=_msssp_frontier,
    source_bits=_msssp_sources,
    not_final=lambda ctx, state: ~state["settled"] & jnp.isfinite(state["dist"]),
    update=_msssp_update,
    setup=_msssp_setup,
    weights=lambda ctx, state: ctx["wts"],
    host_bits=_msssp_host_bits,
)


# ----------------------------------------------------------------- public API


def multi_source_sssp(tiled, roots: Sequence[int], *,
                      delta: Optional[float] = None,
                      need_parents: bool = False, slimwork: bool = True,
                      mode: Optional[str] = None,
                      batch_size: Optional[int] = None,
                      max_iters: Optional[int] = None,
                      log_work: bool = False,
                      backend: Optional[str] = None,
                      config: Optional[EngineConfig] = None
                      ) -> MultiSSSPResult:
    """Delta-stepping SSSP from every root in ``roots``; one fused min-plus
    SpMM loop per batch.

    delta: bucket width shared by every column (None -> mean edge weight;
    ``inf`` -> batched Bellman-Ford).
    config: the engine knobs as one ``EngineConfig`` — mode "fused" (one
    flattened lax.while_loop on device) or "hostloop" (host loop + union
    SlimWork tile gathering per sweep); backend "jnp" (reference) or
    "pallas" (stored-weight SlimSell SpMM kernel; batch widths over 128
    are padded to whole 128-lane tiles).
    Delta-stepping is push-only, so the config's direction must stay the
    default "push". The per-call ``mode``/``backend`` kwargs are the
    deprecated spelling.
    batch_size: roots per device batch (None -> all roots in one batch). The
    final partial batch is padded by repeating its last root; padded columns
    are dropped before returning.
    Returns per-root float32 distances (+inf unreachable), per-root
    sweep/bucket counts that match the per-root ``sssp`` engine exactly,
    and, when requested, shortest-path-tree parents via the weighted DP
    sweep (``sssp_parents``, one sweep per batch).
    """
    cfg = resolve_config("multi_source_sssp", config, mode=mode,
                         backend=backend)
    check_choice("direction", cfg.direction, MULTI_SSSP_SPEC.directions,
                 hint="delta-stepping relaxations are push-only")
    _require_weighted(tiled)
    if slimwork and getattr(tiled, "inc_src", None) is None:
        raise ValueError("SlimWork source masks need the push index; rebuild "
                         "the layout with formats.build_slimsell")
    delta = _resolve_delta(tiled, delta)
    roots = np.asarray(roots, np.int32).reshape(-1)
    if roots.size == 0:
        raise ValueError("multi_source_sssp needs at least one root")
    n = tiled.n
    if not ((0 <= roots) & (roots < n)).all():
        bad = roots[(roots < 0) | (roots >= n)][0]
        raise ValueError(f"root {bad} out of range for n={n}")
    max_iters = int(max_iters) if max_iters is not None else 4 * n + 16
    ctx_args = (jnp.asarray(delta, jnp.float32),)

    d_out = np.empty((roots.size, n), np.float32)
    p_out = np.empty((roots.size, n), np.int32) if need_parents else None
    sweeps = np.empty(roots.size, np.int32)
    buckets = np.empty(roots.size, np.int32)
    iters, work_rows = [], []
    for start, batch, batch_p in _iter_batches(roots, batch_size):
        with cfg.applied():
            if cfg.mode == "fused":
                res = eng.run_fused(MULTI_SSSP_SPEC, tiled,
                                    jnp.asarray(batch_p),
                                    ctx_args=ctx_args, slimwork=slimwork,
                                    max_iters=max_iters, log_work=log_work,
                                    backend=cfg.backend)
            else:
                res = eng.run_hostloop(MULTI_SSSP_SPEC, tiled,
                                       jnp.asarray(batch_p),
                                       ctx_args=ctx_args,
                                       slimwork=slimwork,
                                       max_iters=max_iters,
                                       backend=cfg.backend)
        state = res.state
        d = np.asarray(state["dist"]).T                        # [B, n]
        d_out[start:start + batch.size] = d[: batch.size]
        sweeps[start:start + batch.size] = \
            np.asarray(state["sweeps"])[: batch.size]
        buckets[start:start + batch.size] = \
            np.asarray(state["buckets"])[: batch.size]
        if need_parents:
            p = np.asarray(sssp_parents(tiled, state["dist"], batch_p))
            p_out[start:start + batch.size] = p[: batch.size]
        iters.append(res.iterations)
        if log_work:
            work_rows.append(np.asarray(res.work_log, np.int32))
    wl = None
    if log_work:
        # fused rows are fixed WORK_LOG length; hostloop rows are one entry
        # per executed sweep — pad to the longest so batches stack
        width = max(w.size for w in work_rows)
        wl = np.zeros((len(work_rows), width), np.int32)
        for i, w in enumerate(work_rows):
            wl[i, : w.size] = w
    return MultiSSSPResult(
        distances=d_out, parents=p_out, sweeps=sweeps, buckets=buckets,
        iterations=np.asarray(iters, np.int32), delta=delta, roots=roots,
        work_log=wl)
