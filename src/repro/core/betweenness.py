"""Betweenness centrality (Brandes) on the SlimSell engine.

Brandes' algorithm is two sweep phases per source, both of which are
semiring SpMMs over the same layout the BFS family already uses:

* **forward** — a batched real-semiring multi-source BFS ([n, B] SpMM,
  one column per source) that, unlike ``multi_bfs``'s real spec, keeps the
  accumulated *path counts*: ``sigma[v] = number of shortest s->v paths``
  (the real-semiring sweep sums exactly the Brandes recurrence
  ``sigma[v] = sum_{u in pred(v)} sigma[u]``) alongside the depth stamp
  ``d[v]``.
* **backward** — dependency back-propagation over the recorded levels.
  Each column walks its depth levels from the deepest frontier toward the
  source; one real SpMM per level pushes ``(1 + delta[w]) / sigma[w]`` from
  level ``l`` and rows at level ``l-1`` accumulate
  ``delta[v] += sigma[v] * y[v]`` — Brandes' pairwise dependency without
  materializing the DAG (an adjacent vertex is a DAG successor iff its
  recorded depth is exactly one deeper, so the level masks select DAG edges
  implicitly). Per-column level counters live in the state; columns whose
  counter hits 0 go inert, so mixed-eccentricity batches stay exact.

Path counts ride in float32 (exact up to 2^24 like the sel-max labels);
the backward divisions use masked safe denominators so the checkify
sanitizer never sees a NaN/inf in a discarded branch.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import engine as eng
from .engine import FixpointSpec
from .multi_bfs import _init_state_multi, _iter_batches
from .options import EngineConfig, check_choice, resolve_config

Array = jax.Array


@dataclasses.dataclass
class BetweennessResult:
    scores: np.ndarray   # float64[n]; unnormalized (or nx-normalized) BC
    n_sources: int
    iterations: int      # total forward + backward sweeps across batches


# ------------------------------------------------------------- forward spec


def _fwd_init(n: int, roots: Array, ctx):
    state = _init_state_multi("real", n, roots)   # d / f / visited
    state["sigma"] = state["f"]                   # 1.0 at each root column
    return state


def _fwd_update(ctx, state, y: Array, k):
    new = (y > 0) & ~state["visited"]
    d = jnp.where(new, k.astype(jnp.int32), state["d"])
    sigma = jnp.where(new, y, state["sigma"])     # y = sum of pred sigmas
    f = jnp.where(new, y, 0.0)
    state = dict(state, d=d, sigma=sigma, f=f,
                 visited=state["visited"] | new)
    return state, jnp.any(new)


BRANDES_FORWARD_SPEC = FixpointSpec(
    name="betweenness/forward",
    sr_name="real",
    batched=True,
    directions=("push",),   # pull early-exit may truncate the sigma sums
    init_state=_fwd_init,
    frontier=lambda ctx, state, k: state["f"],
    source_bits=lambda ctx, state, k: state["f"] > 0,
    not_final=lambda ctx, state: ~state["visited"],
    update=_fwd_update,
    host_bits=lambda state, k, need_sb, need_nf:
        (np.asarray(state["f"]) > 0 if need_sb else None,
         ~np.asarray(state["visited"]) if need_nf else None),
)


# ------------------------------------------------------------ backward spec


def _bwd_frontier_mask(d: Array, level: Array) -> Array:
    """bool[n, B]: rows at each column's current level (inert columns off)."""
    return (d == level[None, :]) & (level >= 1)[None, :]


def _bwd_frontier(ctx, state, k):
    on = _bwd_frontier_mask(state["d"], state["level"])
    safe_sigma = jnp.where(on, state["sigma"], 1.0)
    return jnp.where(on, (1.0 + state["delta"]) / safe_sigma, 0.0)


def _bwd_update(ctx, state, y: Array, k):
    level = state["level"]
    active = level >= 1
    # DAG predecessors of the emitting level: adjacent AND exactly one
    # level shallower (the `active` gate keeps d == -1 rows from matching
    # level - 1 when a column has gone inert)
    tgt = active[None, :] & (state["d"] == (level - 1)[None, :])
    delta = state["delta"] + jnp.where(tgt, state["sigma"] * y, 0.0)
    level = jnp.where(active, level - 1, level)
    state = dict(state, delta=delta, level=level)
    return state, jnp.any(level >= 1)


def _bwd_host_bits(state, k, need_sb, need_nf):
    if not need_sb:
        return None, None
    d = np.asarray(state["d"])
    level = np.asarray(state["level"])
    return (d == level[None, :]) & (level >= 1)[None, :], None


BRANDES_BACKWARD_SPEC = FixpointSpec(
    name="betweenness/backward",
    sr_name="real",
    batched=True,
    directions=("push",),
    # d / sigma arrive via ctx_args (replicated operands under dist) and are
    # copied into the state: hostloop's weighted-path ctx gather assumes
    # ctx leaves lead with n_tiles, and state leaves dodge that hazard
    setup=lambda tiled, d, sigma: {"d": d, "sigma": sigma},
    init_state=lambda n, levels0, ctx:
        {"delta": jnp.zeros(ctx["d"].shape, jnp.float32),
         "level": levels0.astype(jnp.int32),
         "d": ctx["d"], "sigma": ctx["sigma"]},
    frontier=_bwd_frontier,
    source_bits=lambda ctx, state, k:
        _bwd_frontier_mask(state["d"], state["level"]),
    not_final=lambda ctx, state: state["d"] >= 0,
    update=_bwd_update,
    host_bits=_bwd_host_bits,
)


# ------------------------------------------------------------- accumulation


def brandes_accumulate(delta: np.ndarray, roots: np.ndarray,
                       n_real: Optional[int] = None) -> np.ndarray:
    """Fold one batch's dependency matrix into a BC partial sum.

    ``delta[:, b]`` is the dependency of every vertex on source
    ``roots[b]``; Brandes excludes the source itself, so its row is zeroed
    per column before summing. ``n_real`` drops padded trailing columns
    (batch padding repeats the last root, which would double count).
    """
    delta = np.asarray(delta, np.float64)
    if n_real is not None:
        delta = delta[:, :n_real]
        roots = roots[:n_real]
    delta = delta.copy()
    delta[np.asarray(roots), np.arange(roots.shape[0])] = 0.0
    return delta.sum(axis=1)


# ----------------------------------------------------------------- public API


def betweenness(tiled, sources: Optional[Sequence[int]] = None, *,
                normalized: bool = False, batch_size: Optional[int] = None,
                slimwork: bool = True, mode: Optional[str] = None,
                max_iters: Optional[int] = None,
                backend: Optional[str] = None,
                config: Optional[EngineConfig] = None) -> BetweennessResult:
    """Brandes betweenness centrality via batched semiring SpMM sweeps.

    sources: vertices to run Brandes from (default: all — exact BC).
    Sampling a subset gives the standard partial-source estimate, matching
    a reference Brandes restricted to the same sources.
    normalized: scale by ``2 / ((n-1)(n-2))`` (the networkx undirected
    convention); unnormalized scores count unordered vertex pairs, halved
    for the undirected doubling.
    batch_size: sources per [n, B] device batch (None -> all in one batch).
    """
    cfg = resolve_config("betweenness", config, mode=mode, backend=backend)
    check_choice("direction", cfg.direction, BRANDES_FORWARD_SPEC.directions,
                 hint="Brandes sweeps are push-only (pull early-exit could "
                      "truncate the path-count sums)")
    if slimwork and getattr(tiled, "inc_src", None) is None:
        raise ValueError("SlimWork masks need the push index; rebuild the "
                         "layout with formats.build_slimsell")
    n = tiled.n
    if n > (1 << 24):
        raise ValueError("betweenness carries path counts in float32 (exact "
                         f"up to 2^24); n={n} would round")
    roots = np.arange(n, dtype=np.int64) if sources is None \
        else np.asarray(list(sources), np.int64)
    if roots.size == 0:
        raise ValueError("betweenness: sources must be non-empty")
    if roots.min() < 0 or roots.max() >= n:
        raise ValueError(f"betweenness: sources out of range for n={n}")
    cap = int(max_iters) if max_iters is not None else n + 1
    bc = np.zeros(n, np.float64)
    iters = 0
    run = eng.run_fused if cfg.mode == "fused" else eng.run_hostloop
    with cfg.applied():
        for start, batch, batch_p in _iter_batches(roots, batch_size):
            roots_p = jnp.asarray(batch_p, jnp.int32)
            fwd = run(BRANDES_FORWARD_SPEC, tiled, roots_p,
                      slimwork=slimwork, max_iters=cap, backend=cfg.backend)
            d, sigma = fwd.state["d"], fwd.state["sigma"]
            levels0 = jnp.max(d, axis=0)  # per-column eccentricity
            bwd = run(BRANDES_BACKWARD_SPEC, tiled, levels0,
                      ctx_args=(d, sigma), slimwork=slimwork,
                      max_iters=cap, backend=cfg.backend)
            bc += brandes_accumulate(bwd.state["delta"], batch_p,
                                     n_real=batch.size)
            iters += fwd.iterations + bwd.iterations
    bc /= 2.0  # undirected: each unordered pair counted from both ends
    if normalized:
        scale = 2.0 / ((n - 1) * (n - 2)) if n > 2 else 0.0
        bc *= scale
    return BetweennessResult(scores=bc, n_sources=roots.size,
                             iterations=iters)
