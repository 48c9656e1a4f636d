"""Delta-stepping SSSP over the min-plus semiring on weighted SlimSell.

The paper closes by arguing its semiring/SpMV principles extend beyond BFS;
this module cashes that claim for single-source shortest paths. The algebra
is the tropical one BFS already uses — (min, +) — but the matrix operand is
the *weighted* SlimSell variant (``SlimSellTiled.wts`` alongside ``cols``,
SlimSell-W): one relaxation sweep is one min-plus SpMV,

    y[v] = min_u ( w(v, u) + x[u] ),    x[u] = dist[u] on the source set,

and ``dist' = min(dist, y)`` is a batch of edge relaxations.

The algorithm is Meyer & Sanders' delta-stepping, expressed entirely in
sweeps so it runs on the same engine strategies as BFS:

* vertices are bucketed by ``floor(dist / delta)``; buckets settle in order;
* **light** edges (w <= delta) are relaxed to a fixpoint *within* the current
  bucket (improvements can land back in the same bucket);
* **heavy** edges (w > delta) are relaxed once per bucket, after it settles
  (a heavy edge from bucket b always lands past bucket b).

Since PR 4 the nested bucket/fixpoint loops are *flattened* into one
``core.engine`` fixpoint: the state carries a **phase** (``_LIGHT`` — keep
relaxing light edges within bucket b; ``_HEAVY`` — fire the settled
bucket's heavy edges once), and the spec's update does the phase
transitions and the jump to the next non-empty bucket. One engine iteration
is exactly one relaxation sweep, so the fused ``lax.while_loop``, the
hostloop with SlimWork tile gathering, and the 2D-distributed strategy all
come from the engine with no SSSP-specific loop code.

The light/heavy split is two masked views of the same ``wts`` array (the
other class's slots are set to +inf, the min-plus zero, so they are inert) —
no second layout is built; the views live in the spec's ``ctx`` and a
``lax.cond`` on the phase picks the sweep operand. SlimWork applies per
sweep: only the tiles holding a *source* column are touched, selected
through the same push index BFS uses.

``delta=inf`` degenerates to Bellman-Ford (one bucket, pure sweeps);
``delta -> 0`` approaches Dijkstra's settling order (many tiny buckets).
The default delta is the mean edge weight — the classic bucket-width
heuristic balancing re-relaxations against bucket count.

Weights must be non-negative (delta-stepping's bucket-ordering argument
needs it); ``sssp`` raises on negative weights. With zero-weight edges the
distances are exact, but parent pointers inside a zero-weight equal-distance
group may form zero-weight cycles (positive-weight parents are preferred
whenever one is tight, so this only affects vertices whose every shortest
path enters through a zero-weight edge).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import engine as eng
from .bfs import dp_transform
from .engine import FixpointSpec
from .options import EngineConfig, MODES, check_choice, resolve_config

Array = jax.Array

_LIGHT, _HEAVY = 0, 1


@dataclasses.dataclass
class SSSPResult:
    distances: np.ndarray          # float32[n]; +inf unreachable
    parents: Optional[np.ndarray]  # int32[n]; parent in SP tree; root -> root
    sweeps: int                    # total relaxation SpMVs (light + heavy)
    buckets: int                   # delta buckets processed
    delta: float                   # bucket width actually used
    work_log: Optional[np.ndarray] = None  # active tiles per sweep


# --------------------------------------------------------------- weight prep


def _require_weighted(tiled):
    if getattr(tiled, "wts", None) is None:
        raise ValueError(
            "sssp needs a weighted layout; build it from a CSR with weights "
            "(e.g. generators.with_random_weights) via formats.build_slimsell")


def _weight_stats(tiled) -> tuple[float, float]:
    """(min, mean) over the real (non-padding) slots.

    Computed once per layout and cached on the instance (the wts array is
    immutable after build): ``run_graph500_sssp`` calls ``sssp`` once per
    root on one layout, and a full-array scan per call would land inside the
    timed path.
    """
    cached = getattr(tiled, "_weight_stats_cache", None)
    if cached is not None:
        return cached
    valid = tiled.cols >= 0
    w = tiled.wts
    wmin = jnp.min(jnp.where(valid, w, jnp.inf))
    wsum = jnp.sum(jnp.where(valid, w, 0.0))
    cnt = jnp.maximum(jnp.sum(valid), 1)
    stats = (float(wmin), float(wsum / cnt))
    try:
        tiled._weight_stats_cache = stats
    except AttributeError:
        pass  # duck-typed/frozen layouts just recompute
    return stats


def default_delta(tiled) -> float:
    """Mean edge weight — the standard bucket-width starting point."""
    _, mean = _weight_stats(tiled)
    return max(float(mean), 1e-6)


def _resolve_delta(tiled, delta: Optional[float]) -> float:
    """Shared front-door validation for the SSSP engines (single-source and
    batched multi-source): non-negative weights, positive bucket width,
    mean-edge-weight default. Returns the delta actually used."""
    wmin, _ = _weight_stats(tiled)  # cached per layout; also warms default_delta
    if wmin < 0:
        raise ValueError(f"delta-stepping needs non-negative weights; "
                         f"min weight is {wmin}")
    if delta is None:
        delta = default_delta(tiled)  # cached stats: no second scan
    delta = float(delta)
    if not delta > 0:
        raise ValueError(f"delta must be positive, got {delta}")
    return delta


# ----------------------------------------------------------------------- spec


def _begin_bucket(dist: Array, settled: Array, delta: Array):
    """(bucket index, its members, any live?) — the jump to the next
    non-empty bucket. All bucket math in float32 so the minimum's bucket
    always contains the minimum; dist=inf gives floor(inf/delta) -> inf (or
    nan under delta=inf), which compares False — exactly what unreached
    rows need."""
    live = ~settled & jnp.isfinite(dist)
    b = jnp.floor(jnp.min(jnp.where(live, dist, jnp.inf)) / delta)
    active = live & (jnp.floor(dist / delta) == b)
    return b, active, jnp.any(live)


def _sssp_setup(tiled, delta):
    """Per-run constants: the light/heavy +inf-masked views of ``wts``.

    These are tile-space leaves ([T, C, L]), so the engine's hostloop
    subset step gathers them alongside ``cols``; ``delta`` is a scalar leaf
    and passes through untouched.
    """
    inf = jnp.inf
    return {
        "light": jnp.where(tiled.wts <= delta, tiled.wts, inf),
        "heavy": jnp.where(tiled.wts > delta, tiled.wts, inf),
        "delta": jnp.asarray(delta, jnp.float32),
    }


def _sssp_init(n: int, root, ctx):
    dist = jnp.full((n,), jnp.inf, jnp.float32).at[root].set(0.0)
    settled = jnp.zeros((n,), bool)
    b, active, _ = _begin_bucket(dist, settled, ctx["delta"])
    return {"dist": dist, "settled": settled,
            "removed": jnp.zeros((n,), bool), "active": active,
            "phase": jnp.asarray(_LIGHT, jnp.int32), "b": b,
            "buckets": jnp.asarray(0, jnp.int32)}


def _sssp_sources(ctx, state, k) -> Array:
    """The sweep's source set: the bucket's light-fixpoint frontier while in
    the light phase; everything the bucket processed for the heavy shot."""
    return jnp.where(state["phase"] == _LIGHT, state["active"],
                     state["removed"])


def _sssp_frontier(ctx, state, k) -> Array:
    return jnp.where(_sssp_sources(ctx, state, k), state["dist"], jnp.inf)


def _sssp_weights(ctx, state) -> Array:
    return jax.lax.cond(state["phase"] == _LIGHT,
                        lambda: ctx["light"], lambda: ctx["heavy"])


def _sssp_update(ctx, state, y: Array, k):
    """One relaxation merge + the delta-stepping phase machine.

    light: re-enter the within-bucket fixpoint with the improvements that
    landed back in bucket b; once none do, switch to the heavy phase.
    heavy: the bucket is settled after its single heavy shot — commit it
    and jump to the next non-empty bucket (done when none remains).
    """
    delta = ctx["delta"]
    nd = jnp.minimum(state["dist"], y)
    improved = nd < state["dist"]

    def light_case():
        removed = state["removed"] | state["active"]
        active = improved & (jnp.floor(nd / delta) == state["b"])
        has_more = jnp.any(active)
        phase = jnp.where(has_more, _LIGHT, _HEAVY)
        return {"dist": nd, "settled": state["settled"], "removed": removed,
                "active": active, "phase": phase.astype(jnp.int32),
                "b": state["b"], "buckets": state["buckets"]}, jnp.asarray(True)

    def heavy_case():
        settled = state["settled"] | state["removed"]
        b, active, live = _begin_bucket(nd, settled, delta)
        return {"dist": nd, "settled": settled,
                "removed": jnp.zeros_like(settled), "active": active,
                "phase": jnp.asarray(_LIGHT, jnp.int32), "b": b,
                "buckets": state["buckets"] + 1}, live

    return jax.lax.cond(state["phase"] == _LIGHT, light_case, heavy_case)


def _sssp_host_bits(state, k, need_sb, need_nf):
    """Host twin: one device->host transfer for the phase's source set."""
    if int(state["phase"]) == _LIGHT:
        return np.asarray(state["active"]), None
    return np.asarray(state["removed"]), None


SSSP_SPEC = FixpointSpec(
    name="sssp",
    sr_name="minplus",
    directions=("push",),
    init_state=_sssp_init,
    frontier=_sssp_frontier,
    source_bits=_sssp_sources,
    not_final=lambda ctx, state: ~state["settled"] & jnp.isfinite(state["dist"]),
    update=_sssp_update,
    setup=_sssp_setup,
    weights=_sssp_weights,
    host_bits=_sssp_host_bits,
)


# -------------------------------------------------------- parents (weighted DP)


def sssp_parents(tiled, dist: Array, roots, *, rtol: float = 1e-6,
                 atol: float = 1e-6) -> Array:
    """Weighted DP transform: for each v pick a neighbor u whose relaxation is
    tight, ``dist[u] + w(v, u) == dist[v]`` (one sel-max SlimSell sweep).

    One sweep for a whole batch: distances ``dist`` [n, B] and ``roots``
    [B] give parents [B, n]; one column [n] with a scalar root gives [n]
    (``bfs.dp_transform`` with ``weighted=True``).

    Positive-weight parents are preferred over zero-weight ones (a ``+ n``
    score bonus), so parent chains strictly decrease ``dist`` whenever any
    strictly-closer tight parent exists.

    The score (id+1, bonus +n) rides in the float32 sel-max payload, so ids
    up to 2n must be float32-exact: guarded at n <= 2^23 (cf. the 2^24 guard
    on cc's unshifted labels).
    """
    n = tiled.n
    if n > (1 << 23):
        raise ValueError("sssp_parents carries (vertex id + n) scores in "
                         "float32 (exact up to 2^24), so n is capped at "
                         f"2^23; got n={n}")
    return dp_transform(tiled, dist, roots, weighted=True, rtol=rtol,
                        atol=atol)


# ------------------------------------------------------------- host oracle


def dijkstra_reference(csr, root: int) -> np.ndarray:
    """Host Dijkstra over CSR (binary heap) — the validation oracle the
    Graph500 SSSP harness and the tests compare against (float64 accumulation,
    returned as float32; +inf where unreachable)."""
    import heapq
    if csr.weights is None:
        raise ValueError("dijkstra_reference needs a weighted CSR")
    n = csr.n
    dist = np.full(n, np.inf, np.float64)
    dist[root] = 0.0
    heap = [(0.0, int(root))]
    while heap:
        d, v = heapq.heappop(heap)
        if d > dist[v]:
            continue
        s, e = csr.indptr[v], csr.indptr[v + 1]
        for u, w in zip(csr.indices[s:e], csr.weights[s:e]):
            nd = d + float(w)
            if nd < dist[u]:
                dist[u] = nd
                heapq.heappush(heap, (nd, int(u)))
    return dist.astype(np.float32)


# ----------------------------------------------------------------- public API


def sssp(tiled, root: int, *, delta: Optional[float] = None,
         need_parents: bool = False, slimwork: bool = True,
         mode: Optional[str] = None, max_iters: Optional[int] = None,
         log_work: bool = False, backend: Optional[str] = None,
         config: Optional[EngineConfig] = None) -> SSSPResult:
    """Single-source shortest paths from ``root`` by delta-stepping.

    delta: bucket width (None -> mean edge weight; ``inf`` -> Bellman-Ford).
    config: the engine knobs as one ``EngineConfig`` — mode "fused" (one
    flattened lax.while_loop on device) or "hostloop" (host loop + SlimWork
    tile gathering per sweep); backend "jnp" (reference) or "pallas"
    (weighted SlimSell TPU kernel). Delta-stepping is push-only, so the
    config's direction must be the default "push". The per-call ``mode`` /
    ``backend`` kwargs are the deprecated spelling.
    Returns float32 distances (+inf where unreachable) and, when requested,
    the shortest-path-tree parents via the weighted DP sweep.
    """
    cfg = resolve_config("sssp", config, mode=mode, backend=backend)
    check_choice("direction", cfg.direction, SSSP_SPEC.directions,
                 hint="delta-stepping relaxations are push-only")
    _require_weighted(tiled)
    if slimwork and getattr(tiled, "inc_src", None) is None:
        raise ValueError("SlimWork source masks need the push index; rebuild "
                         "the layout with formats.build_slimsell")
    delta = _resolve_delta(tiled, delta)
    n = tiled.n
    max_iters = int(max_iters) if max_iters is not None else 4 * n + 16
    root = int(root)
    if not 0 <= root < n:
        raise ValueError(f"root {root} out of range for n={n}")
    ctx_args = (jnp.asarray(delta, jnp.float32),)

    with cfg.applied():
        if cfg.mode == "fused":
            res = eng.run_fused(SSSP_SPEC, tiled,
                                jnp.asarray(root, jnp.int32),
                                ctx_args=ctx_args, slimwork=slimwork,
                                max_iters=max_iters, log_work=log_work,
                                backend=cfg.backend)
        else:
            res = eng.run_hostloop(SSSP_SPEC, tiled,
                                   jnp.asarray(root, jnp.int32),
                                   ctx_args=ctx_args, slimwork=slimwork,
                                   max_iters=max_iters, backend=cfg.backend)

    dist = res.state["dist"]
    buckets = int(res.state["buckets"])
    wl = res.work_log if log_work else None
    parents = None
    if need_parents:
        parents = np.asarray(sssp_parents(tiled, jnp.asarray(dist),
                                          jnp.asarray(root, jnp.int32)))
    return SSSPResult(distances=np.asarray(dist), parents=parents,
                      sweeps=res.iterations, buckets=buckets,
                      delta=delta, work_log=wl)
