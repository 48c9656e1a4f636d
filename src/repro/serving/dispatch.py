"""Batch execution: persistent jitted handles, async harvest, typed results.

``Dispatcher`` turns the batcher's ``BatchSlot``s into engine work:

* **Persistent handles** — each (algorithm, semiring, batch width) bucket
  signature maps to one ``core.engine.FixpointHandle``: a re-entrant jitted
  fixpoint step with state-buffer donation (off on CPU, where XLA ignores
  it anyway). Handles are cached per signature; the hit/miss counters in
  ``ServingMetrics`` make compile churn visible.
* **Async dispatch** — JAX dispatch is asynchronous: ``handle.run`` returns
  device buffers immediately while the sweeps execute. The dispatcher keeps
  up to ``max_inflight`` launched batches un-harvested, so host-side request
  handling (validation, bucketing, the next dispatch) overlaps device
  compute; results are harvested one step late, when the *next* batch has
  been launched (or at ``drain``).
* **Typed results** — harvest converts device state into per-query
  ``QueryResult``s: the query's column of the batch (bit-equal to a
  dedicated per-call run — batching changes the schedule, never the
  answer), parents on request, per-query sweep/bucket counts, and a
  ``status`` from ``options.QUERY_STATUSES``. A query whose deadline passed
  while queued is completed as ``status="timeout"`` with no values; one
  whose deadline passed *after* dispatch degrades to ``status="timeout"``
  with the (late) values attached — ``raise_for_status`` raises
  ``DeadlineExpired`` either way, the data is there for callers who prefer
  a late answer over none.

The ``mode="hostloop"`` engine config falls back to synchronous front-door
calls (the host-driven loop cannot be left in flight), as does boolean CC
(its peeling loop is host-side control flow). Everything else runs on the
handle path.

**Threading.** Since the background-flush-thread PR the dispatcher is
shared between caller threads (``drain`` / ``result`` forcing harvests)
and the session's flush thread (``dispatch``): every public method runs
under one internal ``RLock``, so at most one thread mutates the in-flight
deque, the per-session handle table, or the results map at a time, and the
``results_ready`` condition (on the same lock) is notified whenever a
``QueryResult`` lands — waiters in ``GraphSession.result`` wake without
polling. Pipelining is unchanged by the lock: ``handle.run`` inside
``dispatch`` only *enqueues* device work (async JAX dispatch), so holding
the lock across it never serializes device compute — with
``max_inflight >= 2`` the next slot's host-side padding and prep overlap
the previous slot's device sweep, and only ``_harvest_one`` blocks.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Callable, Deque, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from ..core import engine as eng
from ..core.betweenness import betweenness
from ..core.bfs import dp_transform
from ..core.cc import CC_SPEC, cc
from ..core.formats import layout_signature
from ..core.khop import khop_many
from ..core.multi_bfs import (multi_bfs_spec, multi_source_bfs,
                              packed_multi_bfs_spec)
from ..core.multi_sssp import MULTI_SSSP_SPEC, multi_source_sssp
from ..core.options import EngineConfig, QUERY_STATUSES, check_choice
from ..core.pagerank import (PAGERANK_MAX_ITERS, PAGERANK_SPEC, pagerank,
                             pagerank_views)
from ..core.sssp import sssp_parents
from .batcher import BatchSlot, Query
from .metrics import ServingMetrics
from .tracing import qid_list, span


class DeadlineExpired(RuntimeError):
    """Raised by ``QueryResult.raise_for_status`` for timed-out queries.

    Carries the result: ``exc.result.values`` is None when the query
    expired while queued, or the late (complete but past-deadline) data
    when it expired in flight.
    """

    def __init__(self, result: "QueryResult"):
        super().__init__(
            f"query {result.qid} ({result.algorithm}) missed its deadline")
        self.result = result


class QueryShed(RuntimeError):
    """Raised by ``QueryResult.raise_for_status`` for shed queries.

    A shed query was dropped at submit time by the bounded-queue
    backpressure policy (``on_full="shed"``): it never dispatched, so
    ``exc.result.values`` is always None. Resubmit after a flush, or use
    ``on_full="raise"`` to get ``QueueFull`` at submit instead.
    """

    def __init__(self, result: "QueryResult"):
        super().__init__(
            f"query {result.qid} ({result.algorithm}) was shed by "
            f"backpressure (submission queue full)")
        self.result = result


@dataclasses.dataclass
class QueryResult:
    """What one query gets back from the serving layer."""
    qid: int
    algorithm: str
    semiring: str
    status: str                       # one of options.QUERY_STATUSES
    values: Optional[np.ndarray]      # distances (bfs/sssp/khop), labels
    #                                   (cc), ranks (pagerank) or BC scores
    #                                   (betweenness)
    parents: Optional[np.ndarray] = None
    sweeps: int = 0                   # engine sweeps its batch executed
    buckets: Optional[int] = None     # sssp delta buckets (its column)
    delta: Optional[float] = None     # sssp bucket width actually used
    n_components: Optional[int] = None  # cc
    residual: Optional[float] = None  # pagerank final L1 residual
    latency_s: float = 0.0            # submit -> harvest wall time

    def __post_init__(self):
        check_choice("status", self.status, QUERY_STATUSES)

    @property
    def ok(self) -> bool:
        return self.status == "ok"

    def raise_for_status(self) -> "QueryResult":
        if self.status == "timeout":
            raise DeadlineExpired(self)
        if self.status == "shed":
            raise QueryShed(self)
        return self

    @property
    def distances(self) -> np.ndarray:
        """BFS/SSSP/khop distance vector; raises on timeout or a query
        whose values are not distances (cc / pagerank / betweenness)."""
        if self.algorithm in ("cc", "pagerank", "betweenness"):
            raise AttributeError(
                f"{self.algorithm} results carry no distance vector")
        self.raise_for_status()
        return self.values

    @property
    def labels(self) -> np.ndarray:
        """CC component labels; raises on timeout or a non-cc query."""
        if self.algorithm != "cc":
            raise AttributeError(f"{self.algorithm} results carry no labels")
        self.raise_for_status()
        return self.values

    @property
    def ranks(self) -> np.ndarray:
        """PageRank vector (sums to 1); raises on a non-pagerank query."""
        if self.algorithm != "pagerank":
            raise AttributeError(f"{self.algorithm} results carry no ranks")
        self.raise_for_status()
        return self.values

    @property
    def scores(self) -> np.ndarray:
        """Betweenness centrality scores; raises on other queries."""
        if self.algorithm != "betweenness":
            raise AttributeError(f"{self.algorithm} results carry no "
                                 "centrality scores")
        self.raise_for_status()
        return self.values


@dataclasses.dataclass
class _Inflight:
    """One launched-but-unharvested fused batch (device buffers inside)."""
    slot: BatchSlot
    batch: int                        # the dispatcher's batch sequence id
    state: dict                       # device arrays; harvest blocks on them
    iters: object                     # device scalar
    tiles: tuple                      # device (tiles active, tiles read)
    ctx: Optional[dict]


class Dispatcher:
    """Executes batch slots on one resident layout under one config."""

    def __init__(self, tiled, config: EngineConfig, metrics: ServingMetrics,
                 *, slimwork: bool = True, max_inflight: int = 1,
                 clock: Optional[Callable[[], float]] = None):
        self.tiled = tiled
        self.config = config
        self.metrics = metrics
        self.slimwork = bool(slimwork)
        self.max_inflight = max(0, int(max_inflight))
        self.results: Dict[int, QueryResult] = {}
        # one RLock serializes dispatch/harvest/results mutation across the
        # flush thread and caller threads; results_ready (same lock) wakes
        # session-side waiters the moment a QueryResult lands
        self.lock = threading.RLock()
        self.results_ready = threading.Condition(self.lock)
        self._clock = clock or time.monotonic
        self._inflight: Deque[_Inflight] = collections.deque()
        self._handles: Dict[tuple, eng.FixpointHandle] = {}
        self._layout_sig = layout_signature(tiled)
        self._pr_views = None  # lazy (inv_deg, dangling) for pagerank
        self._next_batch = 0   # sequence id of the next dispatched slot

    def _pagerank_views(self):
        if self._pr_views is None:
            self._pr_views = pagerank_views(self.tiled.deg)
        return self._pr_views

    # ------------------------------------------------------------- handles

    def _handle(self, spec, *, max_iters: int, direction: str,
                batch_width: Optional[int]) -> eng.FixpointHandle:
        """Handle for a bucket signature, with per-session hit/miss counts.

        ``eng.fixpoint_handle`` itself is a process-wide cache keyed on the
        same statics, so a "miss" here at most re-traces when the layout
        shapes are new to the process too — but the per-session counters are
        what the fill/churn diagnostics need.
        """
        key = (spec.name, max_iters, direction, batch_width, self.slimwork,
               self.config.signature(), self._layout_sig)
        with self.lock:
            handle = self._handles.get(key)
            if handle is None:
                self.metrics.inc(compile_cache_misses=1)
                handle = eng.fixpoint_handle(
                    spec, slimwork=self.slimwork, max_iters=max_iters,
                    backend=self.config.backend, direction=direction,
                    batch_width=batch_width)
                self._handles[key] = handle
            else:
                self.metrics.inc(compile_cache_hits=1)
        return handle

    # ------------------------------------------------------------ dispatch

    def inflight(self) -> int:
        with self.lock:
            return len(self._inflight)

    def dispatch(self, slot: BatchSlot) -> None:
        """Launch one slot; harvest the oldest batch beyond ``max_inflight``.

        Fused BFS/SSSP/sel-max-CC go through the jitted handles and stay in
        flight; hostloop mode and boolean CC execute synchronously through
        the core front doors (their loops live on host) and complete
        immediately.
        """
        with self.lock:
            self._dispatch_locked(slot)

    def _dispatch_locked(self, slot: BatchSlot) -> None:
        batch = self._next_batch
        self._next_batch += 1
        with span("dispatch", batch=batch, algorithm=slot.key.algorithm,
                  width=slot.width, n_real=slot.n_real,
                  qids=qid_list(q.qid for q in slot.queries)):
            self._launch(slot, batch)
        while len(self._inflight) > self.max_inflight:
            self._harvest_one()

    def _launch(self, slot: BatchSlot, batch: int) -> None:
        cfg, alg = self.config, slot.key.algorithm
        n = self.tiled.n
        whole_graph = alg in ("cc", "pagerank", "betweenness")
        now = self._clock()
        wait = sum(now - q.submitted_at for q in slot.queries)
        # a whole-graph column serves all its queries: it waited their mean
        self.metrics.inc(
            batches_dispatched=1, columns_total=slot.width,
            columns_real=1 if whole_graph else slot.n_real,
            queue_wait_s=wait / slot.n_real if whole_graph else wait)

        # betweenness is two chained fixpoints with host orchestration
        # between them (level extraction feeds the backward pass), so it
        # always completes synchronously, like the other host-driven loops
        if cfg.mode == "hostloop" or alg == "betweenness" \
                or (alg == "cc" and slot.key.semiring == "boolean"):
            self._dispatch_sync(slot)
            return

        with cfg.applied():
            if alg == "cc":
                handle = self._handle(CC_SPEC, max_iters=n + 1,
                                      direction="push", batch_width=None)
                ctx = handle.setup(self.tiled)
                state = handle.init_state(self.tiled,
                                          jnp.asarray(0, jnp.int32), ctx)
            elif alg == "pagerank":
                handle = self._handle(PAGERANK_SPEC,
                                      max_iters=PAGERANK_MAX_ITERS,
                                      direction="push", batch_width=None)
                # damping/tol are traced ctx scalars, so every (damping,
                # tol) bucket shares this one compiled handle
                ctx = handle.setup(self.tiled, (
                    jnp.asarray(slot.key.damping, jnp.float32),
                    jnp.asarray(slot.key.tol, jnp.float32),
                    *self._pagerank_views()))
                state = handle.init_state(self.tiled,
                                          jnp.asarray(0, jnp.int32), ctx)
            elif alg == "khop":
                # a k-hop batch is the boolean multi-BFS batch whose
                # iteration cap is the bucket's depth k (the early exit)
                spec = (packed_multi_bfs_spec(slot.width) if slot.key.packed
                        else multi_bfs_spec("boolean"))
                handle = self._handle(spec, max_iters=int(slot.key.k),
                                      direction=cfg.direction,
                                      batch_width=slot.width)
                ctx = handle.setup(self.tiled)
                state = handle.init_state(self.tiled,
                                          jnp.asarray(slot.roots()), ctx)
            elif alg == "bfs":
                # packed slots ride the SlimSell-B word-plane spec: the
                # batch's frontier/visited are uint32[n, ceil(width/32)]
                # planes, distances land in the same [n, width] int32 as
                # the lane spec so harvest is shape-identical
                spec = (packed_multi_bfs_spec(slot.width) if slot.key.packed
                        else multi_bfs_spec(slot.key.semiring))
                handle = self._handle(spec, max_iters=n,
                                      direction=cfg.direction,
                                      batch_width=slot.width)
                ctx = handle.setup(self.tiled)
                state = handle.init_state(self.tiled,
                                          jnp.asarray(slot.roots()), ctx)
            else:  # sssp
                handle = self._handle(MULTI_SSSP_SPEC, max_iters=4 * n + 16,
                                      direction="push",
                                      batch_width=slot.width)
                ctx = handle.setup(
                    self.tiled,
                    (jnp.asarray(slot.key.delta, jnp.float32),))
                state = handle.init_state(self.tiled,
                                          jnp.asarray(slot.roots()), ctx)
            state, iters, tiles = handle.run(self.tiled, ctx, state)
        self._inflight.append(_Inflight(slot=slot, batch=batch, state=state,
                                        iters=iters, tiles=tiles, ctx=ctx))

    def drain(self) -> None:
        """Harvest every batch still in flight (blocks on the device)."""
        with self.lock:
            while self._inflight:
                self._harvest_one()

    # ------------------------------------------------------------- harvest

    def _finish(self, query: Query, **fields) -> None:
        now = self._clock()
        status = "ok"
        if query.deadline_at is not None and now >= query.deadline_at:
            status = "timeout"   # late: degraded status, values attached
            self.metrics.inc(timeouts=1)
        else:
            self.metrics.inc(completed=1)
        latency = now - query.submitted_at
        self.metrics.record_latency(latency)
        self._publish(QueryResult(
            qid=query.qid, algorithm=query.algorithm,
            semiring=query.semiring, status=status,
            latency_s=latency, delta=query.delta, **fields))

    def _publish(self, result: QueryResult) -> None:
        with self.lock:
            self.results[result.qid] = result
            self.results_ready.notify_all()

    def expire(self, query: Query) -> None:
        """Complete a queued-expired query with a typed timeout (no values)."""
        now = self._clock()
        self.metrics.inc(timeouts=1)
        self.metrics.record_latency(now - query.submitted_at)
        self._publish(QueryResult(
            qid=query.qid, algorithm=query.algorithm,
            semiring=query.semiring, status="timeout", values=None,
            delta=query.delta, latency_s=now - query.submitted_at))

    def shed(self, query: Query) -> None:
        """Complete a backpressure-dropped query with a typed shed result
        (never dispatched, no values)."""
        now = self._clock()
        self.metrics.inc(shed=1)
        self._publish(QueryResult(
            qid=query.qid, algorithm=query.algorithm,
            semiring=query.semiring, status="shed", values=None,
            delta=query.delta, latency_s=now - query.submitted_at))

    def _harvest_one(self) -> None:
        fl = self._inflight.popleft()
        with span("harvest", batch=fl.batch,
                  qids=qid_list(q.qid for q in fl.slot.queries)):
            with span("harvest.wait"):
                iters = int(fl.iters)    # blocks until the batch is done
            # the program has finished: reading its counters adds no wait
            active, read = (int(t) for t in fl.tiles)
            self.metrics.inc(sweeps_total=iters, tiles_active_total=active,
                             tiles_read_total=read)
            self._harvest_results(fl.slot, fl.state, iters)

    def _harvest_results(self, slot: BatchSlot, state: dict,
                         iters: int) -> None:
        alg, sem = slot.key.algorithm, slot.key.semiring

        if alg == "cc":
            labels = (np.asarray(state["x"]).astype(np.int64) - 1
                      ).astype(np.int32)
            n_comp = len(np.unique(labels))
            for q in slot.queries:
                self._finish(q, values=labels, sweeps=iters,
                             n_components=n_comp)
            return

        if alg == "pagerank":
            ranks = np.asarray(state["r"])
            resid = float(np.asarray(state["resid"]))
            for q in slot.queries:
                self._finish(q, values=ranks, sweeps=iters, residual=resid)
            return

        if alg == "khop":
            d = np.asarray(state["d"]).T          # [width, n]; -1 beyond k
            for col, q in enumerate(slot.queries):
                self._finish(q, values=d[col], sweeps=iters)
            return

        need_dp = any(q.need_parents for q in slot.queries)
        if alg == "bfs":
            d = np.asarray(state["d"]).T          # [width, n]
            p_all = None
            if need_dp and sem == "selmax":
                p_all = np.asarray(state["p"].astype(jnp.int32) - 1).T
            elif need_dp:
                # one batched DP sweep serves every column (as in
                # multi_source_bfs)
                with span("parents"):
                    p_all = np.asarray(dp_transform(
                        self.tiled, state["d"], slot.roots()))
            for col, q in enumerate(slot.queries):
                parents = None
                if q.need_parents:
                    parents = p_all[col].copy()
                    parents[q.root] = q.root
                self._finish(q, values=d[col], parents=parents, sweeps=iters)
            return

        # sssp: per-column sweep/bucket counters match per-root delta-stepping
        d = np.asarray(state["dist"]).T
        col_sweeps = np.asarray(state["sweeps"])
        col_buckets = np.asarray(state["buckets"])
        p_all = None
        if need_dp:
            with span("parents"):
                p_all = np.asarray(sssp_parents(
                    self.tiled, state["dist"], slot.roots()))
        for col, q in enumerate(slot.queries):
            parents = p_all[col] if q.need_parents else None
            self._finish(q, values=d[col], parents=parents,
                         sweeps=int(col_sweeps[col]),
                         buckets=int(col_buckets[col]))

    # ------------------------------------------------- synchronous fallback

    def _dispatch_sync(self, slot: BatchSlot) -> None:
        """Hostloop mode / boolean CC / betweenness: run through the core
        front doors (their loops are host control flow) and complete
        immediately. Their results carry no work log (none is asked for),
        so they add nothing to the tile counters."""
        cfg, alg, sem = self.config, slot.key.algorithm, slot.key.semiring
        if alg == "cc":
            res = cc(self.tiled, semiring=sem, slimwork=self.slimwork,
                     packed=slot.key.packed, config=cfg)
            self.metrics.inc(sweeps_total=int(res.iterations))
            for q in slot.queries:
                self._finish(q, values=res.labels, sweeps=res.iterations,
                             n_components=res.n_components)
            return
        if alg == "pagerank":
            res = pagerank(self.tiled, damping=slot.key.damping,
                           tol=slot.key.tol, slimwork=self.slimwork,
                           config=cfg)
            self.metrics.inc(sweeps_total=int(res.iterations))
            resid = float(res.residuals[-1]) if res.residuals.size else 0.0
            for q in slot.queries:
                self._finish(q, values=res.ranks, sweeps=res.iterations,
                             residual=resid)
            return
        if alg == "betweenness":
            res = betweenness(self.tiled, slimwork=self.slimwork, config=cfg)
            self.metrics.inc(sweeps_total=int(res.iterations))
            for q in slot.queries:
                self._finish(q, values=res.scores, sweeps=res.iterations)
            return
        roots = [q.root for q in slot.queries]
        need_parents = any(q.need_parents for q in slot.queries)
        if alg == "khop":
            res = khop_many(self.tiled, roots, slot.key.k,
                            packed=slot.key.packed, batch_size=slot.width,
                            slimwork=self.slimwork, config=cfg)
            self.metrics.inc(sweeps_total=int(np.sum(res.iterations)))
            for i, q in enumerate(slot.queries):
                self._finish(q, values=res.distances[i],
                             sweeps=int(np.max(res.iterations)))
            return
        if alg == "bfs":
            res = multi_source_bfs(self.tiled, roots, sem,
                                   need_parents=need_parents,
                                   slimwork=self.slimwork,
                                   packed=slot.key.packed,
                                   batch_size=slot.width, config=cfg)
            self.metrics.inc(sweeps_total=int(np.sum(res.iterations)))
            for i, q in enumerate(slot.queries):
                self._finish(
                    q, values=res.distances[i],
                    parents=res.parents[i] if q.need_parents else None,
                    sweeps=int(np.max(res.iterations)))
            return
        res = multi_source_sssp(self.tiled, roots, delta=slot.key.delta,
                                need_parents=need_parents,
                                slimwork=self.slimwork,
                                batch_size=slot.width, config=cfg)
        self.metrics.inc(sweeps_total=int(np.sum(res.iterations)))
        for i, q in enumerate(slot.queries):
            self._finish(q, values=res.distances[i],
                         parents=res.parents[i] if q.need_parents else None,
                         sweeps=int(res.sweeps[i]),
                         buckets=int(res.buckets[i]))
