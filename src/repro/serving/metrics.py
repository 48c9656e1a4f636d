"""Serving-layer observability: one mutable counter block per session.

Everything the ROADMAP's "millions of users" story needs to be *operable*
lives here: how full the device batches run (``batch_fill_ratio`` — the
number the shape-bucketed batcher exists to maximize), whether the jit
compile cache is actually being reused (``compile_cache_hits`` vs
``_misses`` — a miss per batch means the bucket widths are churning),
queue pressure (``queue_depth``, ``queue_wait_s``), end-to-end latency
quantiles, the amortization headline: engine sweeps per served query, and
the engine's tile counts: how many tiles the SlimWork mask selected against
how many the sweeps read.

``ServingMetrics`` is deliberately dumb — plain numbers and a ring of the
most recent latencies — but it is **lock-protected**: the background
flush thread increments from its drain loop while caller threads submit
and snapshot concurrently, so every mutation goes through ``inc()`` /
``record_latency()`` (one short critical section each) and ``snapshot()``
copies the counters under the same lock. The
invariant snapshots must preserve — and ``tests/test_serving_concurrent.py``
asserts — is the lifecycle reconciliation ``submitted == completed +
timeouts + shed`` once the session is drained.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Deque, List

#: latencies kept for the quantiles: the most recent this many queries
LATENCY_WINDOW = 4096


def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile over an already-sorted list (no numpy dep in
    the hot submit path; snapshot() is the only caller)."""
    if not sorted_vals:
        return float("nan")
    idx = min(len(sorted_vals) - 1, max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


@dataclasses.dataclass
class ServingMetrics:
    """Counters and timers for one ``GraphSession``.

    Counter glossary (see docs/SERVING.md for the operator's view):

    * ``submitted`` / ``completed`` / ``timeouts`` / ``shed`` — query
      lifecycle; every submitted query ends in exactly one of completed,
      timeouts or shed (the backpressure drop).
    * ``batches_dispatched`` — device batches launched (one jitted fixpoint
      call each).
    * ``columns_total`` / ``columns_real`` — batch-slot columns launched vs
      columns carrying a real query (the rest is power-of-two padding);
      their ratio is the batch fill ratio.
    * ``compile_cache_hits`` / ``compile_cache_misses`` — ``FixpointHandle``
      lookups that found / created a handle for the bucket signature. A
      steady-state stream should be all hits.
    * ``sweeps_total`` — engine fixpoint iterations executed across all
      batches (one sweep advances every column of its batch, which is the
      whole amortization argument).
    * ``tiles_active_total`` / ``tiles_read_total`` — summed over the
      sweeps of handle-path batches: tiles the SlimWork mask selected (all
      tiles without a mask), and tiles the sweep read (every tile on the
      jnp backend, whose gather is unmasked; the selected ones on Pallas).
    * ``queue_wait_s`` — seconds dispatched batch columns waited in the
      queue, summed: dispatch instant less ``submitted_at`` for a column's
      query (the mean over its queries where whole-graph queries share one
      column), so its mean is over ``columns_real``.
    * ``latencies_s`` — submit-to-harvest wall times of the most recent
      ``LATENCY_WINDOW`` queries (a ring: a long-lived session holds and
      sorts no more than that).

    Mutate through ``inc(counter=delta, ...)`` — direct attribute writes
    are not thread-safe against the flush thread.
    """
    submitted: int = 0
    completed: int = 0
    timeouts: int = 0
    shed: int = 0
    batches_dispatched: int = 0
    columns_total: int = 0
    columns_real: int = 0
    compile_cache_hits: int = 0
    compile_cache_misses: int = 0
    sweeps_total: int = 0
    tiles_active_total: int = 0
    tiles_read_total: int = 0
    queue_wait_s: float = 0.0
    latencies_s: Deque[float] = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=LATENCY_WINDOW))
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    def inc(self, **deltas: float) -> None:
        """Atomically add ``deltas`` to the named counters (one lock hold
        for the whole group, so multi-counter updates — e.g. a batch's
        dispatched/columns trio — land as one consistent event)."""
        with self._lock:
            for name, delta in deltas.items():
                setattr(self, name, getattr(self, name) + delta)

    def record_latency(self, seconds: float) -> None:
        with self._lock:
            self.latencies_s.append(float(seconds))

    def snapshot(self, *, queue_depth: int = 0, inflight: int = 0) -> dict:
        """One immutable stats() payload: counters + derived ratios/quantiles.

        ``queue_depth`` and ``inflight`` are gauges owned by the session
        (pending queries not yet batched; batches launched but not yet
        harvested) and are passed in at snapshot time. The counter block is
        copied under the lock, so one snapshot is internally consistent
        even while the flush thread is harvesting.
        """
        with self._lock:
            c = {f.name: getattr(self, f.name)
                 for f in dataclasses.fields(self) if f.name != "_lock"}
            lat = sorted(c.pop("latencies_s"))
        served = max(1, c["completed"])
        return {
            **c,
            "queue_depth": int(queue_depth),
            "inflight": int(inflight),
            "batch_fill_ratio": (c["columns_real"] / c["columns_total"]
                                 if c["columns_total"] else float("nan")),
            "sweeps_per_query": c["sweeps_total"] / served,
            "queue_wait_ms_mean": (1e3 * c["queue_wait_s"] / c["columns_real"]
                                   if c["columns_real"] else float("nan")),
            "latency_mean_ms": (1e3 * sum(lat) / len(lat)) if lat
                               else float("nan"),
            "latency_p50_ms": 1e3 * _percentile(lat, 0.50),
            "latency_p99_ms": 1e3 * _percentile(lat, 0.99),
        }
