"""Query serving on resident SlimSell graphs: sessions, shape-bucketed
batching, async dispatch.

The package turns the batched sweep engine into a service: a
``GraphSession`` owns one built layout plus one ``EngineConfig``, accepts a
stream of heterogeneous BFS / SSSP / CC / PageRank / betweenness / k-hop
queries (``submit`` -> ``QueryHandle``), buckets them by execution
signature (``Batcher``), runs
them as padded power-of-two device batches on persistent jitted handles
with async harvest (``Dispatcher``), and reports throughput/latency/fill
counters (``ServingMetrics`` via ``stats()``). Named ``repro.*`` profiler
spans mark its layer boundaries (``tracing``).

The layer is concurrent and multi-graph: ``GraphSession`` is thread-safe
(locked batcher/metrics, an optional ``background=True`` flush thread,
bounded submission queue with typed ``QueueFull`` backpressure or
``status="shed"`` load shedding, idempotent ``close()``), and ``Router``
fans one front door out over many resident graphs keyed by
``layout_signature``:

    from repro.serving import Router
    with Router(background=True, max_inflight=2) as router:
        router.add_graph("social", edges)
        router.add_graph("roads", road_edges, weights=w)
        router.bfs("social", root)

    import repro
    sess = repro.session(edges)
    sess.bfs(root)                     # direct: one query, served batched
    hs = [sess.submit("bfs", r) for r in roots]
    sess.drain()                       # streamed: shape-bucketed batches
    [h.result() for h in hs]
"""
from . import (batcher, dispatch, metrics, router, session,  # noqa: F401
               tracing)
from .batcher import (Batcher, BatchSlot, BucketKey, Query,  # noqa: F401
                      QueueFull)
from .dispatch import (DeadlineExpired, Dispatcher,  # noqa: F401
                       QueryResult, QueryShed)
from .metrics import ServingMetrics  # noqa: F401
from .router import Router, UnknownGraph  # noqa: F401
from .session import (GraphSession, QueryHandle, SessionClosed,  # noqa: F401
                      session)
