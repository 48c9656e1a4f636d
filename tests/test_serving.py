"""Serving layer: mixed streams vs per-call oracles, batching/padding,
deadlines, compile-cache accounting, and the EngineConfig shim."""
import time
import warnings

import numpy as np
import pytest

from repro.core.bfs import bfs
from repro.core.cc import cc
from repro.core.options import EngineConfig, resolve_config
from repro.core.formats import build_slimsell
from repro.core.sssp import sssp
from repro.graphs.generators import kronecker, with_random_weights
from repro.serving import (Batcher, BucketKey, DeadlineExpired, GraphSession,
                           Query, ServingMetrics, session)
from repro.serving.metrics import LATENCY_WINDOW


@pytest.fixture(scope="module")
def wtiled():
    csr = with_random_weights(kronecker(7, 8, seed=1), seed=2)
    return build_slimsell(csr, C=8, L=16, sigma=csr.n).to_jax()


@pytest.fixture(scope="module")
def sess(wtiled):
    return GraphSession(wtiled, max_batch=16)


# ------------------------------------------------------- mixed-stream oracle


def test_mixed_stream_bit_equal_to_per_call(wtiled, sess):
    """>=100 heterogeneous queries, streamed, equal their per-call twins."""
    rng = np.random.default_rng(0)
    n = wtiled.n
    plan, handles = [], []
    for i in range(104):
        kind = ("bfs", "sssp", "cc")[i % 3]
        if kind == "cc":
            plan.append(("cc", None, "selmax"))
            handles.append(sess.submit("cc"))
        elif kind == "sssp":
            root = int(rng.integers(n))
            while any(p == ("sssp", root, "minplus") for p in plan):
                root = int(rng.integers(n))
            plan.append(("sssp", root, "minplus"))
            handles.append(sess.submit("sssp", root))
        else:
            semiring = ("tropical", "selmax", "boolean", "real")[i % 4]
            root = int(rng.integers(n))
            while any(p == ("bfs", root, semiring) for p in plan):
                root = int(rng.integers(n))
            plan.append(("bfs", root, semiring))
            handles.append(sess.submit("bfs", root, semiring=semiring))
        if i % 17 == 16:          # interleave flushes with submits
            sess.flush()
    sess.drain()

    cc_oracle = cc(wtiled)
    for (kind, root, semiring), h in zip(plan, handles):
        res = h.result()
        assert res.ok and res.status == "ok"
        if kind == "cc":
            assert np.array_equal(res.labels, cc_oracle.labels)
        elif kind == "sssp":
            o = sssp(wtiled, root)
            assert np.array_equal(res.distances, o.distances)
            assert res.sweeps == o.sweeps and res.buckets == o.buckets
        else:
            o = bfs(wtiled, root, semiring)
            assert np.array_equal(res.distances, o.distances)
    stats = sess.stats()
    assert stats["completed"] >= 104
    assert stats["batches_dispatched"] < 104  # batching actually happened
    assert 0 < stats["batch_fill_ratio"] <= 1


def test_parents_match_per_call(wtiled, sess):
    for semiring in ("tropical", "selmax"):
        res = sess.bfs(3, semiring, need_parents=True)
        o = bfs(wtiled, 3, semiring, need_parents=True)
        assert np.array_equal(res.parents, o.parents)
    res = sess.sssp(5, need_parents=True)
    o = sssp(wtiled, 5, need_parents=True)
    assert np.array_equal(res.parents, o.parents)


# ------------------------------------------------------------------ padding


def test_partial_batch_padding_correctness(wtiled):
    """Widths are powers of two; padded columns never leak into results."""
    s = GraphSession(wtiled, max_batch=8)
    for count in (1, 2, 3, 5, 7):   # 3/5/7 pad up to 4/8/8
        roots = list(range(10, 10 + count))
        results = s.bfs_many(roots)
        for root, res in zip(roots, results):
            assert np.array_equal(res.distances, bfs(wtiled, root).distances)
    st = s.stats()
    assert st["columns_total"] == 1 + 2 + 4 + 8 + 8
    assert st["columns_real"] == 1 + 2 + 3 + 5 + 7


def test_bucketing_separates_incompatible_queries(wtiled):
    s = GraphSession(wtiled, max_batch=16)
    s.submit("bfs", 0)
    s.submit("bfs", 1, semiring="boolean")
    s.submit("sssp", 2)
    s.drain()
    # three buckets -> three batches (semiring and algorithm separate)
    assert s.stats()["batches_dispatched"] == 3


# ------------------------------------------------------- submit validation


def test_duplicate_root_rejected_at_submit(wtiled):
    s = GraphSession(wtiled)
    s.submit("bfs", 4)
    with pytest.raises(ValueError, match="already pending"):
        s.submit("bfs", 4)
    s.submit("bfs", 4, semiring="boolean")  # other bucket: fine
    s.drain()
    s.submit("bfs", 4)                      # previous batch dispatched: fine
    s.drain()


def test_bad_submits_rejected(wtiled):
    s = GraphSession(wtiled)
    with pytest.raises(ValueError, match="unknown algorithm"):
        s.submit("triangles", 0)
    with pytest.raises(ValueError, match="root must be None"):
        s.submit("pagerank", 0)
    with pytest.raises(ValueError, match="out of range"):
        s.submit("bfs", wtiled.n)
    with pytest.raises(ValueError, match="out of range"):
        s.submit("sssp", -1)
    with pytest.raises(ValueError, match="needs a root"):
        s.submit("bfs")
    with pytest.raises(ValueError, match="root must be None"):
        s.submit("cc", 0)
    with pytest.raises(ValueError, match="unknown semiring"):
        s.submit("bfs", 0, semiring="minplus")
    with pytest.raises(ValueError, match="minplus semiring only"):
        s.submit("sssp", 0, semiring="tropical")
    with pytest.raises(ValueError, match="sssp knob"):
        s.submit("bfs", 0, delta=1.0)
    unweighted = build_slimsell(kronecker(5, 8, seed=3)).to_jax()
    with pytest.raises(ValueError, match="weighted"):
        GraphSession(unweighted).submit("sssp", 0)


# ----------------------------------------------------------------- deadlines


def test_deadline_expired_is_typed_timeout(wtiled):
    s = GraphSession(wtiled)
    h = s.submit("bfs", 9, deadline=0.0)
    live = s.submit("bfs", 10)
    time.sleep(0.005)
    res = h.result()                 # drains; must not hang
    assert res.status == "timeout" and not res.ok and res.values is None
    with pytest.raises(DeadlineExpired):
        res.raise_for_status()
    with pytest.raises(DeadlineExpired):
        _ = res.distances
    assert live.result().ok          # the live query is unaffected
    assert s.stats()["timeouts"] == 1


# ------------------------------------------------------------- compile cache


def test_compile_cache_hit_counting(wtiled):
    s = GraphSession(wtiled, max_batch=8)
    s.bfs_many([0, 1, 2, 3])         # width 4: miss
    assert s.stats()["compile_cache_misses"] == 1
    s.bfs_many([4, 5, 6, 7])         # width 4 again: hit
    st = s.stats()
    assert st["compile_cache_hits"] == 1 and st["compile_cache_misses"] == 1
    s.bfs_many([8, 9])               # width 2: new signature, miss
    st = s.stats()
    assert st["compile_cache_hits"] == 1 and st["compile_cache_misses"] == 2


# ---------------------------------------------------------- latency window


def test_latencies_keep_only_the_most_recent_window():
    m = ServingMetrics()
    total = LATENCY_WINDOW + 904
    for i in range(total):
        m.record_latency(i / 1000)
    assert len(m.latencies_s) == LATENCY_WINDOW
    assert m.latencies_s[0] == 904 / 1000
    st = m.snapshot()
    assert st["latency_mean_ms"] == pytest.approx(
        (904 + total - 1) / 2)       # the mean of 904..4999 ms
    assert st["latency_p99_ms"] == pytest.approx(
        904 + round(0.99 * (LATENCY_WINDOW - 1)))


# ----------------------------------------------------------- batcher units


def test_batcher_pow2_widths_and_cc_sharing():
    b = Batcher(max_batch=8)
    now = time.monotonic()
    for i, root in enumerate(range(5)):
        b.add(Query(qid=i, algorithm="bfs", semiring="tropical", root=root,
                    delta=None, need_parents=False, deadline_at=None,
                    submitted_at=now))
    for i in range(3):
        b.add(Query(qid=10 + i, algorithm="cc", semiring="selmax", root=None,
                    delta=None, need_parents=False, deadline_at=None,
                    submitted_at=now))
    assert b.depth() == 8
    slots, expired = b.drain(now)
    assert not expired and b.depth() == 0
    by_key = {s.key: s for s in slots}
    assert by_key[BucketKey("bfs", "tropical")].width == 8      # 5 -> 8
    assert by_key[BucketKey("cc", "selmax")].width == 1         # shared run
    roots = by_key[BucketKey("bfs", "tropical")].roots()
    assert roots.shape == (8,) and (roots[5:] == roots[4]).all()


# -------------------------------------------------------- EngineConfig shim


def test_engineconfig_shim_equivalence(wtiled):
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        old = bfs(wtiled, 0, mode="hostloop", backend="jnp")
    assert any(issubclass(w.category, DeprecationWarning) for w in caught)
    assert "config=EngineConfig" in str(caught[-1].message)
    new = bfs(wtiled, 0, config=EngineConfig(mode="hostloop", backend="jnp"))
    assert np.array_equal(old.distances, new.distances)
    assert old.iterations == new.iterations


def test_engineconfig_rejects_mixed_and_bad_values():
    with pytest.raises(TypeError, match="not both"):
        resolve_config("bfs", EngineConfig(), mode="fused")
    with pytest.raises(ValueError, match="unknown mode"):
        EngineConfig(mode="warp")
    with pytest.raises(ValueError, match="unknown direction"):
        EngineConfig(direction="sideways")
    with pytest.raises(ValueError, match="unknown backend"):
        EngineConfig(backend="cuda")
    with pytest.raises(ValueError, match="unknown comm"):
        EngineConfig(comm="gossip")
    cfg = EngineConfig()
    assert cfg.signature() == ("jnp", "push", "fused", None, "allreduce",
                               False)


def test_session_accepts_config_and_shim(wtiled):
    direct = GraphSession(wtiled, config=EngineConfig(mode="hostloop"))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        shimmed = GraphSession(wtiled, mode="hostloop")
    assert any(issubclass(w.category, DeprecationWarning) for w in caught)
    assert shimmed.config == direct.config
    assert np.array_equal(direct.bfs(0).distances,
                          shimmed.bfs(0).distances)


# ------------------------------------------------------------- construction


def test_session_from_edge_list():
    edges = np.array([[0, 1], [1, 2], [2, 3], [4, 5]])
    s = session(edges)
    assert s.bfs(0).distances.tolist() == [0, 1, 2, 3, -1, -1]
    r = s.cc()
    assert r.n_components == 2
    with pytest.raises(ValueError, match=r"\[m, 2\]"):
        session(np.zeros((3, 3)))
