"""Serving-path spans and engine tile counters: the ``repro.*`` spans land
in a profiler trace with their attributes and nesting, the tile counters
agree with the engine's own work log, and the queue-wait counter is exact
under an injected clock."""
import glob
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core.formats import build_slimsell
from repro.core.multi_bfs import multi_bfs_spec
from repro.core.multi_sssp import MULTI_SSSP_SPEC
from repro.core.sssp import _resolve_delta
from repro.graphs.generators import kronecker, with_random_weights
from repro.serving import GraphSession
from repro.serving.tracing import PREFIX, SPANS

ROOT = Path(__file__).resolve().parents[1]
ALGORITHMS = ("bfs", "sssp")
#: two batches of width 4 (max_batch=4), so spans of one batch can be told
#: from the other's
ROOTS = [1, 2, 3, 5, 8, 13, 21, 34]


@pytest.fixture(scope="module")
def wtiled():
    csr = with_random_weights(kronecker(7, 8, seed=1), seed=2)
    return build_slimsell(csr, C=8, L=16, sigma=csr.n).to_jax()


def read_spans(log_dir) -> dict:
    """span name -> [(start_ns, end_ns, stats, line id)] of every
    ``repro.*`` host event in the one trace under ``log_dir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns, dict(e.stats),
                         (plane.name, i)))
    return out


def qids_of(stats) -> set:
    return {int(q) for q in str(stats["qids"]).split(";")}


def run_batches(sess, algorithm, roots):
    if algorithm == "bfs":
        return sess.bfs_many(roots, need_parents=True)
    return sess.sssp(roots, batch=True, need_parents=True)


@pytest.fixture(scope="module", params=ALGORITHMS)
def traced(request, wtiled, tmp_path_factory):
    """(algorithm, spans, stats) of one traced run of both batches."""
    algorithm = request.param
    sess = GraphSession(wtiled, max_batch=4)
    log_dir = tmp_path_factory.mktemp(f"trace-{algorithm}")
    jax.profiler.start_trace(str(log_dir))
    try:
        results = run_batches(sess, algorithm, ROOTS)
    finally:
        jax.profiler.stop_trace()
    assert all(r.ok for r in results)
    stats = sess.stats()
    sess.close()
    return algorithm, read_spans(log_dir), stats


def test_path_crosses_every_span(traced):
    _, spans, _ = traced
    assert set(SPANS) <= set(spans), set(SPANS) - set(spans)
    assert len(spans["repro.submit"]) == len(ROOTS)
    assert len(spans["repro.harvest"]) == len(ROOTS) // 4


@pytest.mark.parametrize("child", ["repro.harvest.wait", "repro.parents"])
def test_harvest_children_nest_in_harvest(traced, child):
    _, spans, _ = traced
    harvests = spans["repro.harvest"]
    for s, e, _, line in spans[child]:
        assert any(hs <= s and e <= he and hl == line
                   for hs, he, _, hl in harvests), (child, s, e)


def test_batch_and_qids_join_submit_to_its_batch(traced):
    algorithm, spans, _ = traced
    dispatches = spans["repro.dispatch"]
    harvests = spans["repro.harvest"]
    assert len({d[2]["batch"] for d in dispatches}) == len(dispatches)
    for _, _, st, _ in spans["repro.submit"]:
        assert st["algorithm"] == algorithm
        qid = int(st["qid"])
        (d,) = [d for d in dispatches if qid in qids_of(d[2])]
        (h,) = [h for h in harvests if qid in qids_of(h[2])]
        assert d[2]["batch"] == h[2]["batch"]
        assert d[2]["algorithm"] == algorithm
        assert d[2]["n_real"] == len(qids_of(d[2])) == 4
        assert d[2]["width"] == 4


def test_flush_span_counts_its_slots(traced):
    _, spans, _ = traced
    assert sum(st["slots"] for _, _, st, _ in spans["repro.flush"]) \
        == len(spans["repro.dispatch"])


def test_jnp_sweeps_read_every_tile(traced, wtiled):
    _, _, stats = traced
    assert stats["sweeps_total"] > 0
    assert stats["tiles_read_total"] \
        == stats["sweeps_total"] * int(wtiled.n_tiles)
    assert 0 < stats["tiles_active_total"] <= stats["tiles_read_total"]


def test_queue_wait_is_nonnegative(traced):
    _, _, stats = traced
    assert stats["queue_wait_s"] >= 0.0
    assert stats["queue_wait_ms_mean"] >= 0.0


def _fused_work(wtiled, algorithm, roots):
    """Active tiles the fused loop logs for the same batch as a handle."""
    n = wtiled.n
    if algorithm == "bfs":
        spec, max_iters, ctx_args = multi_bfs_spec("tropical"), n, ()
    else:
        delta = _resolve_delta(wtiled, None)
        spec, max_iters = MULTI_SSSP_SPEC, 4 * n + 16
        ctx_args = (jnp.asarray(delta, jnp.float32),)
    res = eng.run_fused(spec, wtiled, jnp.asarray(roots, jnp.int32),
                        ctx_args=ctx_args, max_iters=max_iters,
                        log_work=True, direction="push")
    return int(np.asarray(res.work_log, np.int64).sum()), res.iterations


@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_active_tiles_equal_the_fused_work_log(wtiled, algorithm):
    roots = ROOTS[:4]
    sess = GraphSession(wtiled, max_batch=4)
    run_batches(sess, algorithm, roots)
    stats = sess.stats()
    work, iters = _fused_work(wtiled, algorithm, roots)
    assert stats["sweeps_total"] == iters
    assert stats["tiles_active_total"] == work
    sess.close()


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_queue_wait_is_exact_under_an_injected_clock(wtiled):
    clock = FakeClock()
    sess = GraphSession(wtiled, max_batch=4, clock=clock)
    for t, root in ((1.0, 1), (2.5, 2)):
        clock.t = t
        sess.submit("bfs", root)
    clock.t = 4.0
    sess.flush()
    stats = sess.stats()
    assert stats["queue_wait_s"] == 3.0 + 1.5
    assert stats["queue_wait_ms_mean"] == 1e3 * 4.5 / 2
    sess.close()


def test_whole_graph_column_waits_the_mean_of_its_queries(wtiled):
    clock = FakeClock()
    sess = GraphSession(wtiled, clock=clock)
    for t in (1.0, 3.0):
        clock.t = t
        sess.submit("cc")
    clock.t = 5.0
    sess.flush()
    stats = sess.stats()
    assert stats["columns_real"] == 1
    assert stats["queue_wait_s"] == (4.0 + 2.0) / 2
    sess.close()


def test_fixpoint_program_is_named_for_its_spec(wtiled):
    spec = multi_bfs_spec("tropical")
    handle = eng.fixpoint_handle(spec, max_iters=wtiled.n, batch_width=4)
    ctx = handle.setup(wtiled)
    state = handle.init_state(wtiled, jnp.asarray(ROOTS[:4], jnp.int32), ctx)
    head = handle._jitted.lower(wtiled, ctx, state).as_text().splitlines()[0]
    assert "@jit_fixpoint_multi_bfs_tropical " in head, head


def test_every_span_is_documented_for_operators():
    text = (ROOT / "docs" / "SERVING.md").read_text(encoding="utf-8")
    assert [s for s in SPANS if f"`{s}`" not in text] == []


def test_every_span_opened_in_the_source_is_listed():
    opened = set()
    for path in (ROOT / "src" / "repro").rglob("*.py"):
        opened |= {PREFIX + m for m in re.findall(
            r"\bspan\(\s*\"([^\"]+)\"", path.read_text(encoding="utf-8"))}
    assert opened == set(SPANS)
