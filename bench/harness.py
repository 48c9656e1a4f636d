"""One run of one benchmark cell: set-up, measured window, correctness
check, metrics, result line.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``bench/configs/<config>.json``, its traffic in
``bench/traffic/<traffic>.json``, and each metric's reader in
``bench/metrics/<metric>.py``. The traffic file's ``kind`` picks one of two
drivers:

* ``batches``: Graph500 search keys in whole batches through
  ``GraphSession.bfs_many`` or ``GraphSession.sssp(batch=True)``;
* ``open_loop``: a seed-drawn schedule of queries submitted at their
  instants through ``GraphSession.submit``, each timed from its scheduled
  instant to its result.

The program's front door builds the layout: set-up makes the
configuration's host CSR (``gen.from_config``) and hands it to
``GraphSession(csr, **layout, **session)``, where ``layout`` is every key of
the configuration's ``layout`` but ``sigma`` (``C``, ``L`` and any key the
session takes, such as a mesh) and ``session`` its ``session``. The session
builds every layout with sigma = n, so a configuration's ``sigma`` has to be
``"n"``; another is refused.

A run records what it saw in a ``Run``; the metric readers compute every
reported number from it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import queue
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import checks, gen, schedule
from bench.peaks import peaks_for

ROOT = Path(__file__).resolve().parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: how long past the window's close a run waits for an answer still due
STRAGGLER_WAIT_S = 60.0
#: stands for "beyond any limit" (a query that failed) in a latency
FAILED_LATENCY_S = 1e30
#: threads that wait for the answers of an open-loop window
WAITERS = 4


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What one run saw; the metric readers take their numbers from it."""
    workload: str
    kind: str                     # "batches" | "open_loop"
    seed: int
    seconds: float
    cell: dict
    config: dict
    traffic: dict
    setup_s: float = 0.0
    setup: Dict[str, float] = dataclasses.field(default_factory=dict)
    layout: Dict[str, int] = dataclasses.field(default_factory=dict)
    window: dict = dataclasses.field(default_factory=dict)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    compiles_in_window: int = 0
    device_trace: Optional[dict] = None


# ----------------------------------------------------------------- lookup


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(spec: dict, workload: str, root: Path = ROOT):
    """(cell, configuration, traffic) for a cell name."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(root / configs[cell["config"]]["file"])
    traffic = _load_json(root / "bench" / "traffic"
                         / f"{cell['traffic']}.json")
    return cell, config, traffic


def metrics_for(spec: dict, section: str, workload: str) -> List[dict]:
    """The entries of ``end_to_end`` or ``per_layer`` that a cell reports."""
    return [m for m in spec[section]
            if "workloads" not in m or workload in m["workloads"]]


def load_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read


# ----------------------------------------------------------------- device


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {dev.platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devices)}")
    if require_tpu:
        peaks_for(dev.device_kind)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def sweep_floor_ms(layout: dict, kind: str) -> Optional[float]:
    """Least time one full sweep could take: reading the layout's int32
    column ids once at the chip's peak HBM bandwidth, each device its
    share of them."""
    try:
        bw = peaks_for(kind)["hbm_bytes_per_s"]
    except KeyError:
        return None
    return 1e3 * 4 * layout["slots"] / layout["devices"] / bw


def memory_peak_bytes() -> int:
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


class CompileCounter:
    """Executables JAX builds or loads from its cache, counted from JAX's
    own compile events."""

    def __init__(self):
        from jax._src import monitoring
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **kwargs):
        if name == COMPILE_EVENT:
            self.count += 1


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ set-up


def layout_kwargs(config: dict) -> dict:
    """The configuration's ``layout`` as keyword arguments of the program's
    front door: every key but ``sigma``, which has to be ``"n"``."""
    lay = dict(config["layout"])
    sigma = lay.pop("sigma", "n")
    if sigma != "n":
        raise ValueError(f"layout sigma must be 'n', got {sigma!r}: "
                         f"GraphSession builds every layout with sigma = n")
    return lay


def build(run: Run):
    """The configuration's data on the device, then the session that the
    program's front door builds from its CSR, with the layout resident."""
    import jax
    from repro.core.formats import CSRGraph
    from repro.serving import GraphSession
    layout = layout_kwargs(run.config)
    t = time.monotonic()
    g = gen.from_config(run.config)
    run.setup["gen_s"] = time.monotonic() - t
    csr = CSRGraph(n=g.n, m_undirected=g.nnz // 2, indptr=g.indptr,
                   indices=g.indices, weights=g.weights)
    t = time.monotonic()
    sess = GraphSession(csr, **layout, **run.config["session"])
    jax.block_until_ready(sess.tiled)
    run.setup["layout_build_s"] = time.monotonic() - t
    cols = sess.tiled.cols
    C, L = cols.shape[-2:]
    run.layout = {"n": g.n, "nnz": g.nnz, "slots": int(cols.size),
                  "tiles": int(cols.size // (C * L)),
                  "devices": len(cols.sharding.device_set),
                  "isolated": int((g.deg == 0).sum())}
    return g, sess


#: the program's serving counters (``ServingMetrics``) a run reads
COUNTERS = ("submitted", "completed", "timeouts", "shed",
            "batches_dispatched", "columns_total", "columns_real",
            "compile_cache_hits", "compile_cache_misses", "sweeps_total")


def _counters(sess) -> Dict[str, int]:
    stats = sess.stats()
    return {k: int(stats[k]) for k in COUNTERS}


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after}


def warm_roots(g, count: int, rng) -> np.ndarray:
    """Distinct roots for a warm-up batch: isolated vertices where the
    graph has enough, so the warm-up compiles and loads the same programs
    but stops after one sweep."""
    isolated = np.nonzero(g.deg == 0)[0]
    pool = isolated if isolated.size >= count else np.arange(g.n)
    return rng.choice(pool, count, replace=False)


# --------------------------------------------------------------- batches


def _batch_call(sess, traffic: dict, keys):
    roots = [int(k) for k in keys]
    if traffic["algorithm"] == "bfs":
        return sess.bfs_many(roots, need_parents=traffic["need_parents"])
    return sess.sssp(roots, batch=True, need_parents=traffic["need_parents"])


def _reached(traffic: dict, values: np.ndarray) -> np.ndarray:
    if traffic["algorithm"] == "bfs":
        return values >= 0
    return np.isfinite(values)


def drive_batches(run: Run, g, sess, counter: CompileCounter) -> list:
    """Whole batches of search keys until the window has run its length;
    returns the kept (root, result) samples: ``check_sample`` distinct
    columns of every batch, drawn from the seed."""
    tr = run.traffic
    candidates = np.nonzero(g.deg > 0)[0]
    batches = schedule.key_batches(run.seed, candidates,
                                   run.config["search_keys"], tr["batch"],
                                   key_seed=run.config["key_seed"])
    pick = checks.column_picker(run.seed, int(tr["check_sample"]))
    warm = warm_roots(g, tr["batch"], schedule.rng_for(run.seed, "warm"))
    t = time.monotonic()
    for r in _batch_call(sess, tr, warm):
        r.raise_for_status()
    run.setup["warmup_s"] = time.monotonic() - t
    run.setup_s = time.monotonic() - run.setup["t0"]

    before, compiles = _counters(sess), counter.count
    deg = g.deg
    edges = attempted = completed = 0
    kept, reach = [], []
    with annotate("window"):
        t0 = time.monotonic()
        t_last = t0
        while time.monotonic() - t0 < run.seconds:
            keys = next(batches)
            attempted += keys.size
            with annotate("batch"):
                results = _batch_call(sess, tr, keys)
            t_last = time.monotonic()
            with annotate("account"):
                for key, r in zip(keys, results):
                    if r.ok:
                        completed += 1
                        hit = _reached(tr, r.values)
                        edges += max(1, int(deg[hit].sum()) // 2)
                        reach.append((int(key), int(hit.sum())))
                kept.extend((int(keys[c]), results[c])
                            for c in pick(len(results)))
    run.compiles_in_window = counter.count - compiles
    run.counters = _delta(_counters(sess), before)
    run.window = {"elapsed_s": t_last - t0, "attempted": attempted,
                  "completed": completed, "failed": attempted - completed,
                  "edges": edges, "reach": reach}
    return kept


# ------------------------------------------------------------- open loop


def _widths(max_batch: int) -> List[int]:
    w, out = 1, []
    while w < max_batch:
        out.append(w)
        w *= 2
    return out + [max_batch]


def _submit_kwargs(entry: dict) -> dict:
    return {k: v for k, v in entry.items() if k not in ("algorithm", "share")}


def warm_open_loop(sess, traffic: dict, g, rng) -> None:
    """Run every (query type, batch width) the window can dispatch once,
    from isolated roots (``warm_roots``). The session's flush lock holds
    the background flush thread off while a batch of exactly that width is
    queued."""
    for entry in traffic["mix"]:
        for width in _widths(sess.batcher.max_batch):
            roots = warm_roots(g, width, rng)
            with sess._flush_lock:
                handles = [sess.submit(entry["algorithm"], int(r),
                                       **_submit_kwargs(entry))
                           for r in roots]
                sess.flush()
            sess.drain()
            for h in handles:
                h.result().raise_for_status()


def drive_open_loop(run: Run, g, sess, counter: CompileCounter) -> dict:
    """Warm-up, then the open-loop window."""
    t = time.monotonic()
    warm_open_loop(sess, run.traffic, g, schedule.rng_for(run.seed, "warm"))
    run.setup["warmup_s"] = time.monotonic() - t
    run.setup_s = time.monotonic() - run.setup["t0"]
    return open_loop_window(run, g, sess, counter)


def open_loop_window(run: Run, g, sess, counter: CompileCounter) -> dict:
    """Submit the seed's schedule at its instants; wait for every answer.
    Returns the kept results of the sampled queries, by arrival index."""
    tr = run.traffic
    candidates = np.nonzero(g.deg > 0)[0]
    plan = schedule.open_loop(run.seed, tr, run.seconds, candidates)
    sample = set(checks.sample_arrivals(plan, tr, run.seed))
    n = len(plan)
    lat = np.full(n, FAILED_LATENCY_S)
    late = np.zeros(n)
    answered = np.zeros(n, bool)
    kept: Dict[int, object] = {}
    reach: List[tuple] = []
    mix = tr["mix"]
    work: "queue.Queue" = queue.Queue()
    left = [n]
    done = threading.Condition()

    def finish(i):
        with done:
            left[0] -= 1
            done.notify_all()

    def waiter():
        while True:
            item = work.get()
            if item is None:
                return
            i, t_due, handle = item
            try:
                with annotate("wait"):
                    res = handle.result()
                t_done = time.monotonic()
                if res.ok:
                    lat[i] = t_done - t_due
                    answered[i] = True
                    if i in sample:
                        kept[i] = res
                    if mix[plan[i].query]["algorithm"] in ("bfs", "sssp"):
                        hit = _reached(mix[plan[i].query], res.values)
                        reach.append((plan[i].root, int(hit.sum())))
            except Exception as e:   # noqa: BLE001 - counted as failed
                log(f"[bench] query {i} failed: {e!r}")
            finish(i)

    pool = [threading.Thread(target=waiter, daemon=True,
                             name=f"bench-waiter-{k}")
            for k in range(WAITERS)]
    for th in pool:
        th.start()
    before, compiles = _counters(sess), counter.count
    with annotate("window"):
        t0 = time.monotonic()
        for i, a in enumerate(plan):
            t_due = t0 + a.at_s
            wait = t_due - time.monotonic()
            if wait > 0:
                with annotate("sleep"):
                    time.sleep(wait)
            late[i] = time.monotonic() - t_due
            entry = mix[a.query]
            try:
                with annotate("submit"):
                    h = sess.submit(entry["algorithm"], a.root,
                                    **_submit_kwargs(entry))
            except Exception as e:   # noqa: BLE001 - counted as failed
                log(f"[bench] submit {i} refused: {e!r}")
                finish(i)
                continue
            work.put((i, t_due, h))
        close = t0 + run.seconds
        with done:
            done.wait_for(lambda: left[0] == 0,
                          timeout=max(0.0, close + STRAGGLER_WAIT_S
                                      - time.monotonic()))
        t_end = time.monotonic()
    for _ in pool:
        work.put(None)
    run.compiles_in_window = counter.count - compiles
    run.counters = _delta(_counters(sess), before)
    run.window = {"elapsed_s": t_end - t0, "attempted": n,
                  "completed": int(answered.sum()),
                  "failed": int(n - answered.sum()),
                  "latencies_s": lat.tolist(),
                  "lateness_s": late.tolist(),
                  "offered_qps": n / run.seconds, "reach": reach}
    if left[0]:
        log(f"[bench] {left[0]} queries unanswered {STRAGGLER_WAIT_S:.0f} s "
            f"after the window closed")
    return {i: (plan[i], kept[i]) for i in kept}


# ------------------------------------------------------------------- run


def _trace_window(run: Run, drive, *args):
    """``drive`` under the profiler; the trace is reduced and removed."""
    import jax
    from bench import trace as trace_mod
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            out = drive(*args)
        finally:
            jax.profiler.stop_trace()
        run.device_trace = trace_mod.reduce_trace(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t0: Optional[float] = None, require_tpu: bool = True,
             root: Path = ROOT) -> dict:
    """One run of one cell; returns the result object (last stdout line)."""
    t0 = time.monotonic() if t0 is None else t0
    spec = load_spec(root)
    cell, config, traffic = find_cell(spec, workload, root)
    import jax
    from repro.compile_cache import use_compile_cache
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = device_info(int(cell["chips"]), require_tpu)
    log(f"[bench] device platform={device['platform']} kind={device['kind']} "
        f"count={device['count']} jax={jax.__version__} cache={cache}")
    counter = CompileCounter()
    run = Run(workload=workload, kind=traffic["kind"], seed=int(seed),
              seconds=float(seconds), cell=cell, config=config,
              traffic=traffic)
    run.setup["t0"] = t0
    run.setup["jax_init_s"] = time.monotonic() - t0

    g, sess = build(run)
    drive = drive_batches if run.kind == "batches" else drive_open_loop
    if trace:
        kept = _trace_window(run, drive, run, g, sess, counter)
    else:
        kept = drive(run, g, sess, counter)
    peak = memory_peak_bytes()
    sess.close()
    del sess
    run.setup.pop("t0")

    w = run.window
    log(f"[bench] layout {json.dumps(run.layout)} sweep_floor_ms="
        f"{sweep_floor_ms(run.layout, device['kind'])}")
    log(f"[bench] setup_s={run.setup_s:.3f} "
        + " ".join(f"{k}={v:.3f}" for k, v in run.setup.items()))
    log(f"[bench] window elapsed_s={w['elapsed_s']:.3f} "
        f"attempted={w['attempted']} completed={w['completed']} "
        f"compiles_in_window={run.compiles_in_window} "
        f"counters={json.dumps(run.counters)}")
    if run.kind == "open_loop":
        late = np.asarray(w["lateness_s"])
        log(f"[bench] generator lateness_ms p50={1e3 * np.median(late):.3f} "
            f"p99={1e3 * np.quantile(late, 0.99):.3f} "
            f"max={1e3 * late.max():.3f} offered_qps={w['offered_qps']:.3f}")
    if run.compiles_in_window:
        log(f"[bench] the program built or loaded {run.compiles_in_window} "
            f"executables inside the window")

    limits = checks.compare(run, g, kept)
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_for(spec, section, workload):
        value = load_reader(m["name"], root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if trace and run.device_trace:
        device.update(busy_s=run.device_trace["busy_s"],
                      window_s=run.device_trace["window_s"])
    device["memory_peak_bytes"] = peak
    result = {
        "correct": all(c["value"] <= c["limit"] for c in limits.values()),
        "attempted": w["attempted"],
        "failed": w["failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace and run.device_trace:
        result["breakdown"] = {
            "device_ops": run.device_trace["device_ops"],
            "idle_gaps": run.device_trace["idle_gaps"]}
    result["checks"] = limits
    for name, c in limits.items():
        log(f"[check] {name} value={c['value']!r} limit={c['limit']!r} "
            f"ok={c['value'] <= c['limit']}")
    return result


def _json_number(x):
    if isinstance(x, float) and not math.isfinite(x):
        return FAILED_LATENCY_S if x > 0 else -FAILED_LATENCY_S
    return x


def dumps(result: dict) -> str:
    def clean(o):
        if isinstance(o, dict):
            return {k: clean(v) for k, v in o.items()}
        if isinstance(o, (list, tuple)):
            return [clean(v) for v in o]
        return _json_number(o)
    return json.dumps(clean(result), allow_nan=False)


def parse_args(argv):
    import argparse
    p = argparse.ArgumentParser(description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv, t0: float) -> int:
    args = parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t0=t0)
    except NoChip as e:
        log(f"[bench] {e}")
        return 3
    print(dumps(result), flush=True)
    return 0

