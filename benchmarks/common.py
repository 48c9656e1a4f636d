"""Shared benchmark utilities: timing, graph cache, CSV + JSON emission."""
from __future__ import annotations

import functools
import json
import time

import jax
import numpy as np

from repro.core.formats import build_slimsell
from repro.graphs.generators import erdos_renyi, kronecker

ROWS: list[tuple] = []

# scheme -> metrics dict ({"teps": ..., "bytes": ..., "iterations": ...}).
# run.py serializes this into BENCH_<tag>.json so CI and local runs share one
# machine-readable trajectory format; benches call record() for any result
# that should be tracked over time (TEPS, bytes, iteration counts).
RESULTS: dict[str, dict] = {}


def emit(name: str, us_per_call: float, derived: str = ""):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def record(scheme: str, **metrics):
    """Merge ``metrics`` into the machine-readable results for ``scheme``."""
    RESULTS.setdefault(scheme, {}).update(
        {k: (float(v) if isinstance(v, (int, float, np.floating, np.integer))
             else v) for k, v in metrics.items()})


def write_json(path: str, tag: str) -> dict:
    """Serialize the run's ROWS/RESULTS as one BENCH_<tag>.json snapshot —
    the machine-readable format docs/BENCHMARKS.md documents and
    tools/bench_trajectory.py consumes. The single serializer is shared by
    benchmarks/run.py and the standalone benches so the schema cannot fork."""
    payload = {
        "tag": tag,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "jax_version": jax.__version__,
        "jax_backend": jax.default_backend(),
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
        "schemes": RESULTS,
        "rows": [{"name": n, "us_per_call": us, "derived": d}
                 for n, us, d in ROWS],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"# wrote {path} ({len(RESULTS)} schemes, "
          f"{len(ROWS)} rows)", flush=True)
    return payload


def time_fn(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall time in microseconds (fn must block on its outputs)."""
    for _ in range(warmup):
        fn(*args)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out) if out is not None else None
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


@functools.lru_cache(maxsize=32)
def graph(kind: str, scale: int, ef: int = 16, seed: int = 0):
    if kind == "kron":
        return kronecker(scale, ef, seed=seed)
    return erdos_renyi(1 << scale, ef, seed=seed)


@functools.lru_cache(maxsize=32)
def tiled(kind: str, scale: int, ef: int = 16, C: int = 8, L: int = 128,
          sigma: int | None = None, seed: int = 0):
    return build_slimsell(graph(kind, scale, ef, seed), C=C, L=L,
                          sigma=sigma).to_jax()
