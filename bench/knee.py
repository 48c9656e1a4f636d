"""Knee sweep of an open-loop cell: the highest rate the served path
sustains, found once on the chip, whose 0.8x goes into the traffic file.

    python bench/knee.py --workload svc-mixed --rates 4 8 12 16 --seconds 20

One process sets the cell up once, then runs one window per rate (in the
order given) with the cell's own mix and seed, and prints, per rate, the
offered and answered rates, p50/p95/p99 latency, the p95 of the first
and last thirds of the window (a queue that grows shows there) and the
generator's lateness.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

if __package__ in (None, ""):
    _root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root), str(_root / "src")]

from bench import harness, schedule  # noqa: E402


def summarize(run) -> dict:
    w = run.window
    lat = np.asarray(w["latencies_s"]) * 1e3
    third = max(1, lat.size // 3)
    return {
        "rate_qps": run.traffic["rate_qps"],
        "offered_qps": w["offered_qps"],
        "answered_qps": w["completed"] / w["elapsed_s"],
        "failed": w["failed"],
        "p50_ms": float(np.quantile(lat, 0.5)),
        "p95_ms": float(np.quantile(lat, 0.95)),
        "p99_ms": float(np.quantile(lat, 0.99)),
        "p95_first_third_ms": float(np.quantile(lat[:third], 0.95)),
        "p95_last_third_ms": float(np.quantile(lat[-third:], 0.95)),
        "lateness_p99_ms": 1e3 * float(np.quantile(w["lateness_s"], 0.99)),
        "batch_fill": (run.counters["columns_real"]
                       / max(1, run.counters["columns_total"])),
        "compiles_in_window": run.compiles_in_window,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    t0 = time.monotonic()
    spec = harness.load_spec()
    cell, config, traffic = harness.find_cell(spec, args.workload)
    import jax
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.device_info(int(cell["chips"]))
    counter = harness.CompileCounter()
    base = harness.Run(workload=args.workload, kind=traffic["kind"],
                       seed=args.seed, seconds=args.seconds, cell=cell,
                       config=config, traffic=traffic)
    g, sess = harness.build(base)
    harness.warm_open_loop(sess, traffic, g,
                           schedule.rng_for(args.seed, "warm"))
    harness.log(f"[knee] set-up {time.monotonic() - t0:.1f} s")
    for rate in args.rates:
        run = dataclasses.replace(base, traffic={**traffic,
                                                 "rate_qps": rate})
        harness.open_loop_window(run, g, sess, counter)
        print(json.dumps(summarize(run)), flush=True)
    sess.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
