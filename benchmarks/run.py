"""Benchmark harness: one module per paper table/figure (DESIGN.md §8).

Prints ``name,us_per_call,derived`` CSV *and* writes a machine-readable
``BENCH_<tag>.json`` (scheme -> TEPS/bytes/iterations) shared by local runs
and the CI ``bench-smoke`` job, so perf lands with a tracked trajectory
instead of only human-readable prints.

    PYTHONPATH=src python -m benchmarks.run                      # everything
    PYTHONPATH=src python -m benchmarks.run --only direction \
        --scale 10 --tag ci --check-teps                         # CI smoke
"""
from __future__ import annotations

import argparse
import inspect
import math
import sys
import time

from . import (bench_cc, bench_direction, bench_layout, bench_multisource,
               bench_packed, bench_semirings, bench_serving, bench_slimchunk,
               bench_slimsell, bench_slimwork, bench_sssp, bench_storage,
               bench_vs_traditional, bench_work, bench_workloads)
from . import common
from repro.compile_cache import use_compile_cache

ALL = {
    "storage": bench_storage,            # Table III / Fig 7
    "semirings": bench_semirings,        # Fig 5a-c / Fig 8
    "slimsell": bench_slimsell,          # Table V
    "slimwork": bench_slimwork,          # Fig 5d
    "slimchunk": bench_slimchunk,        # Fig 6e
    "vs_traditional": bench_vs_traditional,  # Fig 9/10 + Fig 1
    "work": bench_work,                  # Table II, Eq (1)(2)
    "layout": bench_layout,              # beyond-paper: SpMM backends
    "direction": bench_direction,        # beyond-paper: push/pull/auto TEPS
    "sssp": bench_sssp,                  # beyond-paper: delta-stepping SSSP
    "cc": bench_cc,                      # beyond-paper: connected components
    "multisource": bench_multisource,    # beyond-paper: batched BFS/SSSP
    "serving": bench_serving,            # beyond-paper: GraphSession qps
    "packed": bench_packed,              # beyond-paper: SlimSell-B word sweeps
    "workloads": bench_workloads,        # beyond-paper: PageRank/BC/k-hop
}


def check_teps(payload: dict) -> int:
    """Exit status: nonzero when any recorded TEPS is missing/NaN/zero."""
    teps = {s: m["teps"] for s, m in payload["schemes"].items() if "teps" in m}
    if not teps:
        print("# TEPS check FAILED: no scheme recorded a teps metric")
        return 1
    bad = {s: v for s, v in teps.items()
           if not math.isfinite(v) or v <= 0}
    if bad:
        print(f"# TEPS check FAILED: {bad}")
        return 1
    print(f"# TEPS check ok: {len(teps)} schemes, "
          f"min={min(teps.values()):.3e} max={max(teps.values()):.3e}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated bench names")
    ap.add_argument("--tag", default="local",
                    help="results file suffix: BENCH_<tag>.json")
    ap.add_argument("--json", default="",
                    help="explicit results path (default BENCH_<tag>.json)")
    ap.add_argument("--scale", type=int, default=None,
                    help="graph scale override for benches that accept one")
    ap.add_argument("--check-teps", action="store_true",
                    help="exit nonzero when any recorded TEPS is NaN/zero")
    args = ap.parse_args(argv)
    use_compile_cache()
    names = [n for n in args.only.split(",") if n] or list(ALL)
    print("name,us_per_call,derived")
    for name in names:
        mod = ALL[name]
        kwargs = {}
        if args.scale is not None and \
                "scale" in inspect.signature(mod.run).parameters:
            kwargs["scale"] = args.scale
        t0 = time.time()
        mod.run(**kwargs)
        print(f"# {name} done in {time.time()-t0:.0f}s", flush=True)
    payload = common.write_json(args.json or f"BENCH_{args.tag}.json",
                                args.tag)
    return check_teps(payload) if args.check_teps else 0


if __name__ == "__main__":
    sys.exit(main())
