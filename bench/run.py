#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

    python bench/run.py --workload g500-bfs --seed 7 --seconds 30 --trace 0

Cells, configurations, traffic mixes and metrics are listed in
``BENCHMARK.json`` at the checkout's root and found under ``bench/`` by
name. Progress and the numbers compared for ``correct`` go to standard
error; the last line of standard output is the result as one JSON object.
Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
import sys
import time

T0 = time.monotonic()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T0))
