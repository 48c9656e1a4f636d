"""Shared set-up of the benchmark's own tests (run by hand, on the CPU):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

``tiny_root`` is a copy of the benchmark whose configurations are cut to
a graph small enough for the CPU; the harness runs it without a chip. It
also lists the served cell ``svc-khop1`` and its metrics, whose files are
under ``bench/`` but which ``BENCHMARK.json`` does not list yet (see
PERF.md, Open questions), as a later change would add them.
"""
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_SCALE = 10

SERVED = {
    "configs": [{"name": "snb-svc-kron",
                 "file": "bench/configs/snb-svc-kron.json"}],
    "workloads": [{"name": "svc-khop1", "config": "snb-svc-kron",
                   "traffic": "snb-khop1", "chips": 1}],
    "end_to_end": [{"name": n, "unit": "ms", "workloads": ["svc-khop1"]}
                   for n in ("query_p95_ms", "query_p50_ms")],
    "per_layer": [{"name": n, "unit": u, "workloads": ["svc-khop1"]}
                  for n, u in (("dev_ms_per_sweep.svc", "ms"),
                               ("sweeps_per_query.svc", "sweeps"),
                               ("batch_fill.svc", "%"),
                               ("idle_share.svc", "%"))],
}


@pytest.fixture
def tiny_root(tmp_path):
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for key, entries in SERVED.items():
        spec[key] += entries
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for cfg in (tmp_path / "bench" / "configs").glob("*.json"):
        data = json.loads(cfg.read_text())
        data["graph"]["scale"] = TINY_SCALE
        cfg.write_text(json.dumps(data))
    return tmp_path
