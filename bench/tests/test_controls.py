"""The controls, at a size a test run holds: the reference with one stated
guarantee broken, put in the program's place, comes out not correct on
every seed (``bench/control.py`` reads the same at the cells' sizes)."""
import json

import pytest

from bench import control, gen, harness

CELLS = ("g500-bfs", "g500-sssp", "svc-khop1")


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(tiny_root, workload):
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    _, config, traffic = harness.find_cell(spec, workload, tiny_root)
    g = gen.from_config(config)
    for seed in (3, 2**31 + 9, 2**33 + 1):
        nums = control.control_numbers(g, config, traffic, seed, 2.0)
        assert any(c["value"] > c["limit"] for c in nums.values()), nums
