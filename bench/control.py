"""Controls: the plain reference put in the program's place with one stated
guarantee broken, read through the comparison that decides ``correct``.

    python bench/control.py --workload g500-sssp --seeds 11 12 13

For each seed this builds the cell's graph as a run does, draws the answers
a run would check (the sampled columns of the window's first batch, or the
sampled arrivals), answers them with the control, and prints the numbers
compared beside their limits. Every seed has to come out not correct. The
controls:

* BFS and k-hop levels one level short: the fixpoint stopping a sweep
  early;
* SSSP distances from Bellman-Ford with every weight and sum in bfloat16,
  the precision below the float32 the configuration states.

It runs on the host and needs no chip; the cell's sizes make it slow.
"""
from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

import numpy as np

if __package__ in (None, ""):
    _root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root), str(_root / "src")]

from bench import checks, gen, oracle, schedule  # noqa: E402
from bench.harness import ROOT, find_cell, load_spec  # noqa: E402


def control_answer(g, entry: dict, root: int):
    """The control's answer to one query (values, parents=None)."""
    alg = entry["algorithm"]
    if alg == "sssp":
        import ml_dtypes
        values = oracle.bellman_ford(g, root, ml_dtypes.bfloat16)
    else:
        depth = entry.get("k")
        if depth is None:
            depth = int(oracle.bfs_levels(g, root).max())
        values = oracle.bfs_levels(g, root, max_depth=depth - 1)
    return types.SimpleNamespace(values=values, parents=None, ok=True)


def _reach(entry, res):
    if entry["algorithm"] == "sssp":
        return int(np.isfinite(res.values).sum())
    return int((res.values >= 0).sum())


def control_numbers(g, config: dict, traffic: dict, seed: int,
                    seconds: float) -> dict:
    """The numbers compared when the control answers a run's sample."""
    candidates = np.nonzero(g.deg > 0)[0]
    run = types.SimpleNamespace(config=config, traffic=traffic, seed=seed,
                                kind=traffic["kind"],
                                window={"failed": 0, "reach": []})
    if traffic["kind"] == "batches":
        keys = next(schedule.key_batches(seed, candidates,
                                         config["search_keys"],
                                         traffic["batch"],
                                         key_seed=config["key_seed"]))
        pick = checks.column_picker(seed, int(traffic["check_sample"]))
        kept = []
        for c in pick(keys.size):
            root = int(keys[c])
            res = control_answer(g, traffic, root)
            kept.append((root, res))
            run.window["reach"].append((root, _reach(traffic, res)))
    else:
        plan = schedule.open_loop(seed, traffic, seconds, candidates)
        kept = {}
        for i in checks.sample_arrivals(plan, traffic, seed):
            entry = traffic["mix"][plan[i].query]
            res = control_answer(g, entry, plan[i].root)
            kept[i] = (plan[i], res)
            if entry["algorithm"] in ("bfs", "sssp"):
                run.window["reach"].append((plan[i].root, _reach(entry, res)))
    return checks.compare(run, g, kept)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=None,
                   help="window length whose schedule is sampled "
                        "(default: BENCHMARK.json run_seconds)")
    args = p.parse_args(argv)
    spec = load_spec(ROOT)
    _, config, traffic = find_cell(spec, args.workload, ROOT)
    seconds = args.seconds or float(spec["run_seconds"])
    all_fail = True
    g = gen.from_config(config)
    for seed in args.seeds:
        nums = control_numbers(g, config, traffic, seed, seconds)
        failed = [k for k, c in nums.items() if c["value"] > c["limit"]]
        all_fail &= bool(failed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_fails": failed, "numbers": nums}),
              flush=True)
    return 0 if all_fail else 1


if __name__ == "__main__":
    sys.exit(main())
