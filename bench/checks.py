"""What decides ``correct``: answers of the timed path, sampled from the
seed once the window has closed, compared with the plain references.

Numbers compared, each with its limit from the configuration's
``checks.limits``:

* ``level_mismatch``: vertices whose BFS or k-hop level differs from the
  reference, summed over the sampled answers (exact: limit 0);
* ``parent_faults``: vertices whose parent breaks a Graph500 tree rule,
  summed over the sampled answers (exact: limit 0);
* ``dist_gap``: the widest relative gap of a sampled SSSP distance from
  float64 Dijkstra (``oracle.distance_gap``);
* ``reach_mismatch``: BFS and SSSP answers of the whole window, sampled or
  not, that reach another number of vertices than the root's connected
  component holds (exact: limit 0);
* ``unanswered``: queries or keys of the window that never came back, or
  came back failed (limit 0).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import oracle, schedule


def sample_arrivals(plan, traffic: dict, seed: int) -> List[int]:
    """Arrival indices to check: ``check_per_type`` of each query type."""
    rng = schedule.rng_for(seed, "check")
    kinds = np.asarray([a.query for a in plan], np.int64)
    out: List[int] = []
    for k in range(len(traffic["mix"])):
        idx = np.nonzero(kinds == k)[0]
        take = min(int(traffic["check_per_type"]), idx.size)
        out.extend(int(i) for i in rng.choice(idx, take, replace=False))
    return sorted(out)


def column_picker(seed: int, take: int):
    """``pick(width)``: the columns of the next batch to check, ``take``
    distinct ones (all of a narrower batch), drawn from the seed."""
    rng = schedule.rng_for(seed, "check")

    def pick(width: int) -> List[int]:
        return sorted(int(c) for c in rng.choice(width, min(take, width),
                                                  replace=False))
    return pick


def _entry(value, limit) -> dict:
    return {"value": value, "limit": limit}


def _sssp_numbers(g, root, res, cfg) -> tuple:
    ref = oracle.dijkstra(g, root)
    gap = oracle.distance_gap(res.values, ref)
    faults = 0
    if res.parents is not None:
        tight = cfg["checks"]["parent_tight"]
        faults = oracle.parent_faults(g, root, ref, res.parents,
                                      dist=res.values, atol=tight["atol"],
                                      rtol=tight["rtol"])
    return gap, faults


def compare_batches(run, g, kept) -> Dict[str, dict]:
    """Every kept answer: the sampled columns of every batch."""
    cfg, tr = run.config, run.traffic
    limits = cfg["checks"]["limits"]
    mismatch = faults = 0
    gap = 0.0
    for root, res in kept:
        if tr["algorithm"] == "bfs":
            ref = oracle.bfs_levels(g, root)
            mismatch += oracle.level_mismatches(res.values, ref)
            if res.parents is not None:
                faults += oracle.parent_faults(g, root, ref, res.parents)
        else:
            gap_r, faults_r = _sssp_numbers(g, root, res, cfg)
            gap = max(gap, gap_r)
            faults += faults_r
    unanswered = run.window["failed"] + (0 if kept else 1)
    out = {}
    if tr["algorithm"] == "bfs":
        out["level_mismatch"] = _entry(mismatch, limits["level_mismatch"])
    else:
        out["dist_gap"] = _entry(gap, limits["dist_gap"])
    if tr["need_parents"]:
        out["parent_faults"] = _entry(faults, limits["parent_faults"])
    out["unanswered"] = _entry(unanswered, limits["unanswered"])
    return out


def compare_open_loop(run, g, kept) -> Dict[str, dict]:
    cfg, mix = run.config, run.traffic["mix"]
    limits = cfg["checks"]["limits"]
    mismatch = 0
    gap = 0.0
    for i in sorted(kept):
        arrival, res = kept[i]
        entry = mix[arrival.query]
        alg = entry["algorithm"]
        if alg == "sssp":
            gap = max(gap, _sssp_numbers(g, arrival.root, res, cfg)[0])
        else:
            ref = oracle.bfs_levels(g, arrival.root,
                                    max_depth=entry.get("k"))
            mismatch += oracle.level_mismatches(res.values, ref)
    algs = {e["algorithm"] for e in mix}
    out = {}
    if algs - {"sssp"}:
        out["level_mismatch"] = _entry(mismatch, limits["level_mismatch"])
    if "sssp" in algs:
        out["dist_gap"] = _entry(gap, limits["dist_gap"])
    out["unanswered"] = _entry(run.window["failed"] + (0 if kept else 1),
                               limits["unanswered"])
    return out


def reach_mismatches(g, reach) -> int:
    """Answers whose reached-vertex count is not their root's component
    size."""
    if not reach:
        return 0
    labels, sizes = oracle.components(g)
    roots, counts = np.asarray(reach, np.int64).T
    return int((sizes[labels[roots]] != counts).sum())


def compare(run, g, kept) -> Dict[str, dict]:
    out = (compare_batches if run.kind == "batches"
           else compare_open_loop)(run, g, kept)
    if run.window["reach"] or run.kind == "batches":
        out["reach_mismatch"] = _entry(
            reach_mismatches(g, run.window["reach"]),
            run.config["checks"]["limits"]["reach_mismatch"])
    return out
