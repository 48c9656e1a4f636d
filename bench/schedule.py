"""Traffic from a seed: Graph500 key sets and open-loop query schedules.

Every seed gets the same amount of work in a different order: an open-loop
schedule has exactly ``round(rate * seconds)`` arrivals and exactly each
query type's share of them; only the arrival instants, the order of the
types and the roots come from the seed. Arrivals are a Poisson process at
``rate_qps`` conditioned on its count, so the instants are independent and
uniform over the window; roots are distinct vertices of degree > 0, drawn
uniformly.

Graph500 key batches come from the configuration's ``key_seed``: every
seed runs the same keys in the same batches, as Graph500 runs kernels 2
and 3 from one key set, and the seed only orders the keys within each
batch.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    at_s: float            # scheduled instant, seconds after the window opens
    query: int             # index into the traffic file's "mix"
    root: int


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """Independent generator per use of the seed."""
    return np.random.default_rng([int(seed), *stream.encode()])


def shares_to_counts(shares: Sequence[float], total: int) -> np.ndarray:
    """Largest-remainder rounding of ``shares`` (normalised) to ``total``."""
    p = np.asarray(shares, np.float64)
    p = p / p.sum()
    raw = p * total
    counts = np.floor(raw).astype(np.int64)
    rest = total - int(counts.sum())
    counts[np.argsort(-(raw - counts), kind="stable")[:rest]] += 1
    return counts


def arrival_times(rng, rate_qps: float, seconds: float) -> np.ndarray:
    total = int(round(rate_qps * seconds))
    return np.sort(rng.uniform(0.0, seconds, total))


def draw_roots(rng, candidates: np.ndarray, count: int) -> np.ndarray:
    if count > candidates.size:
        raise ValueError(f"{count} distinct roots asked of "
                         f"{candidates.size} candidates")
    return rng.choice(candidates, count, replace=False)


def open_loop(seed: int, traffic: dict, seconds: float,
              candidates: np.ndarray) -> List[Arrival]:
    """The whole schedule of one window, sorted by arrival instant."""
    rng = rng_for(seed, "open_loop")
    times = arrival_times(rng, float(traffic["rate_qps"]), seconds)
    counts = shares_to_counts([q["share"] for q in traffic["mix"]],
                              times.size)
    kinds = rng.permutation(np.repeat(np.arange(counts.size), counts))
    roots = draw_roots(rng, candidates, times.size)
    return [Arrival(float(t), int(k), int(r))
            for t, k, r in zip(times, kinds, roots)]


def key_batches(seed: int, candidates: np.ndarray, key_set: int,
                batch: int, key_seed: int):
    """Endless Graph500 key batches: each key set holds ``key_set``
    distinct search keys drawn from ``key_seed``, cut into batches of
    ``batch`` in order; ``seed`` orders the keys of each batch."""
    rng = rng_for(key_seed, "keys")
    order = rng_for(seed, "order")
    while True:
        keys = rng.choice(candidates, min(key_set, candidates.size),
                          replace=False)
        for i in range(0, keys.size - batch + 1, batch):
            yield order.permutation(keys[i:i + batch])
