"""Traffic from a seed: the same work for every seed, in another order."""
import numpy as np

from bench import schedule

MIX = [{"algorithm": "khop", "k": 1, "share": 0.4},
       {"algorithm": "khop", "k": 2, "share": 0.3},
       {"algorithm": "khop", "k": 3, "share": 0.1},
       {"algorithm": "bfs", "share": 0.15},
       {"algorithm": "sssp", "share": 0.05}]
CANDIDATES = np.arange(10, 5000)


def plan(seed, **traffic):
    tr = {"rate_qps": 7.0, "mix": MIX, **traffic}
    return schedule.open_loop(seed, tr, 30.0, CANDIDATES)


def test_same_seed_same_schedule():
    assert plan(5) == plan(5)
    assert plan(5) != plan(6)


def test_every_seed_gets_the_same_counts():
    for seed in (1, 2, 2**33 + 3):
        p = plan(seed)
        assert len(p) == 210
        counts = np.bincount([a.query for a in p], minlength=len(MIX))
        assert counts.tolist() == [84, 63, 21, 32, 10]
        t = [a.at_s for a in p]
        assert t == sorted(t) and 0 <= t[0] and t[-1] < 30.0


def test_uniform_roots_are_distinct_candidates():
    roots = [a.root for a in plan(3)]
    assert len(set(roots)) == len(roots)
    assert set(roots) <= set(CANDIDATES.tolist())


def test_key_batches_are_distinct_within_a_key_set():
    gen = schedule.key_batches(9, CANDIDATES, 64, 16, key_seed=3)
    batches = [next(gen) for _ in range(8)]
    first_set = np.concatenate(batches[:4])
    assert len(set(first_set.tolist())) == 64
    assert all(b.size == 16 for b in batches)


def test_key_seed_fixes_the_keys_and_the_seed_orders_them():
    def first(seed, key_seed):
        gen = schedule.key_batches(seed, CANDIDATES, 64, 16,
                                   key_seed=key_seed)
        return [next(gen) for _ in range(5)]
    a, b = first(1, key_seed=7), first(2**33 + 1, key_seed=7)
    assert all(sorted(x) == sorted(y) for x, y in zip(a, b))
    assert any((x != y).any() for x, y in zip(a, b))
    assert any(sorted(x) != sorted(y)
               for x, y in zip(first(1, key_seed=7), first(1, key_seed=8)))


def test_shares_to_counts_sums_exactly():
    assert schedule.shares_to_counts([0.4, 0.3, 0.1, 0.15, 0.05],
                                     13).tolist() == [5, 4, 1, 2, 1]
