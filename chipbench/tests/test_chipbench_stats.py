"""Percentiles on synthetic timelines and the generator's fixed amount of
work."""
import numpy as np
import pytest

from chipbench import stats
from chipbench.generator import Traffic, multiset

R = stats.Record


def test_percentiles_interpolate_between_order_statistics():
    assert stats.percentile([0.5, 0.5, 1.0, 1.0], 90) == pytest.approx(1.0)
    assert stats.percentile([1, 2, 3, 4, 5], 90) == pytest.approx(4.6)
    assert stats.percentile([], 90) is None


def test_gaps_end_inside_the_window():
    recs = [R(8, token_s=[-1.0, 0.5, 1.0, 3.0, 11.0]),
            R(8, token_s=[2.0, 2.2])]
    g = stats.gaps(recs, 0.0, 10.0)
    assert sorted(g) == pytest.approx(sorted([1.5, 0.5, 2.0, 0.2]))
    assert stats.tokens_in(recs, 0.0, 10.0) == 5
    e2e = stats.end_to_end(recs, 0.0, 10.0)
    assert e2e["tokens_per_s"] == pytest.approx(0.5)
    assert e2e["tbt_p95_s"] == pytest.approx(np.percentile(g, 95))


def test_multiset_follows_weights():
    assert sorted(multiset([[256, 0.4], [512, 0.3], [1024, 0.2],
                            [2048, 0.1]], 10)) == \
        [(256,)] * 4 + [(512,)] * 3 + [(1024,)] * 2 + [(2048,)]
    assert sorted(multiset([[1, 9, 1], [2, 8, 1], [3, 7, 1]], 4)) == \
        [(1, 9), (1, 9), (2, 8), (3, 7)]


MIX = {"requests": [[16, 4, 1], [32, 8, 1], [24, 2, 1]], "round": 3,
       "schedule_seed": 1}


def kinds(tr, n):
    return [(len(i.prompt), i.output_len)
            for i in (tr.request(k % tr.clients, k // tr.clients)
                      for k in range(n))]


def test_every_seed_gets_the_same_work():
    a = Traffic(MIX, seed=3_000_000_001, vocab=100, clients=4)
    b = Traffic(MIX, seed=7, vocab=100, clients=4)
    # the same kinds of request to the same clients, in the same order;
    # other token ids
    assert kinds(a, 24) == kinds(b, 24)
    pairs = [(a.request(c, k), b.request(c, k))
             for c in range(4) for k in range(6)]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in pairs)
    same = Traffic(MIX, seed=7, vocab=100, clients=4)
    assert all(np.array_equal(y.prompt, same.request(c, k).prompt)
               for (c, k), (_, y) in zip(
                   [(c, k) for c in range(4) for k in range(6)], pairs))
    # another schedule seed: the same work in another order
    c = Traffic(dict(MIX, schedule_seed=2), seed=7, vocab=100, clients=4)
    assert sorted(kinds(b, 24)) == sorted(kinds(c, 24))
    assert kinds(b, 24) != kinds(c, 24)


def test_closed_loop_waves_and_shared_prefix():
    """Every round of the schedule is the mix's multiset, and every
    request has a rid of its own and its kind's prompt length."""
    tr = Traffic(MIX, seed=5, vocab=50, clients=4)
    got = kinds(tr, 12)
    for r in range(4):
        assert sorted(got[3 * r:3 * r + 3]) == [(16, 4), (24, 2), (32, 8)]
    rids = {tr.request(c, k).rid for c in range(4) for k in range(3)}
    assert rids == set(range(12))
    assert tr.request(1, 2) is tr.request(1, 2)
    assert tr.lengths() == [16, 24, 32]
    assert all(0 <= t < 50 for c in range(4)
               for t in tr.request(c, 0).prompt)
