"""Percentile and inter-token-gap arithmetic; the request generator."""

import collections

import numpy as np
import pytest

from benchmarks.lib import manifest, stats, traffic


def test_percentile_matches_numpy_linear():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 11.0, 2.0]
    for q in (0, 50, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([4.0], 95) == 4.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    assert stats.samples_beyond(200, 95) == 10


def test_inter_token_gaps_skip_the_first_token():
    arrivals = [[1.0, 1.5, 2.5], [10.0], [], [3.0, 3.25]]
    assert stats.inter_token_gaps(arrivals) == [0.5, 1.0, 0.25]
    # only gaps whose later arrival is inside the window
    assert stats.inter_token_gaps(arrivals, (1.2, 2.0)) == [0.5]


def _mix():
    return manifest.load_json("traffic", "serve-long", manifest.BENCH_DIR)


def test_same_seed_same_requests_other_seed_other_order():
    a = traffic.requests(_mix(), 2**31 + 5, 50304, 130)
    b = traffic.requests(_mix(), 2**31 + 5, 50304, 130)
    c = traffic.requests(_mix(), 11, 50304, 130)
    assert a == b
    assert [r.prompt for r in a] != [r.prompt for r in c]
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]


def test_every_seed_offers_the_same_sizes_per_block():
    mix = _mix()
    n = mix["block"]

    def sizes(seed):
        reqs = traffic.requests(mix, seed, 50304, 2 * n)
        return [collections.Counter(
            (len(r.prompt), r.max_new_tokens) for r in reqs[i:i + n])
            for i in (0, n)]

    assert sizes(1)[0] == sizes(1)[1] == sizes(2)[0]
    lens = [p for p, _ in traffic.block_sizes(mix)]
    news = [m for _, m in traffic.block_sizes(mix)]
    assert 128 <= min(lens) and max(lens) <= 768
    assert 16 <= min(news) and max(news) <= 128
    assert max(p + m for p, m in traffic.block_sizes(mix)) <= 1024
    assert 330 < sum(lens) / n < 390 and 68 < sum(news) / n < 76


def test_token_pool_is_seeded():
    mix = manifest.load_json("traffic", "fit", manifest.BENCH_DIR)
    mix = dict(mix, pool_batches=2, seq_len=16)
    a = traffic.lm_token_pool(mix, 3, 512)
    assert a.shape == (16, 17) and a.dtype == np.int32
    assert (a == traffic.lm_token_pool(mix, 3, 512)).all()
    assert (a != traffic.lm_token_pool(mix, 4, 512)).any()


def test_open_loop_due_times_and_shared_prefixes_for_later_cells():
    mix = dict(_mix(), arrivals={"kind": "open_loop", "rate_per_s": 4.0},
               shared_prefix={"len": 64, "groups": 2})
    reqs = traffic.requests(mix, 9, 50304, 400)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] > 0
    assert 400 / due[-1] == pytest.approx(4.0, rel=0.15)
    heads = {tuple(r.prompt[:64]) for r in reqs}
    assert len(heads) == 2
    assert sorted(len(r.prompt) for r in reqs[:64]) == sorted(
        p for p, _ in traffic.block_sizes(mix))
    assert all(r.due_s is None for r in traffic.requests(_mix(), 9, 50304, 3))
