"""The YCSB request generator copied into bench/traffic/ycsb.py."""

import numpy as np
import pytest

from bench.traffic import ycsb

MIX = {"generator": "ycsb", "readproportion": 1.0,
       "requestdistribution": "zipfian", "zipfian_constant": 0.99,
       "multiget_keys": 4096, "clients": 1, "loop": "closed"}


def test_fnvhash64_matches_ycsb():
    # Utils.fnvhash64(0) and (1), worked through the Java loop by hand:
    # FNV-1a over eight bytes from the offset basis, then Math.abs
    def java(v):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h ^= v & 0xFF
            h = (h * 1099511628211) & (2 ** 64 - 1)
            v >>= 8
        return abs(h - 2 ** 64 if h >= 2 ** 63 else h)
    vals = np.array([0, 1, 255, 2 ** 40 + 7, 9_999_999_999])
    assert ycsb.fnvhash64(vals).tolist() == [java(int(v)) for v in vals]


def test_draws_are_deterministic_per_seed_and_in_range():
    n = 1_000_000
    for dist in ("zipfian", "uniform"):
        mix = dict(MIX, requestdistribution=dist)
        a = ycsb.draw(mix, n, np.random.default_rng(2 ** 31 + 5), 8)
        b = ycsb.draw(mix, n, np.random.default_rng(2 ** 31 + 5), 8)
        c = ycsb.draw(mix, n, np.random.default_rng(2 ** 31 + 6), 8)
        assert a.shape == (8, 4096)
        assert np.array_equal(a, b) and not np.array_equal(a, c)
        assert a.min() >= 0 and a.max() < n
        # one multi-get at a time, as the client draws them, is the same
        # stream
        rng = np.random.default_rng(2 ** 31 + 5)
        one = np.concatenate([ycsb.draw(mix, n, rng) for _ in range(8)])
        assert np.array_equal(one, a)


def test_hottest_key_share_matches_the_closed_form():
    """Rank 0 takes 1/zeta(10^10, 0.99) = 1/26.469 of the draws; it lands
    on record fnvhash64(0) % n."""
    n, draws = 1_000_000, 2_000_000
    got = ycsb.scrambled_zipfian(n, draws, np.random.default_rng(1))
    hot = int(ycsb.fnvhash64(np.array([0]))[0] % n)
    p = 1 / ycsb.ZETAN
    share = np.mean(got == hot)
    assert abs(share - p) < 4 * np.sqrt(p * (1 - p) / draws)
    assert np.bincount(got).argmax() == hot


def test_zipfian_ranks_follow_the_first_two_probabilities():
    u = np.random.default_rng(3).random(1_000_000)
    r = ycsb.zipfian_ranks(u, ycsb.ITEM_COUNT + 1, 0.99, ycsb.ZETAN)
    assert np.mean(r == 0) == pytest.approx(1 / ycsb.ZETAN, abs=2e-3)
    assert np.mean(r == 1) == pytest.approx(0.5 ** 0.99 / ycsb.ZETAN,
                                            abs=2e-3)
    assert r.min() >= 0 and r.max() <= ycsb.ITEM_COUNT


def test_mixes_the_harness_cannot_serve_are_refused():
    rng = np.random.default_rng(0)
    for bad in ({"readproportion": 0.95}, {"clients": 2},
                {"loop": "open"}, {"requestdistribution": "latest"}):
        with pytest.raises(ValueError):
            ycsb.draw(dict(MIX, **bad), 100, rng)
