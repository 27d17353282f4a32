"""The traffic generator: determinism by seed, fixed work, Zipfian skew and
request sizes."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import traffic  # noqa: E402

MIX_CLOSED = {
    "ops": {"lookup": 0.95, "update": 0.05},
    "keys": {"distribution": "scrambled_zipfian", "theta": 0.99},
    "keys_per_request": 1,
    "loop": "closed",
}
MIX_WIDE = dict(MIX_CLOSED, keys_per_request=5)
BIG_SEED = 2**31 + 12345


def _make(mix, seed, n=2000, records=100_000):
    return traffic.make_requests(mix, records, n, seed, 1)


@pytest.mark.parametrize("mix", [MIX_CLOSED, MIX_WIDE], ids=["closed", "wide"])
def test_same_seed_same_requests(mix):
    a, b = _make(mix, BIG_SEED), _make(mix, BIG_SEED)
    for f in ("kind", "offsets", "keys", "values"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


def test_seeds_change_keys_not_work():
    a, b = _make(MIX_CLOSED, BIG_SEED), _make(MIX_CLOSED, 7)
    assert not np.array_equal(a.keys[:100], b.keys[:100])
    assert not np.array_equal(a.kind, b.kind)
    # the same requests of each kind, in another order
    for k in range(len(traffic.KINDS)):
        assert (a.kind == k).sum() == (b.kind == k).sum()
    np.testing.assert_array_equal(a.sizes(), b.sizes())


def test_update_share_of_keys():
    r = _make(MIX_CLOSED, 3, n=20_000)
    share = r.sizes()[r.kind == traffic.KINDS.index("update")].sum() / r.sizes().sum()
    assert 0.045 < share < 0.055


def test_keys_are_loaded_records():
    records = 50_000
    r = _make(MIX_CLOSED, 11, records=records)
    loaded = set(traffic.record_keys(records).tolist())
    assert set(r.keys.tolist()) <= loaded
    np.testing.assert_array_equal(traffic.record_keys(3), [2, 4, 6])
    np.testing.assert_array_equal(traffic.record_values(3), [0, 1, 2])


def test_zipfian_head_share():
    n, theta = 1_000_000, 0.99
    z = traffic.Zipfian(n, theta)
    ranks = z.ranks(np.random.default_rng(0).random(400_000))
    assert ranks.min() >= 0 and ranks.max() < n
    for k in (1, 10, 1000):
        want = z.head_share(k)
        got = float(np.mean(ranks < k))
        assert abs(got - want) < 0.1 * want + 0.01, (k, got, want)
    # a heavy head: the hottest 0.1% of records take over half the draws
    assert z.head_share(1000) > 0.5


def test_scramble_spreads_the_hot_set():
    records = 100_000
    r = _make(dict(MIX_CLOSED, ops={"lookup": 1.0}), 5, n=5000, records=records)
    hot = np.bincount((r.keys - 2) // 2, minlength=records).argsort()[-20:]
    # the hottest records are not the lowest keys, as unscrambled ranks would be
    assert np.median(hot) > records / 10


def test_fixed_request_sizes_and_their_keys():
    r = _make(MIX_WIDE, 9, n=50)
    assert np.all(r.sizes() == 5) and r.keys.size == 250
    js = np.array([7, 3, 3, 49])
    want = np.concatenate([r.keys[r.span(j)] for j in js])
    np.testing.assert_array_equal(r.keys_of(js), want)
    assert r.keys_of(np.zeros(0, np.int64)).size == 0


def test_bad_mixes_are_refused():
    with pytest.raises(ValueError):
        _make(dict(MIX_CLOSED, ops={"lookup": 0.5}), 1)
    with pytest.raises(ValueError):
        _make(dict(MIX_CLOSED, ops={"scan": 1.0}), 1)
    with pytest.raises(ValueError):
        _make(dict(MIX_CLOSED, loop="sometimes"), 1)
    with pytest.raises(ValueError):
        _make(dict(MIX_CLOSED, keys_per_request=0), 1)
