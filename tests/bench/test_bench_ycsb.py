"""The benchmark's YCSB generator: shared popularity, the traffic file's
mix and zipfian constant, and seeds that fix the op sequence."""
import collections

import numpy as np
import pytest

from bench import spec, ycsb

TRAFFIC = sorted(p.stem for p in (spec.BENCH / "traffic").glob("*.json"))


def _fnv_loop(val: int) -> int:
    """YCSB's Utils.fnvhash64, one long at a time."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * 1099511628211) & (2 ** 64 - 1)
    signed = h - 2 ** 64 if h >= 2 ** 63 else h
    return abs(signed)


def test_fnvhash64_matches_the_loop():
    vals = [0, 1, 2, 255, 256, 12345678901, 2 ** 40 + 7, 9_999_999_999]
    got = ycsb.fnvhash64(np.array(vals, np.int64)).tolist()
    assert got == [_fnv_loop(v) for v in vals]


@pytest.mark.parametrize("name", TRAFFIC)
def test_mix_matches_the_traffic_file(name):
    traffic = spec.load_traffic(name)
    kinds, keys, _values = ycsb.OpStream(traffic, 10_000, 7).draw(200_000)
    for kind, share in traffic["mix"].items():
        got = float(np.mean(kinds == ycsb.KINDS[kind]))
        assert abs(got - share) < 0.01, (kind, got, share)
    assert keys.min() >= 1 and keys.max() <= 10_000


def test_zipfian_constant_matches_the_traffic_file():
    traffic = spec.load_traffic("ycsb_c_zipfian")
    theta = traffic["zipfian_constant"]
    u = np.random.default_rng(3).random(2_000_000)
    ranks = ycsb.zipfian_ranks(u, theta)
    p0, p1 = np.mean(ranks == 0), np.mean(ranks == 1)
    # zipfian: P(rank r) = 1 / ((r + 1)^theta * zeta(n, theta))
    assert p0 == pytest.approx(1 / ycsb.ZIPF_ZETAN, rel=0.02)
    assert p0 / p1 == pytest.approx(2 ** theta, rel=0.05)
    with pytest.raises(ValueError):
        ycsb.OpStream(dict(traffic, zipfian_constant=0.9), 1000, 0)


def test_all_clients_share_one_hot_set():
    """Clients take ops from one stream in turn, so every client's
    hottest keys are the global hottest keys (YCSB's shared
    popularity), not a private permutation per client."""
    traffic = spec.load_traffic("ycsb_c_zipfian")
    n_clients = 8
    _k, keys, _v = ycsb.OpStream(traffic, 100_000, 11).draw(400_000)
    top = [k for k, _ in collections.Counter(keys.tolist()).most_common(10)]
    for c in range(n_clients):
        mine = collections.Counter(keys[c::n_clients].tolist())
        assert len(set(top) & {k for k, _ in mine.most_common(10)}) >= 8


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5, 2 ** 40 + 3])
def test_a_seed_fixes_the_ops_whatever_the_draw_sizes(seed):
    traffic = spec.load_traffic("ycsb_a_uniform")
    whole = ycsb.OpStream(traffic, 1000, seed).draw(70_000)
    stream = ycsb.OpStream(traffic, 1000, seed)
    parts = [stream.draw(n) for n in (1, 4095, 61536, 4368)]
    for col, got in zip(whole, zip(*parts)):
        assert np.array_equal(col, np.concatenate(got))
    other = ycsb.OpStream(traffic, 1000, seed + 1).draw(70_000)
    assert not np.array_equal(whole[1], other[1])


def test_load_records_come_from_the_seed():
    k1, v1 = ycsb.load_records(1000, 2 ** 31 + 9)
    k2, v2 = ycsb.load_records(1000, 2 ** 31 + 9)
    assert k1.tolist() == list(range(1, 1001))
    assert np.array_equal(v1, v2)
    assert v1.min() >= 1 and v1.max() <= ycsb.VALUE_MAX
