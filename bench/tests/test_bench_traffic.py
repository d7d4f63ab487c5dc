"""The request generator: repeatable per seed, YCSB's hash and skew, and
the mix's op shares within sampling error."""
import json

import numpy as np
import pytest

from bench_tiny import BENCH
from yardstick import spec, traffic as tf


def java_fnvhash64(val: int) -> int:
    """YCSB ``Utils.fnvhash64`` with Java's 64-bit signed arithmetic."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * 1099511628211) & ((1 << 64) - 1)
    signed = h - (1 << 64) if h >= 1 << 63 else h
    return abs(signed)


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def stream(name, n_keys, seed, length):
    m = mix(name)
    keys = 3 * np.arange(n_keys, dtype=np.int64) + 7
    return keys, tf.make_stream(m, spec.load_ops(BENCH, m), keys,
                                np.random.default_rng(seed), length)


def test_fnvhash64_matches_ycsb():
    vals = [0, 1, 2, 255, 256, 123456789, 9_999_999_999, (1 << 62) + 17]
    got = tf.fnvhash64(np.array(vals, np.int64))
    assert got.tolist() == [java_fnvhash64(v) for v in vals]
    assert (got >= 0).all()


def test_stream_repeats_per_seed_and_differs_across_seeds():
    keys, a = stream("ycsb_a", 1000, 5, 4096)
    _, b = stream("ycsb_a", 1000, 5, 4096)
    _, c = stream("ycsb_a", 1000, 6, 4096)
    for f in ("op", "key", "arg"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.key, c.key)
    assert np.isin(a.key, keys).all()
    assert set(a.op.tolist()) == {0, 1}
    upd = a.op == 1
    assert (a.arg[~upd] == 0).all()
    assert a.arg[upd].min() >= 0 and a.arg[upd].max() < tf.VALUE_LIMIT


@pytest.mark.parametrize("name,p_read", [("ycsb_a", 0.5), ("ycsb_c", 1.0)])
def test_op_shares_within_sampling_error(name, p_read):
    n = 200_000
    _, s = stream(name, 1_000_000, 1, n)
    assert list(mix(name)["ops"])[0] == "read"
    share = float((s.op == 0).mean())
    sigma = np.sqrt(p_read * (1 - p_read) / n)
    assert abs(share - p_read) <= 5 * sigma + 1e-12


def test_scrambled_zipfian_skew_within_sampling_error():
    n = 400_000
    idx = tf.scrambled_zipfian(np.random.default_rng(2), n, 24_000_000)
    items, counts = np.unique(idx, return_counts=True)
    order = np.argsort(counts)[::-1]
    # zipf ranks 0 and 1 carry 1/zeta and 2**-0.99/zeta of the draws
    for rank, count in enumerate(counts[order[:2]]):
        p = (rank + 1) ** -0.99 / tf.YCSB_ZETAN
        assert abs(count / n - p) <= 5 * np.sqrt(p * (1 - p) / n)
    # the hash scatters the hottest items over the whole key range
    assert np.ptp(items[order[:64]]) > 12_000_000


def test_zipfian_ranks_follow_ycsb_formula():
    u = np.array([0.0, 0.9 / tf.YCSB_ZETAN, 1.5 / tf.YCSB_ZETAN, 0.999999])
    z = tf.zipfian(u, tf.YCSB_ITEM_COUNT, 0.99, tf.YCSB_ZETAN)
    assert z[:3].tolist() == [0, 0, 1]
    assert 0 < z[3] < tf.YCSB_ITEM_COUNT
