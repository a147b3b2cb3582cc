"""The key generator and the client batches, drawn from the seed."""

import numpy as np

import traffic

BIG_SEED = 2**31 + 12345


def test_keys_sorted_unique_in_range():
    n = 200_000
    k = traffic.make_keys(n, BIG_SEED)
    assert k.dtype == np.int64 and k.size == n
    assert (np.diff(k) > 0).all()
    slot = k >> traffic.LOW_BITS
    assert slot.min() >= 1 and slot.max() <= traffic.SLOTS_PER_KEY * n
    # one key in four slots, and no key fits in 32 bits
    assert (k > np.iinfo(np.int32).max).all()
    assert np.array_equal(k, traffic.make_keys(n, BIG_SEED))
    assert not np.array_equal(k, traffic.make_keys(n, BIG_SEED + 1))


def _client(dist, mix, n=50_000, lanes=1024, seed=BIG_SEED):
    keys = traffic.make_keys(n, seed)
    config = {"request_distribution": dist, "zipf_theta": 0.99}
    return keys, traffic.Client(keys, config, mix, seed, lanes)


def test_batches_follow_the_mix_and_the_seed():
    mix = {"ops": {"scan": 0.95, "insert": 0.05},
           "scan_length": {"distribution": "uniform", "min": 1, "max": 100}}
    keys, c = _client("zipfian", mix)
    _, c2 = _client("zipfian", mix)
    seen_vals = []
    for _ in range(20):
        opc, kk, vv = c.next_batch()
        o2, k2, v2 = c2.next_batch()
        assert np.array_equal(kk, k2) and np.array_equal(opc, o2)
        assert set(np.unique(opc)) <= {traffic.OP_SCAN, traffic.OP_INSERT}
        scan = opc == traffic.OP_SCAN
        assert vv[scan].min() >= 1 and vv[scan].max() <= 100
        assert np.isin(kk[scan], keys).all()
        ins = opc == traffic.OP_INSERT
        assert not np.isin(kk[ins], keys).any()
        seen_vals.append(vv[ins])
    vals = np.concatenate(seen_vals)
    assert np.unique(vals).size == vals.size      # every write is distinct


def test_zipfian_is_skewed_and_uniform_is_not():
    mix = {"ops": {"lookup": 1.0}}
    _, z = _client("zipfian", mix)
    _, u = _client("uniform", mix)
    kz = np.concatenate([z.next_batch()[1] for _ in range(50)])
    ku = np.concatenate([u.next_batch()[1] for _ in range(50)])
    top = lambda k: np.unique(k, return_counts=True)[1].max() / k.size
    assert top(kz) > 0.02 > top(ku)
