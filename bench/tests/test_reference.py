"""The vectorised reference against the program's per-lane reference
(``chip_smoke.py``'s, in ``lane_reference.py``) on a 30k-key run."""

import dataclasses

import numpy as np
import pytest

import reference
import traffic
from lane_reference import LaneReference, serve
from traffic import KEY_MAX, OP_INSERT, OP_LOOKUP, OP_SCAN, OP_UPDATE

N_KEYS = 30_000
LANES = 256
MAX_COUNT = 128
SEED = 2**33 + 7


@pytest.fixture(scope="module")
def run():
    """A run served by the per-lane reference: mixed ops under Zipf, shed
    lanes retried in the next dispatch, some inserts settled as splits, and
    scans that start just below freshly inserted keys."""
    keys = traffic.make_keys(N_KEYS, SEED)
    mix = {"ops": {"lookup": 0.3, "update": 0.3, "insert": 0.2, "scan": 0.2},
           "scan_length": {"distribution": "uniform", "min": 1, "max": 100}}
    client = traffic.Client(keys, {"request_distribution": "zipfian",
                                   "zipf_theta": 0.99}, mix, SEED, LANES)
    rng = np.random.default_rng(5)
    lane_ref = LaneReference(keys)
    book = reference.LogBuilder(LANES, MAX_COUNT)
    inserted = []
    for b in range(16):
        opc, kk, vv = client.next_batch()
        if inserted and b % 3 == 2:
            # scans from just below keys inserted earlier: the merge of
            # loaded and fresh keys, and reads of written values
            scan = opc == OP_SCAN
            pick = rng.choice(np.concatenate(inserted), int(scan.sum()))
            kk[scan] = pick - 1
        if b % 4 == 1:
            # a key written twice in one dispatch, and an update of a key
            # that was inserted earlier
            upd = np.nonzero(opc == OP_UPDATE)[0]
            kk[upd[1]] = kk[upd[0]]
            if inserted:
                kk[upd[2]] = inserted[-1][0]
        pending = np.ones(LANES, bool)
        shed = rng.random(LANES) < 0.1
        for attempt in range(2):
            lanes = pending & ~shed if attempt == 0 else pending
            ins = np.nonzero(lanes & (opc == OP_INSERT))[0]
            split = set(ins[: ins.size // 3].tolist())
            found, value, status, settle, sk, sv, taken = serve(
                lane_ref, opc, np.where(lanes, kk, KEY_MAX), vv, MAX_COUNT,
                split)
            book.add(opc, kk, vv, found, value, status, lanes, settle=settle,
                     scan_keys=sk, scan_values=sv, taken=taken)
            pending &= ~lanes
        inserted.append(kk[opc == OP_INSERT])
    return keys, book.build()


def test_vectorised_answers_equal_the_lane_reference(run):
    keys, lg = run
    exp = reference.answers(keys, lg)
    look = lg.opc == OP_LOOKUP
    assert np.array_equal(exp.found[look], lg.found[look])
    assert np.array_equal(exp.value[look], lg.value[look])
    upd = lg.opc == OP_UPDATE
    assert np.array_equal(exp.status[upd], lg.status[upd])
    assert np.array_equal(exp.taken, lg.taken)
    assert np.array_equal(exp.scan_keys, lg.scan_keys)
    assert np.array_equal(exp.scan_values, lg.scan_values)
    # the run covered what it is meant to
    assert (lg.status[lg.opc == OP_INSERT] == reference.STATUS_SPLIT).any()
    assert (lg.status[upd] == reference.STATUS_OK).any()
    assert (lg.taken == MAX_COUNT).any() or (lg.taken > 50).any()
    v = reference.compare(keys, lg)
    assert v["mismatched"] == 0 and v["failed"] == 0


def _bad(keys, lg, **fields):
    return reference.compare(keys, dataclasses.replace(lg, **fields))


def test_each_kind_of_wrong_answer_is_caught(run):
    keys, lg = run
    look = np.nonzero((lg.opc == OP_LOOKUP) & lg.found)[0]
    v = lg.value.copy()
    v[look[3]] += 1
    assert _bad(keys, lg, value=v)["by_op"]["lookup"] == 1
    f = lg.found.copy()
    f[look[5]] = False
    assert _bad(keys, lg, found=f)["mismatched"] == 1
    upd = np.nonzero(lg.opc == OP_UPDATE)[0]
    s = lg.status.copy()
    s[upd[0]] = 1 - s[upd[0]]
    assert _bad(keys, lg, status=s)["by_op"]["update"] == 1
    sk = lg.scan_keys.copy()
    row = int(np.argmax(lg.taken))
    sk[row, 0] += 1
    assert _bad(keys, lg, scan_keys=sk)["by_op"]["scan"] == 1
    sk = lg.scan_keys.copy()
    short = int(np.argmin(lg.taken))
    sk[short, MAX_COUNT - 1] = 0                   # padding must be KEY_MAX
    assert _bad(keys, lg, scan_keys=sk)["by_op"]["scan"] == 1
    tk = lg.taken.copy()
    tk[row] -= 1
    assert _bad(keys, lg, taken=tk)["by_op"]["scan"] == 1
    ins = np.nonzero((lg.opc == OP_INSERT) & (lg.status == 1))[0]
    s = lg.status.copy()
    s[ins[0]] = reference.STATUS_MISS
    assert _bad(keys, lg, status=s)["by_op"]["insert"] == 1


def test_unserved_split_is_failed_and_not_written(run):
    keys, lg = run
    split = np.nonzero(lg.settle == reference.STATUS_OK)[0]
    st = lg.settle.copy()
    st[split[0]] = reference.STATUS_SPLIT
    v = reference.compare(keys, dataclasses.replace(lg, settle=st))
    assert v["failed"] == 1


def test_control_with_32_bit_keys_fails(run):
    keys, lg = run
    got = reference.control_answers(keys, lg)
    v = reference.compare(keys, lg, got)
    # every scan that takes a record returns cut keys; a lookup can go
    # wrong only where its key's cut is shared with another key
    scans = (lg.opc == OP_SCAN).sum()
    assert v["by_op"]["scan"] >= 0.9 * scans > 0
    every = np.unique(np.concatenate([keys, lg.key]))
    cut, count = np.unique(reference.truncate32(every), return_counts=True)
    shared = np.isin(reference.truncate32(lg.key), cut[count > 1])
    assert v["by_op"]["lookup"] <= (shared & (lg.opc == OP_LOOKUP)).sum()


def test_control_is_wrong_on_colliding_keys():
    # 2**32 + 5 and 5 share a cut key: the later-loaded one keeps the slot,
    # so a lookup of 5 reads 2**32 + 5's value; 9 collides with nothing
    keys = np.array([5, 9, 2**32 + 5], np.int64)
    b = reference.LogBuilder(lanes=3, max_count=4)
    opc = np.full(3, OP_LOOKUP, np.int32)
    z = np.zeros(3, np.int64)
    b.add(opc, keys, z, np.ones(3, bool), traffic.loaded_values(keys),
          np.zeros(3, np.int32), np.ones(3, bool),
          scan_keys=np.zeros((3, 4), np.int64),
          scan_values=np.zeros((3, 4), np.int64), taken=np.zeros(3, np.int32))
    lg = b.build()
    assert reference.compare(keys, lg)["mismatched"] == 0
    got = reference.control_answers(keys, lg)
    assert got.value.tolist() == traffic.loaded_values(
        np.array([2**32 + 5, 9, 2**32 + 5])).tolist()
    v = reference.compare(keys, lg, got)
    assert v["mismatched"] == 1 and v["first"][0][1] == 5
