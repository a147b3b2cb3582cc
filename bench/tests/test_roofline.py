"""The bytes a kernel's work needs, on known shapes, and the peak table."""

import types

import numpy as np
import pytest

import harness
import reference
import roofline
from traffic import OP_INSERT, OP_LOOKUP, OP_SCAN, OP_UPDATE

SCAN = harness.load_metric("leaf_scan_roofline")
WRITE = harness.load_metric("leaf_write_roofline")


def test_leaf_scan_rows():
    # 44 keys a leaf at fill 0.7: a scan from the 40th key of a leaf that
    # takes 10 records spans two leaves; one that takes none visits one
    start = np.array([40, 0, 43, 44, 5])
    taken = np.array([10, 44, 1, 100, 0])
    assert SCAN.leaf_rows(start, taken, 44).tolist() == [2, 1, 1, 3, 1]


def _log(opc, key, status, taken):
    b = reference.LogBuilder(lanes=len(opc), max_count=128)
    opc = np.array(opc, np.int32)
    n = opc.size
    key = np.array(key, np.int64)
    scan = opc == OP_SCAN
    sk = np.zeros((n, 128), np.int64)
    tk = np.zeros(n, np.int32)
    tk[scan] = np.array(taken)
    b.add(opc, key, np.zeros(n, np.int64), np.zeros(n, bool),
          np.zeros(n, np.int64), np.array(status, np.int32),
          np.ones(n, bool), scan_keys=sk, scan_values=sk, taken=tk)
    return b.build()


def test_leaf_scan_and_write_bytes():
    keys = np.arange(1000, dtype=np.int64) * 10
    lg = _log([OP_SCAN, OP_LOOKUP, OP_SCAN, OP_UPDATE, OP_INSERT, OP_UPDATE],
              [400, 0, 0, 5, 7, 9], [0, 0, 0, 1, 1, 0], [10, 44])
    ctx = types.SimpleNamespace(
        keys=keys, log=lg, traced=np.ones(lg.opc.size, bool),
        cell=types.SimpleNamespace(config={"fill": 0.7}))
    # scan at key 400 = position 40: 2 rows; scan at position 0: 1 row
    assert SCAN.needed_bytes(ctx) == 3 * 1024
    # one applied update and one applied insert: a row read and written each
    assert WRITE.needed_bytes(ctx) == 2 * 2 * 1024
    ctx.traced = np.zeros(lg.opc.size, bool)
    assert WRITE.needed_bytes(ctx) == 0 and SCAN.needed_bytes(ctx) == 0


def test_peaks_and_share():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    assert roofline.share_pct(819e9, 2.0, p) == pytest.approx(50.0)
    assert roofline.share_pct(0, 2.0, p) is None
    assert roofline.share_pct(10, 0.0, p) is None
