"""The per-lane reference of the program's ``chip_smoke.py`` (``Reference``,
and the phase order of its ``check_dispatch``), turned from a checker into
a server of answers, so the vectorised reference can be held against it."""

from __future__ import annotations

import numpy as np

from traffic import KEY_MAX, OP_INSERT, OP_LOOKUP, OP_SCAN, OP_UPDATE, VALUE_MUL

STATUS_MISS, STATUS_OK, STATUS_SPLIT = 0, 1, 2


class LaneReference:
    """The sorted bulk-loaded keys plus every write applied so far
    (``over``), and the inserted keys the load did not hold (``fresh``)."""

    def __init__(self, keys: np.ndarray):
        self.keys = keys
        self.over: dict = {}
        self.fresh = np.empty((0,), np.int64)

    def _loaded(self, k: int) -> bool:
        i = int(np.searchsorted(self.keys, k))
        return i < self.keys.size and int(self.keys[i]) == k

    def exists(self, k: int) -> bool:
        return k in self.over or self._loaded(k)

    def value(self, k: int) -> int:
        if k in self.over:
            return self.over[k]
        with np.errstate(over="ignore"):
            return int(np.int64(k) * np.int64(VALUE_MUL))

    def lookup(self, k: int):
        return (True, self.value(k)) if self.exists(k) else (False, 0)

    def scan(self, q: int, count: int):
        i = int(np.searchsorted(self.keys, q))
        j = int(np.searchsorted(self.fresh, q))
        ks = np.sort(np.concatenate(
            [self.keys[i:i + count], self.fresh[j:j + count]]
        ))[:count]
        return ks, np.array([self.value(int(k)) for k in ks], np.int64)

    def write(self, k: int, v: int) -> None:
        if not self.exists(k):
            self.fresh = np.insert(
                self.fresh, np.searchsorted(self.fresh, k), k
            )
        self.over[k] = v


def serve(ref: LaneReference, opc, kk, vv, max_count, split_lanes=()):
    """Answer one dispatch lane by lane in the engine's phase order: reads,
    then updates, then inserts; the insert lanes in ``split_lanes`` come
    back ``SPLIT`` (if their key is new) and are applied after the rest, as
    an SMO round would.  Returns the answer planes and the settle plane."""
    b = kk.size
    found = np.zeros(b, bool)
    value = np.zeros(b, np.int64)
    status = np.full(b, STATUS_MISS, np.int32)
    settle = np.full(b, -9, np.int32)
    sk = np.full((b, max_count), KEY_MAX, np.int64)
    sv = np.zeros((b, max_count), np.int64)
    taken = np.zeros(b, np.int32)
    live = [i for i in range(b) if kk[i] != KEY_MAX]
    for i in live:
        k = int(kk[i])
        if opc[i] == OP_LOOKUP:
            found[i], value[i] = ref.lookup(k)
        elif opc[i] == OP_SCAN:
            ks, vs = ref.scan(k, min(int(vv[i]), max_count))
            taken[i] = ks.size
            sk[i, :ks.size] = ks
            sv[i, :ks.size] = vs
    for i in live:
        if opc[i] == OP_UPDATE:
            k = int(kk[i])
            if ref.exists(k):
                status[i] = STATUS_OK
                ref.write(k, int(vv[i]))
    later = []
    for i in live:
        if opc[i] == OP_INSERT:
            k = int(kk[i])
            if i in split_lanes and not ref.exists(k):
                status[i] = STATUS_SPLIT
                settle[i] = STATUS_OK
                later.append(i)
            else:
                status[i] = STATUS_OK
                ref.write(k, int(vv[i]))
    for i in later:
        ref.write(int(kk[i]), int(vv[i]))
    return found, value, status, settle, sk, sv, taken
