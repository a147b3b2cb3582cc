"""The plain reference that decides ``correct``: the index as sorted loaded
keys (value ``k * 7``) plus every write acknowledged so far, replayed in the
engine's phase order within a dispatch (reads see the index as it was before
the dispatch, then updates apply, then inserts, then the inserts the SMO
engine settled after it), and in lane order within a phase.

It is the per-lane reference of the program's ``chip_smoke.py`` made
vectorised over every answered lane of a run, and imports nothing of the
program.  A run's answers are a :class:`Log`; :func:`answers` computes what
the reference says each lane should have answered, and :func:`compare`
counts the lanes whose answers differ.

Insert lanes are the one place where the program decides: whether an insert
applied at once (``OK``) or was handed to the SMO engine (``SPLIT``) depends
on how full its leaf is, which the reference does not model.  Both are
accepted; a ``SPLIT`` is wrong for a key that already exists, and a settled
lane must come back ``OK`` (or still ``SPLIT``: a lane the run could not
serve, counted as failed and not written).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from traffic import (KEY_MAX, OP_INSERT, OP_LOOKUP, OP_NAMES, OP_SCAN,
                     OP_UPDATE, loaded_values)

STATUS_MISS, STATUS_OK, STATUS_SPLIT = 0, 1, 2
NO_SETTLE = -9
_PHASE = {OP_LOOKUP: 0, OP_SCAN: 0, OP_UPDATE: 1, OP_INSERT: 2}
_SETTLE_PHASE = 3


@dataclasses.dataclass
class Log:
    """Every answered lane of a run, in dispatch order, with the program's
    answers.  ``scan_*`` rows belong to the lanes where ``opc == OP_SCAN``,
    in the same order."""

    lanes: int
    max_count: int
    dispatch: np.ndarray
    lane: np.ndarray
    opc: np.ndarray
    key: np.ndarray
    val: np.ndarray
    found: np.ndarray
    value: np.ndarray
    status: np.ndarray
    settle: np.ndarray
    scan_keys: np.ndarray
    scan_values: np.ndarray
    taken: np.ndarray

    def times(self, phase):
        """A total order over the events of a run: dispatch, phase, lane."""
        return (self.dispatch * 4 + phase) * self.lanes + self.lane


class LogBuilder:
    """Collects the answered lanes of each dispatch (host numpy)."""

    FIELDS = ("dispatch", "lane", "opc", "key", "val", "found", "value",
              "status", "settle")

    def __init__(self, lanes: int, max_count: int):
        self.lanes = lanes
        self.max_count = max_count
        self.parts = {f: [] for f in self.FIELDS}
        self.scans = {"scan_keys": [], "scan_values": [], "taken": []}
        self.n_dispatches = 0

    def add(self, opc, kk, vv, found, value, status, answered, settle=None,
            scan_keys=None, scan_values=None, taken=None):
        """Record dispatch number ``n_dispatches``: its answered lanes and,
        for scan lanes, their rows.  Returns the dispatch number."""
        d = self.n_dispatches
        self.n_dispatches += 1
        idx = np.nonzero(answered)[0]
        if settle is None:
            settle = np.full(opc.shape, NO_SETTLE, np.int32)
        cols = {
            "dispatch": np.full(idx.size, d, np.int64),
            "lane": idx.astype(np.int64),
            "opc": opc[idx], "key": kk[idx], "val": vv[idx],
            "found": found[idx], "value": value[idx], "status": status[idx],
            "settle": settle[idx],
        }
        for f in self.FIELDS:
            self.parts[f].append(cols[f])
        sidx = idx[opc[idx] == OP_SCAN]
        if sidx.size:
            self.scans["scan_keys"].append(scan_keys[sidx])
            self.scans["scan_values"].append(scan_values[sidx])
            self.scans["taken"].append(taken[sidx])
        return d

    def set_settle(self, d, settle):
        """Attach the SMO statuses to dispatch ``d``'s lanes."""
        part = self.parts["settle"][d]
        part[:] = settle[self.parts["lane"][d]]

    def build(self) -> Log:
        dtypes = {"dispatch": np.int64, "lane": np.int64, "opc": np.int32,
                  "key": np.int64, "val": np.int64, "found": bool,
                  "value": np.int64, "status": np.int32, "settle": np.int32}
        cols = {f: (np.concatenate(self.parts[f]).astype(dtypes[f])
                    if self.parts[f] else np.zeros(0, dtypes[f]))
                for f in self.FIELDS}
        mc = self.max_count
        if self.scans["taken"]:
            scans = {k: np.concatenate(v) for k, v in self.scans.items()}
        else:
            scans = {"scan_keys": np.zeros((0, mc), np.int64),
                     "scan_values": np.zeros((0, mc), np.int64),
                     "taken": np.zeros(0, np.int32)}
        return Log(lanes=self.lanes, max_count=mc, **cols, **scans)


@dataclasses.dataclass
class Answers:
    found: np.ndarray
    value: np.ndarray
    status: np.ndarray
    scan_keys: np.ndarray
    scan_values: np.ndarray
    taken: np.ndarray


class _History:
    """Per key, events sorted by time; ``last_before`` finds each query's
    latest event strictly before its time."""

    def __init__(self, keys, times, vals):
        order = np.lexsort((times, keys))
        self.keys = keys[order]
        self.times = times[order]
        self.vals = vals[order]
        self.uk, first = np.unique(self.keys, return_index=True)
        self.first_time = self.times[first] if first.size else self.times[:0]
        self.span = int(self.times.max()) + 2 if self.times.size else 1
        if self.uk.size * self.span >= 1 << 62:
            raise OverflowError("run too long for the reference's time keys")
        gid = np.searchsorted(self.uk, self.keys)
        self.comp = gid * self.span + self.times

    def _group(self, qk):
        g = np.searchsorted(self.uk, qk)
        gc = np.minimum(g, max(self.uk.size - 1, 0))
        hit = (g < self.uk.size) & (self.uk[gc] == qk) if self.uk.size \
            else np.zeros(qk.shape, bool)
        return gc, hit

    def any_before(self, qk, qt):
        g, hit = self._group(qk)
        if not self.uk.size:
            return hit
        return hit & (self.first_time[g] < qt)

    def last_before(self, qk, qt):
        """``(has, value)`` of each query's latest event before ``qt``."""
        g, hit = self._group(qk)
        if not self.uk.size:
            return hit, np.zeros(qk.shape, np.int64)
        qt = np.minimum(qt, self.span - 1)   # later than every event
        pos = np.searchsorted(self.comp, g * self.span + qt, side="left") - 1
        pc = np.maximum(pos, 0)
        has = hit & (pos >= 0) & (self.comp[pc] // self.span == g)
        return has, np.where(has, self.vals[pc], 0)


class Reference:
    """The loaded keys, their values (``k * 7`` unless given) and the writes
    of one run's log."""

    def __init__(self, keys: np.ndarray, log: Log,
                 values: "np.ndarray | None" = None):
        self.keys = keys
        self.values = loaded_values(keys) if values is None else values
        self.log = log
        lg = log
        ins = lg.opc == OP_INSERT
        ins_now = ins & (lg.status == STATUS_OK)
        ins_later = ins & (lg.status == STATUS_SPLIT) & (lg.settle == STATUS_OK)
        ins_ev = ins_now | ins_later
        t_ins = np.where(ins_now, lg.times(_PHASE[OP_INSERT]),
                         lg.times(_SETTLE_PHASE))
        self.inserts = _History(lg.key[ins_ev], t_ins[ins_ev], lg.val[ins_ev])
        upd = lg.opc == OP_UPDATE
        t_upd = lg.times(_PHASE[OP_UPDATE])
        self.update_hit = upd & self.exists(lg.key, t_upd)
        wr = self.update_hit
        self.writes = _History(
            np.concatenate([lg.key[wr], lg.key[ins_ev]]),
            np.concatenate([t_upd[wr], t_ins[ins_ev]]),
            np.concatenate([lg.val[wr], lg.val[ins_ev]]),
        )
        fresh = ~self.loaded(self.inserts.uk)
        self.fresh_keys = self.inserts.uk[fresh]
        self.fresh_time = self.inserts.first_time[fresh]

    def _find(self, k):
        """Each key's position among the loaded keys, and whether it is
        there."""
        n = self.keys.size
        i = np.minimum(np.searchsorted(self.keys, k), n - 1)
        return i, self.keys[i] == k

    def loaded(self, k):
        return self._find(k)[1]

    def exists(self, k, t):
        return self.loaded(k) | self.inserts.any_before(k, t)

    def value_at(self, k, t):
        """``(found, value)`` of key ``k`` as of time ``t``."""
        has, v = self.writes.last_before(k, t)
        i, ld = self._find(k)
        found = has | ld | self.inserts.any_before(k, t)
        return found, np.where(has, v, np.where(ld, self.values[i], 0))

    def scan(self, q, count, t):
        """Dense ``[len(q), max_count]`` keys and values of each scan's
        first ``count`` records at or above ``q`` as of ``t``, padded with
        ``KEY_MAX`` and 0, and the number taken."""
        keys, n, mc = self.keys, self.keys.size, self.log.max_count
        s = q.size
        i = np.searchsorted(keys, q, side="left")
        m = np.minimum(count, n - i)
        last = i + count - 1
        ub = np.where(last < n, keys[np.clip(last, 0, n - 1)], KEY_MAX)
        fk, ft = self.fresh_keys, self.fresh_time
        j0 = np.searchsorted(fk, q, side="left")
        j1 = np.searchsorted(fk, ub, side="right")
        nf = j1 - j0

        def ragged(start, length):
            lane = np.repeat(np.arange(s), length)
            off = np.arange(lane.size) - np.repeat(np.cumsum(length) - length,
                                                   length)
            return lane, np.repeat(start, length) + off

        l_lane, l_pos = ragged(i, m)
        f_lane, f_pos = ragged(j0, nf)
        f_ok = ft[f_pos] < t[f_lane]
        lane = np.concatenate([l_lane, f_lane[f_ok]])
        kk = np.concatenate([keys[l_pos], fk[f_pos[f_ok]]])
        order = np.lexsort((kk, lane))
        lane, kk = lane[order], kk[order]
        per = np.bincount(lane, minlength=s)
        rank = np.arange(lane.size) - np.repeat(np.cumsum(per) - per, per)
        keep = rank < count[lane]
        lane, kk, rank = lane[keep], kk[keep], rank[keep]
        out_k = np.full((s, mc), KEY_MAX, np.int64)
        out_v = np.zeros((s, mc), np.int64)
        out_k[lane, rank] = kk
        out_v[lane, rank] = self.value_at(kk, t[lane])[1]
        taken = np.bincount(lane, minlength=s).astype(np.int32)
        return out_k, out_v, taken


SCAN_CHUNK = 16384


def answers(keys: np.ndarray, log: Log,
            values: "np.ndarray | None" = None) -> Answers:
    """What the reference answers to every lane of ``log``.  Insert
    statuses are the log's own (see the module docstring)."""
    return _answers(Reference(keys, log, values))


def _answers(ref: Reference) -> Answers:
    lg = ref.log
    t_read = lg.times(0) - lg.lane
    found, value = ref.value_at(lg.key, t_read)
    status = np.where(ref.update_hit, STATUS_OK, STATUS_MISS).astype(np.int32)
    status = np.where(lg.opc == OP_INSERT, lg.status, status)
    scan = np.nonzero(lg.opc == OP_SCAN)[0]
    count = np.clip(lg.val[scan], 0, lg.max_count)
    parts = []
    for a in range(0, scan.size, SCAN_CHUNK):
        sl = scan[a:a + SCAN_CHUNK]
        parts.append(ref.scan(lg.key[sl], count[a:a + SCAN_CHUNK],
                              t_read[sl]))
    if parts:
        sk, sv, tk = (np.concatenate(p) for p in zip(*parts))
    else:
        sk, sv, tk = lg.scan_keys[:0], lg.scan_values[:0], lg.taken[:0]
    return Answers(found=found, value=value, status=status, scan_keys=sk,
                   scan_values=sv, taken=tk)


def compare(keys: np.ndarray, log: Log, got: "Answers | None" = None) -> dict:
    """Count the lanes whose answers differ from the reference's.

    ``got`` defaults to the program's answers in ``log``; the control passes
    answers computed otherwise.  Returns ``{"mismatched": n, "by_op":
    {...}, "failed": lanes the run could not serve, "first": [...]}``."""
    lg = log
    if got is None:
        got = Answers(found=lg.found, value=lg.value, status=lg.status,
                      scan_keys=lg.scan_keys, scan_values=lg.scan_values,
                      taken=lg.taken)
    ref = Reference(keys, lg)
    exp = _answers(ref)
    bad = np.zeros(lg.opc.shape, bool)
    look = lg.opc == OP_LOOKUP
    bad |= look & ((got.found != exp.found)
                   | (exp.found & (got.value != exp.value)))
    upd = lg.opc == OP_UPDATE
    bad |= upd & (got.status != exp.status)
    ins = lg.opc == OP_INSERT
    t_ins = lg.times(_PHASE[OP_INSERT])
    split = ins & (got.status == STATUS_SPLIT)
    bad |= ins & (got.status != STATUS_OK) & ~split
    bad |= split & ref.exists(lg.key, t_ins)
    bad |= split & (lg.settle != STATUS_OK) & (lg.settle != STATUS_SPLIT)
    failed = split & (lg.settle == STATUS_SPLIT)
    scan = np.nonzero(lg.opc == OP_SCAN)[0]
    if scan.size:
        cols = np.arange(lg.max_count)[None, :]
        within = cols < exp.taken[:, None]
        row_bad = (
            (got.taken != exp.taken)
            | (got.scan_keys != exp.scan_keys).any(axis=1)
            | ((got.scan_values != exp.scan_values) & within).any(axis=1)
        )
        bad[scan] |= row_bad
    by_op = {OP_NAMES[c]: int((bad & (lg.opc == c)).sum()) for c in OP_NAMES}
    first = [(OP_NAMES[int(lg.opc[i])], int(lg.key[i]), int(lg.dispatch[i]))
             for i in np.nonzero(bad)[0][:5]]
    return {"mismatched": int(bad.sum()), "by_op": by_op,
            "failed": int(failed.sum()), "first": first}


def truncate32(x: np.ndarray) -> np.ndarray:
    """Keys cut to their low 32 bits, as a 32-bit key plane would hold them."""
    return x.astype(np.int32).astype(np.int64)


def control_answers(keys: np.ndarray, log: Log) -> Answers:
    """The reference put in the program's place with keys cut to 32 bits:
    the lower-precision step a later change could be tempted by.

    The index is loaded with every key's own value under its cut key (where
    cut keys collide, the key loaded last keeps the slot, as a bulk load
    that overwrites would leave it); requests and writes go to cut keys and
    scans return cut keys.  So it is wrong only where a 32-bit index is:
    on colliding keys, on fresh keys that land on a loaded one, and in
    every scan."""
    cut = truncate32(keys)
    order = np.argsort(cut, kind="stable")
    cut_sorted = cut[order]
    last = np.ones(cut.size, bool)
    last[:-1] = cut_sorted[1:] != cut_sorted[:-1]
    lg32 = dataclasses.replace(log, key=truncate32(log.key))
    return answers(cut_sorted[last], lg32,
                   values=loaded_values(keys[order[last]]))
