"""YCSB traffic for the chip benchmark: the loaded key set and the client
batches, both drawn from ``--seed``.

The Zipfian generator, its scramble and the op codes are copied from the
program's ``repro/data/ycsb.py`` so that a later change to the program
cannot move the yardstick.  The key set and the insert keys follow YCSB
itself rather than that module:

* ``make_keys``: ``n`` unique sorted int64 keys in O(n).  Key ``i`` sits in
  slot ``4 i + U{1..4}`` of ``4 n`` slots (one key in four slots, the
  density of ``ycsb.make_dataset``); the slot is the key's high part and a
  uniform 32-bit draw its low part, so keys span 62 bits as YCSB's hashed
  8-byte keys span the key space, and no key fits in 32 bits.
* inserts: YCSB's default ``insertorder=hashed`` puts each new record at a
  hashed, not a hot, position, so an insert key is drawn uniformly from the
  gap after a uniformly chosen loaded key.

A mix file (``bench/mixes/<traffic>.json``) gives the op shares and the
scan lengths; a configuration file gives the request distribution.
"""

from __future__ import annotations

import dataclasses

import numpy as np

OP_LOOKUP, OP_UPDATE, OP_INSERT, OP_SCAN = 0, 1, 2, 3
OP_CODES = {"lookup": OP_LOOKUP, "update": OP_UPDATE, "insert": OP_INSERT,
            "scan": OP_SCAN}
OP_NAMES = {code: name for name, code in OP_CODES.items()}

VALUE_MUL = 7          # the loaded value of key k is k * VALUE_MUL (wrapping)
WRITE_BASE = 1 << 40   # written values: WRITE_BASE + a per-run sequence
KEY_MAX = np.int64(np.iinfo(np.int64).max)
SLOTS_PER_KEY = 4
LOW_BITS = 32


def stream(seed: int, purpose: int) -> np.random.Generator:
    """An independent generator per purpose, from any non-negative seed."""
    return np.random.default_rng([int(seed), int(purpose)])


def make_keys(n: int, seed: int) -> np.ndarray:
    """``n`` sorted unique int64 keys, one per ``SLOTS_PER_KEY`` slots."""
    rng = stream(seed, 0)
    keys = np.arange(n, dtype=np.int64)
    keys *= SLOTS_PER_KEY
    keys += rng.integers(1, SLOTS_PER_KEY + 1, size=n, dtype=np.uint8)
    keys <<= LOW_BITS
    keys |= rng.integers(0, 1 << LOW_BITS, size=n, dtype=np.uint32)
    return keys


def loaded_values(keys: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        return keys * np.int64(VALUE_MUL)


# ---- copied from repro/data/ycsb.py -------------------------------------

@dataclasses.dataclass
class ZipfianGenerator:
    """YCSB's scrambled-Zipfian over ``n`` items (Gray et al. rejection-free
    formulation, vectorized)."""

    n: int
    theta: float = 0.99
    seed: int = 0

    def __post_init__(self):
        n, theta = self.n, self.theta
        self._rng = np.random.default_rng(self.seed)
        self.zetan = self._zeta(n, theta)
        self.zeta2 = self._zeta(2, theta)
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = (1 - (2.0 / n) ** (1 - theta)) / (1 - self.zeta2 / self.zetan)

    @staticmethod
    def _zeta(n: int, theta: float) -> float:
        # exact for small n; Euler-Maclaurin tail for large n
        if n <= 10_000_000:
            i = np.arange(1, n + 1, dtype=np.float64)
            return float(np.sum(i ** (-theta)))
        i = np.arange(1, 10_000_001, dtype=np.float64)
        head = float(np.sum(i ** (-theta)))
        tail = (n ** (1 - theta) - 10_000_000 ** (1 - theta)) / (1 - theta)
        return head + tail

    def draw_ranks(self, size: int) -> np.ndarray:
        """Zipfian *ranks* in [0, n): rank 0 is the hottest item."""
        u = self._rng.random(size)
        uz = u * self.zetan
        ranks = (self.n * (self.eta * u - self.eta + 1) ** self.alpha).astype(np.int64)
        ranks = np.where(uz < 1.0, 0, ranks)
        ranks = np.where((uz >= 1.0) & (uz < 1.0 + 0.5**self.theta), 1, ranks)
        return np.clip(ranks, 0, self.n - 1)


def scramble(ranks: np.ndarray, n: int) -> np.ndarray:
    """FNV-style hash spreading ranks over [0, n) (YCSB ScrambledZipfian)."""
    h = ranks.astype(np.uint64)
    h = (h * np.uint64(0xC6A4A7935BD1E995)) ^ (h >> np.uint64(29))
    h = (h * np.uint64(0xFF51AFD7ED558CCD)) ^ (h >> np.uint64(33))
    return (h % np.uint64(n)).astype(np.int64)


# ---- the client -----------------------------------------------------------

class Client:
    """Closed-loop YCSB client lanes: ``next_batch()`` draws one batch of
    ``lanes`` ops as the engine's ``(opcodes, keys, values)`` planes.  Write
    lanes carry unique values so a read-back can tell every write apart;
    scan lanes carry their record count."""

    def __init__(self, keys: np.ndarray, config: dict, mix: dict, seed: int,
                 lanes: int):
        self.keys = keys
        self.lanes = lanes
        self.rng = stream(seed, 1)
        shares = mix["ops"]
        unknown = set(shares) - set(OP_CODES)
        if unknown:
            raise ValueError(f"unknown ops in mix: {sorted(unknown)}")
        self.codes = np.array([OP_CODES[o] for o in shares], np.int32)
        p = np.array([float(shares[o]) for o in shares])
        self.p = p / p.sum()
        dist = config["request_distribution"]
        if dist == "zipfian":
            self.zipf = ZipfianGenerator(keys.size, config["zipf_theta"],
                                         seed=stream(seed, 2).integers(1 << 62))
        elif dist == "uniform":
            self.zipf = None
        else:
            raise ValueError(f"unknown request_distribution {dist!r}")
        scan = mix.get("scan_length")
        self.scan_lo = int(scan["min"]) if scan else 0
        self.scan_hi = int(scan["max"]) if scan else 0
        self.seq = WRITE_BASE

    def _request_index(self, size: int) -> np.ndarray:
        n = self.keys.size
        if self.zipf is None:
            return self.rng.integers(0, n, size=size)
        return scramble(self.zipf.draw_ranks(size), n)

    def _insert_keys(self, size: int) -> np.ndarray:
        n = self.keys.size
        i = self.rng.integers(0, n, size=size)
        nxt = np.where(i + 1 < n, self.keys[np.minimum(i + 1, n - 1)],
                       self.keys[i] + (np.int64(1) << LOW_BITS))
        room = nxt - self.keys[i] - 1
        off = (self.rng.random(size) * room).astype(np.int64)
        return self.keys[i] + 1 + off

    def next_batch(self):
        b = self.lanes
        opc = self.rng.choice(self.codes, size=b, p=self.p).astype(np.int32)
        kk = self.keys[self._request_index(b)]
        vv = np.zeros(b, np.int64)
        ins = opc == OP_INSERT
        if ins.any():
            kk[ins] = self._insert_keys(int(ins.sum()))
        w = ins | (opc == OP_UPDATE)
        nw = int(w.sum())
        vv[w] = self.seq + np.arange(nw, dtype=np.int64)
        self.seq += nw
        scn = opc == OP_SCAN
        if scn.any():
            vv[scn] = self.rng.integers(self.scan_lo, self.scan_hi + 1,
                                        size=int(scn.sum()))
        return opc, kk, vv
