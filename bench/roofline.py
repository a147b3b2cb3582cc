"""The chip's peaks and a kernel's share of its roofline.

Each ``<kernel>_roofline`` reader in ``bench/metrics/`` counts the bytes
its kernel's work needs (what the algorithm needs, not the kernel's operand
shapes, so a kernel that moves less reads higher on the same yardstick) and
hands them to :func:`share_pct` with the kernel's trace time.
"""

from __future__ import annotations

import json
import os

FANOUT = 64
ROW_BYTES = FANOUT * 8 * 2          # keys + values of one leaf row
STATUS_OK = 1
_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """The peak table's entry for ``device_kind``; a kind missing from the
    table is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def share_pct(needed_bytes: float, seconds: float, peak: dict):
    """Needed bytes at the peak bandwidth over the measured time, in %;
    ``None`` where there is nothing to read."""
    if not needed_bytes or not seconds:
        return None
    return 100.0 * needed_bytes / peak["hbm_bytes_per_s"] / seconds
