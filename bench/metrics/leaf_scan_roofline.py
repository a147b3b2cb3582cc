"""``leaf_scan`` kernel: share of its roofline, the bytes its work needs at
the chip's peak HBM bandwidth over the kernel's device time in the trace of
the traced batches, in %.

The bytes are what the algorithm needs, not the kernel's operand shapes:
every leaf row a scan lane visits, 64 keys and 64 values of 8 bytes (1 KiB
a row).  A lane that starts at loaded key ``i`` and takes ``t`` records
visits the leaves from ``i``'s to ``i + t - 1``'s (one, where it takes
none), with ``int(64 * fill)`` keys a leaf as bulk-loaded."""

import numpy as np

import roofline
from traffic import OP_SCAN


def leaf_rows(start_pos, taken, leaf_keys):
    """Leaf rows each scan lane visits."""
    first = start_pos // leaf_keys
    last = (start_pos + np.maximum(taken, 1) - 1) // leaf_keys
    return last - first + 1


def needed_bytes(ctx):
    lg = ctx.log
    scan = lg.opc == OP_SCAN
    sel = ctx.traced[scan]
    start = np.searchsorted(ctx.keys, lg.key[scan][sel])
    rows = leaf_rows(start, lg.taken[sel].astype(np.int64),
                     int(roofline.FANOUT * ctx.cell.config["fill"]))
    return int(rows.sum()) * roofline.ROW_BYTES


def read(ctx):
    return roofline.share_pct(needed_bytes(ctx),
                              ctx.trace.kernel_s.get("leaf_scan", 0.0),
                              ctx.peaks)
