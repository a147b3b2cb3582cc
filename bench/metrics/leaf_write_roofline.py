"""``leaf_write`` kernel: share of its roofline, the bytes its work needs at
the chip's peak HBM bandwidth over the kernel's device time in the trace of
the traced batches, in %.

The bytes are what the algorithm needs: one leaf row (64 keys and 64
values of 8 bytes) read and one written per write lane the engine applied
(an update or an insert answered ``OK``)."""

import roofline
from traffic import OP_INSERT, OP_UPDATE


def needed_bytes(ctx):
    lg = ctx.log
    applied = ctx.traced & (lg.status == roofline.STATUS_OK) & (
        (lg.opc == OP_UPDATE) | (lg.opc == OP_INSERT))
    return int(applied.sum()) * 2 * roofline.ROW_BYTES


def read(ctx):
    return roofline.share_pct(needed_bytes(ctx),
                              ctx.trace.kernel_s.get("leaf_write", 0.0),
                              ctx.peaks)
