"""Client loop: host time per batch, i.e. the traced batches' wall time not
spent blocked on the device's answers nor in SMO settling, per client batch
(the benchmark's own host clock spans)."""


def read(ctx):
    h = ctx.host
    if not h["batches"]:
        return None
    return 1e3 * (h["loop_s"] - h["wait_s"] - h["smo_s"]) / h["batches"]
