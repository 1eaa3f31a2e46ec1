"""Share of the window's busy device time spent in the ``wire`` phase:
the packed wire's glue (the step's unpack and pack, the pull's shard
concat) over the seconds some op ran, summed over chips."""

from chip import phasefold


def read(ctx):
    ph = phasefold.fold_ctx(ctx)
    busy = ctx["busy_s"] * ctx["n_chips"]
    if not ph or ph.get("wire", 0.0) <= 0 or busy <= 0:
        return None
    return 100.0 * ph["wire"] / busy
