"""Share of the window's busy device time spent routing tokens to the
held experts, the ``moe_route`` phase (router matmul and scores, top-k,
the sort by expert and the row gathers both ways), over the seconds
some op ran, summed over chips."""

from chip import phasefold


def read(ctx):
    ph = phasefold.fold_ctx(ctx)
    busy = ctx["busy_s"] * ctx["n_chips"]
    if not ph or ph.get("moe_route", 0.0) <= 0 or busy <= 0:
        return None
    return 100.0 * ph["moe_route"] / busy
