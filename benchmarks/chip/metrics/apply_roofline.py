"""Share of its roofline the server's momentum apply kernel
(``kernels/fused_update.py``) reaches: p, m and g read and p and m
written per element of the shard it folds, over the summed device time
of its calls in the window.  One call folds one shard; the shards of
the plan are balanced, so a call moves the buffer's mean shard size:
the parameters, as the model's family counts them, over the shards."""

from chip import peaks, tracefold

#: The kernel's calls as the trace names them: the custom call inside the
#: jitted ``_fused_update``.
PATTERN = r'^%_fused_update[\w.]* = .*custom_call_target="tpu_custom_call"'


def read(ctx):
    if ctx["engine"] != "ps-threads":
        return None
    n, secs = tracefold.matching(ctx["ops"], PATTERN)
    if n == 0 or secs <= 0:
        return None
    shards = int(ctx["config"]["server"]["shards"])
    elems = ctx["family"].param_count(ctx["model"]) / shards
    flops, nbytes = peaks.fused_update_cost(elems, 2)
    t_min, bound = peaks.roofline_time(n * flops, n * nbytes, ctx["peak"])
    return {"value": 100.0 * t_min / secs, "bound": bound, "calls": n}
