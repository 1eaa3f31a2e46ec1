"""Share of its roofline the flash-attention forward kernel
(``kernels/flash_attention.py``) reaches: the least time the chip could
take for the calls in the window (FLOPs and bytes from shapes) over the
summed device time of those calls.  Names its bound.  A model without
the sizes of that attention (``peaks.ATTN_KEYS``) gives nothing."""

from chip import peaks, tracefold


def pattern(m, seq: int) -> str:
    """The kernel's calls as the trace names them: a ``tpu_custom_call``
    whose output is (batch, query heads, seq, head_dim).  Under
    ``remat='full'`` the forward runs twice a step, both counted."""
    return (rf"= \w+\[\d+,{m['num_attention_heads']},{seq},"
            rf"{m['head_dim']}\]\{{[^}}]*\}} custom-call\(.*"
            r'custom_call_target="tpu_custom_call"')


def read(ctx):
    if not peaks.has_attn(ctx["model"]):
        return None
    n, secs = tracefold.matching(
        ctx["ops"], pattern(ctx["model"], int(ctx["traffic"]["seq_len"])))
    if n == 0 or secs <= 0:
        return None
    flops, nbytes = peaks.attn_fwd_cost(
        int(ctx["traffic"]["batch"]), int(ctx["traffic"]["seq_len"]),
        ctx["model"], 2)
    t_min, bound = peaks.roofline_time(n * flops, n * nbytes, ctx["peak"])
    return {"value": 100.0 * t_min / secs, "bound": bound, "calls": n}
