"""Share of its roofline the attention backward reaches: the least time
the chip could take for the window's backward calls (one per layer per
step) over the device seconds of the ``attn_bwd`` phase.  The steps in
the traced window are the counted steps at their rate over the window's
length (the count runs between two step ends; the trace, a little
longer, from the window's start).

The least time is that of the causal backward: QK^T recomputed, then
dV, dP, dQ and dK, 2 FLOPs a multiply-add on every attended pair (10
matmul FLOPs a pair and head_dim element, 5 b h_q l^2 d for a causal
l x l); q, k, v, the output and its gradient read and dq, dk, dv
written once, at the parameters' width.  Names its bound.  A model
without the sizes of that attention (``peaks.ATTN_KEYS``) gives
nothing."""

from chip import peaks, phasefold


def attn_bwd_cost(batch: int, seq: int, m, itemsize: int):
    """(FLOPs, bytes) of one causal attention backward over one layer."""
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    flops = 10.0 * batch * hq * hd * peaks.attended_pairs(
        seq, m.get("sliding_window"))
    nbytes = float(itemsize * batch * seq * hd * (4 * hq + 4 * hkv))
    return flops, nbytes


def read(ctx):
    ph = phasefold.fold_ctx(ctx)
    if not ph or ph.get("attn_bwd", 0.0) <= 0:
        return None
    m = ctx["model"]
    if not peaks.has_attn(m) or "num_hidden_layers" not in m:
        return None
    batch, seq = int(ctx["traffic"]["batch"]), int(ctx["traffic"]["seq_len"])
    counted = ctx["counters"]
    if counted["seconds"] <= 0:
        return None
    steps = (counted["tokens"] / (batch * seq) * ctx["window_s"]
             / counted["seconds"])
    calls = steps * m["num_hidden_layers"]
    if calls <= 0:
        return None
    flops, nbytes = attn_bwd_cost(batch, seq, m, 2)
    t_min, bound = peaks.roofline_time(calls * flops, calls * nbytes,
                                       ctx["peak"])
    return {"value": 100.0 * t_min / ph["attn_bwd"], "bound": bound,
            "calls": calls}
