"""Share of its roofline the latent-attention backward reaches: the
least time the chip could take for the window's causal backward calls
(one a layer a step: QK^T recomputed, dV and dP over the v width, dQ
and dK over the q/k width; q, k, v, o, dO read and dq, dk, dv written)
over the device seconds of the ``attn_bwd`` phase.  The steps in the
traced window are the counted steps at their rate over the window's
length.  Names its bound.  A model without latent attention gives
nothing."""

from chip import peaks, phasefold


def read(ctx):
    fam, m = ctx["family"], ctx["model"]
    ph = phasefold.fold_ctx(ctx)
    counted = ctx["counters"]
    if (not hasattr(fam, "attn_costs") or not ph
            or ph.get("attn_bwd", 0.0) <= 0 or counted["seconds"] <= 0):
        return None
    batch, seq = int(ctx["traffic"]["batch"]), int(ctx["traffic"]["seq_len"])
    steps = (counted["tokens"] / (batch * seq) * ctx["window_s"]
             / counted["seconds"])
    calls = steps * m["num_hidden_layers"]
    if calls <= 0:
        return None
    _, (flops, nbytes) = fam.attn_costs(batch, seq, m, 2)
    t_min, bound = peaks.roofline_time(calls * flops, calls * nbytes,
                                       ctx["peak"])
    return {"value": 100.0 * t_min / ph["attn_bwd"], "bound": bound,
            "calls": calls}
