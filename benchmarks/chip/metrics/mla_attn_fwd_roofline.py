"""Share of its roofline the latent-attention forward reaches: the least
time the chip could take for the window's causal forward calls (q/k
``qk_nope_head_dim + qk_rope_head_dim`` wide, v ``v_head_dim``; each
layer's call twice a step, the second the remat recompute) over the
device seconds of the ``attn_fwd`` phase.  The steps in the traced
window are the counted steps at their rate over the window's length.
Names its bound.  A model without latent attention gives nothing."""

from chip import peaks, phasefold


def read(ctx):
    fam, m = ctx["family"], ctx["model"]
    ph = phasefold.fold_ctx(ctx)
    counted = ctx["counters"]
    if (not hasattr(fam, "attn_costs") or not ph
            or ph.get("attn_fwd", 0.0) <= 0 or counted["seconds"] <= 0):
        return None
    batch, seq = int(ctx["traffic"]["batch"]), int(ctx["traffic"]["seq_len"])
    steps = (counted["tokens"] / (batch * seq) * ctx["window_s"]
             / counted["seconds"])
    calls = 2 * steps * m["num_hidden_layers"]
    if calls <= 0:
        return None
    (flops, nbytes), _ = fam.attn_costs(batch, seq, m, 2)
    t_min, bound = peaks.roofline_time(calls * flops, calls * nbytes,
                                       ctx["peak"])
    return {"value": 100.0 * t_min / ph["attn_fwd"], "bound": bound,
            "calls": calls}
