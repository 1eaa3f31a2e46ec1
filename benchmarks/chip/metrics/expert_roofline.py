"""Share of its roofline the held experts' grouped matmuls reach: the
least time the chip could take for them at the rows the router sent to
the held experts in the traced interval (``perfcount.MOE.held_rows``,
over ``TRACE.window_counters``; layer-steps from ``tokens_routed``),
as the family counts their FLOPs and bytes (forward, its remat
recompute, and the backward's two transposes), over the device seconds
of the ``moe_experts`` phase.  Names its bound.  A program without those
counters or that phase, or a family without experts, gives nothing."""

from chip import peaks, phasefold


def read(ctx):
    from repro.obs.trace import TRACE
    fam, m = ctx["family"], ctx["model"]
    window = getattr(TRACE, "window_counters", None)
    moe = window().get("moe", {}) if window else {}
    ph = phasefold.fold_ctx(ctx)
    if (not hasattr(fam, "expert_costs") or moe.get("held_rows", 0) <= 0
            or not ph or ph.get("moe_experts", 0.0) <= 0):
        return None
    tokens = int(ctx["traffic"]["batch"]) * int(ctx["traffic"]["seq_len"])
    layer_steps = moe["tokens_routed"] / tokens
    flops, nbytes = fam.expert_costs(m, moe["held_rows"], layer_steps, 2)
    t_min, bound = peaks.roofline_time(flops, nbytes, ctx["peak"])
    return {"value": 100.0 * t_min / ph["moe_experts"], "bound": bound,
            "held_rows": moe["held_rows"],
            "held_rows_max": moe["held_rows_max"],
            "layer_steps": layer_steps}
