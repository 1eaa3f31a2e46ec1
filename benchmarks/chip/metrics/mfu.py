"""Model FLOP/s utilization of the traced window: model FLOPs per token
from the configuration's shapes, as its family counts them (nothing
recomputed counted, causal attention by the pairs it attends), times
the tokens the window trained, over window x chips x the chip's bf16
peak."""


def read(ctx):
    tokens = ctx["counters"]["tokens"]
    if tokens <= 0:
        return None
    flops = tokens * ctx["family"].train_flops_per_token(
        ctx["model"], int(ctx["traffic"]["seq_len"]))
    return 100.0 * flops / (ctx["window_s"] * ctx["n_chips"]
                            * ctx["peak"]["flops"])
