"""Model FLOPs of the clients that reported in the window's rounds
(forward and backward of the paper MLP on their samples, for each local
step), over window seconds x chips x the chip's bf16 peak."""


def read(ctx):
    flops = ctx["counters"].get("model_flops")
    if not flops:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / (ctx["elapsed"] * ctx["chips"] * peak)
