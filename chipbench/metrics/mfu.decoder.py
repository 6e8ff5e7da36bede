"""Model FLOPs of the window's decoder steps (6 per matmul parameter per
token plus causal attention, recomputation not counted), over window
seconds x chips x the chip's bf16 peak."""


def read(ctx):
    flops = ctx["counters"].get("model_flops")
    if not flops:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / (ctx["elapsed"] * ctx["chips"] * peak)
