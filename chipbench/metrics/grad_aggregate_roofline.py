"""The grad_aggregate kernel's share of its roofline: the time the HBM
bytes its calls must move would take at peak bandwidth (bytes from the
operand shapes, chipbench/counts.py), over the device time of its trace
events. Memory-bound: its FLOPs are a few per byte."""
from chipbench.trace import op_seconds

KERNEL = r"^grad_aggregate_raw "


def read(ctx):
    t, c = ctx["trace"], ctx["counters"]
    per_round = c.get("agg_kernel_bytes_per_round")
    if not t or not per_round:
        return None
    kernel_s = op_seconds(t, KERNEL)
    if kernel_s <= 0:
        return None
    least_s = per_round * c["rounds"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
