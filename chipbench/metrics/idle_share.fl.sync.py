"""Share of the traced window in which the device ran no operation while
the engine handed the chunk's state back, waited on its one
``device_get`` and built the round records (``fl.engine.sync`` the
innermost span open), averaged over the cell's devices. None when the
trace has no idle time under that span, so that a renamed or removed
span reads as missing, not as 0."""

SPAN = "fl.engine.sync"


def read(ctx):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0:
        return None
    gaps = dict(t["idle_gaps"])
    if SPAN not in gaps:
        return None
    return 100.0 * gaps[SPAN] / t["window_s"]
