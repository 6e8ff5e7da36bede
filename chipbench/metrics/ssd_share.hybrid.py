"""Device self time of the operations that lie wholly under the
``granite_hybrid.ssd`` scope (the Mamba2 chunked SSD scan), over device
busy time, averaged over the cell's devices. The driver reads the op
names from the compiled step's HLO (``chipbench/hlo_scopes.py``); an
operation whose rendered name is shared with one outside the scope is
left out. None when the program has no such scope or the trace holds
none of its operations."""


def read(ctx):
    t, names = ctx["trace"], ctx["counters"].get("ssd_op_names")
    if not t or not names or t["busy_s"] <= 0:
        return None
    found = [t["op_s"][n] for n in names if n in t["op_s"]]
    if not found:
        return None
    return 100.0 * sum(found) / t["busy_s"]
