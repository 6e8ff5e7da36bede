"""Share of the traced window in which a collective ran on a device with
no other operation beside it, averaged over devices: the edge-to-hub
combine that the sharded fleet's round cannot hide."""


def read(ctx):
    t = ctx["trace"]
    if not t or t["devices"] < 2 or t["window_s"] <= 0:
        return None
    return 100.0 * t["collective_only_s"] / t["window_s"]
