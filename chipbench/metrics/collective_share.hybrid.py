"""Device self time of the collective operations over the traced window,
averaged over the cell's devices: the tensor-parallel all-reduces around
the hybrid's mixers and MLPs and the tiers' pruning thresholds reduced
across chips. Self time, not the trace's collective-only time: the
step's scans run as while loops whose events span their bodies, so no
collective inside a loop ever runs alone on the ops line, and that time
reads about 0 for this program (0.0013% on a four-chip TPU v5e host)."""
from chipbench.trace import op_seconds

COLLECTIVES = r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"


def read(ctx):
    t = ctx["trace"]
    if not t or t["devices"] < 2 or t["window_s"] <= 0:
        return None
    return 100.0 * op_seconds(t, COLLECTIVES) / t["window_s"]
