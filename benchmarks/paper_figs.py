"""Paper §6 reproductions (Figures 2, 3, 4).

Fig 2: validation accuracy vs epochs for train sizes 500..2000.
Fig 3: per-epoch time and memory vs train size.
Fig 4: float64 vs float32 accuracy/time/memory (x64 is enabled only
       around it, so it never leaks into other benches).

Claims validated (DESIGN.md §1 C1-C5); results land in EXPERIMENTS.md.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.configs.paper_mlp import config
from repro.data import make_gaussian_dataset, paper_splits
from repro.models import mlp

SIZES = (500, 1000, 1500, 2000)
EPOCHS = 80
RUNS = 3          # paper averages 20 runs; 3 keeps the bench < 1 min
TARGET = 0.95


def _train_curve(seed: int, n_train: int, epochs: int = EPOCHS, lr: float = 1.0,
                 dtype=jnp.float32):
    cfg = config()
    key = jax.random.PRNGKey(seed)
    train, val, _ = paper_splits(key, n_train)
    train = jax.tree.map(lambda x: x.astype(dtype) if x.dtype.kind == "f" else x,
                         train)
    params = jax.tree.map(lambda x: x.astype(dtype),
                          mlp.init(jax.random.PRNGKey(seed + 100), cfg))

    @jax.jit
    def step(params):
        g = jax.grad(mlp.loss_fn)(params, train)
        return jax.tree.map(lambda p, g: p - lr * g, params, g)

    params = step(params)          # compile outside the timed region
    accs, times = [], []
    for _ in range(epochs):
        t0 = time.perf_counter()
        params = step(params)
        jax.block_until_ready(params)
        times.append(time.perf_counter() - t0)
        accs.append(float(mlp.accuracy(params, val["x"], val["y"])))
    # live training memory: params + grads + batch (analytic, bytes)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    itemsize = jnp.dtype(dtype).itemsize
    mem = 2 * n_params * itemsize + (n_train + 1000) * (5 + 1) * itemsize
    return accs, sum(times) / len(times), mem


def _epochs_to(accs, target=TARGET):
    for i, a in enumerate(accs):
        if a >= target:
            return i + 1
    return len(accs) + 1


def fig2() -> list[tuple]:
    """acc-vs-epochs per train size -> (name, us_per_call, derived)."""
    rows = []
    for n in SIZES:
        ep, mx, tms = [], [], []
        for r in range(RUNS):
            accs, t_ep, _ = _train_curve(r, n)
            ep.append(_epochs_to(accs))
            mx.append(max(accs))
            tms.append(t_ep)
        rows.append((f"fig2/acc_n{n}", sum(tms) / RUNS * 1e6,
                     f"epochs_to_{TARGET}={sum(ep)/RUNS:.1f};max_acc={sum(mx)/RUNS:.3f}"))
    return rows


def fig3() -> list[tuple]:
    """time+memory per epoch vs train size."""
    rows = []
    for n in SIZES:
        _, t_ep, mem = _train_curve(0, n, epochs=20)
        rows.append((f"fig3/epoch_n{n}", t_ep * 1e6, f"mem_bytes={mem}"))
    return rows


def fig4_body() -> list[tuple]:
    """f64 vs f32 (requires jax_enable_x64; see fig4)."""
    rows = []
    for dtype, name in ((jnp.float32, "f32"), (jnp.float64, "f64")):
        ep, mx, tms, mem = [], [], [], 0
        for r in range(RUNS):
            accs, t_ep, mem = _train_curve(r, 1000, dtype=dtype)
            ep.append(_epochs_to(accs))
            mx.append(max(accs))
            tms.append(t_ep)
        rows.append((f"fig4/{name}", sum(tms) / RUNS * 1e6,
                     f"epochs_to_{TARGET}={sum(ep)/RUNS:.1f};"
                     f"max_acc={sum(mx)/RUNS:.3f};mem_bytes={mem}"))
    return rows


def fig4() -> list[tuple]:
    """Run fig4_body in this process with x64 enabled for its duration."""
    with jax.enable_x64(True):
        return fig4_body()


if __name__ == "__main__":
    for fn in (fig2, fig3, fig4):
        for name, us, derived in fn():
            print(f"{name},{us:.1f},{derived}")
