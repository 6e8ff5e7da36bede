"""Faithful reproduction of the paper's §6 experiments (Figs. 2-4):
5-layer/10-neuron sigmoid MLP, Gaussian binary data, batch GD, 1000
val/test samples, train sizes 500-2000, float64 vs float32.

  PYTHONPATH=src python examples/paper_mlp_repro.py
"""
import time

import jax
import jax.numpy as jnp

from repro.configs.paper_mlp import config
from repro.data import paper_splits
from repro.models import mlp
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

EPOCHS = 80


def train(n_train, seed=0, dtype=jnp.float32, lr=1.0):
    cfg = config()
    train_d, val, test = paper_splits(jax.random.PRNGKey(seed), n_train)
    train_d = jax.tree.map(
        lambda x: x.astype(dtype) if x.dtype.kind == "f" else x, train_d)
    params = jax.tree.map(lambda x: x.astype(dtype),
                          mlp.init(jax.random.PRNGKey(seed + 1), cfg))

    @jax.jit
    def step(p):
        g = jax.grad(mlp.loss_fn)(p, train_d)
        return jax.tree.map(lambda p, g: p - lr * g, p, g)

    params = step(params)
    accs, t0 = [], time.perf_counter()
    for _ in range(EPOCHS):
        params = step(params)
        accs.append(float(mlp.accuracy(params, val["x"], val["y"])))
    t_epoch = (time.perf_counter() - t0) / EPOCHS
    test_acc = float(mlp.accuracy(params, test["x"], test["y"]))
    return accs, t_epoch, test_acc


def epochs_to(accs, tgt=0.95):
    return next((i + 1 for i, a in enumerate(accs) if a >= tgt), None)


print("== Fig 2/3: train-set size sweep (float32) ==")
for n in (500, 1000, 1500, 2000):
    accs, t_ep, test_acc = train(n)
    print(f"n={n:5d}  max_val_acc={max(accs):.3f}  "
          f"epochs_to_0.95={epochs_to(accs)}  t/epoch={t_ep * 1e3:.2f}ms  "
          f"test_acc={test_acc:.3f}")

print("== Fig 4: data-type comparison (n=1000) ==")
# float64 needs the x64 flag; run this example with JAX_ENABLE_X64=1 to see
# the full comparison — float32-only numbers are printed regardless.
for dtype in ((jnp.float64, jnp.float32) if jax.config.read("jax_enable_x64")
              else (jnp.float32,)):
    accs, t_ep, test_acc = train(1000, dtype=dtype)
    print(f"{jnp.dtype(dtype).name}:  max_val_acc={max(accs):.3f}  "
          f"epochs_to_0.95={epochs_to(accs)}  t/epoch={t_ep * 1e3:.2f}ms")
print("(paper: both dtypes reach the same max accuracy; time/memory differ)")
