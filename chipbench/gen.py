"""Inputs and weights, made on the device from the seed.

The Gaussian task is the paper's §6.1 data, copied from the program's
``repro.data.make_gaussian_dataset`` + ``partition_iid`` so that a later
change there cannot move the benchmark's traffic: for a seed below 2**32
the shards are bit-identical to what ``FleetSpec.build_clients`` makes on
its own (``tests/test_gen.py``). Weight scales follow the program's
initializers, so first losses sit where a fresh model's do.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def gaussian_fleet(key, *, n_clients: int, per_client: int, features: int):
    """(n_clients, per_client, features) f32 inputs and (n_clients,
    per_client) int32 labels: class 0 centred at -1, class 1 at +1, std 1,
    one permutation split into equal consecutive shards. Op by op on the
    device, as the program's fleet build runs: one fused program rounds the
    normal draws differently in the last bit."""
    n = n_clients * per_client
    k1, k2 = jax.random.split(key)
    y = jax.random.bernoulli(k1, 0.5, (n,)).astype(jnp.int32)
    mu = jnp.where(y[:, None] == 1, 1.0, -1.0)
    x = (mu + 1.0 * jax.random.normal(k2, (n, features))).astype(jnp.float32)
    perm = jax.random.permutation(key, n)
    return (x[perm].reshape(n_clients, per_client, features),
            y[perm].reshape(n_clients, per_client))


def host_shards(x, y) -> list[dict]:
    """Per-client host (numpy) shards, the form ``partition_iid`` hands
    ``FleetSpec.build_clients`` when a user builds a fleet: the program's
    cohort build then pays its own cost of moving them to the device."""
    x, y = np.asarray(x), np.asarray(y)
    return [{"x": a, "y": b} for a, b in zip(x, y)]


def mlp_dims(cfg: dict) -> list[int]:
    return ([cfg["num_features"]] + [cfg["hidden"]] * cfg["num_layers"]
            + [cfg["num_classes"]])


@functools.partial(jax.jit, static_argnames=("dims",))
def mlp_params(key, *, dims: tuple):
    """The paper MLP's layers ``{"w": (in, out), "b": (out,)}``: normal
    weights scaled by 4/sqrt(fan_in) (sigmoid's slope is at most 1/4),
    zero biases."""
    ks = jax.random.split(key, len(dims) - 1)
    return {"layers": [
        {"w": jax.random.normal(k, (i, o), jnp.float32) * (4.0 / jnp.sqrt(i)),
         "b": jnp.zeros((o,), jnp.float32)}
        for k, i, o in zip(ks, dims[:-1], dims[1:])]}


def decoder_shapes(cfg: dict) -> dict:
    """Leaf shapes of the decoder's parameter tree (layers stacked on a
    leading axis, tied embedding)."""
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    F, V = cfg["intermediate_size"], cfg["vocab_size"]
    hd = D // H
    return {"embed": (V, D), "final_norm": (D,),
            "layers": {"ln1": (L, D), "ln2": (L, D),
                       "attn": {"wq": {"w": (L, D, H, hd)},
                                "wk": {"w": (L, D, K, hd)},
                                "wv": {"w": (L, D, K, hd)},
                                "wo": {"w": (L, H * hd, D)}},
                       "mlp": {"wi": {"w": (L, D, F)},
                               "wg": {"w": (L, D, F)},
                               "wo": {"w": (L, F, D)}}}}


def _decoder_std(cfg: dict) -> dict:
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, F = cfg["num_attention_heads"], cfg["intermediate_size"]
    hd = D // H
    s_in = 1.0 / math.sqrt(D)
    return {"embed": 0.02, "final_norm": None,
            "layers": {"ln1": None, "ln2": None,
                       "attn": {"wq": {"w": s_in}, "wk": {"w": s_in},
                                "wv": {"w": s_in},
                                "wo": {"w": 1.0 / math.sqrt(H * hd * 2 * L)}},
                       "mlp": {"wi": {"w": s_in}, "wg": {"w": s_in},
                               "wo": {"w": 1.0 / math.sqrt(F * 2 * L)}}}}


def decoder_params(key, cfg: dict):
    """f32 master weights in one jitted call: normal leaves at the
    program's init scales, norm scales at 1."""
    shapes = decoder_shapes(cfg)
    stds = _decoder_std(cfg)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=lambda s: isinstance(s, tuple))
    std_leaves = jax.tree.leaves(stds, is_leaf=lambda s: s is None)

    @jax.jit
    def make(key):
        ks = jax.random.split(key, len(leaves))
        out = [jnp.ones(s, jnp.float32) if sd is None
               else jax.random.normal(k, s, jnp.float32) * sd
               for k, s, sd in zip(ks, leaves, std_leaves)]
        return jax.tree.unflatten(treedef, out)

    return make(key)


@functools.partial(jax.jit, static_argnames=("shape", "vocab"))
def token_batches(key, *, shape: tuple, vocab: int):
    """Uniform random token ids: (steps, tiers, seqs, positions + 1)."""
    return jax.random.randint(key, shape, 0, vocab, jnp.int32)
