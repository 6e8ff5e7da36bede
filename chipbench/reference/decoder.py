"""Plain reference of the tier-scanned decoder training step.

A dense GQA decoder (RMSNorm, half-split RoPE, causal softmax attention,
SwiGLU, tied embedding, mean next-token cross-entropy) written from the
configuration file in ``jax.numpy`` at float32 with every matmul at
``HIGHEST`` precision, imports nothing of the program. One step: each
tier compresses every matrix-shaped leaf of the global model (the stacked
per-layer leaves count as matrices, norm scales included; magnitude mask
by log-bisection over the whole leaf, then RNE onto the tier's float
grid, straight-through inside its range), takes the gradient of its
loss, and adds ``w m g`` and ``w m`` to shared accumulators; their
quotient goes to AdamW.

Layers run under one ``lax.scan`` and are recomputed in the backward
pass (``jax.checkpoint``), which changes no value, so the reference fits
one chip beside the program's freed state and compiles one layer. ``quant="fp8"`` rounds both operands of every matmul, and
the incoming gradient of each, to float8 e4m3 with a per-tensor scale:
the control, one precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.numerics import compress_tree, mm, tier_scalars


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x (B, T, heads, hd), rotated by position in half-split pairs."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(x, lp, cfg, quant):
    B, T, D = x.shape
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = D // H
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, lp["ln1"], eps)
    q = rope(mm("btd,dhk->bthk", h, lp["attn"]["wq"]["w"], quant), cfg["rope_theta"])
    k = rope(mm("btd,dhk->bthk", h, lp["attn"]["wk"]["w"], quant), cfg["rope_theta"])
    v = mm("btd,dhk->bthk", h, lp["attn"]["wv"]["w"], quant)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k, quant) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    o = mm("bhqk,bkhd->bqhd", p, v, quant).reshape(B, T, H * hd)
    x = x + mm("btf,fd->btd", o, lp["attn"]["wo"]["w"], quant)
    h = rms_norm(x, lp["ln2"], eps)
    g = mm("btd,df->btf", h, lp["mlp"]["wg"]["w"], quant)
    u = mm("btd,df->btf", h, lp["mlp"]["wi"]["w"], quant)
    return x + mm("btf,fd->btd", jax.nn.silu(g) * u, lp["mlp"]["wo"]["w"], quant)


def loss(params, tokens, cfg, quant):
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"][inputs]
    step = jax.checkpoint(lambda x, lp: (layer(x, lp, cfg, quant), None))
    x, _ = jax.lax.scan(step, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    logits = mm("btd,vd->btv", x, params["embed"], quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"),
                   donate_argnums=(1, 2))
def _tier(params, num, den, tokens, tier, weight, *, cfg_key, quant):
    cfg = dict(cfg_key)
    cw, m, inside = compress_tree(params, *tier)
    value, g = jax.value_and_grad(loss)(cw, tokens, cfg, quant)
    num = jax.tree.map(lambda a, g, m, s: a + weight * m * (g * m * s),
                       num, g, m, inside)
    den = jax.tree.map(lambda a, m: a + weight * m, den, m)
    return num, den, value


@functools.partial(jax.jit, static_argnames=("opt_key",), donate_argnums=(0, 1, 2))
def _adamw(params, m, v, num, den, count, *, opt_key):
    o = dict(opt_key)
    g = jax.tree.map(lambda n, d: n / jnp.maximum(d, 1e-8), num, den)
    m = jax.tree.map(lambda m, g: o["b1"] * m + (1 - o["b1"]) * g, m, g)
    v = jax.tree.map(lambda v, g: o["b2"] * v + (1 - o["b2"]) * g * g, v, g)
    bc1, bc2 = 1 - o["b1"] ** count, 1 - o["b2"] ** count

    def upd(p, m, v):
        u = (m / bc1) / (jnp.sqrt(v / bc2) + o["eps"]) + o["weight_decay"] * p
        return p - o["lr"] * u
    return jax.tree.map(upd, params, m, v), m, v, g


def _key(d: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items()
                        if isinstance(v, (int, float, str, list, type(None)))))


def run_steps(params, batches, cfg: dict, *, quant=None, fault=None,
              on_grad=None):
    """Follow ``len(batches)`` steps from ``params`` (consumed). Each batch
    is (tiers, seqs, positions + 1). ``on_grad(step, grads)`` sees the
    gradient AdamW is given at each step. Returns (losses, params)."""
    opt, tiers = cfg["optimizer"], list(cfg["tiers"].values())
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    m, v = zeros(), zeros()
    wsum = sum(t["weight"] for t in tiers)
    losses = []
    cfg_key = _key(cfg)
    for i, batch in enumerate(batches):
        num = zeros()
        den = jax.tree.map(lambda p: jnp.zeros(p.shape if p.ndim >= 2 else (),
                                               jnp.float32), params)
        total = 0.0
        for t, plan in enumerate(tiers):
            toks = batch[t][:1] if fault == "half" else batch[t]
            num, den, value = _tier(params, num, den, toks,
                                    tier_scalars(plan),
                                    jnp.float32(plan["weight"]),
                                    cfg_key=cfg_key, quant=quant)
            total += plan["weight"] * float(value)
        params, m, v, g = _adamw(params, m, v, num, den, jnp.float32(i + 1),
                                 opt_key=_key(opt))
        if on_grad is not None:
            on_grad(i, g)
        del g
        losses.append(total / wsum)
    return losses, params
