"""Pure-jnp oracle for flash attention (materialized softmax)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        q_offset: int = 0, scale: float | None = None):
    """q: (B, Tq, H, hd); k, v: (B, S, Hkv, hd). ``scale`` multiplies
    the scores (None: 1/sqrt(hd))."""
    b, tq, h, hd = q.shape
    s = k.shape[1]
    n_rep = h // k.shape[2]
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32))
    scores = scores / math.sqrt(hd) if scale is None else scores * scale
    qpos = q_offset + jnp.arange(tq)
    kpos = jnp.arange(s)
    mask = jnp.ones((tq, s), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)      # fully-masked rows -> 0
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
