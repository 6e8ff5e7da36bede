"""Pallas flash attention (online-softmax, causal/sliding-window, GQA).

The prefill roofline is memory-bound largely because naive attention
round-trips (B,H,Tq,S) score tiles through HBM; flash attention keeps the
running (max, sum, acc) statistics in VMEM scratch so scores never leave
the core. GQA is handled in the BlockSpec index_map — the (b, h_kv) block
of K/V is fetched for all `n_rep` query heads of its group, so repeated
K/V are never materialized (the HBM saving GQA exists to provide).

Grid: (B*Hq, Tq/bq, S/bk), k innermost; scratch: m (bq,1), l (bq,1),
acc (bq, hd) f32. Masked positions use -1e30 with an explicit zero-guard
so fully-masked tiles (sliding window) contribute exactly nothing.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, q_offset: int,
                  bq: int, bk: int, k_steps: int, s_valid: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                    # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                    # (bk, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (bq, bk)
    qpos = q_offset + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < s_valid
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]                                 # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # zero-guard: fully-masked rows keep m == NEG; exp(NEG - NEG) must be 0
    p = jnp.where(s > NEG / 2, jnp.exp(s - m_new), 0.0)
    corr = jnp.where(m_prev > NEG / 2, jnp.exp(m_prev - m_new), 0.0)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new

    @pl.when(ik == k_steps - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_rep", "causal", "window", "q_offset", "scale", "block_q", "block_k",
    "s_valid", "interpret"))
def flash_attention_raw(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        n_rep: int = 1, causal: bool = True, window: int = 0,
                        q_offset: int = 0, scale: float | None = None,
                        block_q: int = 128,
                        block_k: int = 128, s_valid: int | None = None,
                        interpret: bool = False) -> jax.Array:
    """q: (BHq, Tq, hd); k, v: (BHkv, S, hd) with BHq == BHkv * n_rep.
    Tq % block_q == 0, S % block_k == 0 (pad before; mask via s_valid).
    ``scale`` multiplies the scores (None: 1/sqrt(hd))."""
    bh, tq, hd = q.shape
    s = k.shape[1]
    bq, bk = min(block_q, tq), min(block_k, s)
    k_steps = s // bk
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if s_valid is None:
        s_valid = s
    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bk=bk, k_steps=k_steps, s_valid=s_valid)
    return pl.pallas_call(
        kern,
        grid=(bh, tq // bq, k_steps),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda b, i, j, n_rep=n_rep: (b // n_rep, j, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda b, i, j, n_rep=n_rep: (b // n_rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
