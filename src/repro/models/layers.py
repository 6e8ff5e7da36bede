"""Shared building blocks: initializers, norms, RoPE, GQA attention
(chunked-causal for train/prefill, ring-buffer KV cache for decode), MLPs.

All modules are pure functions over pytree params (nested dicts of jnp
arrays). Parameters are stored f32; compute runs in ``cfg.dtype``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array
Params = dict[str, Any]


# ---------------------------------------------------------------- initializers

def init_dense(key, d_in: int, d_out: int | tuple, *, bias: bool = False,
               scale: float | None = None) -> Params:
    if isinstance(d_out, int):
        d_out = (d_out,)
    fan_out = math.prod(d_out)
    if scale is None:
        scale = 1.0 / math.sqrt(d_in)
    p = {"w": jax.random.normal(key, (d_in, *d_out), jnp.float32) * scale}
    if bias:
        p["b"] = jnp.zeros(d_out, jnp.float32)
    return p


def dense(p: Params, x: Array) -> Array:
    """x: (..., d_in); w: (d_in, *out_dims)."""
    w = p["w"].astype(x.dtype)
    out_dims = w.shape[1:]
    y = lax.dot_general(x, w.reshape(w.shape[0], -1),
                        (((x.ndim - 1,), (0,)), ((), ())))
    y = y.reshape(*x.shape[:-1], *out_dims)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def scaled(x: Array, multiplier: float) -> Array:
    """x times a configured multiplier; x itself (no op) where it is 1."""
    return x if multiplier == 1.0 else x * multiplier


def attn_scale(cfg) -> float | None:
    """The configured attention scale; None (1/sqrt(head_dim)) unless the
    config sets ``attention_multiplier``."""
    return cfg.attention_multiplier or None


# ---------------------------------------------------------------------- norms

def rms_norm(x: Array, w: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def layer_norm(x: Array, w: Array, b: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * lax.rsqrt(var + eps)
    return (x * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


# ----------------------------------------------------------------------- RoPE

def rope(x: Array, positions: Array, theta: float) -> Array:
    """Rotary embedding, half-split convention.

    x: (..., T, H, hd); positions: broadcastable to (..., T) int32.
    """
    hd = x.shape[-1]
    half = hd // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq   # (..., T, half)
    sin = jnp.sin(ang)[..., None, :].astype(x.dtype)            # (..., T, 1, half)
    cos = jnp.cos(ang)[..., None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


# ------------------------------------------------------------------ attention

def repeat_kv(k: Array, n_rep: int) -> Array:
    """(B, S, Hkv, hd) -> (B, S, Hkv*n_rep, hd)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def chunked_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                      window: int = 0, q_chunk: int = 1024,
                      q_offset: int = 0, scale: float | None = None) -> Array:
    """Memory-bounded attention: scan over query chunks (scores never exceed
    (B, H, q_chunk, S)). O(T*S) FLOPs, O(q_chunk*S) memory.

    q: (B, T, H, hd); k, v: (B, S, Hkv, hd). Returns (B, T, H, hd).
    window > 0 masks keys further than `window` behind the query (sliding
    window); q_offset is the absolute position of q[0] relative to k[0];
    scale multiplies the scores (None: 1/sqrt(hd)).
    """
    b, t, h, hd = q.shape
    s = k.shape[1]
    n_rep = h // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    q_chunk = min(q_chunk, t)
    if t % q_chunk:
        q_chunk = t  # fall back: unchunked (small T)
    nq = t // q_chunk
    kp = jnp.arange(s)

    def one_chunk(ci):
        qs = ci * q_chunk
        qc = lax.dynamic_slice_in_dim(q, qs, q_chunk, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qc, k) * scale
        qpos = q_offset + qs + jnp.arange(q_chunk)
        mask = jnp.ones((q_chunk, s), bool)
        if causal:
            mask &= kp[None, :] <= qpos[:, None]
        if window:
            mask &= kp[None, :] > qpos[:, None] - window
        scores = jnp.where(mask[None, None], scores.astype(jnp.float32), -1e30)
        p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    if nq == 1:
        return one_chunk(0)
    out = lax.map(one_chunk, jnp.arange(nq))           # (nq, B, qc, H, hd)
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, hd)


# Decode KV cache: ring buffer of size W (= full seq len when W >= max pos).
# `slot_pos` records the absolute position stored in each slot (-1 = empty),
# which makes sliding-window decode exact for positions >= W.

def init_kv_cache(batch: int, cache_len: int, n_kv: int, head_dim: int,
                  dtype) -> Params:
    return {
        "k": jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
        "slot_pos": jnp.full((cache_len,), -1, jnp.int32),
    }


def kv_cache_update(cache: Params, k_new: Array, v_new: Array, pos: Array) -> Params:
    """Insert one step (B, 1, Hkv, hd) at slot pos % W.

    §Perf hillclimb #2: the write is a masked SELECT over the (sharded)
    sequence dim, not a dynamic_update_slice — DUS with a traced start
    index on a sharded dim makes GSPMD gather the whole cache every step
    (~1 GB/layer on llama3.2 decode_32k). The select is elementwise, so it
    partitions trivially; the extra full-cache write is HBM-cheap relative
    to the attention read it sits next to.
    """
    w = cache["k"].shape[1]
    slot = pos % w
    sel = (jnp.arange(w) == slot)
    def put(buf, new):
        return jnp.where(sel[None, :, None, None], new.astype(buf.dtype), buf)
    return {
        "k": put(cache["k"], k_new),
        "v": put(cache["v"], v_new),
        "slot_pos": jnp.where(sel, pos, cache["slot_pos"]),
    }


def decode_attention(q: Array, cache: Params, *, window: int = 0,
                     scale: float | None = None) -> Array:
    """Single-token attention against the ring cache.

    q: (B, 1, H, hd). Masking is via slot_pos: valid slots satisfy
    0 <= slot_pos (written) and, with a window, slot_pos > pos - window —
    but since the ring overwrites slots older than W=window, every written
    slot is in-window by construction; we still mask empties. scale
    multiplies the scores (None: 1/sqrt(hd)).
    """
    from repro.models.sharding import hint
    h = q.shape[2]
    n_rep = h // cache["k"].shape[2]
    k = repeat_kv(cache["k"], n_rep)
    v = repeat_kv(cache["v"], n_rep)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    scores = hint(scores, "dec_scores")          # (B, H, 1, S): S on "model"
    valid = cache["slot_pos"] >= 0
    scores = jnp.where(valid[None, None, None, :], scores.astype(jnp.float32), -1e30)
    p = hint(jax.nn.softmax(scores, axis=-1), "dec_scores").astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# --------------------------------------------------------- attention "module"

def init_attn(key, cfg) -> Params:
    ks = jax.random.split(key, 4)
    hd = cfg.head_dim
    return {
        "wq": init_dense(ks[0], cfg.d_model, (cfg.num_heads, hd), bias=cfg.qkv_bias),
        "wk": init_dense(ks[1], cfg.d_model, (cfg.num_kv_heads, hd), bias=cfg.qkv_bias),
        "wv": init_dense(ks[2], cfg.d_model, (cfg.num_kv_heads, hd), bias=cfg.qkv_bias),
        "wo": init_dense(ks[3], cfg.num_heads * hd, cfg.d_model,
                         scale=1.0 / math.sqrt(cfg.num_heads * hd * 2 * cfg.num_layers)),
    }


def attn_forward(p: Params, x: Array, cfg, *, window: int = 0,
                 positions: Array | None = None, use_rope: bool = True,
                 kv_src: Array | None = None, causal: bool = True,
                 scale: float | None = None) -> Array:
    """Full-sequence attention (train / prefill). kv_src != None => cross-attn.
    scale multiplies the scores (None: 1/sqrt(head_dim))."""
    b, t, _ = x.shape
    src = x if kv_src is None else kv_src
    q = dense(p["wq"], x)                      # (B, T, H, hd)
    k = dense(p["wk"], src)
    v = dense(p["wv"], src)
    if use_rope:
        if positions is None:
            positions = jnp.arange(t)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, jnp.arange(src.shape[1]), cfg.rope_theta)
    if getattr(cfg, "use_flash", False):
        from repro.kernels.flash_attention import flash_attention
        o = flash_attention(q, k, v, causal=causal and kv_src is None,
                            window=window, scale=scale)
    else:
        o = chunked_attention(q, k, v, causal=causal and kv_src is None,
                              window=window, scale=scale)
    return dense(p["wo"], o.reshape(b, t, -1))


def attn_decode(p: Params, x: Array, cache: Params, pos: Array, cfg, *,
                window: int = 0, use_rope: bool = True,
                scale: float | None = None) -> tuple[Array, Params]:
    """Single-step decode. x: (B, 1, D); pos: scalar int32."""
    b = x.shape[0]
    q = dense(p["wq"], x)
    k = dense(p["wk"], x)
    v = dense(p["wv"], x)
    if use_rope:
        ppos = jnp.full((1,), pos, jnp.int32)
        q = rope(q, ppos, cfg.rope_theta)
        k = rope(k, ppos, cfg.rope_theta)
    cache = kv_cache_update(cache, k, v, pos)
    o = decode_attention(q, cache, window=window, scale=scale)
    return dense(p["wo"], o.reshape(b, 1, -1)), cache


def cross_attn_decode(p: Params, x: Array, enc_kv: tuple[Array, Array], cfg) -> Array:
    """Decoder cross-attention against precomputed encoder K/V (B, S, Hkv, hd)."""
    b = x.shape[0]
    q = dense(p["wq"], x)
    k, v = enc_kv
    scale = 1.0 / math.sqrt(q.shape[-1])
    n_rep = q.shape[2] // k.shape[2]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, repeat_kv(k, n_rep)) * scale
    pr = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(v.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr, repeat_kv(v, n_rep))
    return dense(p["wo"], o.reshape(b, x.shape[1], -1))


# ----------------------------------------------------------------------- MLPs

def init_swiglu(key, d_model: int, d_ff: int, num_layers: int = 1) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "wi": init_dense(ks[0], d_model, d_ff),
        "wg": init_dense(ks[1], d_model, d_ff),
        "wo": init_dense(ks[2], d_ff, d_model, scale=1.0 / math.sqrt(d_ff * 2 * num_layers)),
    }


def swiglu(p: Params, x: Array) -> Array:
    return dense(p["wo"], jax.nn.silu(dense(p["wg"], x)) * dense(p["wi"], x))


def init_gelu_mlp(key, d_model: int, d_ff: int) -> Params:
    ks = jax.random.split(key, 2)
    return {"wi": init_dense(ks[0], d_model, d_ff, bias=True),
            "wo": init_dense(ks[1], d_ff, d_model, bias=True)}


def gelu_mlp(p: Params, x: Array) -> Array:
    return dense(p["wo"], jax.nn.gelu(dense(p["wi"], x)))


# ------------------------------------------------------------------ embedding

def init_embed(key, vocab: int, d_model: int) -> Array:
    return jax.random.normal(key, (vocab, d_model), jnp.float32) * 0.02


def embed(table: Array, tokens: Array, dtype) -> Array:
    return jnp.take(table, tokens, axis=0).astype(dtype)


def unembed(x: Array, table: Array) -> Array:
    """Logits in f32. table: (V, D) (tied) used transposed."""
    return jnp.einsum("btd,vd->btv", x.astype(jnp.float32), table.astype(jnp.float32))


def cross_entropy(logits: Array, labels: Array, mask: Array | None = None) -> Array:
    """Mean next-token NLL. logits: (B, T, V) f32; labels: (B, T) int32."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def stack_layers(keys, init_fn):
    """Init per-layer params and stack leaves along a leading L axis (for scan)."""
    per_layer = [init_fn(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
