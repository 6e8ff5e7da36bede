"""Unified decoder-only transformer: dense (llama/granite/qwen/deepseek),
MoE (granite-moe, qwen3-moe), and VLM (llava — consumes stub patch
embeddings prepended to text tokens).

Layers are scanned (stacked params, `lax.scan`) with optional remat so the
HLO stays one-layer-sized regardless of depth; decode runs the same scan
over per-layer ring-buffer KV caches.

Granite's multipliers (``cfg.embedding_multiplier``, ``residual_multiplier``
on both branches of a layer, ``attention_multiplier`` as the attention
scale, ``logits_scaling`` dividing the logits) and NoPE (``use_rope``
False) apply where the config sets them; at their identity defaults no
op is added.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models.moe import init_moe, moe_apply
from repro.models.sharding import hint


# ------------------------------------------------------------------- init

def init(key, cfg):
    ks = jax.random.split(key, 4 + cfg.num_layers)
    params = {
        "embed": L.init_embed(ks[0], cfg.vocab_size, cfg.d_model),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_dense(ks[1], cfg.d_model, cfg.vocab_size, scale=0.02)
    if cfg.family == "vlm":
        params["projector"] = L.init_dense(ks[2], cfg.d_model, cfg.d_model)

    def one_layer(k):
        k1, k2 = jax.random.split(k)
        lp = {
            "ln1": jnp.ones((cfg.d_model,), jnp.float32),
            "ln2": jnp.ones((cfg.d_model,), jnp.float32),
            "attn": L.init_attn(k1, cfg),
        }
        if cfg.is_moe:
            lp["moe"] = init_moe(k2, cfg)
        else:
            lp["mlp"] = L.init_swiglu(k2, cfg.d_model, cfg.d_ff, cfg.num_layers)
        return lp

    params["layers"] = L.stack_layers(ks[4:4 + cfg.num_layers], one_layer)
    return params


# ----------------------------------------------------------------- blocks

def _ffn(lp, x, cfg, num_groups):
    h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.is_moe:
        y, aux = moe_apply(lp["moe"], h, cfg, num_groups)
    else:
        y, aux = L.swiglu(lp["mlp"], h), jnp.float32(0.0)
    return x + L.scaled(y, cfg.residual_multiplier), aux


def _block(lp, x, cfg, window, num_groups):
    h = L.attn_forward(lp["attn"], L.rms_norm(x, lp["ln1"], cfg.norm_eps),
                       cfg, window=window, use_rope=cfg.use_rope,
                       scale=L.attn_scale(cfg))
    x = hint(x + L.scaled(h, cfg.residual_multiplier), "act_btd")
    x, aux = _ffn(lp, x, cfg, num_groups)
    return hint(x, "act_btd"), aux


def _block_decode(lp, x, cache_l, pos, cfg, window, num_groups):
    h, cache_l = L.attn_decode(lp["attn"], L.rms_norm(x, lp["ln1"], cfg.norm_eps),
                               cache_l, pos, cfg, window=window,
                               use_rope=cfg.use_rope, scale=L.attn_scale(cfg))
    x = x + L.scaled(h, cfg.residual_multiplier)
    x, _ = _ffn(lp, x, cfg, num_groups)
    return x, cache_l


# ---------------------------------------------------------------- forward

def _embed_inputs(params, tokens, cfg, patches):
    x = L.scaled(L.embed(params["embed"], tokens, jnp.dtype(cfg.dtype)),
                 cfg.embedding_multiplier)
    if patches is not None:
        pe = L.dense(params["projector"], patches.astype(x.dtype))
        x = jnp.concatenate([pe, x], axis=1)
    return hint(x, "act_btd")


def _unembed(params, x, cfg):
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = L.unembed(x, params["embed"])
    else:
        logits = L.dense(params["lm_head"], x.astype(jnp.float32))
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return hint(logits, "logits")


def forward(params, tokens, cfg, *, patches=None, window: int = 0,
            num_groups: int = 1, remat: bool = True):
    """Returns (logits (B, T, V) f32, aux_loss)."""
    x = _embed_inputs(params, tokens, cfg, patches)

    def body(carry, lp):
        x, aux = carry
        x, a = _block(lp, x, cfg, window, num_groups)
        return (x, aux + a), None

    body_fn = jax.checkpoint(body) if remat else body
    (x, aux), _ = lax.scan(body_fn, (x, jnp.float32(0.0)), params["layers"])
    return _unembed(params, x, cfg), aux


def loss_fn(params, batch, cfg, *, num_groups: int = 1):
    """batch: {"tokens": (B, T+1)} (+ "patches" (B, P, D) for vlm).
    For vlm, `tokens` covers only the text part; patch positions carry no loss.
    """
    tokens = batch["tokens"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    patches = batch.get("patches")
    logits, aux = forward(params, inputs, cfg, patches=patches)
    if patches is not None:
        logits = logits[:, patches.shape[1]:, :]
    return L.cross_entropy(logits, labels) + aux


# ---------------------------------------------------------------- prefill

def prefill(params, tokens, cfg, *, patches=None, window: int = 0,
            num_groups: int = 1):
    """Full-sequence forward that also fills the KV cache.
    Returns (last-token logits (B, 1, V), cache)."""
    x = _embed_inputs(params, tokens, cfg, patches)
    t = x.shape[1]

    def body(x, lp):
        h_in = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = L.dense(lp["attn"]["wq"], h_in)
        k = L.dense(lp["attn"]["wk"], h_in)
        v = L.dense(lp["attn"]["wv"], h_in)
        if cfg.use_rope:
            pos = jnp.arange(t)
            q = L.rope(q, pos, cfg.rope_theta)
            k = L.rope(k, pos, cfg.rope_theta)
        o = L.chunked_attention(q, k, v, causal=True, window=window,
                                scale=L.attn_scale(cfg))
        h = L.dense(lp["attn"]["wo"], o.reshape(x.shape[0], t, -1))
        x = hint(x + L.scaled(h, cfg.residual_multiplier), "act_btd")
        x, _ = _ffn(lp, x, cfg, num_groups)
        return hint(x, "act_btd"), {"k": k, "v": v}

    x, kv = lax.scan(body, x, params["layers"])
    cache = {"layers": {**kv, "slot_pos": jnp.broadcast_to(
        jnp.arange(t, dtype=jnp.int32), (cfg.num_layers, t))}}
    return _unembed(params, x[:, -1:, :], cfg), cache


# ----------------------------------------------------------------- decode

def init_cache(cfg, batch: int, cache_len: int):
    dt = jnp.dtype(cfg.dtype)
    kv = L.init_kv_cache(batch, cache_len, cfg.num_kv_heads, cfg.head_dim, dt)
    return {"layers": {
        "k": jnp.zeros((cfg.num_layers, *kv["k"].shape), dt),
        "v": jnp.zeros((cfg.num_layers, *kv["v"].shape), dt),
        "slot_pos": jnp.full((cfg.num_layers, cache_len), -1, jnp.int32),
    }}


def decode_step(params, cache, tokens, pos, cfg, *, window: int = 0,
                num_groups: int = 1):
    """One decode step. tokens: (B, 1); pos: scalar int32 (shared across batch
    in this synthetic setting). Returns (logits (B, 1, V), cache)."""
    x = L.scaled(L.embed(params["embed"], tokens, jnp.dtype(cfg.dtype)),
                 cfg.embedding_multiplier)

    def body(x, xs):
        lp, cl = xs
        x, cl = _block_decode(lp, x, cl, pos, cfg, window, num_groups)
        return x, cl

    x, new_layers = lax.scan(body, x, (params["layers"], cache["layers"]))
    return _unembed(params, x, cfg), {"layers": new_layers}
