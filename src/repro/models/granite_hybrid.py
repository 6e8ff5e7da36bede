"""Granite 4.0-H hybrid (HF ``GraniteMoeHybrid`` with no experts): a stack
whose layers follow ``cfg.layer_types``, each a Mamba2 or a GQA attention
mixer followed by its own SwiGLU MLP, every layer with its own
parameters. Layer i computes

    h  = x + r * mixer_i(rms_norm(x))
    x' = h + r * swiglu_i(rms_norm(h))

with r = ``cfg.residual_multiplier``; the embedding is multiplied by
``cfg.embedding_multiplier``, attention scales its scores by
``cfg.attention_multiplier`` and uses no position embedding when
``cfg.use_rope`` is False (NoPE), and the tied logits are divided by
``cfg.logits_scaling``.

Layers of one kind that come in a run are stacked, and each run is one
``lax.scan`` (``params["runs"]``, in order), its body rematerialised as
``decoder.forward``'s is. Spans: ``granite_hybrid.mamba`` (projections,
conv, gated norm), ``granite_hybrid.ssd`` (the chunked SSD scan) and
``granite_hybrid.attn``.

Decode state: per layer, the Mamba2 conv and SSM states or a ring-buffer
KV cache, stacked by run as the parameters are.
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models.mamba2 import (init_mamba, init_mamba_state, mamba_decode,
                                 mamba_forward)
from repro.models.sharding import hint

KINDS = ("mamba", "attention")
SCOPE = "granite_hybrid"


def runs(cfg) -> list[tuple[str, int]]:
    """(kind, length) of each run of equal mixers, in layer order."""
    types = tuple(cfg.layer_types)
    if len(types) != cfg.num_layers:
        raise ValueError(f"{len(types)} layer_types for {cfg.num_layers} "
                         f"layers")
    bad = set(types) - set(KINDS)
    if bad:
        raise ValueError(f"unknown layer types {sorted(bad)}; known {KINDS}")
    return [(k, len(list(g))) for k, g in itertools.groupby(types)]


def init(key, cfg):
    ks = jax.random.split(key, 2 + cfg.num_layers)
    params = {
        "embed": L.init_embed(ks[0], cfg.vocab_size, cfg.d_model),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_dense(ks[1], cfg.d_model, cfg.vocab_size,
                                         scale=0.02)

    def one_layer(kind):
        def make(k):
            k1, k2 = jax.random.split(k)
            lp = {"ln1": jnp.ones((cfg.d_model,), jnp.float32),
                  "ln2": jnp.ones((cfg.d_model,), jnp.float32),
                  "mlp": L.init_swiglu(k2, cfg.d_model, cfg.d_ff,
                                       cfg.num_layers)}
            if kind == "mamba":
                lp["mamba"] = init_mamba(k1, cfg)
            else:
                lp["attn"] = L.init_attn(k1, cfg)
            return lp
        return make

    out, i = [], 2
    for kind, n in runs(cfg):
        out.append(L.stack_layers(ks[i:i + n], one_layer(kind)))
        i += n
    params["runs"] = out
    return params


# ----------------------------------------------------------------- blocks

def _mlp(lp, x, cfg):
    y = L.swiglu(lp["mlp"], L.rms_norm(x, lp["ln2"], cfg.norm_eps))
    return hint(x + L.scaled(y, cfg.residual_multiplier), "act_btd")


def _layer(kind, lp, x, cfg):
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    if kind == "mamba":
        y, _ = mamba_forward(lp["mamba"], h, cfg, scope=SCOPE)
    else:
        with jax.named_scope(f"{SCOPE}.attn"):
            y = L.attn_forward(lp["attn"], h, cfg, use_rope=cfg.use_rope,
                               scale=L.attn_scale(cfg))
    x = hint(x + L.scaled(y, cfg.residual_multiplier), "act_btd")
    return _mlp(lp, x, cfg)


def _embed(params, tokens, cfg):
    x = L.embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
    return hint(L.scaled(x, cfg.embedding_multiplier), "act_btd")


def _unembed(params, x, cfg):
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = L.unembed(x, params["embed"])
    else:
        logits = L.dense(params["lm_head"], x.astype(jnp.float32))
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return hint(logits, "logits")


# ---------------------------------------------------------------- forward

def forward(params, tokens, cfg, *, remat: bool = True):
    """Returns (logits (B, T, V) f32, aux_loss 0)."""
    x = _embed(params, tokens, cfg)
    for (kind, _), rp in zip(runs(cfg), params["runs"]):
        def body(x, lp, kind=kind):
            return _layer(kind, lp, x, cfg), None
        x, _ = lax.scan(jax.checkpoint(body) if remat else body, x, rp)
    return _unembed(params, x, cfg), jnp.float32(0.0)


def loss_fn(params, batch, cfg, *, num_groups: int = 1):
    tokens = batch["tokens"]
    logits, _ = forward(params, tokens[:, :-1], cfg)
    return L.cross_entropy(logits, tokens[:, 1:])


# ---------------------------------------------------------------- prefill

def _attn_prefill(p, h, cfg, cache_len):
    """Attention over the prompt; returns (out, KV cache of ``cache_len``
    slots with the prompt in the first ones)."""
    b, t, _ = h.shape
    q, k, v = (L.dense(p[n], h) for n in ("wq", "wk", "wv"))
    if cfg.use_rope:
        pos = jnp.arange(t)
        q = L.rope(q, pos, cfg.rope_theta)
        k = L.rope(k, pos, cfg.rope_theta)
    o = L.chunked_attention(q, k, v, causal=True, scale=L.attn_scale(cfg))
    out = L.dense(p["wo"], o.reshape(b, t, -1))
    cache = L.init_kv_cache(b, cache_len, cfg.num_kv_heads, cfg.head_dim,
                            jnp.dtype(cfg.dtype))
    cache = {"k": lax.dynamic_update_slice_in_dim(
                 cache["k"], k.astype(cache["k"].dtype), 0, 1),
             "v": lax.dynamic_update_slice_in_dim(
                 cache["v"], v.astype(cache["v"].dtype), 0, 1),
             "slot_pos": jnp.where(jnp.arange(cache_len) < t,
                                   jnp.arange(cache_len), -1).astype(jnp.int32)}
    return out, cache


def prefill(params, tokens, cfg, *, window: int = 0, num_groups: int = 1,
            cache_len: int | None = None):
    """Full-sequence forward that fills every layer's decode state (KV
    caches of ``cache_len`` slots, the prompt's length by default).
    Returns (last-token logits (B, 1, V), cache)."""
    if window:
        raise ValueError("granite_hybrid attention has no sliding window")
    t = tokens.shape[1]
    cache_len = cache_len or t
    x = _embed(params, tokens, cfg)
    caches = []
    for (kind, _), rp in zip(runs(cfg), params["runs"]):
        def body(x, lp, kind=kind):
            h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
            if kind == "mamba":
                y, state = mamba_forward(lp["mamba"], h, cfg, scope=SCOPE)
            else:
                y, state = _attn_prefill(lp["attn"], h, cfg, cache_len)
            x = hint(x + L.scaled(y, cfg.residual_multiplier), "act_btd")
            return _mlp(lp, x, cfg), state
        x, state = lax.scan(body, x, rp)
        caches.append(state)
    return _unembed(params, x[:, -1:, :], cfg), {"runs": caches}


# ----------------------------------------------------------------- decode

def init_cache(cfg, batch: int, cache_len: int):
    out = []
    for kind, n in runs(cfg):
        one = (init_mamba_state(cfg, batch) if kind == "mamba" else
               L.init_kv_cache(batch, cache_len, cfg.num_kv_heads,
                               cfg.head_dim, jnp.dtype(cfg.dtype)))
        out.append(jax.tree.map(lambda s, n=n: jnp.broadcast_to(s, (n, *s.shape)),
                                one))
    return {"runs": out}


def decode_step(params, cache, tokens, pos, cfg, *, window: int = 0,
                num_groups: int = 1):
    """One decode step. tokens: (B, 1); pos: scalar int32."""
    if window:
        raise ValueError("granite_hybrid attention has no sliding window")
    x = _embed(params, tokens, cfg)
    new = []
    for (kind, _), rp, rc in zip(runs(cfg), params["runs"], cache["runs"]):
        def body(x, xs, kind=kind):
            lp, state = xs
            h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
            if kind == "mamba":
                y, state = mamba_decode(lp["mamba"], h, state, cfg)
            else:
                y, state = L.attn_decode(lp["attn"], h, state, pos, cfg,
                                         use_rope=cfg.use_rope,
                                         scale=L.attn_scale(cfg))
            x = x + L.scaled(y, cfg.residual_multiplier)
            return _mlp(lp, x, cfg), state
        x, state = lax.scan(body, x, (rp, rc))
        new.append(state)
    return _unembed(params, x, cfg), {"runs": new}
