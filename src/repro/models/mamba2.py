"""Mamba2 (SSD) block: chunked state-space-dual training form (matmul-heavy,
TPU/MXU-friendly) + O(1) recurrent decode step.

Simplifications vs. the reference CUDA implementation (documented in
DESIGN.md): n_groups = 1 (B/C shared across heads), no sequence-parallel
conv halo (conv runs full-sequence under pjit; XLA shards the batch dim).
Every head-indexed leaf is laid out by head (``init_mamba``), so a
"model"-sharded layer computes its own heads' z, x, dt and conv with no
resharding and B/C replicated.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import dense, init_dense, rms_norm

CHUNK = 256


def dims(cfg):
    d_in = cfg.ssm_expand * cfg.d_model
    nheads = d_in // cfg.ssm_headdim
    conv_dim = d_in + 2 * cfg.ssm_state
    return d_in, nheads, conv_dim


def init_mamba(key, cfg) -> dict:
    """The input projection is one leaf per part, each laid out by head:
    ``in_z``/``in_x`` (D, d_in) and ``in_dt`` (D, H) hold head h's columns
    at [h*P, (h+1)*P) and h, so sharding their last axis gives each rank
    whole heads; ``in_bc`` (D, 2N) is B and C, which every head shares
    (n_groups = 1). The depthwise conv over [x | B | C] is split the same
    way: ``conv_x_*`` per x channel, ``conv_bc_*`` for B and C."""
    d_in, nheads, _ = dims(cfg)
    n, w = cfg.ssm_state, cfg.conv_width
    ks = jax.random.split(key, 7)
    return {
        "in_z": init_dense(ks[0], cfg.d_model, d_in),
        "in_x": init_dense(ks[1], cfg.d_model, d_in),
        "in_bc": init_dense(ks[2], cfg.d_model, 2 * n),
        "in_dt": init_dense(ks[3], cfg.d_model, nheads),
        "conv_x_w": jax.random.normal(ks[4], (d_in, w), jnp.float32)
                    * (1.0 / math.sqrt(w)),
        "conv_x_b": jnp.zeros((d_in,), jnp.float32),
        "conv_bc_w": jax.random.normal(ks[5], (2 * n, w), jnp.float32)
                     * (1.0 / math.sqrt(w)),
        "conv_bc_b": jnp.zeros((2 * n,), jnp.float32),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nheads).astype(jnp.float32)),
        "dt_bias": jnp.log(jnp.expm1(jnp.linspace(1e-3, 0.1, nheads)).astype(jnp.float32)),
        "d_skip": jnp.ones((nheads,), jnp.float32),
        "gate_norm": jnp.ones((d_in,), jnp.float32),
        "out_proj": init_dense(ks[6], d_in, cfg.d_model,
                               scale=1.0 / math.sqrt(d_in * 2 * cfg.num_layers)),
    }


def _project(p, u):
    """(z, x, bc, dt) of the input projection."""
    return (dense(p["in_z"], u), dense(p["in_x"], u), dense(p["in_bc"], u),
            dense(p["in_dt"], u))


def _causal_conv(w, b, xs, cfg):
    """Depthwise causal conv over (B, T, C); w: (C, W), b: (C,). The bias
    and silu run in float32 (the bias's gradient sums over every position)."""
    w = w.astype(xs.dtype)
    c = xs.shape[-1]
    out = lax.conv_general_dilated(
        xs, w.T[:, None, :],                           # (W, 1, C)
        window_strides=(1,), padding=[(cfg.conv_width - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=c)
    return jax.nn.silu(out.astype(jnp.float32) + b).astype(xs.dtype)


def _conv_tail(xs, cfg):
    """Decode conv state: the last W-1 raw (pre-conv) inputs, left-padded
    where t < W-1."""
    t, w1 = xs.shape[1], cfg.conv_width - 1
    tail = xs[:, -w1:, :] if t >= w1 else jnp.pad(
        xs, ((0, 0), (w1 - t, 0), (0, 0)))
    return tail.astype(jnp.dtype(cfg.dtype))


def _ssd_scan(x, bmat, cmat, dt, a, cfg, init_state=None):
    """Chunked SSD. x: (B,T,H,P); bmat/cmat: (B,T,N); dt: (B,T,H) (post-
    softplus); a: (H,) negative. Returns (y (B,T,H,P), final_state (B,H,P,N)).
    """
    b, t, h, p = x.shape
    n = bmat.shape[-1]
    q = CHUNK if t % CHUNK == 0 else t
    nc = t // q
    xc = x.reshape(b, nc, q, h, p)
    bc = bmat.reshape(b, nc, q, n)
    cc = cmat.reshape(b, nc, q, n)
    dtc = dt.reshape(b, nc, q, h)
    da = dtc * a[None, None, None, :]                  # (B,nc,Q,H) log-decay (<0)

    s0 = (jnp.zeros((b, h, p, n), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def chunk(state, xs):
        xq, bq, cq, dtq, daq = xs                      # (B,Q,...) for one chunk
        cum = jnp.cumsum(daq, axis=1)                  # (B,Q,H)
        # intra-chunk: y_i += sum_{j<=i} (C_i.B_j) exp(cum_i-cum_j) dt_j x_j
        seg = cum[:, :, None, :] - cum[:, None, :, :]  # (B,Q,Q,H) i,j
        tri = jnp.tril(jnp.ones((q, q), bool))
        seg = jnp.where(tri[None, :, :, None], seg, -jnp.inf)
        lmat = jnp.exp(seg)                            # (B,Q,Q,H)
        scores = jnp.einsum("bin,bjn->bij", cq.astype(jnp.float32),
                            bq.astype(jnp.float32))    # (B,Q,Q)
        m = scores[..., None] * lmat * dtq[:, None, :, :]      # (B,Qi,Qj,H)
        y_intra = jnp.einsum("bijh,bjhp->bihp", m, xq.astype(jnp.float32))
        # inter-chunk: y_i += exp(cum_i) C_i . state
        y_inter = jnp.einsum("bqn,bhpn->bqhp", cq.astype(jnp.float32), state) \
            * jnp.exp(cum)[..., None]                  # (B,Q,H,1)
        # state update: S' = exp(cum_last) S + sum_j exp(cum_last-cum_j) dt_j x_j B_j^T
        wj = jnp.exp(cum[:, -1:, :] - cum) * dtq       # (B,Q,H)
        new_state = jnp.exp(cum[:, -1])[:, :, None, None] * state \
            + jnp.einsum("bqhp,bqn,bqh->bhpn", xq.astype(jnp.float32),
                         bq.astype(jnp.float32), wj)
        return new_state, (y_intra + y_inter).astype(x.dtype)

    xs = (jnp.moveaxis(xc, 1, 0), jnp.moveaxis(bc, 1, 0), jnp.moveaxis(cc, 1, 0),
          jnp.moveaxis(dtc, 1, 0), jnp.moveaxis(da, 1, 0))
    state, yc = lax.scan(chunk, s0, xs)
    y = jnp.moveaxis(yc, 0, 1).reshape(b, t, h, p)
    return y, state


def mamba_forward(p: dict, u: jax.Array, cfg, state=None, scope="mamba2"):
    """u: (B, T, D) -> (out (B, T, D), decode-ready state dict). The
    projections, conv and gated norm run under ``jax.named_scope`` of
    ``<scope>.mamba``, the chunked SSD under ``<scope>.ssd``."""
    b, t, _ = u.shape
    d_in, nheads, _ = dims(cfg)
    n = cfg.ssm_state
    with jax.named_scope(f"{scope}.mamba"):
        z, x_raw, bc_raw, dt = _project(p, u)
        x = _causal_conv(p["conv_x_w"], p["conv_x_b"], x_raw, cfg)
        bc = _causal_conv(p["conv_bc_w"], p["conv_bc_b"], bc_raw, cfg)
        bmat, cmat = bc[..., :n], bc[..., n:]
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        a = -jnp.exp(p["a_log"])
        xh = x.reshape(b, t, nheads, cfg.ssm_headdim)
    with jax.named_scope(f"{scope}.ssd"):
        y, fstate = _ssd_scan(xh, bmat, cmat, dt, a, cfg,
                              None if state is None else state["ssm"])
    with jax.named_scope(f"{scope}.mamba"):
        y = (y.astype(jnp.float32) + xh.astype(jnp.float32)
             * p["d_skip"][None, None, :, None]).astype(x.dtype)
        y = y.reshape(b, t, d_in)
        y = rms_norm(y * jax.nn.silu(z), p["gate_norm"], cfg.norm_eps)
        out = dense(p["out_proj"], y)
    return out, {"conv_x": _conv_tail(x_raw, cfg),
                 "conv_bc": _conv_tail(bc_raw, cfg), "ssm": fstate}


def init_mamba_state(cfg, batch: int):
    d_in, nheads, _ = dims(cfg)
    w1, dt = cfg.conv_width - 1, jnp.dtype(cfg.dtype)
    return {
        "conv_x": jnp.zeros((batch, w1, d_in), dt),
        "conv_bc": jnp.zeros((batch, w1, 2 * cfg.ssm_state), dt),
        "ssm": jnp.zeros((batch, nheads, cfg.ssm_headdim, cfg.ssm_state), jnp.float32),
    }


def _conv_step(w, b, window):
    """One output of the depthwise conv over the (B, W, C) window."""
    return jax.nn.silu(jnp.einsum("bwc,cw->bc", window, w.astype(window.dtype))
                       + b.astype(window.dtype))[:, None, :]


def mamba_decode(p: dict, u: jax.Array, state: dict, cfg):
    """Single-step recurrence. u: (B, 1, D). Returns (out (B,1,D), state)."""
    b = u.shape[0]
    d_in, nheads, _ = dims(cfg)
    n = cfg.ssm_state
    z, x, bc, dt = _project(p, u)
    # rolling conv states
    win_x = jnp.concatenate([state["conv_x"], x], axis=1)       # (B,W,d_in)
    win_bc = jnp.concatenate([state["conv_bc"], bc], axis=1)    # (B,W,2N)
    x = _conv_step(p["conv_x_w"], p["conv_x_b"], win_x)
    bc = _conv_step(p["conv_bc_w"], p["conv_bc_b"], win_bc)
    bmat, cmat = bc[..., :n], bc[..., n:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])[:, 0]   # (B,H)
    a = -jnp.exp(p["a_log"])
    decay = jnp.exp(dt * a[None, :])                   # (B,H)
    xh = x[:, 0].reshape(b, nheads, cfg.ssm_headdim).astype(jnp.float32)
    bn = bmat[:, 0].astype(jnp.float32)                # (B,N)
    cn = cmat[:, 0].astype(jnp.float32)
    new_ssm = decay[:, :, None, None] * state["ssm"] \
        + jnp.einsum("bhp,bn,bh->bhpn", xh, bn, dt)
    y = jnp.einsum("bn,bhpn->bhp", cn, new_ssm)        # (B,H,P)
    y = y + xh * p["d_skip"][None, :, None]
    y = y.reshape(b, 1, d_in).astype(u.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm"], cfg.norm_eps)
    return dense(p["out_proj"], y), {"conv_x": win_x[:, 1:, :],
                                     "conv_bc": win_bc[:, 1:, :],
                                     "ssm": new_ssm}
