"""Public wrapper: (B, T, H, hd) flash attention with GQA, padding, CPU
interpret fallback, and a custom VJP (forward = Pallas kernel; backward =
the jnp oracle's VJP — a dedicated backward kernel is the next step for
TPU training; serving/prefill only needs the forward)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_raw
from repro.kernels.flash_attention.ref import flash_attention_ref

_BQ = 128
_BK = 128


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, scale: float | None = None,
                    interpret: bool | None = None):
    """q: (B, Tq, H, hd); k, v: (B, S, Hkv, hd) -> (B, Tq, H, hd).
    ``scale`` multiplies the scores (None: 1/sqrt(hd))."""
    return _flash(q, k, v, causal, window, q_offset, scale, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, q_offset, scale, interpret):
    return _forward(q, k, v, causal, window, q_offset, scale, interpret)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "scale", "interpret"))
def _forward(q, k, v, causal: bool = True, window: int = 0,
             q_offset: int = 0, scale: float | None = None,
             interpret: bool | None = None):
    if interpret is None:
        interpret = _auto_interpret()
    b, tq, h, hd = q.shape
    s, hkv = k.shape[1], k.shape[2]
    n_rep = h // hkv

    # (B, T, H, hd) -> (B*H, T, hd) with heads grouped by kv head so the
    # kernel's b//n_rep K/V index mapping lines up
    def to_bht(x):
        return jnp.moveaxis(x, 2, 1).reshape(-1, x.shape[1], hd)

    q2 = to_bht(q)          # (B*H, Tq, hd): head-major per batch
    k2 = to_bht(k)
    v2 = to_bht(v)

    pad_q = (-tq) % _BQ
    pad_k = (-s) % _BK
    if pad_q:
        q2 = jnp.pad(q2, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k2 = jnp.pad(k2, ((0, 0), (0, pad_k), (0, 0)))
        v2 = jnp.pad(v2, ((0, 0), (0, pad_k), (0, 0)))

    o = flash_attention_raw(q2, k2, v2, n_rep=n_rep, causal=causal,
                            window=window, q_offset=q_offset, scale=scale,
                            s_valid=s, interpret=interpret)
    if pad_q:
        o = o[:, :tq, :]
    return jnp.moveaxis(o.reshape(b, h, tq, hd), 1, 2)


def _fwd(q, k, v, causal, window, q_offset, scale, interpret):
    return (_forward(q, k, v, causal, window, q_offset, scale, interpret),
            (q, k, v))


def _bwd(causal, window, q_offset, scale, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q, k, v: flash_attention_ref(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        scale=scale), q, k, v)
    return vjp(g)


_flash.defvjp(_fwd, _bwd)
