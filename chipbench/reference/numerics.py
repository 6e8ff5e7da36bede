"""Number formats of the plain references, written from their
definitions: the tiers' (1, e, m) float grids, magnitude pruning by
log-bisection, and matmuls at the precision a configuration states
(``HIGHEST`` unless it says otherwise) or, for the controls, with every
operand rounded to float8 e4m3 under a per-tensor scale.

The tier parameters may be traced, so one compiled reference serves
every tier.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
PRECISION = {"highest": HI, "default": jax.lax.Precision.DEFAULT}
BISECT_ITERS = 16
BISECT_RANGE = 1e-12
FP8_MAX = 448.0


def _pow2(k):
    return jnp.ldexp(jnp.float32(1.0), jnp.asarray(k, jnp.int32))


def grid_max(e_bits, m_bits):
    """Largest finite value of a (1, e, m) format with no codes kept for
    inf or nan (inf where that overflows float32, as for e = 8)."""
    e_bits = jnp.asarray(e_bits, jnp.int32)
    bias = _pow2(e_bits - 1) - 1.0
    emax = _pow2(e_bits) - 1.0 - bias
    return _pow2(emax) * (2.0 - _pow2(-jnp.asarray(m_bits, jnp.int32)))


def round_to_format(x, e_bits, m_bits):
    """Round to nearest even onto the format's grid, saturating, with
    subnormals on the grid below the smallest normal exponent."""
    e_bits = jnp.asarray(e_bits, jnp.int32)
    emin = 2 - _pow2(e_bits - 1)
    maxv = grid_max(e_bits, m_bits)
    xc = jnp.clip(x, -maxv, maxv)
    _, e2 = jnp.frexp(jnp.abs(xc))
    step = _pow2(jnp.maximum(e2 - 1, emin.astype(jnp.int32))
                 - jnp.asarray(m_bits, jnp.int32))
    return jnp.round(xc / step) * step


def magnitude_mask(w, density):
    """Keep the weights whose magnitude is at least a threshold found by
    16 halvings of [max|w| * 1e-12, max|w|] in log space, each step moving
    up while more than ``density`` of the weights would be kept; a density
    of 1 keeps everything."""
    aw = jnp.abs(w)
    amax = jnp.max(aw) + 1e-30
    lo, hi = jnp.log(amax * BISECT_RANGE), jnp.log(amax)
    for _ in range(BISECT_ITERS):
        mid = 0.5 * (lo + hi)
        more = jnp.mean((aw >= jnp.exp(mid)).astype(jnp.float32)) > density
        lo, hi = jnp.where(more, mid, lo), jnp.where(more, hi, mid)
    return jnp.where(density >= 1.0, 1.0, (aw >= jnp.exp(lo))).astype(w.dtype)


def compress_leaf(w, density, e_bits, m_bits):
    """A matrix leaf as a tier holds it: (compressed weights, mask, where
    the straight-through gradient passes). ``e_bits == 0`` is no rounding.
    Vectors pass untouched."""
    if w.ndim < 2:
        return w, jnp.float32(1.0), jnp.float32(1.0)
    m = magnitude_mask(w, density)
    cw = w * m
    quant = jnp.asarray(e_bits) > 0
    inside = jnp.where(quant, jnp.abs(cw) <= grid_max(jnp.maximum(e_bits, 1),
                                                      jnp.maximum(m_bits, 1)),
                       True).astype(w.dtype)
    q = round_to_format(cw, jnp.maximum(e_bits, 1), jnp.maximum(m_bits, 1)) * m
    return jnp.where(quant, q, cw), m, inside


def compress_tree(params, density, e_bits, m_bits):
    out = jax.tree.map(lambda w: compress_leaf(w, density, e_bits, m_bits),
                       params)
    is_t = lambda t: isinstance(t, tuple)
    return tuple(jax.tree.map(lambda t: t[i], out, is_leaf=is_t)
                 for i in range(3))


def tier_scalars(plan: dict) -> tuple:
    q = plan["quant"] or (0, 0)
    return (jnp.float32(plan["density"]), jnp.int32(q[0]), jnp.int32(q[1]))


def fp8(x):
    """Round to float8 e4m3 under a per-tensor scale that maps max|x| to
    the format's largest value."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _einsum_fp8(spec, precision):
    @jax.custom_vjp
    def f(a, b):
        return jnp.einsum(spec, fp8(a), fp8(b), precision=precision)

    def fwd(a, b):
        qa, qb = fp8(a), fp8(b)
        return jnp.einsum(spec, qa, qb, precision=precision), (qa, qb)

    def bwd(res, g):
        _, vjp = jax.vjp(
            lambda a, b: jnp.einsum(spec, a, b, precision=precision), *res)
        return vjp(fp8(g))

    f.defvjp(fwd, bwd)
    return f


def mm(spec: str, a, b, quant=None, precision=HI):
    """``einsum`` at ``precision``; with ``quant="fp8"`` every operand,
    forward and backward, is rounded to scaled float8 e4m3 first (exact
    in bfloat16, so a one-pass matmul adds no rounding of its own)."""
    if quant == "fp8":
        return _einsum_fp8(spec, precision)(a, b)
    if quant is not None:
        raise ValueError(f"unknown matmul quantization {quant!r}")
    return jnp.einsum(spec, a, b, precision=precision)
