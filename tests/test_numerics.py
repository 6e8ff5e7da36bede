"""Bit-level validation of the arbitrary-(e,m) float simulation (§7.1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.numerics import FORMATS, max_finite, quantize_em
from repro.numerics.float_formats import quantize_int


def _rand(key, n=4096, scale=8.0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(key))
    return jax.random.normal(k1, (n,)) * jnp.exp(
        jax.random.normal(k2, (n,)) * scale)


def test_bf16_bit_exact():
    x = _rand(0)
    q = quantize_em(x, 8, 7)
    ref = x.astype(jnp.bfloat16).astype(jnp.float32)
    ok = jnp.isfinite(ref)  # ref overflows to inf where we saturate
    assert bool(jnp.all(jnp.where(ok, q == ref, True)))


def test_fp16_bit_exact_in_range():
    x = _rand(1, scale=3.0)
    q = quantize_em(x, 5, 10)
    ref = x.astype(jnp.float16).astype(jnp.float32)
    in_range = jnp.abs(x) < 65504 * (1 - 2**-11)
    assert bool(jnp.all(jnp.where(in_range, q == ref, True)))


def test_saturation():
    _, maxv = 0, max_finite(4, 3)
    assert float(quantize_em(jnp.float32(1e9), 4, 3)) == float(maxv)
    assert float(quantize_em(jnp.float32(-1e9), 4, 3)) == -float(maxv)


def test_fp8_e4m3_values():
    # spot-check known e4m3 (no inf/nan reservation in our variant) values
    assert float(quantize_em(jnp.float32(1.0), 4, 3)) == 1.0
    assert float(quantize_em(jnp.float32(0.0), 4, 3)) == 0.0
    # quantum at 1.0 <= x < 2.0 is 1/8
    assert float(quantize_em(jnp.float32(1.06), 4, 3)) == 1.0
    assert float(quantize_em(jnp.float32(1.07), 4, 3)) == 1.125
    # subnormal grid: emin = -6, quantum 2^-9
    assert float(quantize_em(jnp.float32(2.0**-9), 4, 3)) == 2.0**-9
    assert float(quantize_em(jnp.float32(2.0**-11), 4, 3)) == 0.0


@settings(max_examples=200, deadline=None)
@given(st.integers(2, 8), st.integers(1, 10), st.integers(0, 2**31 - 1))
def test_idempotent(e, m, seed):
    x = _rand(seed, n=256)
    q = quantize_em(x, e, m)
    assert bool(jnp.all(quantize_em(q, e, m) == q))


@settings(max_examples=100, deadline=None)
@given(st.integers(2, 8), st.integers(1, 10), st.integers(0, 2**31 - 1))
def test_monotone_and_bounded_error(e, m, seed):
    x = jnp.sort(_rand(seed, n=256, scale=2.0))
    q = quantize_em(x, e, m)
    assert bool(jnp.all(jnp.diff(q) >= 0)), "rounding must be monotone"
    # in-range relative error bounded by half ulp = 2^-(m+1)
    maxv = max_finite(e, m)
    inr = (jnp.abs(x) <= maxv) & (jnp.abs(x) >= 2.0 ** (2 - 2 ** (e - 1)))
    rel = jnp.abs(q - x) / jnp.maximum(jnp.abs(x), 1e-30)
    assert bool(jnp.all(jnp.where(inr, rel <= 2.0 ** (-m - 1) + 1e-7, True)))


def test_dynamic_bits_match_static():
    x = _rand(3, n=512)
    for name, f in FORMATS.items():
        qs = quantize_em(x, f.e_bits, f.m_bits)
        qd = quantize_em(x, jnp.int32(f.e_bits), jnp.int32(f.m_bits))
        assert bool(jnp.all(qs == qd)), name


def test_int_quant():
    x = jnp.array([-1.0, -0.5, 0.0, 0.26, 1.0])
    q = quantize_int(x, 8)
    assert float(jnp.max(jnp.abs(q - x))) <= 1.0 / 127 + 1e-6
    q4 = quantize_int(x, 4)
    assert len(np.unique(np.asarray(jnp.abs(q4)))) <= 8
