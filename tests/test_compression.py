"""Compression suite: pruning / quantization-STE / clustering / plans."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.compression import (CompressionPlan, DEVICE_TIERS,
                                    compress_params, compress_with_masks,
                                    kmeans_codebook, cluster_ste,
                                    magnitude_mask, payload_bits, plan_arrays)
from repro.core.compression.quantization import fake_quant_ste


@settings(max_examples=50, deadline=None)
@given(st.floats(0.1, 1.0), st.integers(0, 2**31 - 1))
def test_mask_density(density, seed):
    w = jax.random.normal(jax.random.PRNGKey(seed), (128, 64))
    m = magnitude_mask(w, density)
    got = float(m.mean())
    assert abs(got - density) < 0.06 or density >= 1.0


def test_mask_is_magnitude_threshold():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    m = np.asarray(magnitude_mask(w, 0.5))
    aw = np.abs(np.asarray(w))
    kept, dropped = aw[m == 1], aw[m == 0]
    assert kept.min() >= dropped.max() - 1e-7


def test_mask_full_density_is_ones():
    w = jax.random.normal(jax.random.PRNGKey(0), (32, 32))
    assert bool(jnp.all(magnitude_mask(w, 1.0) == 1.0))


def test_ste_gradient_identity_in_range():
    x = jnp.linspace(-2, 2, 101)
    g = jax.grad(lambda x: fake_quant_ste(x, 4, 3).sum())(x)
    assert bool(jnp.all(g == 1.0))  # max e4m3 = 448, all in range


def test_ste_gradient_zero_out_of_range():
    x = jnp.array([1e6, -1e6, 1.0])
    g = jax.grad(lambda x: fake_quant_ste(x, 4, 3).sum())(x)
    assert g.tolist() == [0.0, 0.0, 1.0]


def test_cluster_values_in_codebook():
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    cw = cluster_ste(w, 16)
    cb = kmeans_codebook(w, 16)
    dif = jnp.min(jnp.abs(cw[..., None] - cb[None, None, :]), axis=-1)
    assert float(jnp.max(dif)) < 1e-6
    assert len(np.unique(np.asarray(cw))) <= 16


def test_cluster_ste_grad():
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
    g = jax.grad(lambda w: cluster_ste(w, 8).sum())(w)
    assert bool(jnp.all(g == 1.0))


def test_kmeans_reduces_distortion():
    w = jax.random.normal(jax.random.PRNGKey(2), (4096,))
    cb8 = kmeans_codebook(w, 8)
    cb64 = kmeans_codebook(w, 64)

    def dist(cb):
        return float(jnp.mean(jnp.min(jnp.abs(w[:, None] - cb), axis=1) ** 2))

    assert dist(cb64) < dist(cb8)


def _params():
    k = jax.random.PRNGKey(0)
    return {"layers": {"attn": {"wq": {"w": jax.random.normal(k, (32, 16))}},
                       "ln1": jnp.ones((32,)),
                       "moe": {"router": {"w": jax.random.normal(k, (32, 4))}}}}


def test_policy_excludes_1d_and_router():
    p = _params()
    cp, masks = compress_params(p, CompressionPlan("x", density=0.5,
                                                   quant="fp8_e4m3"))
    assert bool(jnp.all(cp["layers"]["ln1"] == p["layers"]["ln1"]))
    assert bool(jnp.all(cp["layers"]["moe"]["router"]["w"]
                        == p["layers"]["moe"]["router"]["w"]))
    # wq compressed: ~half zeros
    zeros = float((cp["layers"]["attn"]["wq"]["w"] == 0).mean())
    assert 0.4 < zeros < 0.6
    assert masks["layers"]["ln1"].shape == ()


def test_traced_matches_static_prune_quant():
    p = _params()
    plan = CompressionPlan("x", density=0.5, quant="fp8_e4m3")
    cp_s, m_s = compress_params(p, plan)
    e, m = plan.quant_em()
    cp_t, m_t = compress_with_masks(p, jnp.float32(0.5), jnp.int32(e),
                                    jnp.int32(m))
    for a, b in zip(jax.tree.leaves(cp_s), jax.tree.leaves(cp_t)):
        assert bool(jnp.all(a == b))


def test_payload_bits_ordering():
    p = _params()
    sizes = [payload_bits(p, DEVICE_TIERS[t])
             for t in ("hub", "high", "mid", "low", "embedded")]
    assert sizes == sorted(sizes, reverse=True), sizes


def test_payload_bits_excluded_leaves_ship_fp32():
    """The excluded-leaf path: 1-D scales and the router always count at
    32 bits regardless of the plan's density/quant — only the
    compressible wq leaf scales."""
    p = _params()
    n_wq = p["layers"]["attn"]["wq"]["w"].size
    n_excl = p["layers"]["ln1"].size + p["layers"]["moe"]["router"]["w"].size
    plan = CompressionPlan("x", density=0.5, quant="fp8_e4m3")
    assert payload_bits(p, plan) == n_wq * 0.5 * 8 + n_excl * 32
    # at full density / no quant everything is fp32
    assert payload_bits(p, CompressionPlan("hub")) == (n_wq + n_excl) * 32


def test_payload_bits_clustering_codebook_overhead():
    """Clustered plans ship log2(k) bits per kept weight PLUS one
    k-entry fp32 codebook per compressible leaf; excluded leaves pay
    neither."""
    p = _params()
    n_wq = p["layers"]["attn"]["wq"]["w"].size
    n_excl = p["layers"]["ln1"].size + p["layers"]["moe"]["router"]["w"].size
    plan = CompressionPlan("c", density=0.5, cluster_k=16)
    expect = n_wq * 0.5 * 4 + 16 * 32 + n_excl * 32    # log2(16)=4 bits
    assert payload_bits(p, plan) == expect
    # codebook overhead is per compressible leaf: a second matrix leaf
    # adds its own 16-entry codebook
    p2 = dict(p)
    p2["extra"] = {"w": jnp.zeros((8, 8))}
    assert payload_bits(p2, plan) == expect + 64 * 0.5 * 4 + 16 * 32


def test_plan_arrays_shapes():
    arrs = plan_arrays([DEVICE_TIERS["hub"], DEVICE_TIERS["low"]])
    assert arrs["density"].shape == (2,)
    assert arrs["density"].tolist() == [1.0, 0.25]
    assert arrs["e_bits"].tolist() == [0, 5]
