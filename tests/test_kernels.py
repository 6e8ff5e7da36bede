"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs the
ref.py pure-jnp oracle (interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import (codebook_matmul, fake_quant, grad_aggregate,
                           masked_matmul, structured_scatter)
from repro.kernels.codebook_matmul.ref import codebook_matmul_ref
from repro.kernels.fake_quant.ref import fake_quant_ref
from repro.kernels.grad_aggregate.ref import grad_aggregate_ref
from repro.kernels.masked_matmul.ref import masked_matmul_ref
from repro.kernels.structured_scatter.ops import structured_scatter_batched
from repro.kernels.structured_scatter.ref import structured_scatter_ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.slow
@pytest.mark.parametrize("shape", [(16,), (100, 37), (8, 16, 32), (1, 1),
                                   (999,), (256, 512)])
@pytest.mark.parametrize("em", [(4, 3), (5, 2), (8, 7), (5, 10), (2, 1),
                                (3, 2)])
def test_fake_quant_sweep(shape, em):
    x = jax.random.normal(KEY, shape) * 7
    q = fake_quant(x, *em)
    r = fake_quant_ref(x, *em)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(r))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fake_quant_dtypes(dtype):
    x = (jax.random.normal(KEY, (64, 64)) * 3).astype(dtype)
    q = fake_quant(x, 4, 3)
    assert q.dtype == dtype
    r = fake_quant_ref(x.astype(jnp.float32), 4, 3).astype(dtype)
    np.testing.assert_array_equal(np.asarray(q, np.float32),
                                  np.asarray(r, np.float32))


def test_fake_quant_grad_is_clip_aware_ste():
    x = jnp.array([0.5, 1e6, -1e6])
    g = jax.grad(lambda v: fake_quant(v, 4, 3).sum())(x)
    assert g.tolist() == [1.0, 0.0, 0.0]


@pytest.mark.slow
@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (64, 200, 96),
                                   (1, 128, 128), (130, 257, 129),
                                   (256, 384, 512)])
def test_masked_matmul_sweep(m, k, n):
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (m, k))
    w = jax.random.normal(ks[1], (k, n))
    mask = (jax.random.uniform(ks[2], (k, n)) > 0.5).astype(jnp.float32)
    y = masked_matmul(x, w, mask)
    r = masked_matmul_ref(x, w, mask)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r),
                               rtol=1e-4, atol=1e-4 * k ** 0.5)


@pytest.mark.slow
def test_masked_matmul_grads_match_ref():
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (32, 64))
    w = jax.random.normal(ks[1], (64, 48))
    mask = (jax.random.uniform(ks[2], (64, 48)) > 0.3).astype(jnp.float32)

    def f(fn):
        return jax.grad(lambda x, w: (fn(x, w, mask) ** 2).sum(), (0, 1))(x, w)

    (gx, gw), (rx, rw) = f(masked_matmul), f(masked_matmul_ref)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), rtol=1e-3,
                               atol=1e-2)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), rtol=1e-3,
                               atol=1e-2)
    # gradient respects the mask: pruned entries get zero
    assert bool(jnp.all(jnp.where(mask == 0, gw == 0, True)))


@pytest.mark.slow
@pytest.mark.parametrize("m,k,n,codes", [(64, 128, 64, 16), (128, 256, 128, 4),
                                         (32, 100, 60, 256), (1, 128, 128, 2)])
def test_codebook_matmul_sweep(m, k, n, codes):
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (m, k))
    idx = jax.random.randint(ks[1], (k, n), 0, codes)
    cb = jnp.sort(jax.random.normal(ks[2], (codes,)))
    y = codebook_matmul(x, idx, cb)
    r = codebook_matmul_ref(x, idx, cb)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r),
                               rtol=1e-4, atol=1e-3)


def test_codebook_matmul_int8_indices():
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (16, 128))
    idx = jax.random.randint(ks[1], (128, 64), 0, 16).astype(jnp.int8)
    cb = jax.random.normal(ks[2], (16,))
    np.testing.assert_allclose(
        np.asarray(codebook_matmul(x, idx, cb)),
        np.asarray(codebook_matmul_ref(x, idx, cb)), rtol=1e-4, atol=1e-3)


@pytest.mark.slow
@pytest.mark.parametrize("t,n", [(2, 100), (4, 4096), (8, 1 << 15), (1, 7)])
def test_grad_aggregate_sweep(t, n):
    ks = jax.random.split(KEY, 2)
    g = jax.random.normal(ks[0], (t, n))
    m = (jax.random.uniform(ks[1], (t, n)) > 0.4).astype(jnp.float32)
    w = jnp.linspace(0.5, 2.0, t)
    np.testing.assert_allclose(np.asarray(grad_aggregate(g, m, w)),
                               np.asarray(grad_aggregate_ref(g, m, w)),
                               rtol=1e-5, atol=1e-6)


def test_grad_aggregate_all_pruned_is_zero():
    g = jnp.ones((3, 16))
    m = jnp.zeros((3, 16))
    out = grad_aggregate(g, m, jnp.ones((3,)))
    assert bool(jnp.all(out == 0.0))


@pytest.mark.parametrize("n", [999, 1500, 2049])
def test_grad_aggregate_padded_tail(n):
    """n % 1024 != 0 exercises ops.py's zero-pad + unpad path: the padded
    tail (mask 0, den 0 -> output 0) must be sliced off exactly."""
    ks = jax.random.split(KEY, 2)
    g = jax.random.normal(ks[0], (3, n))
    m = (jax.random.uniform(ks[1], (3, n)) > 0.4).astype(jnp.float32)
    w = jnp.linspace(0.5, 2.0, 3)
    out = grad_aggregate(g, m, w)
    assert out.shape == (n,)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(grad_aggregate_ref(g, m, w)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape,mshape", [
    ((4, 2048), (4, 1)),            # scalar per-tier mask (1-D param leaves)
    ((4, 1500), (4, 1)),            # broadcast + padded tail combined
    ((3, 37, 41), (3, 1, 1)),       # nd leaf, scalar mask, padded
    ((2, 16, 64), (2, 16, 64)),     # nd leaf, full mask (flatten path)
])
def test_grad_aggregate_broadcast_mask(shape, mshape):
    """m.size != g.size takes ops.py's broadcast branch (per-tier scalar
    masks, the den shape zeros_like_acc gives ndim<2 leaves)."""
    ks = jax.random.split(KEY, 2)
    g = jax.random.normal(ks[0], shape)
    m = (jax.random.uniform(ks[1], mshape) > 0.3).astype(jnp.float32)
    w = jnp.linspace(0.5, 2.0, shape[0])
    out = grad_aggregate(g, m, w)
    assert out.shape == shape[1:]
    t = shape[0]
    mb = jnp.broadcast_to(m, shape).reshape(t, -1)
    ref = grad_aggregate_ref(g.reshape(t, -1), mb, w).reshape(shape[1:])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


# --------------------------- grad_aggregate pad-path property tests

@settings(max_examples=20, deadline=None)
@given(st.integers(1, 3000), st.integers(1, 6), st.booleans())
def test_grad_aggregate_pad_path_roundtrips_any_size(n, t, scalar_mask):
    """Property: for ANY leaf size (odd n exercises the ``(-n) % 1024``
    zero-pad + unpad path) and broadcast or full masks, grad_aggregate
    returns exactly shape (n,) matching the unpadded oracle — the padded
    tail never leaks into ``out[:n]``."""
    kg, km = jax.random.split(jax.random.fold_in(KEY, n * 7 + t), 2)
    g = jax.random.normal(kg, (t, n))
    mshape = (t, 1) if scalar_mask else (t, n)
    m = (jax.random.uniform(km, mshape) > 0.4).astype(jnp.float32)
    w = jnp.linspace(0.5, 2.0, t)
    out = grad_aggregate(g, m, w)
    assert out.shape == (n,)
    ref = grad_aggregate_ref(g, jnp.broadcast_to(m, (t, n)), w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 2047))
def test_grad_aggregate_padded_tail_is_exact_zero(n):
    """The pad's correctness mechanism, observed directly on the raw
    kernel: zero-padded coordinates carry mask 0, so their denominator
    is 0, the ``max(den, eps)`` guard kicks in, and ``0 / eps`` is an
    EXACT 0.0 — which is why ``out[:n]`` can slice the pad off without
    any masking arithmetic."""
    from repro.kernels.grad_aggregate.kernel import grad_aggregate_raw
    pad = (-n) % 1024
    kg, km = jax.random.split(jax.random.fold_in(KEY, n), 2)
    g = jnp.pad(jax.random.normal(kg, (3, n)), ((0, 0), (0, pad)))
    m = jnp.pad((jax.random.uniform(km, (3, n)) > 0.4).astype(jnp.float32),
                ((0, 0), (0, pad)))
    w = jnp.linspace(0.5, 2.0, 3).reshape(3, 1)
    out = grad_aggregate_raw(g, m, w, None, eps=1e-8, interpret=True)[0]
    assert out.shape == (n + pad,)
    tail = np.asarray(out[n:])
    assert (tail == 0.0).all()                  # exact zeros, not just small
    np.testing.assert_allclose(
        np.asarray(out[:n]),
        np.asarray(grad_aggregate_ref(g[:, :n], m[:, :n],
                                      jnp.linspace(0.5, 2.0, 3))),
        rtol=1e-5, atol=1e-6)


# ------------------------------------------ structured_scatter kernel

def _prefix_cases():
    """(global shape, per-tier local shapes): SubmodelSpec-style only —
    slicing touches the FIRST and LAST axes, mid axes stay full-size
    (the kernel's prefix-block precondition)."""
    return [
        ((10, 10), [(10, 10), (5, 5), (3, 3)]),          # paper-MLP hidden
        ((5, 10), [(5, 10), (5, 5), (5, 3)]),            # input layer
        ((10,), [(10,), (5,), (3,)]),                    # co-sliced bias
        ((2, 6, 4), [(2, 6, 4), (1, 6, 2)]),             # 3-D, first+last
        ((37, 129), [(37, 129), (19, 65)]),              # odd, multi-block
        ((16, 16), [(16, 16), (16, 16)]),                # all tiers full
    ]


def _tiers(out_shape, locals_, seed=0, scalar_masks=False):
    k = jax.random.fold_in(KEY, seed)
    gs, ms = [], []
    for i, loc in enumerate(locals_):
        k, kg, km = jax.random.split(k, 3)
        gs.append(jax.random.normal(kg, loc))
        if scalar_masks:
            ms.append(jnp.float32(i % 2))               # exact 0/1 only
        else:
            ms.append((jax.random.uniform(km, loc) > 0.3)
                      .astype(jnp.float32))
    w = jnp.linspace(0.5, 2.0, len(locals_))
    wd = w * jnp.arange(1.0, len(locals_) + 1.0)        # w·n_participants
    return gs, ms, w, wd


@pytest.mark.parametrize("case", range(6))
@pytest.mark.parametrize("scalar_masks", [False, True])
def test_structured_scatter_bitwise_vs_ref(case, scalar_masks):
    """The tentpole's acceptance bar: the fused kernel is BITWISE the
    scatter_accumulate -> finalize chain, for array and scalar 0/1
    masks, full and sliced tiers, 1-D/2-D/3-D leaves, w_den columns.
    (The contract requires exact 0/1 masks — that is what makes the
    kernel's FMA-contracted adds bit-transparent.)"""
    out_shape, locals_ = _prefix_cases()[case]
    gs, ms, w, wd = _tiers(out_shape, locals_, seed=case,
                           scalar_masks=scalar_masks)
    out = structured_scatter(gs, ms, w, wd, out_shape=out_shape)
    ref = structured_scatter_ref(gs, ms, w, wd, out_shape=out_shape)
    assert out.shape == tuple(out_shape) and out.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_structured_scatter_uncovered_coords_are_exact_zero():
    """Coordinates no tier covers have den == 0: the max(den, eps) guard
    turns them into EXACT 0.0 (the same mechanism the pad path uses)."""
    gs, ms, w, wd = _tiers((10, 10), [(4, 4), (6, 2)], seed=9)
    out = np.asarray(structured_scatter(gs, ms, w, wd,
                                        out_shape=(10, 10)))
    assert (out[6:, :] == 0.0).all() and (out[:, 4:] == 0.0).all()
    assert out[:4, :4].any()                     # covered region is live


def test_structured_scatter_default_wden_and_unsorted_tiers():
    """w_den defaults to w, and tier ORDER (not size-sortedness) fixes
    the accumulation sequence — shuffled tiers match the ref shuffled
    the same way, bitwise."""
    out_shape, locals_ = (10, 10), [(3, 3), (10, 10), (5, 5)]
    gs, ms, w, _ = _tiers(out_shape, locals_, seed=3)
    out = structured_scatter(gs, ms, w, out_shape=out_shape)
    ref = structured_scatter_ref(gs, ms, w, out_shape=out_shape)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_structured_scatter_gridded_path_matches_whole():
    """The TPU-shaped tiled wrapper (block quanta, zero-padding) must
    agree bitwise with the gridless whole-leaf call and the oracle — run
    in interpret mode; this leaf fits one block (the multi-step grid is
    covered below)."""
    from repro.kernels.structured_scatter import ops as ss_ops
    out_shape, locals_ = (37, 300), [(37, 300), (19, 140), (7, 65)]
    gs, ms, w, wd = _tiers(out_shape, locals_, seed=5)
    ref = structured_scatter_ref(gs, ms, w, wd, out_shape=out_shape)
    tiled = ss_ops._scatter_tiled(
        gs, ms, jnp.asarray(w, jnp.float32).reshape(-1, 1),
        jnp.asarray(wd, jnp.float32).reshape(-1, 1),
        rows=37, cols=300, out_shape=out_shape, eps=1e-8, interpret=True)
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(ref))
    whole = structured_scatter(gs, ms, w, wd, out_shape=out_shape,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(ref))


@pytest.mark.parametrize("n_tiers", [1, 2, 3, 4, 6, 8, 16])
def test_structured_scatter_block_fits_scoped_vmem(n_tiers):
    """A grid step's double-buffered 2·T+1 blocks plus the body's two
    accumulators stay within the VMEM budget, on the (8, 128) tile
    quanta; small leaves keep their one exact block."""
    from repro.kernels.structured_scatter import ops as ss_ops
    for rows, cols in ((4096, 14336), (2048, 2048), (49155, 2048),
                       (1, 2048)):
        br, bc = ss_ops._block_shape(rows, cols, n_tiers)
        assert br % 8 == 0 and bc % 128 == 0
        assert ((2 * (2 * n_tiers + 1) + 2) * br * bc * 4
                <= ss_ops._VMEM_BUDGET)
    assert ss_ops._block_shape(10, 10, n_tiers) == (16, 128)
    assert ss_ops._block_shape(256, 1024, 2) == (256, 1024)


def test_structured_scatter_gridded_multi_step_bitwise():
    """Three tiers on a leaf wider than one block: the budget halves the
    block to (128, 1024), so the grid has 3 x 2 steps with clamped index
    maps and gated partial tiers — still bitwise the oracle."""
    from repro.kernels.structured_scatter import ops as ss_ops
    out_shape, locals_ = (264, 1100), [(264, 1100), (132, 550), (66, 275)]
    assert ss_ops._block_shape(*out_shape, len(locals_)) == (128, 1024)
    gs, ms, w, wd = _tiers(out_shape, locals_, seed=7)
    ref = structured_scatter_ref(gs, ms, w, wd, out_shape=out_shape)
    tiled = ss_ops._scatter_tiled(
        gs, ms, jnp.asarray(w, jnp.float32).reshape(-1, 1),
        jnp.asarray(wd, jnp.float32).reshape(-1, 1),
        rows=264, cols=1100, out_shape=out_shape, eps=1e-8,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(ref))


@pytest.mark.parametrize("out_shape,locals_,scalar_masks", [
    ((10, 10), [(10, 10), (5, 5), (3, 3)], False),
    ((10, 10), [(10, 10), (5, 5), (3, 3)], True),
    ((10,), [(10,), (5,), (3,)], True),          # 1-D bias group
])
def test_structured_scatter_batched_bitwise_per_leaf(out_shape, locals_,
                                                     scalar_masks):
    """structured_scatter_batched stacks L same-shaped leaves into ONE
    kernel call (the engine's op-count win); every slice of the result
    must be bitwise the per-leaf call and the oracle."""
    L = 4
    per = [_tiers(out_shape, locals_, seed=20 + i,
                  scalar_masks=scalar_masks) for i in range(L)]
    w, wd = per[0][2], per[0][3]
    gs = [jnp.stack([per[i][0][t] for i in range(L)])
          for t in range(len(locals_))]
    ms = [jnp.stack([jnp.asarray(per[i][1][t]) for i in range(L)])
          for t in range(len(locals_))]
    res = structured_scatter_batched(gs, ms, w, wd, out_shape=out_shape)
    assert res.shape == (L,) + tuple(out_shape)
    for i in range(L):
        one = structured_scatter(per[i][0], per[i][1], w, wd,
                                 out_shape=out_shape)
        ref = structured_scatter_ref(per[i][0], per[i][1], w, wd,
                                     out_shape=out_shape)
        np.testing.assert_array_equal(np.asarray(res[i]), np.asarray(one))
        np.testing.assert_array_equal(np.asarray(res[i]), np.asarray(ref))
