"""Properties of the mask-aware heterogeneous gradient aggregation — the
algorithm the paper poses as the open problem (§3.2)."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.aggregation import hetero_aggregate
from repro.kernels import grad_aggregate
from repro.kernels.grad_aggregate.ref import grad_aggregate_ref


def _grads(seed, t=3, shape=(8, 4)):
    ks = jax.random.split(jax.random.PRNGKey(seed), t)
    return [jax.random.normal(k, shape) for k in ks]


@settings(max_examples=50, deadline=None)
@given(st.integers(0, 2**31 - 1),
       st.lists(st.floats(0.1, 5.0), min_size=3, max_size=3))
def test_reduces_to_weighted_fedsgd_when_uncompressed(seed, ws):
    """With all-ones masks the aggregation must equal the classic weighted
    FedSGD average — the paper's baseline [3]."""
    gs = _grads(seed)
    ms = [jnp.ones_like(g) for g in gs]
    agg = hetero_aggregate([{"w": g} for g in gs], [{"w": m} for m in ms], ws)
    expect = sum(w * g for w, g in zip(ws, gs)) / sum(ws)
    np.testing.assert_allclose(np.asarray(agg["w"]), np.asarray(expect),
                               rtol=2e-5, atol=1e-6)


def test_pruned_param_gets_full_update_from_keepers():
    g1, g2 = jnp.full((4,), 2.0), jnp.full((4,), 10.0)
    m1, m2 = jnp.array([1., 1., 0., 0.]), jnp.array([1., 0., 1., 0.])
    agg = hetero_aggregate([{"w": g1}, {"w": g2}], [{"w": m1}, {"w": m2}],
                           [1.0, 1.0])
    # idx0: both kept -> mean(2,10)=6 ; idx1: only c1 -> 2 (NOT 1!)
    # idx2: only c2 -> 10 ; idx3: pruned everywhere -> 0
    assert agg["w"].tolist() == [6.0, 2.0, 10.0, 0.0]


def test_scalar_mask_broadcasts():
    gs = [{"w": jnp.ones((3,)), "b": jnp.ones(())}] * 2
    ms = [{"w": jnp.ones((3,)), "b": jnp.float32(1.0)}] * 2
    agg = hetero_aggregate(gs, ms, [1.0, 3.0])
    assert float(agg["b"]) == 1.0


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_kernel_matches_core(seed):
    t, n = 4, 600
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    g = jax.random.normal(k1, (t, n))
    m = (jax.random.uniform(k2, (t, n)) > 0.4).astype(jnp.float32)
    w = jnp.array([1.0, 0.5, 2.0, 1.5])
    core = hetero_aggregate([{"x": g[i]} for i in range(t)],
                            [{"x": m[i]} for i in range(t)],
                            [float(x) for x in w])
    kern = grad_aggregate(g, m, w)
    ref = grad_aggregate_ref(g, m, w)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(ref), atol=1e-6)
    np.testing.assert_allclose(np.asarray(core["x"]), np.asarray(ref),
                               rtol=2e-5, atol=1e-6)
