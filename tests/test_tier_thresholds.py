"""The tier step's pruning thresholds, searched once a step for every tier
(``compression.tier_thresholds``), against the per-tier search they
replace: the same thresholds, masks, state and loss bit for bit, and a
tier scan whose body searches nothing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro import optim
from repro.configs import get_smoke_config
from repro.core import TrainState, make_hetero_train_step
from repro.core.aggregation import accumulate, finalize, zeros_like_acc
from repro.core.compression import (compress_with_masks, compressible,
                                    default_tier_plans, plan_arrays,
                                    tier_thresholds)
from repro.core.compression.pruning import ITERS, _threshold, search_thresholds
from repro.models import get_model

KEY = jax.random.PRNGKey(5)
PLANS = default_tier_plans(4)
DENSITIES = sorted({p.density for p in PLANS if p.density < 1.0})


@pytest.mark.parametrize("shape", [(64, 48), (3, 24, 40), (2, 16, 8, 4),
                                   (1000, 7)])
def test_search_gives_each_density_the_per_tier_threshold(shape):
    """One search over several leaves, a stacked [layers, d, f] leaf among
    them, gives every leaf and density ``_threshold``'s value."""
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, len(shape)))
    leaves = [jax.random.normal(k1, shape),
              # heavy tails and exact zeros, as pruned weights have
              jax.random.cauchy(k2, shape) * (jax.random.uniform(k2, shape)
                                              > 0.3)]
    got = jax.jit(lambda ws: search_thresholds(ws, DENSITIES))(leaves)
    one = jax.jit(_threshold)
    for w, thr in zip(leaves, got):
        assert thr.shape == (len(DENSITIES),)
        want = [one(jnp.abs(w), jnp.float32(d)) for d in DENSITIES]
        np.testing.assert_array_equal(np.asarray(thr), np.asarray(want))


def _per_tier_step(model, optimizer, plans):
    """The tier step as it was before the hoist: every tier searches its own
    thresholds inside the scan (``compress_with_masks`` without them)."""
    arrs = plan_arrays(plans)
    wsum = float(sum(p.weight for p in plans))
    cdt = jnp.dtype(model.cfg.dtype)

    def train_step(state, batch):
        params = state["params"]

        def tier_fn(carry, xs):
            num, den, loss_acc = carry
            plan_t, batch_t = xs

            def loss_of(p):
                cp, masks = compress_with_masks(
                    p, plan_t["density"], plan_t["e_bits"], plan_t["m_bits"],
                    out_dtype=cdt)
                return model.loss_fn(cp, batch_t), masks

            (loss, masks), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)
            num, den = accumulate((num, den), grads, masks, plan_t["weight"])
            return (num, den, loss_acc + plan_t["weight"] * loss), None

        (num, den, loss_sum), _ = lax.scan(
            tier_fn, (*zeros_like_acc(params), jnp.float32(0.0)),
            (arrs, batch))
        new_params, new_opt = optimizer.update(finalize((num, den)),
                                               state["opt"], params,
                                               step=state["step"])
        return (dict(params=new_params, opt=new_opt, step=state["step"] + 1),
                {"loss": loss_sum / wsum})

    return train_step


def _smoke(arch):
    cfg = get_smoke_config(arch)
    if arch == "granite-4.0-h-micro":
        cfg = cfg.replace(num_layers=4,
                          layer_types=("mamba", "mamba", "attention", "mamba"))
    return cfg


def _setup(arch):
    cfg = _smoke(arch)
    model = get_model(cfg)
    opt = optim.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    state = TrainState.create(model, opt, KEY)
    batch = {"tokens": jax.random.randint(jax.random.fold_in(KEY, 1),
                                          (len(PLANS), 2, 33), 0,
                                          cfg.vocab_size)}
    return model, opt, state, batch


@pytest.mark.parametrize("arch", ["granite-3-2b", "granite-4.0-h-micro"])
def test_hoisted_step_equals_per_tier_step_bit_for_bit(arch):
    model, opt, state, batch = _setup(arch)
    new, m_new = jax.jit(make_hetero_train_step(model, opt, PLANS))(state,
                                                                    batch)
    old, m_old = jax.jit(_per_tier_step(model, opt, PLANS))(state, batch)
    np.testing.assert_array_equal(np.asarray(m_new["loss"]),
                                  np.asarray(m_old["loss"]))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(new)[0],
                            jax.tree.leaves(old)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))


def test_each_tier_masks_as_its_own_search_would():
    """Every tier's masks from its slice of ``tier_thresholds`` are those
    ``compress_with_masks`` finds by searching on its own."""
    model, _, state, _ = _setup("granite-3-2b")
    p = state["params"]
    thr = jax.jit(lambda p: tier_thresholds(p, PLANS))(p)
    arrs = plan_arrays(PLANS)
    for t, plan in enumerate(PLANS):
        a = jax.tree.map(lambda x: x[t], arrs)
        thr_t = jax.tree.map(lambda x: x[t], thr)
        got = compress_with_masks(p, a["density"], a["e_bits"], a["m_bits"],
                                  thresholds=thr_t)
        want = compress_with_masks(p, a["density"], a["e_bits"], a["m_bits"])
        for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=plan.name)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _leaf_maxes(eqns):
    """Maxes over every axis of an operand of rank two or more: the
    search's ``max|w|``, and the int quantizer's scale of each masked
    leaf (the model's own maxes reduce one axis of activations)."""
    return len([e for e in eqns if e.primitive.name == "reduce_max"
                and len(e.invars[0].aval.shape) >= 2
                and len(e.params["axes"]) == len(e.invars[0].aval.shape)])


def _searches(eqns):
    return len([e for e in eqns
                if e.primitive.name == "scan" and e.params["length"] == ITERS])


def _outside_and_inside_tier_scan(step, state, batch):
    jaxpr = jax.make_jaxpr(step)(state, batch).jaxpr
    tier_scan, = [e for e in jaxpr.eqns if e.primitive.name == "scan"
                  and e.params["length"] == len(PLANS)]
    return jaxpr.eqns, list(_eqns(tier_scan.params["jaxpr"].jaxpr))


def test_tier_scan_body_searches_nothing():
    """The scan over tiers holds no ITERS-step search and none of its
    ``max|w|``: the step takes ``max|w|`` once per compressible leaf,
    before the scan, where the per-tier step took it once per leaf in
    every tier."""
    model, opt, state, batch = _setup("granite-3-2b")
    n_leaves = sum(compressible(path, w) for path, w in
                   jax.tree_util.tree_flatten_with_path(state["params"])[0])
    top, body = _outside_and_inside_tier_scan(
        make_hetero_train_step(model, opt, PLANS), state, batch)
    old_top, old_body = _outside_and_inside_tier_scan(
        _per_tier_step(model, opt, PLANS), state, batch)
    assert (_searches(old_body), _searches(old_top)) == (n_leaves, 0)
    assert (_searches(body), _searches(top)) == (0, 1)
    assert (_leaf_maxes(old_body), _leaf_maxes(old_top)) == (2 * n_leaves, 0)
    assert (_leaf_maxes(body), _leaf_maxes(top)) == (n_leaves, n_leaves)
