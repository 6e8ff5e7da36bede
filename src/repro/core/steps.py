"""Jittable steps implementing the paper's federated round at datacenter
simulation scale.

``make_hetero_train_step`` builds ONE SPMD program for a full heterogeneous
federated round:

  search every tier's pruning thresholds of every leaf, once, before the
  scan (all tiers compress the same global params; one read of a leaf per
  bisection step serves every pruning density, and dense tiers search
  nothing — ``compression.tier_thresholds``)
  scan over device tiers t (sequential => memory is 1 gradient + 2
  accumulators regardless of tier count):
      1. compress the global params with tier t's plan and thresholds
         (paper Fig. 1, down)
      2. compute local gradients of the COMPRESSED model on tier t's
         sub-batch (straight-through; data-parallel mean over the mesh's
         data/pod axes = averaging within the tier's client cohort)
      3. accumulate mask-aware numerator/denominator   (paper Fig. 1, up)
  then: hetero-aggregate (core.aggregation) and apply the optimizer to the
  GLOBAL (uncompressed) params.

Batches arrive shaped (n_tiers, per_tier_batch, ...); the per-tier batch is
sharded over ("pod","data"). Tier plans are traced scalar arrays, so one
compiled step serves any tier mix without retracing.

``make_serve_step`` / ``make_prefill_step`` are the inference counterparts:
they run the model AS DEPLOYED on a device (params already compressed once
via ``compress_for_serving`` — IoT devices store the compressed model; the
dry-run roofline therefore reflects pure decode cost).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.aggregation import accumulate, finalize, zeros_like_acc
from repro.core.compression import (CompressionPlan, compress_params,
                                    compress_with_masks, plan_arrays,
                                    tier_thresholds)


class TrainState:
    """Train state is a plain dict {"params", "opt", "step"} (pjit-friendly);
    this namespace only provides the constructor."""

    @staticmethod
    def create(model, optimizer, key) -> dict:
        params = model.init(key)
        return dict(params=params, opt=optimizer.init(params),
                    step=jnp.zeros((), jnp.int32))


def make_hetero_train_step(model, optimizer, plans: list[CompressionPlan],
                           *, num_groups: int = 1, acc_shardings=None):
    """acc_shardings: optional NamedSharding pytree (params-shaped). The
    mask-aware accumulators are param-sized f32; without an explicit
    constraint GSPMD may keep them data-replicated, which alone is
    2x params bytes per chip on 30B models (dry-run memory_analysis)."""
    arrs = plan_arrays(plans)
    wsum = float(sum(p.weight for p in plans))
    # compressed weights live in the model's compute dtype (§Perf: halves
    # the partitioner's cross-shard weight traffic, numerically identical)
    cdt = jnp.dtype(getattr(model.cfg, "dtype", "float32"))

    def constrain(tree):
        if acc_shardings is None:
            return tree

        def one(x, s):
            # skip rank-mismatched leaves (e.g. scalar mask denominators)
            if len(getattr(x, "shape", ())) != len(s.spec):
                return x
            return lax.with_sharding_constraint(x, s)

        return jax.tree.map(one, tree, acc_shardings)

    def train_step(state: dict, batch: dict) -> tuple[dict, dict]:
        params = state["params"]

        def tier_fn(carry, xs):
            num, den, loss_acc = carry
            plan_t, thr_t, batch_t = xs

            def loss_of(p):
                cp, masks = compress_with_masks(
                    p, plan_t["density"], plan_t["e_bits"], plan_t["m_bits"],
                    out_dtype=cdt, thresholds=thr_t)
                return model.loss_fn(cp, batch_t, num_groups=num_groups), masks

            (loss, masks), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)
            grads = constrain(grads)
            num, den = accumulate((num, den), grads, masks, plan_t["weight"])
            return (constrain(num), den, loss_acc + plan_t["weight"] * loss), None

        num0, den0 = zeros_like_acc(params)
        num0, den0 = constrain(num0), constrain(den0)
        (num, den, loss_sum), _ = lax.scan(
            tier_fn, (num0, den0, jnp.float32(0.0)),
            (arrs, tier_thresholds(params, plans), batch))
        grads = finalize((num, den))
        new_params, new_opt = optimizer.update(grads, state["opt"], params,
                                               step=state["step"])
        new_state = dict(params=new_params, opt=new_opt,
                         step=state["step"] + 1)
        return new_state, {"loss": loss_sum / wsum}

    return train_step


def make_fedsgd_train_step(model, optimizer, *, num_groups: int = 1):
    """Baseline: classic FedSGD (identical uncompressed local models) — the
    McMahan et al. [3] comparison point."""
    def train_step(state: dict, batch: dict) -> tuple[dict, dict]:
        loss, grads = jax.value_and_grad(
            lambda p: model.loss_fn(p, batch, num_groups=num_groups))(
                state["params"])
        new_params, new_opt = optimizer.update(grads, state["opt"],
                                               state["params"],
                                               step=state["step"])
        return (dict(params=new_params, opt=new_opt, step=state["step"] + 1),
                {"loss": loss})

    return train_step


def compress_for_serving(params, plan: CompressionPlan):
    """One-time compression of the global model for deployment on a tier."""
    return compress_params(params, plan)[0]


def make_serve_step(model, *, window: int = 0, num_groups: int = 1):
    def serve_step(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos,
                                 window=window, num_groups=num_groups)
    return serve_step


def make_prefill_step(model, *, window: int = 0, num_groups: int = 1):
    def prefill_step(params, batch):
        return model.prefill(params, batch, window=window,
                             num_groups=num_groups)
    return prefill_step
