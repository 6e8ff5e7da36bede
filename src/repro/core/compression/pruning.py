"""Magnitude pruning with traced keep-density.

§Perf hillclimb #3 (EXPERIMENTS.md, iterations 1a/1b): the threshold is a
LOG-BISECTION quantile. Two rejected designs, both measured:
  - strided-sample + sort: needs ``w.reshape(-1)``, and flattening a
    tensor whose minor dim is "model"-sharded makes GSPMD all-gather the
    whole weight (~512 GB/step on qwen2.5-32b train_4k);
  - scatter-add histogram: the (2048,)-bin scatter partitions cleanly for
    some layouts but gathers the weight-sized int32 index tensor for
    others (llama3.2-3b train_4k collective 0.25 s -> 4.1 s).
Bisection uses ONLY elementwise compares + full reductions — local
partials + one scalar all-reduce per iteration on any sharding, by
construction. 16 iterations over 12 decades give ~4e-4 log resolution.

The resulting mask is still an EXACT magnitude threshold (every kept
|w| >= every dropped |w|); only the keep-fraction carries the (tiny)
quantile resolution error.

Where the search runs: ``magnitude_mask`` searches its own threshold
(the FL runtimes' ``compress_params``). The tier-scanned step
(``core/steps.py``) instead calls ``search_thresholds`` once a step,
before its scan over tiers, for every pruning density of its plans at
once: each bisection step reads a leaf once and counts for all
densities, and dense tiers search nothing. Per density the arithmetic is
``_threshold``'s, so the threshold and the exact-threshold property
above are unchanged, bit for bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

ITERS = 16
EPS = 1e-12            # dynamic range of the log search (12 decades)


def _bracket(aw):
    """The log bracket of the search over ``aw`` = |w|:
    kept-fraction(exp(lo)) ~ 1, kept-fraction(exp(hi)) ~ 0."""
    amax = jnp.max(aw) + 1e-30
    return jnp.log(amax * EPS), jnp.log(amax)


def _narrow(aw, lo, hi, density):
    """One bisection step: one counting pass over ``aw``."""
    mid = 0.5 * (lo + hi)
    kept = jnp.mean((aw >= jnp.exp(mid)).astype(jnp.float32))
    # too many kept -> raise the threshold (move lo up), else lower hi
    return jnp.where(kept > density, mid, lo), jnp.where(kept > density, hi, mid)


def _threshold(aw: jax.Array, density) -> jax.Array:
    """|w| threshold such that ~`density` fraction of weights survive."""
    def step(carry, _):
        return _narrow(aw, *carry, density), None

    (lo, hi), _ = lax.scan(step, _bracket(aw), None, length=ITERS)
    return jnp.exp(lo)            # the >=density side of the bracket


def search_thresholds(leaves, densities) -> list[jax.Array]:
    """``_threshold`` of every leaf for each of ``densities`` (static
    floats < 1), found by one search: each of its ITERS bisection steps
    reads every leaf once and counts for all densities. Each density runs
    ``_threshold``'s bracket and step, so each threshold is the same bit
    for bit. Returns one ``[len(densities)]`` array per leaf."""
    aws = [lax.stop_gradient(jnp.abs(w)) for w in leaves]
    brackets = [[_bracket(aw)] * len(densities) for aw in aws]

    def step(brackets, _):
        return [[_narrow(aw, lo, hi, d) for (lo, hi), d in zip(leaf, densities)]
                for aw, leaf in zip(aws, brackets)], None

    brackets, _ = lax.scan(step, brackets, None, length=ITERS)
    return [jnp.stack([jnp.exp(lo) for lo, _ in leaf]) for leaf in brackets]


def magnitude_mask(w: jax.Array, density, thr=None) -> jax.Array:
    """0/1 keep-mask (same dtype as w, stop-gradient), traced density OK.
    density >= 1.0 short-circuits to all-ones. ``thr``: the leaf's
    threshold for ``density`` from ``search_thresholds``; without it the
    search runs here."""
    aw = lax.stop_gradient(jnp.abs(w))  # threshold path is never differentiated
    if thr is None:
        thr = _threshold(aw, density)
    mask = jnp.where(density >= 1.0, jnp.ones_like(w), (aw >= thr).astype(w.dtype))
    return lax.stop_gradient(mask)
