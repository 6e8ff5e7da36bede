"""Apply a compression plan to a whole parameter pytree.

Policy (``compressible``, defined in ``structured.py`` and shared with
the width-slicing path): only matrix-shaped leaves (ndim >= 2) are
compressed; 1-D leaves (norm scales, gates, biases, SSM dt/A parameters —
quantization-sensitive) and the MoE router (load-balance stability) stay
full precision. This is the standard practice the paper's framework would
expose as configuration.

Two entry points:
  - ``compress_with_masks(params, density, e_bits, m_bits)``: traced per-tier
    scalars, prune+quant only — used by the tier-scanned datacenter step,
    which searches every tier's pruning thresholds once a step with
    ``tier_thresholds`` and hands each tier its own.
  - ``compress_params(params, plan)``: static CompressionPlan, adds k-means
    clustering and structured width slicing — used by the FL runtimes.

Shape contract of ``compress_params`` for STRUCTURED plans (DESIGN.md
§13): the returned ``cparams`` live at the LOCAL (sliced) shapes — the
device genuinely trains a smaller dense model — while ``masks`` stay at
GLOBAL shapes, naming exactly which global coordinates the tier's update
covers (zero-padded inner mask for sliced matrices, prefix coverage
vectors for co-sliced biases). Unstructured plans keep the historical
contract: cparams and masks both full-shape.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.compression.clustering import cluster_ste
from repro.core.compression.plan import CompressionPlan
from repro.core.compression.pruning import magnitude_mask, search_thresholds
from repro.core.compression.quantization import fake_quant_ste
from repro.core.compression.structured import (compressible, expand_masks,
                                               slice_tree, submodel_spec)

__all__ = ["compressible", "compress_with_masks", "compress_params",
           "tier_thresholds", "payload_bits", "active_param_count"]


def tier_thresholds(params, plans: list[CompressionPlan]):
    """Every tier's pruning threshold of every compressible leaf, searched
    once for all tiers (``search_thresholds``): a params-shaped tree with
    a ``[len(plans)]`` array at each compressible leaf and None elsewhere.
    Only the distinct densities below 1 are searched; a tier of density
    >= 1 gets 0, which its all-ones mask ignores. The
    ``tier_compression.thresholds`` scope names the search."""
    densities = sorted({p.density for p in plans if p.density < 1.0})
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    pruned = [i for i, (path, w) in enumerate(flat) if compressible(path, w)]
    with jax.named_scope("tier_compression.thresholds"):
        found = (search_thresholds([flat[i][1] for i in pruned], densities)
                 if densities else [None] * len(pruned))
        out = [None] * len(flat)
        for i, thr in zip(pruned, found):
            out[i] = jnp.stack([thr[densities.index(p.density)]
                                if p.density < 1.0 else jnp.float32(0.0)
                                for p in plans])
    return jax.tree_util.tree_unflatten(treedef, out)


def compress_with_masks(params, density, e_bits, m_bits, out_dtype=None,
                        thresholds=None):
    """Traced-scalar compression (prune -> fake-quant, both STE).

    Returns (compressed_params, masks) where masks has a full-size 0/1 leaf
    for compressible params and a scalar 1.0 for excluded ones (so the
    mask-aware aggregation denominators broadcast correctly).

    out_dtype (§Perf): casting compressed weights to the model's compute
    dtype HERE is numerically identical to the cast the matmuls do anyway,
    but halves the bytes of every cross-shard weight movement the
    partitioner inserts downstream (measured on qwen2.5-32b train_4k).
    The cast's VJP restores f32 cotangents, so aggregation is unaffected.

    thresholds: this tier's slice of ``tier_thresholds`` (a scalar at each
    compressible leaf, None elsewhere); without it each leaf's threshold
    is searched here.
    """
    def one(path, w, thr=None):
        if not compressible(path, w):
            return w, jnp.float32(1.0)
        m = magnitude_mask(w, density, thr)
        cw = fake_quant_ste(w * m, e_bits, m_bits) * m
        if out_dtype is not None:
            cw = cw.astype(out_dtype)
        return cw, m.astype(jnp.float32)

    flat = (jax.tree_util.tree_map_with_path(one, params)
            if thresholds is None else
            jax.tree_util.tree_map_with_path(one, params, thresholds))
    cparams = jax.tree.map(lambda t: t[0], flat, is_leaf=lambda t: isinstance(t, tuple))
    masks = jax.tree.map(lambda t: t[1], flat, is_leaf=lambda t: isinstance(t, tuple))
    return cparams, masks


def compress_params(params, plan: CompressionPlan):
    """Static-plan compression including clustering and structured width
    slicing. Returns (cparams, masks) — see the module docstring for the
    structured shape contract."""
    if plan.structured:
        spec = submodel_spec(params, plan.width)
        csub, sub_masks = compress_params(slice_tree(params, spec),
                                          plan.inner())
        return csub, expand_masks(sub_masks, spec, params)

    e, m = plan.quant_em()

    def one(path, w):
        if not compressible(path, w):
            return w, jnp.float32(1.0)
        mask = (magnitude_mask(w, plan.density) if plan.density < 1.0
                else jnp.ones_like(w))
        cw = w * mask
        if plan.cluster_k:
            cw = cluster_ste(cw, plan.cluster_k) * mask
        if e or m:
            cw = fake_quant_ste(cw, e, m) * mask
        return cw, mask.astype(jnp.float32)

    flat = jax.tree_util.tree_map_with_path(lambda p, w: one(p, w), params)
    cparams = jax.tree.map(lambda t: t[0], flat, is_leaf=lambda t: isinstance(t, tuple))
    masks = jax.tree.map(lambda t: t[1], flat, is_leaf=lambda t: isinstance(t, tuple))
    return cparams, masks


def payload_bits(params, plan: CompressionPlan) -> float:
    """Model/gradient payload size in bits under a plan (the paper's
    T_upload/T_download communication model).

    Per leaf: compressible leaves ship ``n_local * density`` values at
    ``bits_per_weight`` (plus the ``cluster_k * 32``-bit codebook when
    clustering is on); excluded leaves ship fp32. For structured plans
    ``n_local`` is the EXACT sliced count from the width spec (ceil
    slicing, co-sliced biases included) — the payload shrinks by the
    sliced parameter count, not a density-scaled estimate.
    """
    spec = (submodel_spec(params, plan.width) if plan.structured else None)
    total = 0.0
    for i, (path, leaf) in enumerate(
            jax.tree_util.tree_flatten_with_path(params)[0]):
        n = math.prod(spec.local_shape(i)) if spec is not None else leaf.size
        if compressible(path, leaf):
            total += n * plan.density * plan.bits_per_weight
            if plan.cluster_k:
                total += plan.cluster_k * 32          # codebook overhead
        else:
            total += n * 32
    return total


def active_param_count(params, plan: CompressionPlan) -> float:
    """The number of parameters a device actually TRAINS under ``plan``
    — the FLOP basis of Eq. (1)'s T_local (``core/heterogeneity.py``).

    Masked plans emulate sparsity on full shapes, so the legacy
    density-scaled estimate ``n_params * density`` stands. Structured
    plans train a genuinely smaller dense model: the count is the exact
    sliced total (density applying within the slice for compressible
    leaves; pass-through leaves count in full).
    """
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    if not plan.structured:
        return sum(leaf.size for _, leaf in flat) * plan.density
    spec = submodel_spec(params, plan.width)
    total = 0.0
    for i, (path, leaf) in enumerate(flat):
        n = math.prod(spec.local_shape(i))
        total += n * plan.density if compressible(path, leaf) else n
    return total
