"""Structured sub-model compression (DESIGN.md §13): width-sliced local
models, HeteroFL-style (Diao et al., 2021).

The masked path (``pruning.py`` + ``apply.py``) emulates a smaller local
model on FULL-shape arrays with 0/1 masks: a 0.25-density tier pays the
same per-step FLOPs and memory as the hub. This module cuts REAL smaller
dense arrays out of the global model instead:

  - every compressible matrix leaf ``(d_in, ..., d_out)`` becomes the
    dense PREFIX slice ``(ceil(w*d_in), ..., ceil(w*d_out))`` on its
    FIRST and LAST axes (middle axes of >=3-D leaves pass through at
    full size — only the in/out feature dims carry the width budget) —
    prefix slicing keeps tier sub-models nested (a 0.25-width model is a
    sub-matrix of the 0.5-width model), which is what lets the server
    aggregate per-coordinate over whichever tiers cover a weight;
  - the model's INPUT dimension (axis 0 of the first matrix leaf) and
    OUTPUT dimension (last axis of the last matrix leaf) are preserved,
    so the sub-model consumes the same features and emits the same
    classes as the global model;
  - a 1-D leaf living next to a sliced matrix leaf whose out-dimension
    it matches (the ``{"w", "b"}`` dense-layer convention) is co-sliced
    to the matrix's out-slice — a bias must follow its layer's width;
  - everything else (router, free-standing 1-D scales) passes through
    at full shape.

The slice plan is a static, hashable :class:`SubmodelSpec` — it depends
only on the tree's SHAPES and the width, never on values, so cohort
runtimes compute it once per (fleet, width) and jitted steps re-derive
it at trace time with zero retracing churn.

``slice_submodel`` / ``expand_update`` are exact adjoints: slicing is a
linear map whose transpose is zero-padding, so ``expand_update`` of a
sub-model gradient IS the global-model gradient of the sliced loss.
A width of 1.0 produces an all-``None`` spec and every function here
short-circuits to identity — the structured code path is then
bit-identical to the masked path by construction (pinned in
``tests/test_structured.py``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

_EXCLUDE = ("router",)
# Mamba2's per-channel and per-head leaves: the depthwise conv's 4-tap
# filters and biases, A, dt's bias, D and the gated norm's scale. Each is
# a vector per layer (the filter a (channels, 4) stack of them); stacked
# over layers they are 2-D, but they stay full precision and full shape
# as vectors do. A pruned 4-tap filter loses most of its taps per channel.
_MAMBA_VECTORS = ("conv_x_w", "conv_x_b", "conv_bc_w", "conv_bc_b", "a_log",
                  "dt_bias", "d_skip", "gate_norm")


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def compressible(path, leaf) -> bool:
    """The compression policy shared by every path in ``compression/``:
    matrix-shaped (ndim >= 2) leaves compress; 1-D leaves (norm scales,
    gates, biases — quantization-sensitive), the MoE router (load-balance
    stability) and Mamba2's per-channel and per-head leaves
    (``_MAMBA_VECTORS``) stay full precision / full shape."""
    p = _path_str(path)
    if any(x in p for x in _EXCLUDE):
        return False
    parts = p.split("/")
    if "mamba" in parts[:-1] and parts[-1] in _MAMBA_VECTORS:
        return False
    return getattr(leaf, "ndim", len(getattr(leaf, "shape", ()))) >= 2


def _ceil_dim(width: float, d: int) -> int:
    return min(d, max(1, math.ceil(width * d)))


@dataclass(frozen=True)
class SubmodelSpec:
    """Static slice plan for one (param tree, width) pair.

    ``slices[i]`` is the LOCAL shape of flattened leaf ``i`` (a tuple of
    ints) when the leaf is sliced, or ``None`` when it passes through at
    full shape; ``shapes[i]`` is the leaf's global shape. Frozen and
    hashable — shapes only, no arrays.
    """
    width: float
    slices: tuple
    shapes: tuple

    @property
    def is_identity(self) -> bool:
        return all(s is None for s in self.slices)

    def local_shape(self, i: int) -> tuple:
        return self.slices[i] if self.slices[i] is not None else self.shapes[i]

    def local_size(self) -> int:
        """Total parameter count of the sliced sub-model."""
        return sum(math.prod(self.local_shape(i))
                   for i in range(len(self.shapes)))


def submodel_spec(params, width: float) -> SubmodelSpec:
    """The slice plan for ``params`` at ``width`` (shape-only; works on
    real arrays and ``jax.eval_shape`` stand-ins alike).

    The first/last matrix leaves (whose model input/output dims are
    preserved) are taken in PYTREE FLATTEN ORDER — keep layer containers
    order-preserving (lists/tuples, as this repo's models do), or key
    dicts so lexicographic order matches the forward pass; a tree keyed
    ``layer1..layer10`` flattens ``layer10`` before ``layer2`` and would
    misidentify the output layer (the mistake surfaces loudly as a
    logits/labels shape mismatch, but surfaces late).

    Raises when ``width < 1.0`` but the tree has no sliceable axis at
    all — a single matrix leaf is both first AND last, so its in/out
    dims are preserved and the width budget would silently evaporate
    (the sub-model would BE the full model). Such models should use
    masked ``density`` instead. Ceil-rounding a sliceable axis back up
    to full size (e.g. width 0.99 on a dim of 10) is NOT an error.
    """
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    shapes = tuple(tuple(leaf.shape) for _, leaf in flat)
    slices: list = [None] * len(flat)
    mat = [i for i, (p, leaf) in enumerate(flat) if compressible(p, leaf)]
    if mat:
        first, last = mat[0], mat[-1]
        # parent path -> (out-slice, full out) of its matrix leaf, for
        # co-slicing sibling 1-D leaves (the {"w","b"} layer convention)
        out_by_parent: dict = {}
        for i in mat:
            shape = shapes[i]
            rows = shape[0] if i == first else _ceil_dim(width, shape[0])
            cols = shape[-1] if i == last else _ceil_dim(width, shape[-1])
            loc = (rows,) + shape[1:-1] + (cols,)
            if loc != shape:
                slices[i] = loc
            out_by_parent.setdefault(flat[i][0][:-1], (cols, shape[-1]))
        # a lone matrix leaf is both first and last: nothing is sliceable
        if width < 1.0 and len(mat) == 1:
            raise ValueError(
                "width slicing needs an interior dimension to cut: this "
                "tree's only matrix leaf carries the model input AND "
                "output dims, which are preserved — the width budget "
                "would be silently dropped. Use a masked plan (density) "
                "for single-matrix models.")
        for i, (path, leaf) in enumerate(flat):
            if i in mat or len(shapes[i]) != 1:
                continue
            oc = out_by_parent.get(path[:-1])
            if oc is not None and shapes[i][0] == oc[1] and oc[0] != oc[1]:
                slices[i] = (oc[0],)
    return SubmodelSpec(width=width, slices=tuple(slices), shapes=shapes)


def slice_tree(params, spec: SubmodelSpec):
    """Cut the dense sub-model out of ``params``. Unsliced leaves are
    returned AS-IS (same objects) — at width 1.0 this is the identity,
    so the structured path traces the exact same jaxpr as the masked
    one."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    out = [leaf if s is None else leaf[tuple(slice(0, k) for k in s)]
           for leaf, s in zip(leaves, spec.slices)]
    return jax.tree_util.tree_unflatten(treedef, out)


def slice_submodel(params, width: float):
    """``(sub_params, spec)``: the dense width-``width`` sub-model plus
    the static slice plan needed to scatter updates back."""
    spec = submodel_spec(params, width)
    return slice_tree(params, spec), spec


def expand_update(sub_grads, spec: SubmodelSpec, global_params):
    """Zero-pad sub-model gradients/deltas back to global shapes — the
    exact transpose of :func:`slice_tree` (autodiff through slicing
    produces precisely this padding)."""
    gl, treedef = jax.tree_util.tree_flatten(global_params)
    out = []
    for g, s, full in zip(jax.tree.leaves(sub_grads), spec.slices, gl):
        if s is None:
            out.append(g)
        else:
            out.append(jnp.pad(g, [(0, f - k) for f, k in zip(full.shape, s)]))
    return jax.tree_util.tree_unflatten(treedef, out)


def expand_masks(sub_masks, spec: SubmodelSpec, global_params):
    """Lift local-model masks to GLOBAL shapes: array masks on sliced
    leaves are zero-padded (coverage ∧ inner mask), scalar masks on
    sliced leaves become prefix coverage vectors, pass-through leaves
    keep their mask unchanged (scalar 1.0 for excluded leaves). The
    result obeys the aggregation contract: a mask names exactly the
    global coordinates this tier's update covers."""
    gl, treedef = jax.tree_util.tree_flatten(global_params)
    out = []
    for m, s, full in zip(jax.tree.leaves(sub_masks), spec.slices, gl):
        if s is None:
            out.append(m)
        elif getattr(m, "ndim", 0) == len(s):
            out.append(jnp.pad(m, [(0, f - k)
                                   for f, k in zip(full.shape, s)]))
        else:                       # scalar mask on a co-sliced 1-D leaf
            cov = jnp.pad(jnp.full(s, m, jnp.float32),
                          [(0, f - k) for f, k in zip(full.shape, s)])
            out.append(cov)
    return jax.tree_util.tree_unflatten(treedef, out)
