"""Public wrapper: aggregate one parameter leaf's per-tier structured
(width-sliced) uploads through the fused prefix-block Pallas kernel.

Geometry (DESIGN.md §15): every leaf is viewed 2-D row-major —
``rows = prod(shape[:-1])`` (1 for 1-D leaves), ``cols = shape[-1]``.
Because width slicing keeps mid axes full-size, a tier whose local
shape is ``local`` covers exactly rows ``[0, prod(local[:-1]))`` x cols
``[0, local[-1])`` of that view: a true 2-D prefix block, no index
arithmetic on the data path. This is a PRECONDITION, not a convenience:
local shapes must come from :class:`SubmodelSpec` (or be full-shape) —
a shape sliced on a MIDDLE axis has non-contiguous coverage in the 2-D
view and is outside this kernel's contract (``submodel_spec`` never
produces one). Tiers are padded (zeros — exact no-ops
under the mask algebra) to block multiples, never to the global shape,
so the structured ~width² upload-memory win survives up to one block of
slack per axis.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.grad_aggregate.ops import _auto_interpret
from repro.kernels.structured_scatter.kernel import (structured_scatter_raw,
                                                    structured_scatter_whole)

# f32 TPU tile quanta (sublane, lane); caps keep one block VMEM-sized
# while letting small leaves compile to a single (1, 1) grid step.
# Interpret mode (CPU) skips the quanta entirely: there is no tile
# alignment to honour, and rounding a 10-wide leaf's blocks up to
# (16, 128) would make every tier pay ~20x its actual data — the
# whole-view gridless call is both exact-sized and machinery-free.
_BR, _BC = 8, 128
_BR_MAX, _BC_MAX = 256, 1024
# A grid step holds 2·T input blocks and one output block, each double
# buffered, plus the body's num/den accumulators. v5e's default scoped
# VMEM is 16 MiB; the 4 MiB left over is for the body's per-tier
# temporaries (a 3-tier (256, 1024) f32 step needs 17.8 MiB in all).
_VMEM_BUDGET = 12 * 2**20


def _rup(n: int, q: int) -> int:
    return -(-n // q) * q


def _view2d(shape: tuple) -> tuple:
    """(rows, cols) of ``shape``'s row-major 2-D view."""
    return (math.prod(shape[:-1]), shape[-1]) if len(shape) > 1 \
        else (1, shape[0] if shape else 1)


def structured_scatter(gs, ms, w, w_den=None, *, out_shape: tuple,
                       eps: float = 1e-8,
                       interpret: bool | None = None) -> jax.Array:
    """Fused coverage-counted aggregation of one leaf across tiers.

    ``gs``/``ms``: per-tier update-sums and masks at each tier's LOCAL
    (prefix-sliced) shape — full-coverage (masked-plan) tiers simply
    pass their global-shape arrays; scalar or broadcastable masks (the
    excluded-leaf convention) are broadcast to the tier's local shape.
    ``w``: (T,) numerator weights; ``w_den``: (T,) denominator weights
    (``w·n_participants`` — the cohort accumulator form, exactly
    ``grad_aggregate``'s column semantics), defaulting to ``w``.
    ``out_shape``: the GLOBAL leaf shape. Returns the aggregated f32
    leaf — bitwise ``scatter_accumulate`` -> ``finalize``.
    """
    if interpret is None:
        interpret = _auto_interpret()
    t = len(gs)
    rows, cols = _view2d(tuple(out_shape))
    wn = jnp.asarray(w, jnp.float32).reshape(t, 1)
    wd = wn if w_den is None else jnp.asarray(w_den,
                                              jnp.float32).reshape(t, 1)
    if interpret:
        # CPU: one gridless whole-leaf call on UNPADDED local views —
        # there is no tile alignment to honour, and padding a 10-wide
        # leaf's tiers to (8, 128)-quantized blocks would cost ~20x
        # their data in pure op traffic. Scalar masks stay (1, 1) and
        # broadcast inside the kernel arithmetic.
        g2s, m2s = [], []
        for g, m in zip(gs, ms):
            r, c = _view2d(tuple(g.shape))
            g2s.append(g.reshape(r, c))
            m = jnp.asarray(m)
            if m.size == 1:
                m2s.append(m.reshape(1, 1))
            elif m.size == g.size:
                m2s.append(m.reshape(r, c))
            else:
                m2s.append(jnp.broadcast_to(
                    m.reshape((1,) * (g.ndim - m.ndim) + m.shape),
                    g.shape).reshape(r, c))
        out = structured_scatter_whole(tuple(g2s), tuple(m2s), wn, wd,
                                       out_rc=(rows, cols), eps=eps,
                                       interpret=True)
        return out.reshape(out_shape)
    # TPU: tile-quantized, VMEM-capped blocks over the global leaf
    return _scatter_tiled(gs, ms, wn, wd, rows=rows, cols=cols,
                          out_shape=out_shape, eps=eps,
                          interpret=interpret)


def structured_scatter_batched(gs, ms, w, w_den=None, *,
                               out_shape: tuple, eps: float = 1e-8,
                               interpret: bool | None = None) -> jax.Array:
    """Batched :func:`structured_scatter`: aggregate L same-shaped
    leaves in ONE kernel call. ``gs[t]``/``ms[t]`` are stacked
    ``(L, *local_t)`` arrays (masks may be ``(L,)`` scalars-per-leaf);
    ``out_shape`` is the SINGLE-leaf global shape; returns
    ``(L, *out_shape)``. Per-leaf results are bitwise identical to L
    separate :func:`structured_scatter` calls — the kernel's adds and
    prefix-slice scatters act on the trailing two view axes only, the
    batch dim just rides along (pinned in tests/test_kernels.py). On
    CPU this is the op-count win that puts the fused structured round
    ahead of the sequential scatter: a round body's aggregation cost is
    dominated by XLA op dispatch, not bytes, and batching the paper
    MLP's four hidden layers (and five biases) collapses ~2.4x of it.
    The TPU path keeps per-leaf tiled calls (grid geometry is per-leaf).
    """
    if interpret is None:
        interpret = _auto_interpret()
    L = gs[0].shape[0]
    rows, cols = _view2d(tuple(out_shape))
    if not interpret:
        outs = [structured_scatter(
                    [g[i] for g in gs],
                    [m if getattr(m, "ndim", 0) == 0 else m[i]
                     for m in ms],
                    w, w_den, out_shape=tuple(out_shape), eps=eps,
                    interpret=interpret)
                for i in range(L)]
        return jnp.stack(outs)
    t = len(gs)
    wn = jnp.asarray(w, jnp.float32).reshape(t, 1)
    wd = wn if w_den is None else jnp.asarray(w_den,
                                              jnp.float32).reshape(t, 1)
    g3s, m3s = [], []
    for g, m in zip(gs, ms):
        r, c = _view2d(tuple(g.shape[1:]))
        g3s.append(g.reshape(L, r, c))
        m = jnp.asarray(m)
        if m.size == L:                 # one scalar mask per leaf
            m3s.append(m.reshape(L, 1, 1))
        else:
            m3s.append(jnp.broadcast_to(m, g.shape).reshape(L, r, c))
    out = structured_scatter_whole(tuple(g3s), tuple(m3s), wn, wd,
                                   out_rc=(L, rows, cols), eps=eps,
                                   interpret=True)
    return out.reshape((L,) + tuple(out_shape))


def _block_shape(rows: int, cols: int, n_tiers: int) -> tuple:
    """(br, bc) for a ``rows`` x ``cols`` leaf aggregated over
    ``n_tiers``: the capped tile-quantized block, rows halved first and
    then cols, until a grid step's f32 blocks fit ``_VMEM_BUDGET``."""
    br = min(_rup(rows, _BR), _BR_MAX)
    bc = min(_rup(cols, _BC), _BC_MAX)
    n_blocks = 2 * (2 * n_tiers + 1) + 2
    while n_blocks * br * bc * 4 > _VMEM_BUDGET:
        if br > _BR:
            br = _rup(br // 2, _BR)
        elif bc > _BC:
            bc = _rup(bc // 2, _BC)
        else:
            break
    return br, bc


def _scatter_tiled(gs, ms, wn, wd, *, rows, cols, out_shape, eps,
                   interpret):
    br, bc = _block_shape(rows, cols, len(gs))
    g2s, m2s = [], []
    for g, m in zip(gs, ms):
        r, c = _view2d(tuple(g.shape))
        g2 = g.reshape(r, c)
        m = jnp.asarray(m)
        m2 = (jnp.broadcast_to(m.reshape((1,) * (g.ndim - m.ndim)
                                         + m.shape), g.shape)
              if m.size != g.size else m).reshape(r, c)
        pr, pc = _rup(r, br) - r, _rup(c, bc) - c
        if pr or pc:
            g2 = jnp.pad(g2, ((0, pr), (0, pc)))
            m2 = jnp.pad(m2, ((0, pr), (0, pc)))
        g2s.append(g2)
        m2s.append(m2)
    grid = (_rup(rows, br) // br, _rup(cols, bc) // bc)
    out = structured_scatter_raw(tuple(g2s), tuple(m2s), wn, wd,
                                 grid=grid, block=(br, bc), eps=eps,
                                 interpret=interpret)
    return out[:rows, :cols].reshape(out_shape)
