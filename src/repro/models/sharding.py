"""Sharding: logical-rule registry for activation hints + a path-based
PartitionSpec builder for parameter/cache pytrees.

Models are mesh-agnostic: they call ``hint(x, "act_btd")`` etc., which is a
no-op unless the launcher installed rules via ``set_rules``. The launcher
builds parameter shardings from ``param_spec_tree`` (Megatron-style: heads /
d_ff / vocab / experts on the "model" axis, batch on ("pod","data")).

Where a dimension is not divisible by the axis size (e.g. 24 heads over 16
ranks) we rely on GSPMD's padded uneven sharding — the padding waste shows up
honestly in cost_analysis and is a hillclimb target (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import contextlib
import re
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# ------------------------------------------------------- activation hints

_RULES: dict[str, Any] = {}


def set_rules(rules: dict[str, Any]) -> None:
    """rules: logical name -> NamedSharding (or None to clear)."""
    global _RULES
    _RULES = dict(rules)


def clear_rules() -> None:
    set_rules({})


@contextlib.contextmanager
def activation_rules(rules: dict[str, Any]):
    """``set_rules(rules)`` for the duration of the block (a trace), then
    the rules that were in place before."""
    prev = dict(_RULES)
    set_rules(rules)
    try:
        yield
    finally:
        set_rules(prev)


def hint(x, name: str):
    s = _RULES.get(name)
    if s is None:
        return x
    return jax.lax.with_sharding_constraint(x, s)


def make_activation_rules(mesh, batch_axes, *, vocab_ok: bool = True,
                          experts_ok: bool = True,
                          seq_shard: bool = False) -> dict[str, Any]:
    def ns(*spec):
        return NamedSharding(mesh, P(*spec))
    return {
        # §Perf hillclimb #3: sequence parallelism — with T on "model" the
        # post-attention/post-MLP partial sums reduce-scatter to small
        # T-sharded f32 tiles (norms/residuals run T-sharded) and re-gather
        # as bf16 before the next projection, instead of all-reducing
        # full f32 (B,T,D) activations (Megatron-SP, GSPMD-inferred).
        "act_btd": ns(batch_axes, "model" if seq_shard else None, None),
        "act_btf": ns(batch_axes, None, "model"),   # (B, T, F) ff-sharded
        "logits": ns(batch_axes, None, "model" if vocab_ok else None),
        # §Perf hillclimb #1 (EXPERIMENTS.md): with einsum dispatch, both
        # the (g,e,c,d) capacity buffer and the (g,n,e,c) dispatch/combine
        # masks shard cleanly: groups on data, experts on model — every
        # expert contraction is then shard-local and only the combine's
        # e-partial sums all-reduce (g,n,d)-sized activations.
        "moe_buf": ns(batch_axes, "model" if experts_ok else None, None, None),
        "moe_mask": ns(batch_axes, None, "model" if experts_ok else None, None),
        # decode scores (B, H, 1, S): keep S on "model" so flash-decoding
        # partials stay local — without this constraint GSPMD prefers
        # all-gathering the S-sharded KV cache (~1 GB/layer/token).
        "dec_scores": ns(batch_axes, None, None, "model"),
    }


# ----------------------------------------------- parameter PartitionSpecs
#
# Matched against "/".join(path keys) for each leaf; first match wins.
# Each rule lists CANDIDATE dims (negative = from the end of the shape) to
# place on the "model" axis, in preference order; the first candidate whose
# size divides the axis evenly is used, else the leaf is replicated. This
# gives Megatron-style sharding where divisible (heads / d_ff / vocab /
# experts) with automatic per-tensor fallback (e.g. 24 heads on a 16-wide
# axis -> shard head_dim=128 instead). pjit rejects uneven shardings, so
# divisibility is checked against the actual mesh.

_PARAM_RULES: list[tuple[re.Pattern, tuple[int, ...]]] = [
    (re.compile(p), c) for p, c in [
        # embeddings / unembedding (odd vocabs like 49155 fall back to D)
        (r"(^|/)embed$",                      (-2, -1)),      # (V, D)
        (r"(^|/)pos_embed$",                  ()),
        (r"(^|/)lm_head/w$",                  (-1, -2)),      # (D, V)
        # attention: heads, else head_dim, else input dim
        (r"attn[^/]*/w[qkv]/w$",              (-2, -1, -3)),  # (D, H, hd)
        (r"attn[^/]*/w[qkv]/b$",              (-2, -1)),      # (H, hd)
        (r"attn[^/]*/wo/w$",                  (-2, -1)),      # (H*hd, D)
        (r"attn[^/]*/wo/b$",                  ()),
        # dense MLPs
        (r"mlp/w[ig]/w$",                     (-1,)),         # (D, F)
        (r"mlp/w[ig]/b$",                     (-1,)),
        (r"mlp/wo/w$",                        (-2,)),         # (F, D)
        (r"mlp/wo/b$",                        ()),
        # MoE (experts on model = expert parallelism)
        (r"moe/router/w$",                    ()),            # (D, E)
        (r"moe/we_[igo]$",                    (-3,)),         # (E, D, F)
        # mamba2 / ssd: heads on model; B/C (shared by every head) whole
        (r"mamba/in_(z|x|dt)/w$",             (-1,)),         # (D, d_in|H)
        (r"mamba/in_bc/w$",                   ()),            # (D, 2N)
        (r"mamba/conv_x_w$",                  (-2,)),         # (d_in, W)
        (r"mamba/conv_bc_[wb]$",              ()),            # (2N, W), (2N,)
        (r"mamba/(conv_x_b|a_log|dt_bias|d_skip|gate_norm)$", (-1,)),
        (r"mamba/out_proj/w$",                (-2,)),         # (d_in, D)
        # xlstm
        (r"(mlstm|slstm)/(up|qkv|gates|gates_x)/w$", (-1,)),
        (r"(mlstm|slstm)/(up|qkv|gates|gates_x)/b$", (-1,)),
        (r"(mlstm|slstm)/down/w$",            (-2,)),
        (r"(mlstm|slstm)/r_gates$",           (-1, -2)),      # (4, H, hd, hd)
        (r"(mlstm|slstm)/(skip|mnorm|gnorm)$", (-1,)),
        # vlm projector
        (r"projector/w$",                     (-1,)),
    ]]


def _spec_for(path: str, shape: tuple[int, ...], model_size: int,
              fsdp=None) -> P:
    """fsdp: optional (axes_tuple, size) — after the "model" dim is chosen,
    the largest REMAINING divisible dim is sharded over the data axes
    (ZeRO-3 / FSDP). Without it a 34B train state is only 16-way sharded
    (~26 GB/chip of args on llava-next — over v5e HBM); with it the state
    spreads over all 256/512 chips and GSPMD all-gathers weights layer-by-
    layer inside the scan. The dry-run's memory_analysis is the proof."""
    nd = len(shape)
    spec = [None] * nd
    matched = False
    for pat, candidates in _PARAM_RULES:
        if pat.search(path):
            matched = True
            for c in candidates:
                dim = nd + c
                if 0 <= dim < nd and shape[dim] % model_size == 0 \
                        and shape[dim] >= model_size:
                    spec[dim] = "model"
                    break
            break
    if matched and fsdp is not None:
        axes, size = fsdp
        dims = sorted(range(nd), key=lambda d: -shape[d])
        for d in dims:
            if spec[d] is None and shape[d] % size == 0 and shape[d] >= size:
                spec[d] = axes
                break
    return P(*spec)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_spec_tree(params, model_size: int = 16, fsdp=None) -> Any:
    """PartitionSpec pytree mirroring a parameter pytree (works on
    ShapeDtypeStructs too). fsdp=(batch_axes, n_shards) adds ZeRO-3
    data-axis sharding of parameters/optimizer state."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _spec_for(_path_str(path), tuple(leaf.shape),
                                     model_size, fsdp), params)


# name-suffix -> (trailing-ndim, batch dim from end, model candidates from end)
_CACHE_RULES: list[tuple[re.Pattern, tuple | None]] = [
    (re.compile(p), s) for p, s in [
        (r"(^|/)slot_pos$",      None),
        # §Perf hillclimb #2 (EXPERIMENTS.md): decode caches shard the
        # SEQUENCE dim on "model" (flash-decoding style): per-shard partial
        # scores/softmax + one tiny (B,1,H,hd) all-reduce per layer,
        # instead of gathering head_dim-sharded caches (8.6 GB/layer/step
        # on llama3.2 decode_32k). Falls back to Hkv, then hd, when S is
        # not divisible (e.g. whisper's 1500-frame cross-KV).
        (r"(^|/)(enc_)?[kv]$",   (4, -4, (-3, -2, -1))),  # (B, S, Hkv, hd)
        (r"(^|/)enc_x$",         (3, -3, ())),         # (B, S, D)
        (r"(^|/)conv(_x)?$",     (3, -3, (-1,))),      # (B, W, C)
        (r"(^|/)conv_bc$",       (3, -3, ())),         # (B, W, 2N): shared
        (r"(^|/)ssm$",           (4, -4, (-3,))),      # (B, H, P, N)
        (r"(^|/)mC$",            (4, -4, (-3, -1))),   # (B, H, dv, dk)
        (r"(^|/)(mn|sn|sc|sh)$", (3, -3, (-2, -1))),   # (B, H, d)
    ]]


def cache_spec_tree(cache, batch_axes, model_size: int = 16) -> Any:
    """PartitionSpec pytree for decode caches: shard batch + the first
    divisible heads/channels dim; anything unmatched is replicated."""
    def spec(path, leaf):
        p = _path_str(path)
        shape = tuple(leaf.shape)
        nd = len(shape)
        for pat, s in _CACHE_RULES:
            if pat.search(p):
                out = [None] * nd
                if s is None:
                    return P(*out)
                _, bdim, cands = s
                if batch_axes:
                    out[nd + bdim] = batch_axes
                for c in cands:
                    dim = nd + c
                    if shape[dim] % model_size == 0 and shape[dim] >= model_size:
                        out[dim] = "model"
                        break
                return P(*out)
        return P(*((None,) * nd))

    return jax.tree_util.tree_map_with_path(spec, cache)


def named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
