"""Operations and bytes from shapes alone, for the benchmark's
utilization and roofline shares. These never read the program: a change
that drops work must not move the denominator.
"""
from __future__ import annotations


def mlp_macs_per_sample(dims) -> int:
    """Multiply-accumulates of one forward pass of a dense MLP."""
    return sum(i * o for i, o in zip(dims[:-1], dims[1:]))


def mlp_train_flops_per_sample(dims) -> int:
    """Forward and backward: 2 FLOPs a MAC, three passes' worth of MACs."""
    return 6 * mlp_macs_per_sample(dims)


def decoder_param_count(cfg: dict) -> int:
    """Every parameter of a dense GQA decoder with SwiGLU, RMSNorm and a
    tied embedding."""
    return decoder_matmul_params(cfg) + (
        cfg["hidden_size"] * (2 * cfg["num_hidden_layers"] + 1))


def decoder_matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matmul for every token: the
    attention and MLP projections of each layer and the tied LM head (the
    embedding lookup itself is a gather)."""
    D, H, K = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["num_key_value_heads"])
    hd = D // H
    attn = D * H * hd * 2 + D * K * hd * 2
    mlp = 3 * D * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attn + mlp) + cfg["vocab_size"] * D


def decoder_train_flops(cfg: dict, seqs: int, positions: int) -> float:
    """Model FLOPs of one training step over ``seqs`` sequences of
    ``positions`` tokens: 6 per matmul parameter per token, plus causal
    attention (QK^T and PV at half of S x S, forward and backward).
    Recomputation is not counted."""
    D = cfg["hidden_size"]
    dense = 6.0 * decoder_matmul_params(cfg) * seqs * positions
    attn = 6.0 * D * positions ** 2 * cfg["num_hidden_layers"] * seqs
    return dense + attn


def grad_aggregate_call_bytes(tiers: int, numel: int) -> int:
    """HBM bytes one ``grad_aggregate`` call must read and write for a
    leaf of ``numel`` values: the (tiers, numel) f32 updates and masks,
    two (tiers,) f32 weight columns and the (numel,) f32 result. No
    padding: what an implementation adds to its tiles is not work."""
    return 4 * (2 * tiers * numel + 2 * tiers + numel)


def grad_aggregate_round_bytes(dims, tiers: int) -> int:
    """One round of the masked fleet's fused aggregation: one call per
    weight matrix (vectors take the plain formula)."""
    return sum(grad_aggregate_call_bytes(tiers, i * o)
               for i, o in zip(dims[:-1], dims[1:]))
