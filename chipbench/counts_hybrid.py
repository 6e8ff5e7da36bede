"""Parameters and model FLOPs of the Granite 4.0-H hybrid from its
configuration alone, for ``mfu.decoder`` in the hybrid's cell. Like
``chipbench/counts.py`` these never read the program: a change that
drops work must not move the denominator.
"""
from __future__ import annotations


def _types(cfg: dict) -> list[str]:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _mamba_dims(cfg: dict) -> tuple[int, int, int, int]:
    """(d_in, heads, head width, state width)."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    return h * p, h, p, cfg["mamba_d_state"]


def layer_matmul_params(cfg: dict, kind: str) -> int:
    """Parameters of one layer that take part in a matmul for every token:
    the mixer's projections and the SwiGLU MLP."""
    D = cfg["hidden_size"]
    mlp = 3 * D * cfg["shared_intermediate_size"]
    if kind == "mamba":
        d_in, h, _, n = _mamba_dims(cfg)
        # z, x, B and C, dt in; out
        return D * (2 * d_in + 2 * n + h) + d_in * D + mlp
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = D // H
    return D * H * hd * 2 + D * K * hd * 2 + mlp


def matmul_params(cfg: dict) -> int:
    """Every layer's matmul parameters and the tied LM head (the
    embedding lookup itself is a gather)."""
    return (sum(layer_matmul_params(cfg, k) for k in _types(cfg))
            + cfg["vocab_size"] * cfg["hidden_size"])


def param_count(cfg: dict) -> int:
    """Every parameter: the matmul parameters, each layer's two norm
    scales, Mamba2's conv filters and biases, A, dt's bias, D and gated
    norm scale, and the final norm."""
    D = cfg["hidden_size"]
    d_in, h, _, n = _mamba_dims(cfg)
    conv = (d_in + 2 * n) * (cfg["mamba_d_conv"] + 1)
    per_mamba = conv + 3 * h + d_in
    types = _types(cfg)
    return (matmul_params(cfg) + 2 * D * len(types) + D
            + per_mamba * types.count("mamba"))


def ssd_macs(cfg: dict, positions: int, chunk: int) -> int:
    """Multiply-accumulates of one Mamba2 layer's SSD over one sequence in
    the chunked form, forward: per chunk of Q positions, the causal half
    of C B^T (Q^2/2 x N) and of the intra-chunk product (Q^2/2 x P per
    head), the inter-chunk output C . state and the state update
    (Q x N x P per head each)."""
    _, h, p, n = _mamba_dims(cfg)
    q = min(chunk, positions)
    chunks = positions // q
    return chunks * (q * q * n // 2 + h * q * q * p // 2 + 2 * h * q * n * p)


def train_flops(cfg: dict, seqs: int, positions: int) -> float:
    """Model FLOPs of one tier's forward and backward over ``seqs``
    sequences of ``positions`` tokens: 6 per matmul parameter per token,
    causal attention (QK^T and PV at half of S x S) in each attention
    layer, and 6 per SSD multiply-accumulate in each Mamba2 layer.
    Recomputation is not counted."""
    types = _types(cfg)
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = D // H
    dense = 6.0 * matmul_params(cfg) * seqs * positions
    attn = 6.0 * H * hd * positions ** 2 * types.count("attention") * seqs
    ssd = 6.0 * ssd_macs(cfg, positions, cfg["mamba_chunk_size"]) \
        * types.count("mamba") * seqs
    return dense + attn + ssd
