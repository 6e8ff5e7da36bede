"""Operation and byte counts from shapes, against the hand counts."""
from chipbench import counts, gen
from chipbench.common import load_json, BENCH


def test_paper_mlp_macs_and_flops():
    dims = gen.mlp_dims(load_json(BENCH / "configs" / "paper-mlp.json"))
    assert dims == [5, 10, 10, 10, 10, 10, 2]
    assert counts.mlp_macs_per_sample(dims) == 470
    assert counts.mlp_train_flops_per_sample(dims) == 2820


def test_granite_params_and_step_flops():
    cfg = load_json(BENCH / "configs" / "granite-3-2b.json")
    assert counts.decoder_param_count(cfg) == 343_957_504 == cfg["params"]
    shapes = gen.decoder_shapes(cfg)
    import math
    import jax
    n = sum(math.prod(s) for s in jax.tree.leaves(
        shapes, is_leaf=lambda s: isinstance(s, tuple)))
    assert n == 343_957_504
    step = 4 * counts.decoder_train_flops(cfg, seqs=2, positions=1024)
    assert abs(step - 17.3e12) / 17.3e12 < 0.005
    # 6 x (4 x 60 817 408 + 100 669 440) x 8192 + 6 x 2048 x 1024^2 x 4 x 8
    assert step == 6 * 343_939_072 * 8192 + 6 * 2048 * 1024 ** 2 * 4 * 8


def test_grad_aggregate_bytes():
    # a 10x10 leaf over 4 tiers: f32 updates and masks, two weight
    # columns, one output row, with no padding
    assert counts.grad_aggregate_call_bytes(4, 100) == 4 * (2 * 4 * 100 + 8 + 100)
    assert counts.grad_aggregate_call_bytes(4, 1025) == 4 * (2 * 4 * 1025 + 8 + 1025)
    # the paper MLP's six matrices hold 50, 100, 100, 100, 100 and 20 values
    assert counts.grad_aggregate_round_bytes([5, 10, 10, 10, 10, 10, 2], 4) == \
        4 * (9 * (50 + 4 * 100 + 20) + 6 * 8)
