"""Parameters and model FLOPs of the hybrid cell, against hand counts."""
import math

import jax

from chipbench import counts_hybrid
from chipbench.common import BENCH, load_json
from chipbench.reference import granite_hybrid as ref

CFG = load_json(BENCH / "configs" / "granite-4.0-h-micro.json")


def test_params_of_the_cell():
    # Mamba2 layer: z, x (2048 x 4096 each), B and C (2048 x 256), dt
    # (2048 x 64), out (4096 x 2048), SwiGLU 3 x 2048 x 8192
    mamba = 2048 * (2 * 4096 + 256 + 64) + 4096 * 2048 + 3 * 2048 * 8192
    # attention: q and o 2048 x 2048, k and v 2048 x 512, the same SwiGLU
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192
    assert (mamba, attn) == (76_152_832, 60_817_408)
    assert counts_hybrid.matmul_params(CFG) == 9 * mamba + attn + 100352 * 2048
    # every Mamba2 layer also holds conv taps and biases over 4352
    # channels, A, dt's bias and D over 64 heads, the gated norm's 4096;
    # every layer two norm scales; one final norm
    vectors = 9 * (4352 * 5 + 3 * 64 + 4096) + 10 * 2 * 2048 + 2048
    assert counts_hybrid.param_count(CFG) == 951_991_232 == CFG["params"] \
        == counts_hybrid.matmul_params(CFG) + vectors
    n = sum(math.prod(s) for s in jax.tree.leaves(
        ref.shapes(CFG), is_leaf=lambda s: isinstance(s, tuple)))
    assert n == 951_991_232


def test_step_flops_of_the_cell():
    # per 256-position chunk of a Mamba2 layer: causal half of C B^T
    # (256^2/2 x 128), of the intra-chunk product (64 heads x 256^2/2 x
    # 64), and C . state and the state update (64 x 256 x 128 x 64 each)
    chunk = 256 ** 2 * 128 // 2 + 64 * 256 ** 2 * 64 // 2 + 2 * 64 * 256 * 128 * 64
    assert counts_hybrid.ssd_macs(CFG, 4096, 256) == 16 * chunk == 6_509_559_808
    tier = (6 * 951_713_792 * 4096          # matmul parameters x tokens
            + 6 * 2048 * 4096 ** 2          # one causal attention layer
            + 6 * 16 * chunk * 9)           # nine SSDs
    step = 4 * counts_hybrid.train_flops(CFG, seqs=1, positions=4096)
    assert step == 4 * tier == 95_787_971_248_128
