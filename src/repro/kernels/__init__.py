"""Pallas TPU kernels for the paper's compute hot-spots.

The paper's platform trains *compressed* models; on TPU the hot-spots are:
  - fake_quant:      (e,m)-format rounding of weights (every tier, every step)
  - masked_matmul:   pruned-weight matmul with the mask applied in VMEM
                     (the dense masked weight never round-trips to HBM)
  - codebook_matmul: clustered-weight matmul, codebook decoded tile-by-tile
  - grad_aggregate:  fused mask-aware hetero gradient aggregation
  - structured_scatter: fused prefix-block aggregation of width-sliced
                     (structured) tier uploads into the dense
                     coverage-counted accumulators
  - flash_attention: online-softmax attention (causal / sliding-window /
                     GQA via BlockSpec index mapping) — the prefill
                     memory-roofline hot-spot

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
public wrapper), ref.py (pure-jnp oracle used by the allclose test sweeps).
The tests check the kernels in interpret mode on CPU and compile them
ahead of time for a described TPU v5e (tests/test_tpu_compile.py);
``chip_smoke.py`` runs them on the chip.
"""
from repro.kernels.fake_quant.ops import fake_quant  # noqa: F401
from repro.kernels.masked_matmul.ops import masked_matmul  # noqa: F401
from repro.kernels.codebook_matmul.ops import codebook_matmul  # noqa: F401
from repro.kernels.grad_aggregate.ops import grad_aggregate  # noqa: F401
from repro.kernels.structured_scatter.ops import structured_scatter  # noqa: F401
from repro.kernels.flash_attention.ops import flash_attention  # noqa: F401
