"""End-to-end training driver: a ~100M-parameter llama-style model trained
with the heterogeneous federated step for a few hundred rounds.

Default flags are the real run (~115M params, 300 steps, batch 8 x seq 512):
several hours on a CPU; its time on a TPU has not been measured. Use
--steps/--batch/--seq to scale down for a quick look:

  PYTHONPATH=src python examples/train_100m.py --steps 5 --batch 4 --seq 128
"""
import argparse
import json
import time

import jax

from repro import optim
from repro.configs.base import ModelConfig
from repro.core import TrainState, make_hetero_train_step
from repro.core.compression import default_tier_plans
from repro.checkpoint import Checkpointer
from repro.compile_cache import enable_compile_cache
from repro.data.synthetic import TokenStream
from repro.models import get_model


def config_100m() -> ModelConfig:
    # ~115M params: 12L x d512 x ffn2048, 32k vocab (llama-style, GQA 8/4)
    return ModelConfig(
        name="llama-100m", family="dense", num_layers=12, d_model=512,
        num_heads=8, num_kv_heads=4, d_ff=2048, vocab_size=32768,
        dtype="float32")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--n-tiers", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = config_100m()
    model = get_model(cfg)
    opt = optim.adamw(optim.warmup_cosine(3e-4, 30, args.steps))
    step = jax.jit(make_hetero_train_step(
        model, opt, default_tier_plans(args.n_tiers)))
    state = TrainState.create(model, opt, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"params: {n / 1e6:.1f}M, tiers: {args.n_tiers}, "
          f"tokens/step: {args.batch * args.seq}")

    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    stream = TokenStream(cfg.vocab_size, args.batch, args.seq)
    per = args.batch // args.n_tiers
    t0 = time.time()
    for i, batch in zip(range(args.steps), stream):
        tiered = {"tokens": batch["tokens"].reshape(args.n_tiers, per, -1)}
        state, m = step(state, tiered)
        if (i + 1) % max(args.steps // 20, 1) == 0 or i == 0:
            print(json.dumps({"step": i + 1, "loss": round(float(m["loss"]), 4),
                              "elapsed_s": round(time.time() - t0, 1)}),
                  flush=True)
        if ckpt and (i + 1) % 100 == 0:
            ckpt.save(state, i + 1)
    print("done")


if __name__ == "__main__":
    main()
