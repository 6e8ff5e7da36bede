"""End-to-end training driver for the heterogeneous-FL framework.

Runs the tiered federated train step (paper Fig. 1 at datacenter scale) on
whatever devices exist — CPU host mesh for smoke/dev runs, the production
mesh on real hardware. Includes the full substrate: data stream,
checkpointing, metrics logging.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \
      --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import json
import time

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro.configs import get_config, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.core import TrainState, make_hetero_train_step
from repro.core.compression import default_tier_plans
from repro.checkpoint import Checkpointer
from repro.data.synthetic import make_train_batch
from repro.launch.mesh import batch_axes, make_host_mesh, num_batch_shards
from repro.models import get_model
from repro.models.sharding import (activation_rules, make_activation_rules,
                                   named, param_spec_tree)


def sharded_train_step(model, optimizer, plans, mesh, state):
    """The tier-scanned step jitted under ``mesh`` with the state donated.

    State shardings come from ``param_spec_tree`` (heads, d_ff, vocab on
    "model"), the mask-aware accumulators are constrained to the params'
    shardings, and the model's activation hints follow
    ``make_activation_rules`` while the step traces. ``state`` may hold
    ``jax.ShapeDtypeStruct``s. Returns (jitted step, state shardings,
    batch sharding: tiers whole, each tier's batch over the data axes)."""
    baxes = batch_axes(mesh)
    state_sh = named(mesh, param_spec_tree(state, mesh.shape["model"]))
    rules = make_activation_rules(
        mesh, baxes, vocab_ok=model.cfg.vocab_size % mesh.shape["model"] == 0)
    step = make_hetero_train_step(model, optimizer, plans,
                                  num_groups=num_batch_shards(mesh),
                                  acc_shardings=state_sh["params"])

    def traced(state, batch):
        with activation_rules(rules):
            return step(state, batch)

    batch_sh = NamedSharding(mesh, P(None, baxes))
    jitted = jax.jit(traced, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None), donate_argnums=(0,))
    return jitted, state_sh, batch_sh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-tiers", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    mesh = make_host_mesh(args.model_parallel)

    model = get_model(cfg)
    opt = optim.adamw(optim.warmup_cosine(args.lr, args.warmup, args.steps))
    state = TrainState.create(model, opt, jax.random.PRNGKey(args.seed))
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"arch={cfg.name} params={n_params:,} mesh={dict(mesh.shape)} "
          f"tiers={args.n_tiers}")

    jstep, state_sh, batch_sh = sharded_train_step(
        model, opt, default_tier_plans(args.n_tiers), mesh, state)
    with mesh:
        state = jax.device_put(state, state_sh)

        ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
        start = 0
        if ckpt and ckpt.latest_step() is not None:
            state, start = ckpt.restore(state)
            print(f"restored step {start}")

        t0 = time.time()
        for i in range(start, args.steps):
            batch = make_train_batch(cfg, shape, n_tiers=args.n_tiers,
                                     seed=args.seed, index=i)
            state, metrics = jstep(state, jax.device_put(batch, batch_sh))
            if (i + 1) % args.log_every == 0 or i == start:
                loss = float(metrics["loss"])
                dt = (time.time() - t0) / (i - start + 1)
                tok_s = args.batch * args.seq / dt
                print(json.dumps({"step": i + 1, "loss": round(loss, 4),
                                  "sec_per_step": round(dt, 3),
                                  "tokens_per_sec": round(tok_s)}), flush=True)
            if ckpt and (i + 1) % args.ckpt_every == 0:
                ckpt.save(state, i + 1)
        if ckpt:
            ckpt.save(state, args.steps)
    print("done")


if __name__ == "__main__":
    main()
