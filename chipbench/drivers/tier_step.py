"""Driver: the tier-scanned decoder training step,
``jax.jit(make_hetero_train_step(model, adamw, plans), donate_argnums=0)``.

Set-up makes the f32 master weights and a pool of token batches on the
device from the seed, compiles the step, and drives it through its first
three steps on the pool's first three batches: the steps the reference
follows. The window then keeps stepping the same donated state through
the rest of the pool, each step on a batch of its own.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts, gen
from chipbench.common import BenchError, leaf_norm_gaps, seed_key
from chipbench.reference import decoder as ref

FIRST_STEPS = 3


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def _diff_norms(a, b):
    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b)


def _named(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
    return {jax.tree_util.keystr(p): float(x) for p, x in flat}


class Driver:
    def __init__(self, cell: dict, seed: int):
        self.cell, self.seed = cell, seed
        self.cfg, self.tr = cell["config"], cell["traffic"]

    def _model(self):
        from repro.configs.base import ModelConfig
        from repro.models import get_model
        c = self.cfg
        mc = ModelConfig(
            name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
            d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
            vocab_size=c["vocab_size"], tie_embeddings=c["tie_word_embeddings"],
            rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
            dtype=c["compute_dtype"])
        return get_model(mc)

    def _plans(self):
        from repro.core.compression import default_tier_plans
        plans = default_tier_plans(len(self.cfg["tiers"]))
        for plan, (name, want) in zip(plans, self.cfg["tiers"].items()):
            q = (0, 0) if want["quant"] is None else tuple(want["quant"])
            got = (plan.name, plan.density, plan.quant_em(), plan.weight)
            if got != (name, want["density"], q, want["weight"]):
                raise BenchError(f"tier plan {got} is not the "
                                 f"configuration's {name}: {want}")
        return plans

    def setup(self):
        from repro import optim
        from repro.core import make_hetero_train_step
        tr, o = self.tr, self.cfg["optimizer"]
        self.key = seed_key(self.seed)
        opt = optim.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                          weight_decay=o["weight_decay"])
        self.step = jax.jit(make_hetero_train_step(self._model(), opt,
                                                   self._plans()),
                            donate_argnums=(0,))
        self.batches = gen.token_batches(
            jax.random.fold_in(self.key, 2),
            shape=(tr["batches"], len(self.cfg["tiers"]), tr["seqs"],
                   tr["positions"] + 1),
            vocab=self.cfg["vocab_size"])
        params = gen.decoder_params(jax.random.fold_in(self.key, 1), self.cfg)
        self.state = {"params": params, "opt": opt.init(params),
                      "step": jnp.zeros((), jnp.int32)}
        self.next_batch = 0
        self.first_losses = []
        for i in range(FIRST_STEPS):
            m = self.call()
            self.first_losses.append(float(m["loss"]))
            if i == 0:
                # AdamW's first moment after one step is (1 - b1) g
                self.first_grad = {k: v / (1 - o["b1"]) for k, v in
                                   _named(_norms(self.state["opt"]["m"])).items()}
        p0 = gen.decoder_params(jax.random.fold_in(self.key, 1), self.cfg)
        self.first_change = _named(_diff_norms(self.state["params"], p0))
        del p0

    def call(self):
        batch = {"tokens": self.batches[self.next_batch % self.tr["batches"]]}
        self.next_batch += 1
        self.state, metrics = self.step(self.state, batch)
        return metrics

    def window(self, seconds: float, span) -> dict:
        losses, prev = [], None
        t0 = time.perf_counter()
        while True:
            with span("decoder.step_dispatch"):
                m = self.call()
            losses.append(m["loss"])
            if prev is not None:
                # keep one step queued behind the one running
                with span("decoder.wait"):
                    prev.block_until_ready()
            prev = m["loss"]
            if time.perf_counter() - t0 >= seconds:
                break
        with span("decoder.wait"):
            jax.block_until_ready(self.state)
        elapsed = time.perf_counter() - t0
        steps = len(losses)
        tr = self.tr
        tokens = steps * len(self.cfg["tiers"]) * tr["seqs"] * tr["positions"]
        flops = steps * len(self.cfg["tiers"]) * counts.decoder_train_flops(
            self.cfg, tr["seqs"], tr["positions"])
        vals = np.asarray(jax.device_get(losses))
        return {"elapsed": elapsed, "attempted": steps,
                "failed": int(np.sum(~np.isfinite(vals))),
                "end_to_end": {"tokens_per_s": tokens / elapsed},
                "counters": {"steps": steps, "tokens": tokens,
                             "model_flops": flops}}

    def wrap_spans(self, span):
        pass

    def release(self):
        del self.state, self.step

    def readings(self, control: bool = False, fault=None) -> dict:
        """Gaps of the reference (or, in fp8, the control) to the program's first
        three steps: the first step's loss, the worst step's loss, the first
        gradient by leaf, and the change after three steps by leaf. The
        limits compare the last two only: neither loss separates the
        program from the control (PERF.md), so the losses are logged for
        the record and not compared."""
        grads = {}

        def on_grad(i, g):
            if i == 0:
                grads.update(_named(_norms(g)))

        params = gen.decoder_params(jax.random.fold_in(self.key, 1), self.cfg)
        batches = [self.batches[i] for i in range(FIRST_STEPS)]
        losses, p3 = ref.run_steps(params, batches, self.cfg,
                                   quant="fp8" if control else None,
                                   fault=fault, on_grad=on_grad)
        p0 = gen.decoder_params(jax.random.fold_in(self.key, 1), self.cfg)
        change = _named(_diff_norms(p3, p0))
        del p3, p0
        gaps = [abs(p - r) / abs(r) if np.isfinite(p) else np.inf
                for p, r in zip(self.first_losses, losses)]
        g_gap, g_leaf, _ = leaf_norm_gaps(self.first_grad, grads)
        c_gap, c_leaf, skipped = leaf_norm_gaps(self.first_change, change,
                                                ref_grad_norms=grads)
        return {"loss1_gap": gaps[0], "loss_gap": max(gaps), "grad_gap": g_gap,
                "change_gap": c_gap,
                "_worst": {"grad_gap": g_leaf, "change_gap": c_leaf,
                           "change_skipped": skipped}}
