"""Driver: the tier-scanned training step of a Granite 4.0-H hybrid,
sharded over the cell's chips: ``repro.launch.train.sharded_train_step``,
which jits ``make_hetero_train_step(model, adamw, plans)`` under the
``(data 1, model chips)`` mesh of ``make_host_mesh`` with the state
donated, its shardings from ``param_spec_tree``.

Set-up checks the configuration against what the program runs, makes the
f32 master weights on the mesh and a pool of token batches from the
seed, compiles the step once (``lower(...).compile()``) and reads from
the compiled program which operations lie under the SSD's scope, then
drives the compiled step through its first three steps on the pool's
first three batches: the steps the reference follows, on the same
chips. The window keeps stepping the same donated state through the rest
of the pool, one batch a step, with one step queued behind the one
running.
"""
from __future__ import annotations

import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts_hybrid, gen, hlo_scopes
from chipbench.common import BenchError, leaf_norm_gaps, log, seed_key
from chipbench.reference import granite_hybrid as ref

FIRST_STEPS = 3
SSD_SCOPE = "granite_hybrid.ssd"
# what the program's hybrid runs, by the configuration's keys
FIXED = {"model_type": "granitemoehybrid", "hidden_act": "silu",
         "attention_bias": False, "mamba_n_groups": 1,
         "mamba_conv_bias": True, "mamba_proj_bias": False,
         "position_embedding_type": "nope", "num_local_experts": 0,
         "normalization_function": "rmsnorm", "tie_word_embeddings": True}
_COMPILED: dict = {}


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def _diff_norms(a, b):
    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b)


def _named(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
    return {jax.tree_util.keystr(p): float(x) for p, x in flat}


def _std(cfg: dict) -> dict:
    """Init scale of each leaf as the program's initializers set it;
    None for norm scales (ones) and a string for Mamba2's constants."""
    D, F = cfg["hidden_size"], cfg["shared_intermediate_size"]
    H = cfg["num_attention_heads"]
    L = cfg["num_hidden_layers"]
    d_in = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    s_in, conv = 1.0 / math.sqrt(D), 1.0 / math.sqrt(cfg["mamba_d_conv"])
    runs = []
    for kind, _ in ref.layer_runs(cfg):
        r = {"ln1": None, "ln2": None,
             "mlp": {"wi": {"w": s_in}, "wg": {"w": s_in},
                     "wo": {"w": 1.0 / math.sqrt(F * 2 * L)}}}
        if kind == "mamba":
            r["mamba"] = {
                "in_z": {"w": s_in}, "in_x": {"w": s_in}, "in_bc": {"w": s_in},
                "in_dt": {"w": s_in}, "conv_x_w": conv, "conv_x_b": 0.0,
                "conv_bc_w": conv, "conv_bc_b": 0.0, "a_log": "a_log",
                "dt_bias": "dt_bias", "d_skip": None, "gate_norm": None,
                "out_proj": {"w": 1.0 / math.sqrt(d_in * 2 * L)}}
        else:
            r["attn"] = {"wq": {"w": s_in}, "wk": {"w": s_in},
                         "wv": {"w": s_in},
                         "wo": {"w": 1.0 / math.sqrt(D * 2 * L)}}
        runs.append(r)
    return {"embed": 0.02, "final_norm": None, "runs": runs}


def hybrid_params(key, cfg: dict, shardings=None):
    """f32 master weights in one jitted call, placed by ``shardings``:
    normal leaves at the program's init scales, norm scales and D at 1,
    conv biases at 0, A = -(1..16) and dt's bias softplus^-1(1e-3..0.1)
    over the heads, as the program initialises them."""
    is_shape = lambda s: isinstance(s, tuple)
    leaves, treedef = jax.tree.flatten(ref.shapes(cfg), is_leaf=is_shape)
    stds = jax.tree.leaves(_std(cfg), is_leaf=lambda s: s is None)

    def one(k, shape, sd):
        h = shape[-1]
        if sd is None:
            return jnp.ones(shape, jnp.float32)
        if sd == "a_log":
            v = jnp.log(jnp.linspace(1.0, 16.0, h).astype(jnp.float32))
        elif sd == "dt_bias":
            v = jnp.log(jnp.expm1(jnp.linspace(1e-3, 0.1, h)).astype(jnp.float32))
        else:
            return jax.random.normal(k, shape, jnp.float32) * sd
        return jnp.broadcast_to(v, shape)

    def make(key):
        ks = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [one(k, s, sd) for k, s, sd in
                                            zip(ks, leaves, stds)])

    return jax.jit(make, out_shardings=shardings)(key)


class Driver:
    def __init__(self, cell: dict, seed: int):
        self.cell, self.seed = cell, seed
        self.cfg, self.tr = cell["config"], cell["traffic"]

    def _model_config(self):
        from repro.configs.base import ModelConfig
        from repro.models import mamba2
        c = self.cfg
        for k, v in FIXED.items():
            if c.get(k) != v:
                raise BenchError(f"{k} is {c.get(k)!r}; the program's hybrid "
                                 f"runs {v!r}")
        if c["mamba_chunk_size"] != mamba2.CHUNK:
            raise BenchError(f"mamba_chunk_size {c['mamba_chunk_size']} is "
                             f"not the program's chunk {mamba2.CHUNK}")
        if c["mamba_expand"] * c["hidden_size"] != (c["mamba_n_heads"]
                                                    * c["mamba_d_head"]):
            raise BenchError("mamba_expand x hidden_size is not "
                             "mamba_n_heads x mamba_d_head")
        L = c["num_hidden_layers"]
        return ModelConfig(
            name=c["name"], family="granite_hybrid", num_layers=L,
            d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"],
            d_ff=c["shared_intermediate_size"], vocab_size=c["vocab_size"],
            ssm_state=c["mamba_d_state"], ssm_headdim=c["mamba_d_head"],
            ssm_expand=c["mamba_expand"], conv_width=c["mamba_d_conv"],
            layer_types=tuple(c["layer_types"][:L]), norm_eps=c["rms_norm_eps"],
            tie_embeddings=True,
            embedding_multiplier=float(c["embedding_multiplier"]),
            residual_multiplier=float(c["residual_multiplier"]),
            attention_multiplier=float(c["attention_multiplier"]),
            logits_scaling=float(c["logits_scaling"]), use_rope=False,
            dtype=c["compute_dtype"])

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

    def _compile(self, model, opt, state):
        """The step compiled for the mesh, once a process per cell."""
        from repro.launch.train import sharded_train_step
        key = (json.dumps(self.cfg, sort_keys=True),
               json.dumps(self.tr, sort_keys=True),
               tuple(d.id for d in self.mesh.devices.flat))
        if key not in _COMPILED:
            step, state_sh, batch_sh = sharded_train_step(
                model, opt, self._plans(), self.mesh, state)
            sds = jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=s), state, state_sh)
            tr = self.tr
            batch = {"tokens": jax.ShapeDtypeStruct(
                (len(self.cfg["tiers"]), tr["seqs"], tr["positions"] + 1),
                jnp.int32, sharding=batch_sh)}
            compiled = step.lower(sds, batch).compile()
            try:
                text = compiled.as_text()
            except Exception as e:  # an executable that keeps no HLO text
                log("no HLO text to read the SSD's op names from:", e)
                text = None
            names, stats = ((), {}) if not text else \
                hlo_scopes.scope_op_names(text, SSD_SCOPE)
            _COMPILED[key] = (compiled, state_sh, batch_sh, names, stats)
        return _COMPILED[key]

    def setup(self):
        from repro import optim
        from repro.launch.mesh import make_host_mesh
        from repro.models import get_model
        tr, o = self.tr, self.cfg["optimizer"]
        self.key = seed_key(self.seed)
        self.mesh = make_host_mesh(self.cell["chips"])
        if self.mesh.shape["data"] != 1:
            raise BenchError(f"mesh {dict(self.mesh.shape)}: the cell is one "
                             f"model-parallel group of {self.cell['chips']}")
        model = get_model(self._model_config())
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        if jax.tree.map(lambda x: tuple(x.shape), params) != ref.shapes(self.cfg):
            raise BenchError("the program's parameter tree is not the "
                             "reference's")
        opt = optim.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                          weight_decay=o["weight_decay"])
        shapes = {"params": params, "opt": jax.eval_shape(opt.init, params),
                  "step": jax.ShapeDtypeStruct((), jnp.int32)}
        (self.step, state_sh, batch_sh, self.ssd_ops,
         stats) = self._compile(model, opt, shapes)
        log("mesh", dict(self.mesh.shape), "ssd_scope", stats)
        self.param_sh = state_sh["params"]
        pool = gen.token_batches(
            jax.random.fold_in(self.key, 2),
            shape=(tr["batches"], len(self.cfg["tiers"]), tr["seqs"],
                   tr["positions"] + 1),
            vocab=self.cfg["vocab_size"])
        self.batches = [jax.device_put(pool[i], batch_sh)
                        for i in range(tr["batches"])]
        del pool
        params = hybrid_params(jax.random.fold_in(self.key, 1), self.cfg,
                               self.param_sh)
        self.state = jax.device_put(
            {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}, state_sh)
        self.next_batch = 0
        self.first_losses = []
        for i in range(FIRST_STEPS):
            m = self.call()
            self.first_losses.append(float(m["loss"]))
            if i == 0:
                # AdamW's first moment after one step is (1 - b1) g
                self.first_grad = {k: v / (1 - o["b1"]) for k, v in
                                   _named(_norms(self.state["opt"]["m"])).items()}
        self.first_change = self._change(self.state["params"])

    def _change(self, params) -> dict:
        p0 = hybrid_params(jax.random.fold_in(self.key, 1), self.cfg,
                           self.param_sh)
        out = _named(_diff_norms(params, p0))
        del p0
        return out

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
                with span("decoder.wait"):
                    prev.block_until_ready()
            prev = m["loss"]
            if time.perf_counter() - t0 >= seconds:
                break
        with span("decoder.wait"):
            jax.block_until_ready(self.state)
        elapsed = time.perf_counter() - t0
        steps, tr, tiers = len(losses), self.tr, len(self.cfg["tiers"])
        tokens = steps * tiers * tr["seqs"] * tr["positions"]
        flops = steps * tiers * counts_hybrid.train_flops(
            self.cfg, tr["seqs"], tr["positions"])
        vals = np.asarray(jax.device_get(losses))
        return {"elapsed": elapsed, "attempted": steps,
                "failed": int(np.sum(~np.isfinite(vals))),
                "end_to_end": {"tokens_per_s": tokens / elapsed},
                "counters": {"steps": steps, "tokens": tokens,
                             "model_flops": flops,
                             "ssd_op_names": self.ssd_ops}}

    def wrap_spans(self, span):
        pass

    def release(self):
        del self.state, self.step

    def readings(self, control: bool = False, fault=None) -> dict:
        """Gaps of the reference (or, in fp8, the control) to the program's
        first three steps, as the decoder cell reads them: the first
        gradient and the change after three steps by leaf are compared;
        the first step's loss and the worst step's are logged."""
        grads = {}

        def on_grad(i, g):
            if i == 0:
                grads.update(_named(_norms(g)))

        losses, p3 = ref.run_steps(
            hybrid_params(jax.random.fold_in(self.key, 1), self.cfg,
                          self.param_sh),
            self.batches[:FIRST_STEPS], self.cfg,
            quant="fp8" if control else None, fault=fault, on_grad=on_grad,
            mesh=self.mesh)
        change = self._change(p3)
        del p3
        gaps = [abs(p - r) / abs(r) if np.isfinite(p) else np.inf
                for p, r in zip(self.first_losses, losses)]
        g_gap, g_leaf, _ = leaf_norm_gaps(self.first_grad, grads)
        c_gap, c_leaf, skipped = leaf_norm_gaps(self.first_change, change,
                                                ref_grad_norms=grads)
        return {"loss1_gap": gaps[0], "loss_gap": max(gaps), "grad_gap": g_gap,
                "change_gap": c_gap,
                "_worst": {"grad_gap": g_leaf, "change_gap": c_leaf,
                           "change_skipped": skipped}}
