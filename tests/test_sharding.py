"""Sharding spec builders: every produced PartitionSpec must be legal for
the production mesh (divisibility), and the expected dims land on "model"."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import optim
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.core import TrainState
from repro.models import get_model
from repro.models.sharding import cache_spec_tree, param_spec_tree

MODEL = 16


def _check_legal(tree, specs):
    flat_t = jax.tree_util.tree_flatten_with_path(tree)[0]
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_t) == len(flat_s)
    n_sharded = 0
    for (path, leaf), spec in zip(flat_t, flat_s):
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            if ax == "model":
                n_sharded += 1
                assert leaf.shape[dim] % MODEL == 0, \
                    f"{path}: dim {dim} ({leaf.shape}) not divisible"
    return n_sharded


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_legal_and_nontrivial(arch):
    cfg = get_config(arch)  # FULL config: the real divisibility cases
    model = get_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = param_spec_tree(params, MODEL)
    n = _check_legal(params, specs)
    # the bulk of parameters must actually be sharded
    assert n >= len(jax.tree.leaves(params)) // 3, f"only {n} leaves sharded"


@pytest.mark.parametrize("arch", ["llama3.2-3b", "qwen2.5-32b", "xlstm-1.3b",
                                  "zamba2-2.7b", "whisper-tiny"])
def test_cache_specs_legal(arch):
    cfg = get_config(arch)
    model = get_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(128, 1024))
    specs = cache_spec_tree(cache, ("data",), MODEL)
    flat_t = jax.tree_util.tree_flatten_with_path(cache)[0]
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(flat_t, flat_s):
        for dim, ax in enumerate(spec):
            if ax == "model":
                assert leaf.shape[dim] % MODEL == 0, (path, leaf.shape, spec)


def test_opt_state_specs_mirror_params():
    """adam m/v get the same specs as their params (path-suffix matching)."""
    cfg = get_config("llama3.2-3b")
    model = get_model(cfg)
    opt = optim.adamw(1e-3)
    state = jax.eval_shape(lambda k: TrainState.create(model, opt, k),
                           jax.random.PRNGKey(0))
    specs = param_spec_tree(state, MODEL)
    sp = specs["params"]["layers"]["mlp"]["wi"]["w"]
    sm = specs["opt"]["m"]["layers"]["mlp"]["wi"]["w"]
    sv = specs["opt"]["v"]["layers"]["mlp"]["wi"]["w"]
    assert sp == sm == sv
    assert "model" in tuple(x for x in sp if x)


def test_nondivisible_heads_fall_back_to_head_dim():
    """llama3.2: 24 q-heads / 8 kv-heads on a 16-wide axis -> hd sharded."""
    cfg = get_config("llama3.2-3b")
    model = get_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = param_spec_tree(params, MODEL)
    wq = specs["layers"]["attn"]["wq"]["w"]   # (L, D, 24, 128)
    assert wq == P(None, None, None, "model")
    wk = specs["layers"]["attn"]["wk"]["w"]   # (L, D, 8, 128)
    assert wk == P(None, None, None, "model")


def test_divisible_heads_shard_heads():
    cfg = get_config("deepseek-7b")          # 32 heads
    model = get_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = param_spec_tree(params, MODEL)
    assert specs["layers"]["attn"]["wq"]["w"] == P(None, None, "model", None)


def test_odd_vocab_replicates_vocab_dim():
    cfg = get_config("granite-3-2b")          # vocab 49155
    model = get_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = param_spec_tree(params, MODEL)
    assert specs["embed"] == P(None, "model")  # falls back to d_model


def test_experts_sharded():
    cfg = get_config("qwen3-moe-30b-a3b")
    model = get_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = param_spec_tree(params, MODEL)
    assert specs["layers"]["moe"]["we_g"] == P(None, "model", None, None)
    assert specs["layers"]["moe"]["router"]["w"] == P(None, None, None)


def test_hybrid_specs_shard_heads_and_keep_b_c_whole():
    """Over a 4-wide axis every head-indexed Mamba2 leaf splits by head
    (so a rank holds its own heads' z, x, dt, conv and D), B and C stay
    whole, and attention, MLP and vocabulary shard as in the decoder."""
    cfg = get_smoke_config("granite-4.0-h-micro")    # mamba, attention
    params = jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))
    specs = param_spec_tree(params, 4)
    m = specs["runs"][0]["mamba"]
    last, mid = P(None, None, "model"), P(None, "model", None)
    assert (m["in_z"]["w"], m["in_x"]["w"], m["in_dt"]["w"]) == (last,) * 3
    assert m["in_bc"]["w"] == P(None, None, None)
    assert m["conv_x_w"] == mid and m["conv_x_b"] == P(None, "model")
    assert m["conv_bc_w"] == P(None, None, None)
    assert m["conv_bc_b"] == P(None, None)
    for k in ("a_log", "dt_bias", "d_skip", "gate_norm"):
        assert m[k] == P(None, "model"), k
    assert m["out_proj"]["w"] == mid
    a = specs["runs"][1]["attn"]
    assert a["wq"]["w"] == P(None, None, "model", None)    # 4 heads
    assert a["wk"]["w"] == P(None, None, None, "model")    # 2 kv heads: hd
    assert a["wo"]["w"] == mid
    for r in specs["runs"]:
        assert r["mlp"]["wi"]["w"] == last and r["mlp"]["wo"]["w"] == mid
    assert specs["embed"] == P("model", None)


def test_hybrid_sharded_step_matches_one_device():
    """``tests/_granite_hybrid_mesh.py``: one tier step of the smoke hybrid
    through ``sharded_train_step`` on 4 forced host devices equals the
    unsharded step within float32 round-off. A process of its own, since
    the host device count is fixed when JAX starts."""
    import os
    import subprocess
    import sys
    script = os.path.join(os.path.dirname(__file__), "_granite_hybrid_mesh.py")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run([sys.executable, script], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    assert "sharded step matches" in out.stdout
