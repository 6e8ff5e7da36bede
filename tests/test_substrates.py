"""Data pipeline, optimizers, schedules, checkpointing."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import optim
from repro.checkpoint import Checkpointer, load_pytree, save_pytree
from repro.configs import SHAPES, get_smoke_config
from repro.data import (TokenStream, make_gaussian_dataset, make_train_batch,
                        partition_dirichlet, partition_iid)

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------------- data

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 1000), st.integers(0, 50))
def test_token_stream_deterministic_and_seekable(seed, idx):
    s1 = TokenStream(1000, 4, 32, seed=seed)
    s2 = TokenStream(1000, 4, 32, seed=seed)
    b1, b2 = s1.batch_at(idx), s2.batch_at(idx)
    assert bool(jnp.all(b1["tokens"] == b2["tokens"]))
    assert b1["tokens"].shape == (4, 33)
    assert int(b1["tokens"].max()) < 1000


def test_token_stream_zipf_skew():
    b = TokenStream(10_000, 64, 256, seed=1).batch_at(0)["tokens"]
    # low token ids must be much more frequent than high ids
    low = float((b < 100).mean())
    high = float((b > 5000).mean())
    assert low > 10 * max(high, 1e-4)


def test_gaussian_dataset_separable():
    d = make_gaussian_dataset(KEY, 4000)
    mu0 = d["x"][d["y"] == 0].mean()
    mu1 = d["x"][d["y"] == 1].mean()
    assert float(mu0) < -0.8 and float(mu1) > 0.8


def test_partition_iid_preserves_all_samples():
    d = make_gaussian_dataset(KEY, 1000)
    shards = partition_iid(KEY, d, 7)
    assert sum(s["y"].shape[0] for s in shards) == 1000


def test_partition_dirichlet_skews_labels():
    d = make_gaussian_dataset(KEY, 4000)
    shards = partition_dirichlet(KEY, d, 8, alpha=0.1)
    assert sum(s["y"].shape[0] for s in shards) == 4000
    fracs = [float(s["y"].mean()) for s in shards if s["y"].shape[0] > 10]
    assert max(fracs) - min(fracs) > 0.3  # strong label skew at alpha=0.1


@pytest.mark.parametrize("arch", ["granite-3-2b", "whisper-tiny",
                                  "llava-next-34b"])
def test_make_train_batch_matches_specs(arch):
    cfg = get_smoke_config(arch)
    shape = SHAPES["train_4k"]
    shape = type(shape)("t", 64, 8, "train")
    b = make_train_batch(cfg, shape, n_tiers=4)
    assert b["tokens"].shape[0] == 4 and b["tokens"].shape[1] == 2
    if cfg.family == "audio":
        assert b["frames"].shape == (4, 2, cfg.encoder_seq, cfg.d_model)
    if cfg.family == "vlm":
        assert b["tokens"].shape[-1] == 64 - cfg.num_patches + 1


# ------------------------------------------------------------------ optim

@pytest.mark.parametrize("maker", [lambda: optim.sgd(0.1),
                                   lambda: optim.momentum(0.05),
                                   lambda: optim.adam(0.1),
                                   lambda: optim.adamw(0.1, weight_decay=0.0)])
def test_optimizers_minimize_quadratic(maker):
    opt = maker()
    params = {"x": jnp.array([3.0, -2.0])}
    state = opt.init(params)
    for i in range(200):
        g = jax.grad(lambda p: jnp.sum(p["x"] ** 2))(params)
        params, state = opt.update(g, state, params, step=i)
    assert float(jnp.abs(params["x"]).max()) < 0.05


def test_adamw_decays_weights():
    opt = optim.adamw(0.1, weight_decay=0.5)
    params = {"x": jnp.array([5.0])}
    state = opt.init(params)
    zero_g = {"x": jnp.array([0.0])}
    for i in range(50):
        params, state = opt.update(zero_g, state, params, step=i)
    assert float(params["x"][0]) < 1.0


def test_schedules():
    s = optim.warmup_cosine(1.0, 10, 110)
    assert float(s(0)) == 0.0
    assert abs(float(s(10)) - 1.0) < 1e-6
    assert float(s(110)) < 0.01
    assert float(optim.constant(0.3)(5)) == pytest.approx(0.3)
    c = optim.cosine_decay(1.0, 100)
    assert float(c(0)) == 1.0 and float(c(100)) < 1e-6


# ------------------------------------------------------------- checkpoint

def test_checkpoint_roundtrip_and_retention():
    tree = {"a": {"w": jnp.arange(6.0).reshape(2, 3),
                  "b": jnp.ones(3, jnp.bfloat16)},
            "layers": [{"x": jnp.zeros(2, jnp.int32)},
                       {"x": jnp.ones(2, jnp.int32)}],
            "step": jnp.int32(7)}
    with tempfile.TemporaryDirectory() as d:
        c = Checkpointer(d, keep=2)
        for s in (1, 2, 3):
            c.save(tree, s)
        restored, step = c.restore(jax.tree.map(jnp.zeros_like, tree))
        assert step == 3
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            assert a.dtype == b.dtype and bool(jnp.all(a == b))
        assert len(os.listdir(d)) == 2


def test_checkpoint_shape_mismatch_raises():
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "x.npz")
        save_pytree({"w": jnp.zeros((2, 2))}, p)
        with pytest.raises(ValueError):
            load_pytree({"w": jnp.zeros((3, 3))}, p)


@pytest.mark.slow
def test_checkpoint_train_state_resume():
    from repro.core import TrainState, make_hetero_train_step
    from repro.core.compression import default_tier_plans
    from repro.models import get_model
    cfg = get_smoke_config("granite-3-2b")
    model = get_model(cfg)
    opt = optim.adamw(1e-3)
    state = TrainState.create(model, opt, KEY)
    step = jax.jit(make_hetero_train_step(model, opt, default_tier_plans(2)))
    batch = {"tokens": jax.random.randint(KEY, (2, 2, 17), 0, cfg.vocab_size)}
    state, _ = step(state, batch)
    with tempfile.TemporaryDirectory() as d:
        c = Checkpointer(d)
        c.save(state, 1)
        restored, _ = c.restore(jax.tree.map(jnp.zeros_like, state))
    s2a, m_a = step(state, batch)
    s2b, m_b = step(restored, batch)
    assert float(m_a["loss"]) == pytest.approx(float(m_b["loss"]), abs=1e-6)
