"""The Granite 4.0-H hybrid (``models/granite_hybrid.py``) against the plain
reference (``chipbench/reference/granite_hybrid.py``: float32, HIGHEST
matmuls, the SSD in its quadratic form, no program imports), on seeded
random weights at the smoke size, in float32 on the CPU.

Tolerances: the program and the reference compute the same sums in
different orders (the SSD chunked against quadratic, per-layer scans
against per-block maps), so they agree to float32 round-off, which
grows through the layers' backward passes to about 1e-5 of the largest
value. Each limit below is a few times that and far under what a
bfloat16 computation of the same model gives (about 4e-3).
"""
import hashlib
import json
import pathlib
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench.reference import granite_hybrid as ref  # noqa: E402
from repro import optim  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.core import TrainState, make_hetero_train_step  # noqa: E402
from repro.core.compression import (compress_with_masks,  # noqa: E402
                                    default_tier_plans, plan_arrays)
from repro.models import get_model, granite_hybrid as gh, mamba2  # noqa: E402
from repro.models import layers as L  # noqa: E402

KEY = jax.random.PRNGKey(11)
TYPES = ("mamba", "mamba", "attention", "mamba")
# the tiers and AdamW of the benchmark's granite configurations
CELL = json.loads((ROOT / "chipbench/configs/granite-4.0-h-micro.json").read_text())
# float32 round-off after the backward pass through 4 layers (module doc)
RTOL = 1e-3


def smoke(**kw):
    cfg = get_smoke_config("granite-4.0-h-micro")
    return cfg.replace(num_layers=len(TYPES), layer_types=TYPES, **kw)


def ref_cfg(cfg) -> dict:
    """The program's config as the reference's configuration file holds it."""
    return dict(
        hidden_size=cfg.d_model, shared_intermediate_size=cfg.d_ff,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, vocab_size=cfg.vocab_size,
        num_hidden_layers=cfg.num_layers, layer_types=list(cfg.layer_types),
        mamba_n_heads=cfg.ssm_expand * cfg.d_model // cfg.ssm_headdim,
        mamba_d_head=cfg.ssm_headdim, mamba_d_state=cfg.ssm_state,
        mamba_d_conv=cfg.conv_width, rms_norm_eps=cfg.norm_eps,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        logits_scaling=cfg.logits_scaling,
        optimizer=CELL["optimizer"], tiers=CELL["tiers"])


VECTORS = ("ln1", "ln2", "final_norm") + ref.MAMBA_VECTORS


def params_for(cfg, key=KEY):
    """Init weights with the norm scales and Mamba2's vectors moved off
    their constant init values, so that each takes part."""
    p = get_model(cfg).init(key)

    def move(path, x):
        if getattr(path[-1], "key", None) not in VECTORS:
            return x
        k = jax.random.fold_in(key, zlib.crc32(jax.tree_util.keystr(path).encode()))
        return x + 0.1 * jax.random.normal(k, x.shape)
    return jax.tree_util.tree_map_with_path(move, p)


def tokens(cfg, shape, key=KEY):
    return jax.random.randint(jax.random.fold_in(key, 2), shape, 0,
                              cfg.vocab_size)


def close(a, b, rtol=RTOL, what=""):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=rtol, err_msg=what,
                               atol=rtol * max(float(np.max(np.abs(b))), 1e-30))


def test_forward_logits_loss_and_grads_match_reference():
    cfg = smoke()
    rc = ref_cfg(cfg)
    p = params_for(cfg)
    tok = tokens(cfg, (1, 2 * mamba2.CHUNK + 1))   # two SSD chunks
    with jax.default_matmul_precision("highest"):
        logits, _ = gh.forward(p, tok[:, :-1], cfg)
        want = ref.logits(p, tok[:, :-1], rc)
        loss, grads = jax.value_and_grad(
            lambda p: gh.loss_fn(p, {"tokens": tok}, cfg))(p)
        rloss, rgrads = jax.value_and_grad(ref.loss)(
            p, tok, jnp.ones((tok.shape[1] - 1,)), rc)
    close(logits, want, 1e-4)
    assert abs(float(loss) - float(rloss)) < 1e-5 * abs(float(rloss))
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(rgrads)):
        close(g, r, what=jax.tree_util.keystr(path))


def test_ssd_scan_matches_quadratic_form_over_two_chunks():
    b, t, h, p, n = 2, 2 * mamba2.CHUNK, 4, 8, 16
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, t, h, p))
    bm = jax.random.normal(ks[1], (b, t, n))
    cm = jax.random.normal(ks[2], (b, t, n))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)) - 2.0)
    a = -jnp.exp(jax.random.normal(ks[4], (h,)))
    with jax.default_matmul_precision("highest"):
        y, _ = mamba2._ssd_scan(x, bm, cm, dt, a, None)
        want = ref.ssd(x, bm, cm, dt, a, None, None, head_block=h)
    close(y, want, 1e-5)


def test_one_tier_step_matches_reference():
    cfg = smoke()
    rc = ref_cfg(cfg)
    o = rc["optimizer"]
    opt = optim.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                      weight_decay=o["weight_decay"])
    model = get_model(cfg)
    p = params_for(cfg)
    state = {"params": p, "opt": opt.init(p), "step": jnp.zeros((), jnp.int32)}
    batch = tokens(cfg, (4, 2, 33))
    with jax.default_matmul_precision("highest"):
        step = jax.jit(make_hetero_train_step(model, opt,
                                              default_tier_plans(4)))
        new, metrics = step(state, {"tokens": batch})
        got = {}
        losses, _ = ref.run_steps(
            p, [batch], rc, on_grad=lambda i, g: got.update(g=g))
    assert abs(float(metrics["loss"]) - losses[0]) < 1e-5 * losses[0]
    # AdamW's first moment after one step is (1 - b1) g
    for m, g in zip(jax.tree.leaves(new["opt"]["m"]), jax.tree.leaves(got["g"])):
        close(np.asarray(m) / (1 - o["b1"]), g)


def _logits(cfg, p, tok):
    with jax.default_matmul_precision("highest"):
        return np.asarray(gh.forward(p, tok, cfg)[0])


@pytest.mark.parametrize("name,value", [
    ("embedding_multiplier", 3.0), ("residual_multiplier", 0.5),
    ("attention_multiplier", 0.125), ("logits_scaling", 2.0)])
def test_each_multiplier_moves_the_logits_as_the_reference_predicts(name,
                                                                    value):
    """Changing one of Granite's multipliers moves the program's logits,
    and to where the reference puts them with the same change."""
    cfg = smoke()
    p = params_for(cfg)
    tok = tokens(cfg, (1, 16))
    base = _logits(cfg, p, tok)
    moved = cfg.replace(**{name: value})
    got = _logits(moved, p, tok)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(p, tok, ref_cfg(moved)))
    close(got, want, 1e-4)
    assert np.max(np.abs(got - base)) > 100 * 1e-4 * np.max(np.abs(base))


def test_published_multipliers():
    cfg = get_smoke_config("granite-4.0-h-micro")
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (12.0, 0.22,
                                                              1 / 64, 8.0)
    assert not cfg.use_rope
    p = params_for(smoke())
    tok = tokens(cfg, (1, 16))
    # the logits are divided by logits_scaling, exactly
    np.testing.assert_allclose(_logits(smoke(logits_scaling=4.0), p, tok),
                               2 * _logits(smoke(), p, tok), rtol=1e-6)


def test_nope_attention_ignores_a_shift_of_positions():
    cfg = smoke()
    lp = jax.tree.map(lambda x: x[0], params_for(cfg)["runs"][1]["attn"])
    x = jax.random.normal(KEY, (2, 16, cfg.d_model))
    t = jnp.arange(16)

    def out(cfg, positions):
        return np.asarray(L.attn_forward(lp, x, cfg, positions=positions,
                                         use_rope=cfg.use_rope,
                                         scale=L.attn_scale(cfg)))
    np.testing.assert_array_equal(out(cfg, t), out(cfg, t + 5))
    roped = cfg.replace(use_rope=True)
    assert np.max(np.abs(out(roped, t) - out(roped, t + 5))) > 1e-3


def test_prefill_then_decode_matches_full_forward():
    cfg = smoke()
    model = get_model(cfg)
    p = params_for(cfg)
    tok = tokens(cfg, (2, 12))
    full, _ = gh.forward(p, tok, cfg, remat=False)
    logits, cache = gh.prefill(p, tok[:, :8], cfg, cache_len=12)
    close(logits[:, 0], full[:, 7], 1e-4)
    for i in range(8, 12):
        logits, cache = model.decode_step(p, cache, tok[:, i:i + 1],
                                          jnp.int32(i))
        close(logits[:, 0], full[:, i], 1e-4)


COMPRESSED = {"embed", "ln1", "ln2", "mlp/wi/w", "mlp/wg/w", "mlp/wo/w",
              "mamba/in_z/w", "mamba/in_x/w", "mamba/in_bc/w", "mamba/in_dt/w",
              "mamba/out_proj/w", "attn/wq/w", "attn/wk/w", "attn/wv/w",
              "attn/wo/w"}


@pytest.mark.parametrize("tier", range(4))
def test_which_hybrid_leaves_each_tier_compresses(tier):
    """Every tier but the hub changes the matrices and the stacked norm
    scales; Mamba2's conv filters and biases, A, dt's bias, D and gated
    norm scale, and the final norm, pass every tier untouched with a
    scalar mask."""
    cfg = smoke()
    p = params_for(cfg)
    arr = jax.tree.map(lambda a: a[tier], plan_arrays(default_tier_plans(4)))
    cp, masks = jax.jit(compress_with_masks)(p, arr["density"],
                                             arr["e_bits"], arr["m_bits"])
    changed, kept = set(), set()
    for (path, w), c, m in zip(jax.tree_util.tree_flatten_with_path(p)[0],
                               jax.tree.leaves(cp), jax.tree.leaves(masks)):
        name = "/".join(str(getattr(k, "key", k)) for k in path
                        if getattr(k, "key", None) != "runs"
                        and not hasattr(k, "idx"))
        if np.ndim(m) == 0:
            kept.add(name)
            np.testing.assert_array_equal(c, w)
        elif not np.array_equal(np.asarray(c), np.asarray(w)):
            changed.add(name)
    assert changed == (set() if tier == 0 else COMPRESSED)
    assert kept == {"final_norm"} | {f"mamba/{k}" for k in ref.MAMBA_VECTORS}


# the StableHLO (no debug info) of granite-3-2b's tier step at its cell's
# shapes, with every tier's pruning thresholds searched once before the
# tier scan (``compression.tier_thresholds``); it changes with the JAX
# version and with the step's program, not with Granite's multipliers or
# NoPE switch at their identity defaults
GRANITE_3_2B_STEP_SHA256 = \
    "b5d4ef647aca235c089a3a3408129ce826fc1af3f3d79487df795707f48bc52a"


def _granite_3_2b_step_text(**fields) -> str:
    c = json.loads((ROOT / "chipbench/configs/granite-3-2b.json").read_text())
    cfg = ModelConfig(
        name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], tie_embeddings=c["tie_word_embeddings"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        dtype=c["compute_dtype"], **fields)
    model = get_model(cfg)
    o = c["optimizer"]
    opt = optim.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                      weight_decay=o["weight_decay"])
    step = jax.jit(make_hetero_train_step(model, opt, default_tier_plans(4)),
                   donate_argnums=(0,))
    state = jax.eval_shape(lambda k: TrainState.create(model, opt, k), KEY)
    batch = {"tokens": jax.ShapeDtypeStruct((4, 2, 1025), jnp.int32)}
    return step.lower(state, batch).as_text(debug_info=False)


def test_granite_3_2b_step_lowers_to_the_same_program():
    """The pinned program, and the same program with Granite's multipliers
    and RoPE set explicitly to the identity (the attention scale given as
    1/sqrt(64), granite-3-2b's head size): at identity they leave the
    step unchanged."""
    text = _granite_3_2b_step_text()
    assert hashlib.sha256(text.encode()).hexdigest() == GRANITE_3_2B_STEP_SHA256
    assert _granite_3_2b_step_text(
        embedding_multiplier=1.0, residual_multiplier=1.0,
        attention_multiplier=64 ** -0.5, logits_scaling=1.0,
        use_rope=True) == text
