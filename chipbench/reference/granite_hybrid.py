"""Plain reference of the tier-scanned training step over the Granite 4.0-H
hybrid (HF ``GraniteMoeHybrid`` with no experts), written from the
configuration file in ``jax.numpy`` at float32 with every matmul at
``HIGHEST`` precision. It imports nothing of the program.

Layer i of ``layer_types`` (cut to ``num_hidden_layers``) computes
``h = x + r mixer_i(rms_norm(x))`` and ``x' = h + r mlp_i(rms_norm(h))``,
r = ``residual_multiplier``; the embedding is multiplied by
``embedding_multiplier`` and the tied logits divided by
``logits_scaling``. The attention mixer is causal GQA with scores scaled
by ``attention_multiplier`` and no position embedding (NoPE). The Mamba2
mixer is written in its quadratic (masked-attention) dual form, head by
head, with no chunking:

    y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<u<=t} dt_u a) dt_s x_s + D x_t

over the silu of a depthwise causal conv of the projected x, B and C,
dt = softplus(x W_dt + dt_bias), a = -exp(a_log), then
``rms_norm(y * silu(z))`` over all heads and the output projection. The
segment sums are cumulative sums of the masked (T, T) matrix of dt a,
so no sum spans more positions than the segment it covers. Heads are
computed in blocks of ``head_block`` so that the (T, T) matrices fit.

Departures from HF's implementation: the input projection and conv are
held as separate leaves for z, x, B/C and dt (HF holds one ``in_proj``
over [z | x B C | dt] and one conv over [x B C]): the same arithmetic,
column for column. HF's chunked SSD (chunk 256) is replaced by the
quadratic form above, which is the same sum. There is no dropout, no
cache and no padding mask. Layers of one kind that come in a run are
stacked and scanned as the program holds them, since the tiers'
magnitude pruning takes each stacked leaf as one tensor.

One step: each tier compresses every matrix-shaped leaf of the global
model except Mamba2's per-channel and per-head leaves (the conv filters
and biases, A, dt's bias, D and the gated norm's scale, which stay full
precision as vectors do), takes the gradient of its loss, and adds
``w m g`` and ``w m`` to shared accumulators; their quotient goes to
AdamW, as in ``chipbench/reference/decoder.py``. ``quant="fp8"`` rounds
both operands of every matmul and the incoming gradient of each to
float8 e4m3: the control, one precision below the configuration's
bfloat16, switched by a traced flag so that it shares the reference's
compile. Given a mesh, the arrays are placed over its ``model`` axis
with plain ``NamedSharding``s so that the state fits four chips.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench.reference.decoder import _adamw, _key
from chipbench.reference.numerics import HI, compress_leaf, fp8, tier_scalars

MAMBA_VECTORS = ("conv_x_w", "conv_x_b", "conv_bc_w", "conv_bc_b", "a_log",
                 "dt_bias", "d_skip", "gate_norm")
BLOCK_BYTES = 1 << 28          # one (T, T, heads) f32 block of the SSD


def layer_runs(cfg: dict) -> list[tuple[str, int]]:
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return [(k, len(list(g))) for k, g in itertools.groupby(types)]


def shapes(cfg: dict) -> dict:
    """Leaf shapes of the parameter tree: tied embedding, final norm, and
    one stacked tree per run of equal mixers."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    F = cfg["shared_intermediate_size"]
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = D // H
    mh, mp, n, w = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                    cfg["mamba_d_state"], cfg["mamba_d_conv"])
    d_in = mh * mp
    runs = []
    for kind, L in layer_runs(cfg):
        r = {"ln1": (L, D), "ln2": (L, D),
             "mlp": {"wi": {"w": (L, D, F)}, "wg": {"w": (L, D, F)},
                     "wo": {"w": (L, F, D)}}}
        if kind == "mamba":
            r["mamba"] = {
                "in_z": {"w": (L, D, d_in)}, "in_x": {"w": (L, D, d_in)},
                "in_bc": {"w": (L, D, 2 * n)}, "in_dt": {"w": (L, D, mh)},
                "conv_x_w": (L, d_in, w), "conv_x_b": (L, d_in),
                "conv_bc_w": (L, 2 * n, w), "conv_bc_b": (L, 2 * n),
                "a_log": (L, mh), "dt_bias": (L, mh), "d_skip": (L, mh),
                "gate_norm": (L, d_in), "out_proj": {"w": (L, d_in, D)}}
        else:
            r["attn"] = {"wq": {"w": (L, D, H, hd)}, "wk": {"w": (L, D, K, hd)},
                         "wv": {"w": (L, D, K, hd)}, "wo": {"w": (L, H * hd, D)}}
        runs.append(r)
    return {"embed": (V, D), "final_norm": (D,), "runs": runs}


# ------------------------------------------------------------- placement

# the axis of each leaf placed over the mesh (heads, channels, d_ff and
# vocabulary, as tensor parallelism splits them); others replicated
AXIS = {"embed": 0, "in_z": -1, "in_x": -1, "in_dt": -1, "conv_x_w": 1,
        "conv_x_b": -1, "a_log": -1, "dt_bias": -1, "d_skip": -1,
        "gate_norm": -1, "out_proj": 1, "wq": 2, "wk": 2, "wv": 2, "wi": -1,
        "wg": -1}
AXIS_BY_PARENT = {("attn", "wo"): 1, ("mlp", "wo"): 1}


def _spec(path, shape, size):
    keys = [getattr(k, "key", None) for k in path if hasattr(k, "key")]
    keys = [k for k in keys if k != "w"]
    axis = AXIS_BY_PARENT.get(tuple(keys[-2:]), AXIS.get(keys[-1]))
    if axis is None or shape[axis] % size:
        return P()
    axis %= len(shape)
    return P(*["model" if a == axis else None for a in range(len(shape))])


def shardings(tree, mesh):
    return jax.tree_util.tree_map_with_path(
        lambda p, x: NamedSharding(mesh, _spec(p, x.shape, mesh.shape["model"])),
        tree)


def _fp8_if(x, on):
    return x if on is None else jnp.where(on, fp8(x), x)


@functools.lru_cache(maxsize=None)
def _einsum(spec):
    """``einsum`` at HIGHEST whose operands, and the incoming gradient,
    are rounded to scaled float8 e4m3 where the traced flag ``on`` is set
    (the control): ``numerics.mm`` with the rounding switched at run
    time, so that the reference and its control share one compile."""
    @jax.custom_vjp
    def f(a, b, on):
        return jnp.einsum(spec, _fp8_if(a, on), _fp8_if(b, on), precision=HI)

    def fwd(a, b, on):
        qa, qb = _fp8_if(a, on), _fp8_if(b, on)
        return jnp.einsum(spec, qa, qb, precision=HI), (qa, qb, on)

    def bwd(res, g):
        qa, qb, on = res
        _, vjp = jax.vjp(lambda a, b: jnp.einsum(spec, a, b, precision=HI),
                         qa, qb)
        return (*vjp(_fp8_if(g, on)), None)

    f.defvjp(fwd, bwd)
    return f


def mm(spec, a, b, fp8_on=None):
    if fp8_on is None:
        return jnp.einsum(spec, a, b, precision=HI)
    return _einsum(spec)(a, b, fp8_on)


def _constrain(x, mesh, *spec):
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


# --------------------------------------------------------------- forward

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def causal_conv(x, w, b):
    """Depthwise causal conv of (B, T, C) with taps w (C, W): output t
    sums x_{t-W+1+k} w_k, inputs before the sequence read as 0."""
    W, T = w.shape[-1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(xp[:, k:k + T, :] * w[:, k] for k in range(W)) + b


def ssd(x, bm, cm, dt, a, fp8_on, mesh, head_block):
    """The quadratic form, per head. x (B, T, H, P), bm/cm (B, T, N),
    dt (B, T, H) after softplus, a (H,). With a mesh the heads split as
    (G, H/G), G = the mesh's model size, and each block takes
    ``head_block`` heads from every one of the G groups."""
    B, T, H, Pd = x.shape
    G = 1 if mesh is None else mesh.shape["model"]
    hb = min(head_block, H // G)
    nb = H // G // hb
    scores = mm("btn,bsn->bts", cm, bm, fp8_on)               # (B, T, T)
    t = jnp.arange(T)
    after = t[:, None] > t[None, :]                           # u > s
    causal = t[:, None] >= t[None, :]

    def block(args):
        xb, dab, dtb = args           # (B, T, G, hb, P), (B, T, G, hb) x2
        xb = _constrain(xb, mesh, None, None, "model", None, None)
        # seg[t, s] = sum over s < u <= t of dt_u a
        seg = jnp.cumsum(jnp.where(after[None, :, :, None, None],
                                   dab[:, :, None], 0.0), axis=1)
        lmat = jnp.where(causal[None, :, :, None, None], jnp.exp(seg), 0.0)
        m = scores[..., None, None] * lmat * dtb[:, None]     # (B,T,S,G,hb)
        m = _constrain(m, mesh, None, None, None, "model", None)
        return mm("btsgh,bsghp->btghp", m, xb, fp8_on)

    def blocks(v):                    # (B, T, H, ...) -> (nb, B, T, G, hb, ...)
        v = v.reshape(B, T, G, nb, hb, *v.shape[3:])
        return jnp.moveaxis(v, 3, 0)

    y = jax.lax.map(jax.checkpoint(block),
                    (blocks(x), blocks(dt * a), blocks(dt)))
    return jnp.moveaxis(y, 0, 3).reshape(B, T, H, Pd)


def mamba(x, p, cfg, fp8_on, mesh, head_block):
    B, T, _ = x.shape
    H, Pd, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    z = mm("btd,de->bte", x, p["in_z"]["w"], fp8_on)
    xs = mm("btd,de->bte", x, p["in_x"]["w"], fp8_on)
    bc = mm("btd,de->bte", x, p["in_bc"]["w"], fp8_on)
    dt = mm("btd,dh->bth", x, p["in_dt"]["w"], fp8_on)
    xs = jax.nn.silu(causal_conv(xs, p["conv_x_w"], p["conv_x_b"]))
    bc = jax.nn.silu(causal_conv(bc, p["conv_bc_w"], p["conv_bc_b"]))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    z = _constrain(z, mesh, None, None, "model")
    xh = _constrain(xs.reshape(B, T, H, Pd), mesh, None, None, "model", None)
    dt = _constrain(dt, mesh, None, None, "model")
    y = ssd(xh, bc[..., :N], bc[..., N:], dt, a, fp8_on, mesh, head_block)
    y = (y + xh * p["d_skip"][:, None]).reshape(B, T, H * Pd)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm"], cfg["rms_norm_eps"])
    return mm("bte,ed->btd", y, p["out_proj"]["w"], fp8_on)


def attention(x, p, cfg, fp8_on, mesh):
    B, T, D = x.shape
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = mm("btd,dhk->bthk", x, p["wq"]["w"], fp8_on)
    k = jnp.repeat(mm("btd,dhk->bthk", x, p["wk"]["w"], fp8_on), H // K, axis=2)
    v = jnp.repeat(mm("btd,dhk->bthk", x, p["wv"]["w"], fp8_on), H // K, axis=2)
    q, k, v = (_constrain(u, mesh, None, None, "model", None) for u in (q, k, v))
    s = mm("bqhd,bkhd->bhqk", q, k, fp8_on) * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((T, T), bool))
    pr = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    o = mm("bhqk,bkhd->bqhd", pr, v, fp8_on).reshape(B, T, D)
    return mm("btf,fd->btd", o, p["wo"]["w"], fp8_on)


def layer(kind, x, lp, cfg, fp8_on, mesh, head_block):
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = rms_norm(x, lp["ln1"], eps)
    if kind == "mamba":
        y = mamba(h, lp["mamba"], cfg, fp8_on, mesh, head_block)
    else:
        y = attention(h, lp["attn"], cfg, fp8_on, mesh)
    x = x + r * y
    h = rms_norm(x, lp["ln2"], eps)
    g = mm("btd,df->btf", h, lp["mlp"]["wg"]["w"], fp8_on)
    u = mm("btd,df->btf", h, lp["mlp"]["wi"]["w"], fp8_on)
    return x + r * mm("btf,fd->btd", jax.nn.silu(g) * u,
                      lp["mlp"]["wo"]["w"], fp8_on)


def logits(params, tokens, cfg, fp8_on=None, mesh=None, head_block=None):
    """(B, T, V) logits of (B, T) token ids."""
    T = tokens.shape[1]
    if head_block is None:
        head_block = max(1, BLOCK_BYTES // (4 * T * T))
    x = params["embed"][tokens] * cfg["embedding_multiplier"]
    for (kind, _), rp in zip(layer_runs(cfg), params["runs"]):
        step = jax.checkpoint(
            lambda x, lp, kind=kind: (layer(kind, x, lp, cfg, fp8_on, mesh,
                                            head_block), None))
        x, _ = jax.lax.scan(step, x, rp)
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    return mm("btd,vd->btv", x, params["embed"], fp8_on) / cfg["logits_scaling"]


def loss(params, tokens, weights, cfg, fp8_on=None, mesh=None):
    """Mean next-token cross-entropy of (B, T + 1) tokens, each position
    weighted by ``weights`` (T,)."""
    logp = jax.nn.log_softmax(logits(params, tokens[:, :-1], cfg, fp8_on, mesh),
                              axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    return jnp.sum(nll * weights) / (nll.shape[0] * jnp.sum(weights))


# ------------------------------------------------------------------ step

def compressed(path, w) -> bool:
    keys = [getattr(k, "key", None) for k in path]
    return w.ndim >= 2 and not ("mamba" in keys and keys[-1] in MAMBA_VECTORS)


def compress(params, density, e_bits, m_bits):
    def one(path, w):
        if not compressed(path, w):
            return w, jnp.float32(1.0), jnp.float32(1.0)
        return compress_leaf(w, density, e_bits, m_bits)
    out = jax.tree_util.tree_map_with_path(one, params)
    is_t = lambda t: isinstance(t, tuple)
    return tuple(jax.tree.map(lambda t: t[i], out, is_leaf=is_t)
                 for i in range(3))


@functools.partial(jax.jit, static_argnames=("cfg_key", "mesh"),
                   donate_argnums=(1, 2))
def _tier(params, num, den, tokens, weights, tier, weight, fp8_on, *, cfg_key,
          mesh):
    cfg = dict(cfg_key)
    cw, m, inside = compress(params, *tier)
    value, g = jax.value_and_grad(loss)(cw, tokens, weights, cfg, fp8_on, mesh)
    num = jax.tree.map(lambda a, g, m, s: a + weight * m * (g * m * s),
                       num, g, m, inside)
    den = jax.tree.map(lambda a, m: a + weight * m, den, m)
    return num, den, value


def run_steps(params, batches, cfg: dict, *, quant=None, fault=None,
              on_grad=None, mesh=None):
    """Follow ``len(batches)`` steps from ``params`` (consumed). Each batch
    is (tiers, seqs, positions + 1). ``fault="half"`` leaves the second
    half of every sequence's positions out of the loss. ``on_grad(step,
    grads)`` sees the gradient AdamW is given. Returns (losses, params)."""
    opt, tiers = cfg["optimizer"], list(cfg["tiers"].values())
    if mesh is not None:
        params = jax.device_put(params, shardings(params, mesh))
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    m, v = zeros(), zeros()
    wsum = sum(t["weight"] for t in tiers)
    positions = batches[0].shape[-1] - 1
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown matmul quantization {quant!r}")
    fp8_on = jnp.bool_(quant == "fp8")
    weights = jnp.ones((positions,), jnp.float32)
    if fault == "half":
        weights = weights.at[positions // 2:].set(0.0)
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    losses, cfg_key = [], _key(cfg)
    for i, batch in enumerate(batches):
        num = zeros()
        den = jax.tree_util.tree_map_with_path(
            lambda path, p: (jnp.zeros_like(p) if compressed(path, p)
                             else jnp.zeros((), jnp.float32)), params)
        total = 0.0
        for t, plan in enumerate(tiers):
            num, den, value = _tier(params, num, den, batch[t], weights,
                                    tier_scalars(plan),
                                    jnp.float32(plan["weight"]), fp8_on,
                                    cfg_key=cfg_key, mesh=mesh)
            total += plan["weight"] * float(value)
        params, m, v, g = _adamw(params, m, v, num, den, jnp.float32(i + 1),
                                 opt_key=_key(opt))
        if on_grad is not None:
            on_grad(i, g)
        del g
        losses.append(total / wsum)
    return losses, params
