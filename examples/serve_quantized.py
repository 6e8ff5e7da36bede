"""Serving compressed models — deploy the same global model to three
device tiers and compare outputs, payload sizes, and decode agreement.

  PYTHONPATH=src python examples/serve_quantized.py
"""
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core.compression import DEVICE_TIERS, payload_bits
from repro.core.steps import compress_for_serving, make_serve_step
from repro.models import get_model
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

GEN = 24
cfg = get_smoke_config("granite-3-2b")
model = get_model(cfg)
params = model.init(jax.random.PRNGKey(0))
serve = jax.jit(make_serve_step(model))
prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)


def decode(p):
    cache = model.init_cache(1, 8 + GEN)
    pos = 0
    for i in range(prompt.shape[1]):
        logits, cache = serve(p, cache, prompt[:, i:i + 1], jnp.int32(pos))
        pos += 1
    toks = [jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)]
    for _ in range(GEN - 1):
        logits, cache = serve(p, cache, toks[-1], jnp.int32(pos))
        toks.append(jnp.argmax(logits[:, -1:], -1).astype(jnp.int32))
        pos += 1
    return jnp.concatenate(toks, axis=1)[0]


base = decode(params)
base_bits = payload_bits(params, DEVICE_TIERS["hub"])
print(f"hub (fp32 full):  payload {base_bits / 8e3:.0f}kB")
print("  tokens:", base[:12].tolist())
for tier in ("high", "mid", "low", "embedded"):
    plan = DEVICE_TIERS[tier]
    cp = compress_for_serving(params, plan)
    toks = decode(cp)
    agree = float((toks == base).mean())
    bits = payload_bits(params, plan)
    print(f"{tier:9s} (density={plan.density}, quant={plan.quant}, "
          f"k={plan.cluster_k}): payload {bits / 8e3:.0f}kB "
          f"({base_bits / bits:.1f}x smaller), token agreement {agree:.2f}")
    print("  tokens:", toks[:12].tolist())
