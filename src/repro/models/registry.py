"""Model registry: one uniform interface per architecture family.

``get_model(cfg)`` returns a ``Model`` namespace with:
  init(key)                                    -> params
  loss_fn(params, batch, *, num_groups)        -> scalar loss      (train)
  prefill(params, batch, *, window, num_groups)-> (logits, cache)  (prefill)
  decode_step(params, cache, tokens, pos, *, window, num_groups)
                                               -> (logits, cache)  (decode)
  init_cache(batch, cache_len)                 -> cache pytree
"""
from __future__ import annotations

import functools
from types import SimpleNamespace

from repro.models import decoder, granite_hybrid, whisper, xlstm, zamba


def get_model(cfg) -> SimpleNamespace:
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        mod = decoder

        def prefill(params, batch, *, window=0, num_groups=1):
            return decoder.prefill(params, batch["tokens"], cfg,
                                   patches=batch.get("patches"),
                                   window=window, num_groups=num_groups)
    elif fam == "ssm":
        mod = xlstm

        def prefill(params, batch, *, window=0, num_groups=1):
            return xlstm.prefill(params, batch["tokens"], cfg,
                                 window=window, num_groups=num_groups)
    elif fam == "hybrid":
        mod = zamba

        def prefill(params, batch, *, window=0, num_groups=1):
            return zamba.prefill(params, batch["tokens"], cfg,
                                 window=window, num_groups=num_groups)
    elif fam == "granite_hybrid":
        mod = granite_hybrid

        def prefill(params, batch, *, window=0, num_groups=1):
            return granite_hybrid.prefill(params, batch["tokens"], cfg,
                                          window=window,
                                          num_groups=num_groups)
    elif fam == "audio":
        mod = whisper

        def prefill(params, batch, *, window=0, num_groups=1):
            return whisper.prefill(params, batch, cfg,
                                   window=window, num_groups=num_groups)
    else:
        raise ValueError(f"unknown family {fam!r}")

    return SimpleNamespace(
        cfg=cfg,
        init=functools.partial(mod.init, cfg=cfg),
        loss_fn=functools.partial(mod.loss_fn, cfg=cfg),
        prefill=prefill,
        decode_step=functools.partial(mod.decode_step, cfg=cfg),
        init_cache=functools.partial(mod.init_cache, cfg),
    )
