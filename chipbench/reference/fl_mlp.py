"""Plain reference of the paper's federated round on the §6.1 MLP.

Written from the paper and the configuration file alone, in
``jax.numpy`` at float32 with every model matmul at the precision the
configuration states (``matmul_precision``; JAX's default, one bfloat16
pass on a TPU, for the paper MLP). It imports nothing of the program: each tier compresses the global model
(``numerics.compress_leaf``), clients train on their own 16 samples
(FedSGD: one gradient; FedAvg: local SGD re-compressed after every
step), and the server takes the coverage-counted mean

    agg = sum_t w_t m_t sum_c u_c / max(sum_t w_t n_t m_t, 1e-8)

before applying it (FedSGD: ``p - lr agg``; FedAvg: ``p + lr agg``).

``quant="fp8"`` rounds every matmul operand to scaled float8 e4m3: the
control, one precision below the configuration's bfloat16 passes.
``fault="half"`` drops every second reporting client of each tier and
takes the mean over the rest: a fault the comparison has to see.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.numerics import (HI, PRECISION, compress_tree, mm,
                                          tier_scalars)


def client_losses(params, x, y, quant, precision):
    """Mean cross-entropy of each client over its own samples:
    x (clients, samples, features), y (clients, samples)."""
    h = x
    n = len(params["layers"])
    for i, lp in enumerate(params["layers"]):
        h = mm("...i,io->...o", h, lp["w"], quant, PRECISION[precision]) + lp["b"]
        if i < n - 1:
            h = jax.nn.sigmoid(h)
    logp = jax.nn.log_softmax(h, axis=-1)
    ll = jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll, axis=-1)


@functools.partial(jax.jit, static_argnames=("quant", "precision"))
def _fedsgd_tier(params, x, y, part, tier, *, quant, precision):
    cw, m, inside = compress_tree(params, *tier)

    def total(cw):
        return jnp.sum(part * client_losses(cw, x, y, quant, precision))

    loss_sum, g = jax.value_and_grad(total)(cw)
    g = jax.tree.map(lambda g, m, s: g * m * s, g, m, inside)
    return loss_sum, g, m


@functools.partial(jax.jit,
                   static_argnames=("quant", "precision", "steps", "lr"))
def _fedavg_tier(params, x, y, part, tier, *, quant, precision, steps, lr):
    cw0, m, _ = compress_tree(params, *tier)

    def one_client(xc, yc):
        def loss(w):
            return client_losses(w, xc[None], yc[None], quant, precision)[0]
        w, last = cw0, jnp.float32(0.0)
        for _ in range(steps):
            last, g = jax.value_and_grad(loss)(w)
            w = jax.tree.map(lambda w, g: w - lr * g, w, g)
            w = compress_tree(w, *tier)[0]
        return last, jax.tree.map(lambda a, b: a - b, w, cw0)

    losses, deltas = jax.vmap(one_client)(x, y)
    d_sum = jax.tree.map(lambda d: jnp.tensordot(part, d, axes=1, precision=HI),
                         deltas)
    return jnp.sum(part * losses), d_sum, m


def run_rounds(params, x, y, tiers, participants, cfg: dict, *,
               quant=None, fault=None):
    """Follow ``len(participants)`` rounds from ``params``.

    ``tiers[c]`` is client c's tier name; ``participants[r]`` the ids of
    the clients that report in round r. FedSGD computes every client of a
    tier with weight 0 or 1; FedAvg only the reporting ones, padded with
    weight-0 rows to one shape per round. Returns the loss of each round,
    the parameters after each round (host copies) and the number of
    client updates each round aggregated."""
    plans = cfg["tiers"]
    tiers = np.asarray(tiers)
    tier_ids = {t: np.flatnonzero(tiers == t) for t in plans}
    mode, lr = cfg["mode"], cfg["server_lr"]
    precision = cfg.get("matmul_precision", "highest")
    width = max(len(p) for p in participants)
    losses, states, counts = [], [], []
    for ids in participants:
        acc_n = jax.tree.map(jnp.zeros_like, params)
        acc_d = jax.tree.map(lambda p: jnp.zeros(p.shape if p.ndim >= 2 else (),
                                                 jnp.float32), params)
        loss_sum, n_sum = 0.0, 0
        for t, plan in plans.items():
            members = tier_ids[t]
            report = np.intersect1d(members, ids)
            if fault == "half":
                report = report[::2]
            n_t = len(report)
            if n_t == 0:
                continue
            tier = tier_scalars(plan)
            if mode == "fedavg":
                rows = np.concatenate([report, np.full(width - n_t, report[0])])
                part = jnp.asarray(np.arange(width) < n_t, jnp.float32)
                l_sum, u, m = _fedavg_tier(params, x[rows], y[rows], part, tier,
                                           quant=quant, precision=precision,
                                           steps=cfg["local_steps"],
                                           lr=cfg["local_lr"])
            else:
                part = jnp.asarray(np.isin(members, report), jnp.float32)
                l_sum, u, m = _fedsgd_tier(params, x[members], y[members], part,
                                           tier, quant=quant,
                                           precision=precision)
            w = jnp.float32(plan["weight"])
            acc_n = jax.tree.map(lambda a, m, u: a + m * (w * u), acc_n, m, u)
            acc_d = jax.tree.map(lambda a, m: a + m * (w * n_t), acc_d, m)
            loss_sum += float(l_sum)
            n_sum += n_t
        agg = jax.tree.map(lambda a, d: a / jnp.maximum(d, 1e-8), acc_n, acc_d)
        sign = 1.0 if mode == "fedavg" else -1.0
        params = jax.tree.map(lambda p, a: p + sign * lr * a, params, agg)
        losses.append(loss_sum / n_sum)
        counts.append(n_sum)
        states.append(jax.device_get(params))
    return losses, states, counts
