"""Federated partitioners: split a dataset across clients, IID or label-skew
non-IID (Dirichlet), the standard FL evaluation protocols — plus cohort
batch stacking for the vectorized runtime (DESIGN.md §9)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def partition_iid(key, dataset: dict, n_clients: int) -> list[dict]:
    """IID split: one permutation, ``np.array_split`` shard sizes. The
    gathers run on HOST — at 100k-client fleet scale the former
    per-shard device gather was n_clients × n_leaves dispatches, and the
    shards are host-side staging data anyway (cohort builds re-stack
    them into one device transfer per leaf). Same values bit-for-bit:
    a gather copies, it never computes."""
    n = dataset["y"].shape[0]
    perm = np.asarray(jax.random.permutation(key, n))
    shards = np.array_split(perm, n_clients)
    host = {k: np.asarray(v) for k, v in dataset.items()}
    return [{k: v[s] for k, v in host.items()} for s in shards]


def partition_dirichlet(key, dataset: dict, n_clients: int,
                        alpha: float = 0.5) -> list[dict]:
    """Label-skew non-IID: per-class Dirichlet allocation over clients."""
    y = np.asarray(dataset["y"])
    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 2**31 - 1)))
    idx_per_client: list[list[int]] = [[] for _ in range(n_clients)]
    for c in np.unique(y):
        idx = np.flatnonzero(y == c)
        rng.shuffle(idx)
        props = rng.dirichlet([alpha] * n_clients)
        cuts = (np.cumsum(props)[:-1] * len(idx)).astype(int)
        for ci, part in enumerate(np.split(idx, cuts)):
            idx_per_client[ci].extend(part.tolist())
    out = []
    for ci in range(n_clients):
        sel = jnp.asarray(sorted(idx_per_client[ci]), jnp.int32)
        out.append({k: v[sel] for k, v in dataset.items()})
    return out


def stack_shards(shards: list[dict]) -> dict:
    """Stack per-client shards into leading-axis cohort batches.

    ``[{k: (n_i, ...)}] -> {k: (C, n, ...)}`` where ``n`` is the smallest
    shard length — vmap needs a rectangular batch, so longer shards are
    truncated to the common floor (with Dirichlet skew this drops tail
    samples; use equal-size IID shards when exact data parity with the
    per-client loop matters). Single host sync-free reshape, done once at
    cohort build time, not per round. Host (numpy) shards are stacked on
    the host and moved to the device in one transfer, not one a shard.
    """
    n = min(next(iter(s.values())).shape[0] for s in shards)
    out = {}
    for k in shards[0]:
        parts = [s[k][:n] for s in shards]
        if all(isinstance(x, np.ndarray) for x in parts):
            out[k] = jnp.asarray(np.stack(parts))
        else:
            out[k] = jnp.stack(parts)
    return out
