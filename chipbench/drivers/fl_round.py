"""Driver: the compiled federated round, ``ScanEngine.run`` over a fleet
built through ``FLScenario -> build_server``.

Set-up makes the fleet's shards and the MLP's weights from the seed,
builds the server and its engine, and drives the engine through its
first three calls (the first compiles): those are the steps the reference
follows. The window then keeps calling the same engine, one call of
``chunk_rounds`` rounds at a time, each ending in the engine's own
``device_get`` of the chunk's metrics.
"""
from __future__ import annotations

import time
import types

import jax
import numpy as np

from chipbench import counts, gen
from chipbench.common import BenchError, leaf_norm_gaps, log, seed_key
from chipbench.reference import fl_mlp

FIRST_CALLS = 3


def _leaf_norms(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.linalg.norm(np.asarray(x, np.float64)))
            for p, x in flat}


def _sub(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64), a, b)


class Driver:
    def __init__(self, cell: dict, seed: int):
        self.cell, self.seed = cell, seed
        self.cfg, self.tr = cell["config"], cell["traffic"]

    # ------------------------------------------------------------ set-up

    def _check_plans(self):
        """The program's tier plans are the ones the configuration states."""
        from repro.fl import DEVICE_TIERS
        for name, want in self.cfg["tiers"].items():
            plan = DEVICE_TIERS[name]
            q = (0, 0) if want["quant"] is None else tuple(want["quant"])
            got = (plan.density, plan.quant_em(), plan.weight, plan.cluster_k,
                   plan.width)
            if got != (want["density"], q, want["weight"], 0, None):
                raise BenchError(f"tier {name}: program plan {got} is not "
                                 f"the configuration's {want}")

    def setup(self):
        from repro import optim
        from repro.fl import (FleetSpec, FLScenario, LocalTraining,
                              ParticipationPolicy, ScanEngine)
        from repro.core.scenario import build_server
        from repro.core.topology import make_edge_mesh, shard_fleet
        from repro.models import mlp

        tr, cfg = self.tr, self.cfg
        self._check_plans()
        t0 = time.perf_counter()
        key = seed_key(self.seed)
        n, spc = tr["clients"], tr["samples_per_client"]
        x, y = gen.gaussian_fleet(key, n_clients=n, per_client=spc,
                                  features=cfg["num_features"])
        self.x, self.y = np.asarray(x), np.asarray(y)
        shards = gen.host_shards(self.x, self.y)
        self.dims = tuple(gen.mlp_dims(cfg))
        p0 = gen.mlp_params(jax.random.fold_in(key, 1), dims=self.dims)
        self.p0 = jax.device_get(p0)
        tiers = list(cfg["tiers"])
        spec = FleetSpec.cycling(tiers, n, samples_per_client=spc,
                                 edges=tr.get("edges"))
        t1 = time.perf_counter()
        self.tiers = spec.tiers
        sc = FLScenario(
            fleet=spec,
            local=LocalTraining(mode=tr["mode"], local_steps=tr["local_steps"],
                                local_lr=tr["local_lr"],
                                server_lr=tr["server_lr"]),
            participation=ParticipationPolicy(fraction=tr["participation"],
                                              seed=self.seed))
        srv = build_server(sc, types.SimpleNamespace(loss_fn=mlp.loss_fn),
                           optim.sgd(tr["server_lr"]), p0,
                           clients=spec.build_clients(shards))
        if tr.get("edges"):
            mesh = make_edge_mesh(tr["edges"])
            if mesh.devices.size != self.cell["chips"]:
                raise BenchError(f"edge mesh holds {mesh.devices.size} "
                                 f"devices, the cell asks for "
                                 f"{self.cell['chips']}")
            shard_fleet(srv, mesh)
        self.srv = srv
        self.engine = ScanEngine(srv, chunk_rounds=tr["chunk_rounds"],
                                 agg=tr["agg"])
        log("agg_backend", self.engine.agg_backend)
        t2 = time.perf_counter()
        # the first calls of the window's own entry, on the window's feed
        self.first_losses, self.first_counts, self.first_states = [], [], []
        for _ in range(FIRST_CALLS):
            recs = self.call()
            self.first_losses += [r["loss"] for r in recs]
            self.first_counts += [r["n_participants"] for r in recs]
            self.first_states.append(jax.device_get(srv.params))
        log("setup_s_inputs", t1 - t0, "setup_s_build_server", t2 - t1,
            "setup_s_first_calls", time.perf_counter() - t2)

    # ------------------------------------------------------------ window

    def call(self) -> list[dict]:
        return self.engine.run(self.tr["chunk_rounds"])

    def window(self, seconds: float, span) -> dict:
        times, updates, rounds, losses = [], 0, 0, []
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter()
            with span("fl.chunk"):
                recs = self.call()
            times.append(time.perf_counter() - t)
            updates += sum(r["n_participants"] for r in recs)
            rounds += len(recs)
            losses += [r["loss"] for r in recs]
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        per_update = counts.mlp_train_flops_per_sample(self.dims) * (
            self.tr["samples_per_client"] * self.tr["local_steps"])
        return {
            "elapsed": elapsed,
            "attempted": len(times),
            "failed": sum(1 for v in losses if v is None or not np.isfinite(v)),
            "end_to_end": {
                "client_updates_per_s": updates / elapsed,
                "chunk_ms_p90": 1e3 * float(np.percentile(times, 90)),
            },
            "counters": {"rounds": rounds, "updates": updates,
                         "chunks": len(times),
                         "model_flops": per_update * updates,
                         "agg_kernel_bytes_per_round": (
                             counts.grad_aggregate_round_bytes(
                                 self.dims, len(self.cfg["tiers"]))
                             if self.engine.agg_backend == "pallas" else None)},
        }

    def wrap_spans(self, span):
        """Name the engine's host phases in the trace: the host mask build
        and the chunk dispatch. What is left of a chunk call is the
        ``device_get`` and the record loop. The engine has no spans of its
        own, so its methods are wrapped from outside; a method that is
        gone is an error, so that no span vanishes unseen."""
        for attr, name in (("_host_masks", "fl.host_masks"),
                           ("_chunk", "fl.chunk_dispatch")):
            fn = getattr(self.engine, attr, None)
            if not callable(fn):
                raise BenchError(f"ScanEngine has no method {attr!r} to "
                                 f"open the span {name!r} around")

            def wrapped(*a, _fn=fn, _name=name, **kw):
                with span(_name):
                    return _fn(*a, **kw)
            setattr(self.engine, attr, wrapped)

    def release(self):
        del self.engine, self.srv

    # ------------------------------------------------------------- check

    def participants(self, rounds: int) -> list[np.ndarray]:
        """Who reports in each of the first rounds: every client, or the
        ParticipationPolicy's draw of max(1, round(f * N)) clients per
        round, uniform without replacement by
        ``np.random.default_rng([seed, round])`` over the clients listed
        tier by tier (in order of first appearance, ids ascending)."""
        n, f = self.tr["clients"], self.tr["participation"]
        if f >= 1.0:
            return [np.arange(n)] * rounds
        tiers = np.asarray(self.tiers)
        order = np.concatenate([np.flatnonzero(tiers == t)
                                for t in dict.fromkeys(self.tiers)])
        n_sel = max(1, int(round(f * n)))
        out = []
        for r in range(rounds):
            rng = np.random.default_rng([self.seed, r])
            out.append(np.sort(order[rng.choice(n, size=n_sel, replace=False)]))
        return out

    def readings(self, control: bool = False, fault=None) -> dict:
        """The reference's (or, with fp8 matmuls, the control's) gaps to
        what the program's first calls produced: each round's loss and
        count of aggregated client updates (exact), the update of the
        first call, and the change after the third, the last two by
        leaf."""
        R = self.tr["chunk_rounds"]
        ref_cfg = dict(self.tr, tiers=self.cfg["tiers"],
                       matmul_precision=self.cfg["matmul_precision"])
        losses, states, n_ref = fl_mlp.run_rounds(
            jax.device_put(self.p0), self.x, self.y, self.tiers,
            self.participants(FIRST_CALLS * R), ref_cfg,
            quant="fp8" if control else None, fault=fault)
        loss_gap = max(abs(p - r) / abs(r) if p is not None else np.inf
                       for p, r in zip(self.first_losses, losses))
        upd, upd_leaf, _ = leaf_norm_gaps(
            _leaf_norms(_sub(self.first_states[0], self.p0)),
            _leaf_norms(_sub(states[R - 1], self.p0)))
        chg, chg_leaf, _ = leaf_norm_gaps(
            _leaf_norms(_sub(self.first_states[-1], self.p0)),
            _leaf_norms(_sub(states[-1], self.p0)))
        count_gap = max(abs(p - r) for p, r in zip(self.first_counts, n_ref))
        return {"loss_gap": loss_gap, "count_gap": float(count_gap),
                "update_gap": upd, "change_gap": chg,
                "_worst": {"update_gap": upd_leaf, "change_gap": chg_leaf}}
