#!/usr/bin/env python3
"""Smoke check: the federated round and the tier-scanned decoder step
run on a TPU through the entry points a user calls.

    python chip_smoke.py              # six phases on one chip
    python chip_smoke.py --chips 4    # the hierarchical fleet sharded over
                                      # four chips against one, nothing else

One process, phases in order, each one fatal. Every phase prints one
JSON line: compile seconds (JAX's trace, lower and compile events,
persistent-cache reads included), steady seconds (the rest of the
phase's wall time), and per comparison the aggregation backend, the
largest parameter difference against the reference run and whether the
two are bitwise equal. These are one-shot smoke timings, not benchmark
numbers. The last line is ``{"ok": true, "device": {...}}``; it is
printed only when every phase passed. With no TPU the script exits 2
before running anything.

Compiled programs are kept in ``$JAX_COMPILATION_CACHE_DIR`` when it is
set, else in ``.jax_cache/`` at the repository root. Checkpoints and IR
dumps go under ``--out``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

# A run passes its comparison when, on every parameter leaf,
# max|run - ref| <= REL_TOL * max|ref|.
REL_TOL = 1e-5

TIERS = ("hub", "high", "mid", "low")
N_CLIENTS = 256
SAMPLES_PER_CLIENT = 16
SYNC_ROUNDS = 12
ASYNC_BUFFER = 64
ASYNC_WINDOWS = 24
FAULT_ROUNDS = 20
CHECKPOINT_EVERY = 10
SHARD_CLIENTS = 100_000
SHARD_EDGES = 8
# under sgd(1.0) this fleet's loss rises through round 5 and is below
# round 1's from round 8 on
SHARD_ROUNDS = 12
DECODER_ARCH = "granite-3-2b"
DECODER_LAYERS = 4          # a dense model's period is one layer
DECODER_TIERS = 4
DECODER_SEQS = 2            # sequences per tier
DECODER_TOKENS = 1025       # 1024 positions + the shifted target
DECODER_STEPS = 3

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_spans: list = []       # (start, end) on the perf_counter clock


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _on_event(name, secs, **_):
    if name in _COMPILE_EVENTS:
        end = time.perf_counter()
        _compile_spans.append((end - secs, end))


def compile_seconds(t0: float) -> float:
    """Seconds since ``t0`` covered by compile events. Nested jits
    report nested trace events, so this is the union of the spans, not
    their sum."""
    total, reach = 0.0, t0
    for start, end in sorted(_compile_spans):
        start = max(start, reach)
        if end > start:
            total += end - start
            reach = end
    return total


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_phase(name: str, fn) -> dict:
    """Run one phase, print its JSON line, and end the script on any
    failed check or exception (after printing what failed)."""
    t0 = time.perf_counter()
    line = {"phase": name}
    try:
        line.update(fn())
    except Exception as e:
        line["error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(line), flush=True)
        raise
    wall = time.perf_counter() - t0
    comp = compile_seconds(t0)
    line.update(compile_s=comp, steady_s=wall - comp,
                peak_bytes_in_use=peak_bytes())
    print(json.dumps(line), flush=True)
    return line


# ------------------------------------------------------------- checks

def compare(run_params, ref_params, *, run: str, ref: str,
            agg_backend: str) -> dict:
    """Largest parameter difference of ``run`` against ``ref``; fails
    the phase above REL_TOL."""
    import jax
    import numpy as np
    a, b = ([np.asarray(x) for x in jax.tree.leaves(jax.device_get(p))]
            for p in (run_params, ref_params))
    check(len(a) == len(b) and all(x.shape == y.shape
                                   for x, y in zip(a, b)),
          f"{run} and {ref} params differ in structure")
    check(all(np.isfinite(x).all() for x in a), f"{run} params not finite")
    abs_d = max(float(np.max(np.abs(x - y))) for x, y in zip(a, b))
    rel_d = max(float(np.max(np.abs(x - y)))
                / max(float(np.max(np.abs(y))), 1e-30)
                for x, y in zip(a, b))
    bitwise = all(x.dtype == y.dtype and x.tobytes() == y.tobytes()
                  for x, y in zip(a, b))
    out = {"run": run, "ref": ref, "agg_backend": agg_backend,
           "max_abs_diff": abs_d, "max_rel_diff": rel_d,
           "bitwise": bitwise}
    check(rel_d <= REL_TOL,
          f"{run} vs {ref}: relative difference {rel_d} > {REL_TOL}")
    return out


def check_losses(result, what: str) -> dict:
    losses = [r.loss for r in result.records if r.loss is not None]
    check(len(losses) >= 2 and all(math.isfinite(v) for v in losses),
          f"{what}: losses not finite: {losses}")
    check(losses[-1] < losses[0],
          f"{what}: last loss {losses[-1]} not below first {losses[0]}")
    return {"loss_first": losses[0], "loss_last": losses[-1]}


def check_mosaic_kernels(dump_dir: pathlib.Path) -> bool:
    """The round chunk ran real Mosaic kernels: the kernels' interpret
    switch reads False, and every lowered chunk holds tpu_custom_call."""
    from repro.kernels.grad_aggregate import ops as ga_ops
    from repro.kernels.structured_scatter import ops as ss_ops
    check(not ga_ops._auto_interpret() and not ss_ops._auto_interpret(),
          "Pallas kernels would run in interpret mode")
    chunks = sorted(p for p in dump_dir.iterdir() if "chunk_fn" in p.name)
    check(bool(chunks), f"no lowered round chunk in {dump_dir}")
    check(all("tpu_custom_call" in p.read_text() for p in chunks),
          "a lowered round chunk holds no tpu_custom_call")
    return True


def simulate_dumped(dump_dir: pathlib.Path, *args, **kw):
    """``simulate`` with the lowered IR of every program dumped into
    ``dump_dir`` (emptied first)."""
    import jax

    from repro.fl import simulate
    shutil.rmtree(dump_dir, ignore_errors=True)
    dump_dir.mkdir(parents=True)
    jax.config.update("jax_dump_ir_to", str(dump_dir))
    try:
        return simulate(*args, **kw)
    finally:
        jax.config.update("jax_dump_ir_to", "")


# ------------------------------------------------------------- phases

def fleet(n: int = None, **kw):
    from repro.fl import FleetSpec
    return FleetSpec.cycling(TIERS, n or N_CLIENTS,
                             samples_per_client=SAMPLES_PER_CLIENT, **kw)


def phase_masked_sync(out: pathlib.Path) -> dict:
    from repro.fl import FLScenario, simulate
    spec = fleet()
    sc = FLScenario(fleet=spec)
    clients = spec.build_clients()
    eager = simulate(sc, SYNC_ROUNDS, engine="eager", clients=clients)
    scan = simulate(sc, SYNC_ROUNDS, engine="scan", clients=clients)
    fused = simulate_dumped(out / "ir_masked", sc, SYNC_ROUNDS,
                            engine="scan_pallas", clients=clients)
    check(fused.agg_backend == "pallas",
          f"scan_pallas ran {fused.agg_backend!r}, not 'pallas'")
    cmp_fused = compare(fused.params, scan.params, run="scan_pallas",
                        ref="scan", agg_backend=fused.agg_backend)
    cmp_fused["mosaic"] = check_mosaic_kernels(out / "ir_masked")
    for r, name in ((eager, "eager"), (scan, "scan"), (fused, "scan_pallas")):
        losses = check_losses(r, name)
    return {"comparisons": [
                compare(scan.params, eager.params, run="scan", ref="eager",
                        agg_backend=scan.agg_backend),
                cmp_fused],
            **losses}


def phase_width_sliced(out: pathlib.Path) -> dict:
    from repro.fl import FLScenario, LocalTraining, simulate
    spec = fleet()
    sc = FLScenario(fleet=spec, local=LocalTraining(submodel="width"))
    clients = spec.build_clients()
    scan = simulate(sc, SYNC_ROUNDS, engine="scan", clients=clients)
    fused = simulate_dumped(out / "ir_width", sc, SYNC_ROUNDS,
                            engine="scan_pallas", clients=clients)
    check(fused.agg_backend == "pallas_structured",
          f"scan_pallas ran {fused.agg_backend!r}, not 'pallas_structured'")
    cmp_fused = compare(fused.params, scan.params, run="scan_pallas",
                        ref="scan", agg_backend=fused.agg_backend)
    cmp_fused["mosaic"] = check_mosaic_kernels(out / "ir_width")
    check_losses(scan, "scan")
    return {"comparisons": [cmp_fused], **check_losses(fused, "scan_pallas")}


def phase_async(out: pathlib.Path) -> dict:
    from repro.fl import AsyncBuffered, FLScenario, simulate
    spec = fleet()
    sc = FLScenario(fleet=spec, timing=AsyncBuffered(
        buffer_size=ASYNC_BUFFER, staleness_exp=0.5))
    clients = spec.build_clients()
    eager = simulate(sc, ASYNC_WINDOWS, engine="eager", clients=clients)
    scan = simulate(sc, ASYNC_WINDOWS, engine="scan", clients=clients)
    losses = [r.loss for r in scan.records]
    check(all(v is not None and math.isfinite(v) for v in losses),
          f"async window losses not finite: {losses}")
    return {"comparisons": [compare(scan.params, eager.params, run="scan",
                                    ref="eager",
                                    agg_backend=scan.agg_backend)],
            "loss_first": losses[0], "loss_last": losses[-1]}


def phase_faults_durable(out: pathlib.Path) -> dict:
    import jax
    import numpy as np

    from repro.fl import FaultPolicy, FLScenario, LocalTraining, simulate
    spec = fleet()
    sc = FLScenario(fleet=spec,
                    local=LocalTraining(mode="fedavg", local_steps=2,
                                        local_lr=0.1),
                    faults=FaultPolicy(seed=9, churn_rate=0.1,
                                       corrupt_rate=0.01))
    clients = spec.build_clients()
    full = simulate(sc, FAULT_ROUNDS, engine="scan", clients=clients)
    check(all(np.isfinite(np.asarray(x)).all()
              for x in jax.tree.leaves(full.params)),
          "params not finite under corrupted uploads")
    n_corrupt = sum(r.n_corrupt for r in full.records)
    check(n_corrupt > 0, "no upload was corrupted")
    ckpt = out / "ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    simulate(sc, CHECKPOINT_EVERY, engine="scan", clients=clients,
             checkpoint_every=CHECKPOINT_EVERY, checkpoint_dir=str(ckpt))
    resumed = simulate(sc, FAULT_ROUNDS, engine="scan", clients=clients,
                       checkpoint_every=CHECKPOINT_EVERY,
                       resume_from=str(ckpt))
    cmp = compare(resumed.params, full.params, run="resumed",
                  ref="uninterrupted", agg_backend=resumed.agg_backend)
    check(resumed.records == full.records,
          "resumed records differ from the uninterrupted run's")
    return {"comparisons": [cmp], "n_corrupt": n_corrupt,
            **check_losses(full, "faulty scan")}


def _shard_fleet():
    from repro.fl import FLScenario
    spec = fleet(SHARD_CLIENTS, edges=SHARD_EDGES)
    return FLScenario(fleet=spec), spec.build_clients()


def phase_hierarchical(out: pathlib.Path) -> dict:
    from repro.fl import simulate
    sc, clients = _shard_fleet()
    eager = simulate(sc, SHARD_ROUNDS, engine="eager", clients=clients)
    scan = simulate(sc, SHARD_ROUNDS, engine="scan", clients=clients)
    return {"clients": SHARD_CLIENTS, "edges": SHARD_EDGES,
            "comparisons": [compare(scan.params, eager.params, run="scan",
                                    ref="eager",
                                    agg_backend=scan.agg_backend)],
            **check_losses(scan, "hierarchical scan")}


def phase_hierarchical_mesh(out: pathlib.Path) -> dict:
    """The --chips 4 path: phase 5's fleet sharded over the edge mesh
    against the same fleet unsharded on one chip."""
    import jax

    from repro.fl import make_edge_mesh, simulate
    sc, clients = _shard_fleet()
    one = simulate(sc, SHARD_ROUNDS, engine="scan", clients=clients)
    mesh = make_edge_mesh(SHARD_EDGES)
    sharded = simulate(sc, SHARD_ROUNDS, engine="scan", clients=clients,
                       mesh=mesh)
    devs = {d.id for c in sharded.server.cohorts
            for x in jax.tree.leaves(c.data)
            for d in x.sharding.device_set}
    n_dev = len(jax.devices())
    check(mesh.devices.size == n_dev,
          f"edge mesh holds {mesh.devices.size} of {n_dev} devices")
    check(len(devs) == n_dev,
          f"edge grids span {len(devs)} devices, not {n_dev}")
    return {"clients": SHARD_CLIENTS, "edges": SHARD_EDGES,
            "mesh_devices": len(devs),
            "comparisons": [compare(sharded.params, one.params,
                                    run="sharded", ref="one_chip",
                                    agg_backend=sharded.agg_backend)],
            **check_losses(sharded, "sharded scan")}


def phase_decoder(out: pathlib.Path) -> dict:
    import jax

    from repro import optim
    from repro.configs import get_config
    from repro.core import TrainState, make_hetero_train_step
    from repro.core.compression import default_tier_plans
    from repro.models import get_model
    cfg = dataclasses.replace(get_config(DECODER_ARCH),
                              num_layers=DECODER_LAYERS)
    model = get_model(cfg)
    opt = optim.adamw(3e-4)
    key = jax.random.PRNGKey(0)
    state = TrainState.create(model, opt, key)
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    batch = {"tokens": jax.random.randint(
        jax.random.fold_in(key, 1),
        (DECODER_TIERS, DECODER_SEQS, DECODER_TOKENS), 0, cfg.vocab_size)}
    step = jax.jit(make_hetero_train_step(
        model, opt, default_tier_plans(DECODER_TIERS)), donate_argnums=(0,))
    compiled = step.lower(state, batch).compile()
    mem = compiled.memory_analysis()
    losses = []
    for _ in range(DECODER_STEPS):
        state, m = compiled(state, batch)
        losses.append(float(m["loss"]))
    jax.block_until_ready(state)
    check(all(math.isfinite(v) for v in losses),
          f"decoder losses not finite: {losses}")
    # random init gives near-uniform logits: the first loss sits at
    # ln(vocab); repeating one batch must then lower it
    check(abs(losses[0] - math.log(cfg.vocab_size)) < 1.0,
          f"first loss {losses[0]} far from ln(vocab) "
          f"{math.log(cfg.vocab_size)}")
    check(losses[-1] < losses[0],
          f"decoder loss did not fall: {losses}")
    return {"arch": DECODER_ARCH, "layers": DECODER_LAYERS,
            "params": n_params, "dtype": cfg.dtype,
            "tokens_per_step": DECODER_TIERS * DECODER_SEQS
            * (DECODER_TOKENS - 1),
            "losses": losses, "comparisons": [],
            "memory_analysis": {
                k: getattr(mem, k) for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "alias_size_in_bytes", "temp_size_in_bytes",
                    "generated_code_size_in_bytes")
                if hasattr(mem, k)}}


ONE_CHIP = (("1_masked_sync", phase_masked_sync),
            ("2_width_sliced", phase_width_sliced),
            ("3_async_buffered", phase_async),
            ("4_faults_durable", phase_faults_durable),
            ("5_hierarchical", phase_hierarchical),
            ("6_decoder_tier_step", phase_decoder))
FOUR_CHIPS = (("5_hierarchical_mesh", phase_hierarchical_mesh),)


def run(phases, out: pathlib.Path) -> None:
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    out.mkdir(parents=True, exist_ok=True)
    for name, fn in phases:
        run_phase(name, lambda: fn(out))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the hierarchical fleet sharded "
                         "over four chips against one")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="directory for checkpoints and IR dumps")
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is {backend!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    n_dev = len(jax.devices())
    if n_dev < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {n_dev}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(json.dumps({"compile_cache": cache}), flush=True)
    run(FOUR_CHIPS if args.chips == 4 else ONE_CHIP, pathlib.Path(args.out))
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
