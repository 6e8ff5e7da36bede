"""Federated-round benches: the paper's Table-equivalent system numbers.

Fleets and runtimes come from the declarative scenario API (DESIGN.md
§11): every fleet is a ``FleetSpec`` and every server is assembled by
``build_server`` — no bespoke fleet-construction loops.

- fl/round_{mode}: wall time of one client-granular federated round on the
  paper MLP fleet (4 tiers), derived = final loss after 30 rounds.
- fl/scale_{path}_{n}: clients-vs-wall-time scaling curve at n clients /
  4 plans — per-client loop vs. cohort-vectorized runtime (DESIGN.md §9),
  derived = per-round loss + (for the cohort rows) speedup over the loop.
- fl/api_{path}_{n}: factory-built cohort server (``build_server``) vs
  direct ``CohortFLServer`` construction at n clients — the scenario
  layer must keep O(#plans) dispatches and within-noise round time.
- fl/engine_{path}_{n}: the multi-round scan engine (DESIGN.md §12) vs
  the eager cohort loop at n clients / 4 plans / 50 rounds — one
  donated-buffer program per chunk must deliver ≥5x rounds/sec over the
  eager loop (rows for the bit-identical sequential backend and the
  fused-Pallas-kernel aggregation backend), derived = rounds/sec,
  speedup over eager and the one-off chunk compile cost (trajectory
  bit-identity vs eager is pinned by tests/test_engine.py).
- fl/async_{path}_{n}: simulated (virtual-clock) time for the async
  staleness-aware runtime (DESIGN.md §10) to reach the sync-wait
  baseline's round-50 loss on the heterogeneous hub/mid/low 256-client /
  4-plan fleet, derived = sim-time speedup + staleness profile.
- fl/async_scan_{path}_{n}: the window-scan async engine (DESIGN.md §14)
  vs eager ``AsyncFLServer.step()`` windows on the same 256-client fleet
  at buffer 64 — the host-materialized schedule compiled into one
  donated-buffer ``lax.scan`` must deliver ≥5x windows/sec over the
  eager group loop, derived = windows/sec, speedup and the one-off chunk
  compile cost (window-trajectory bit-identity vs eager is pinned by
  tests/test_engine.py).
- fl/submodel_{path}_{n}: masked emulation vs structured width slicing
  (DESIGN.md §13) at matched tier budget — one jitted cohort STEP over
  64 clients on a 0.25 plan and a 256-wide MLP (wide enough that matmul
  FLOPs, not dispatch, dominate). The width-sliced step must be >=2x
  faster than the masked full-shape step, and its Eq. (1) payload is the
  exact sliced parameter count; derived = loss, payload bytes, speedup.
- fl/submodel_pallas_{path}_{n}: fused prefix-block aggregation
  (DESIGN.md §15) vs the sequential per-tier scatter inside the scan
  engine on the STRUCTURED width-sliced fleet at n clients / 4 plans /
  50 rounds — the ``structured_scatter`` kernel must deliver >=1x the
  sequential-scatter rounds/sec with a bit-identical trajectory,
  derived = rounds/sec, reported agg backend, compile cost and (for the
  fused row) speedup over the sequential scatter.
- fl/fault_{path}_{n}: fault-injection overhead (DESIGN.md §17) — the
  scan engine at n clients / 4 plans / 25 rounds, clean vs a
  FaultPolicy with 10% churn + 1% corrupted uploads and the
  finite-guard quarantine. Both arms run mode=fedavg through the
  sequential-aggregation path, so the delta isolates the fault
  machinery (host mask sampling, corruption injection, the isfinite
  quarantine and the coverage denominator); derived = rounds/sec and
  the overhead ratio, which tests/test_bench_record.py floors at 1.10.
- fl/shard_{path}_{n}: the sharded hierarchical fleet runtime
  (DESIGN.md §16) at 100k clients / 4 plans / 8 edge groups through the
  scan engine — unsharded vs sharded over the edge mesh
  (``shard_fleet``; on CPU the mesh comes from the forced host devices
  set up below). Derived = rounds/sec, scaling efficiency of the
  sharded run, and the analytic per-round edge→hub traffic, which is
  independent of client count.
- fl/eq1_{tier}: the paper's Eq. (1) analytic round time per device tier
  for the granite-3-2b model, derived = component breakdown.
- fl/tierstep_{arch}: one datacenter tier-scanned hetero train step
  (smoke config), derived = loss delta over 5 steps.
"""
from __future__ import annotations

import os
import sys

if "jax" not in sys.modules and "XLA_FLAGS" not in os.environ:
    # the fl/shard_* rows exercise a real multi-device mesh on CPU; the
    # forced host device count must land before the first jax import
    # (same recipe as launch/dryrun.py). An inherited XLA_FLAGS or an
    # already-imported jax wins — the rows then run on whatever exists.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import time
import types

import jax

from repro import optim
from repro.configs import get_smoke_config
from repro.configs.paper_mlp import config as mlp_config
from repro.core import TrainState, make_hetero_train_step
from repro.core.compression import DEVICE_TIERS, default_tier_plans
from repro.core.federated import CohortFLServer
from repro.core.heterogeneity import PROFILES, round_time
from repro.core.scenario import (AsyncBuffered, FleetSpec, FLScenario,
                                 LocalTraining, build_server)
from repro.models import get_model, mlp

KEY = jax.random.PRNGKey(0)
# one shared loss_fn identity so the per-plan jit caches in core.federated
# are hit across all fl/* benches instead of recompiling per section
MLP_MODEL = types.SimpleNamespace(loss_fn=mlp.loss_fn)

SCALE_POPULATIONS = (32, 256)
SCALE_TIERS = ("hub", "high", "mid", "low")     # 4 plans


def _fleet_spec(n: int, profiles: tuple = SCALE_TIERS) -> FleetSpec:
    """n clients cycling over the 4 SCALE_TIERS plans on equal IID shards
    of 16 samples each, with profiles cycling independently."""
    return FleetSpec.cycling(SCALE_TIERS, n, profiles=profiles,
                             samples_per_client=16)


def _mlp_server(scenario: FLScenario, clients=None):
    return build_server(scenario, MLP_MODEL, optim.sgd(1.0),
                        mlp.init(KEY, mlp_config()), clients=clients)


def _timed_rounds(srv, rounds: int):
    """(per-round wall micros, last record) after a compile warm-up round."""
    srv.round()                                  # compile
    t0 = time.perf_counter()
    for _ in range(rounds):
        rec = srv.round()
    return (time.perf_counter() - t0) / rounds * 1e6, rec


def _scaling_rows(rounds: int = 3) -> list[tuple]:
    """Per-client loop vs. cohort runtime at growing population sizes.

    The loop pays one dispatch + one host sync per client; the cohort path
    pays one vmapped dispatch per plan and one sync per round, so its
    wall time is ~flat in the population while the loop's grows linearly.
    """
    rows = []
    for n in SCALE_POPULATIONS:
        clients = _fleet_spec(n).build_clients()
        times = {}
        for path, runtime in (("loop", "client"), ("cohort", "cohort")):
            srv = _mlp_server(FLScenario(fleet=_fleet_spec(n),
                                         runtime=runtime), clients=clients)
            times[path], rec = _timed_rounds(srv, rounds)
            derived = f"loss={rec['loss']:.4f}"
            if path == "cohort":
                derived += f";speedup_vs_loop={times['loop'] / times['cohort']:.1f}x"
            rows.append((f"fl/scale_{path}_{n}", times[path], derived))
    return rows


API_N = 256
API_ROUNDS = 5


def _api_overhead_rows() -> list[tuple]:
    """The scenario layer must be free: a factory-built cohort server
    keeps O(#plans) vmapped dispatches per round and within-noise round
    time vs direct CohortFLServer construction at 256 clients."""
    spec = _fleet_spec(API_N)
    clients = spec.build_clients()
    params = mlp.init(KEY, mlp_config())

    direct = CohortFLServer.from_clients(
        clients, model=MLP_MODEL, optimizer=optim.sgd(1.0), params=params)
    us_direct, rec_d = _timed_rounds(direct, API_ROUNDS)

    factory = build_server(FLScenario(fleet=spec), MLP_MODEL,
                           optim.sgd(1.0), params, clients=clients)
    us_api, rec_a = _timed_rounds(factory, API_ROUNDS)
    return [
        (f"fl/api_direct_{API_N}", us_direct, f"loss={rec_d['loss']:.4f}"),
        (f"fl/api_factory_{API_N}", us_api,
         f"loss={rec_a['loss']:.4f};vs_direct={us_direct / us_api:.2f}x;"
         f"cohort_dispatches={len(factory.cohorts)}"),
    ]


ENGINE_N = 256
ENGINE_ROUNDS = 50


def _engine_rows() -> list[tuple]:
    """Scan engine vs the eager cohort loop at 256 clients / 4 plans /
    50 rounds (the ISSUE-4 acceptance config). Timing excludes the
    one-off chunk compile (reported in the derived column); the engine's
    measured chunk reuses the cached program, which is the steady-state
    regime the engine exists for."""
    from repro.core.engine import ScanEngine
    spec = _fleet_spec(ENGINE_N)
    clients = spec.build_clients()
    scenario = FLScenario(fleet=spec)
    rows = []

    eager = _mlp_server(scenario, clients=clients)
    us_eager, rec_e = _timed_rounds(eager, ENGINE_ROUNDS)
    eager_rps = 1e6 / us_eager
    rows.append((f"fl/engine_eager_{ENGINE_N}", us_eager,
                 f"rounds_per_sec={eager_rps:.1f};"
                 f"loss_round51={rec_e['loss']:.4f}"))

    for path, agg in (("scan", "sequential"), ("pallas", "pallas")):
        srv = _mlp_server(scenario, clients=clients)
        eng = ScanEngine(srv, chunk_rounds=ENGINE_ROUNDS, agg=agg)
        t0 = time.perf_counter()
        # warm-up covers the same 51 rounds as the eager row (1 compile
        # round + 50 timed there), so the derived losses are the SAME
        # round's record — equal for the bit-identical scan backend
        warm = eng.run(ENGINE_ROUNDS + 1)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.run(ENGINE_ROUNDS)
        us = (time.perf_counter() - t0) / ENGINE_ROUNDS * 1e6
        rows.append((f"fl/engine_{path}_{ENGINE_N}", us,
                     f"rounds_per_sec={1e6 / us:.1f};"
                     f"speedup_vs_eager={us_eager / us:.1f}x;"
                     f"compile_s={compile_s:.2f};"
                     f"loss_round51={warm[-1]['loss']:.4f}"))
    return rows


SUBMODEL_N = 64
SUBMODEL_HIDDEN = 256
SUBMODEL_STEPS = 20


def _submodel_rows() -> list[tuple]:
    """Structured width slicing vs masked emulation (the ISSUE-5
    acceptance config): the device-side cohort step — the unit a tier
    actually pays per round — on one 64-client 0.25-budget cohort over a
    256-wide MLP. The masked step runs full-shape matmuls plus the
    magnitude-threshold bisection; the width-sliced step runs the dense
    (ceil(0.25*d_in), ceil(0.25*d_out)) sub-model, ~1/16th the matmul
    FLOPs. Eq. (1) payload comes from the exact sliced counts."""
    import jax.numpy as jnp

    from repro.configs.paper_mlp import MLPConfig
    from repro.core.compression import CompressionPlan
    from repro.core.federated import _cohort_step_jit
    from repro.data import make_gaussian_dataset, partition_iid, stack_shards

    cfg = MLPConfig(name="paper-mlp-wide", hidden=SUBMODEL_HIDDEN,
                    num_layers=4)
    params = mlp.init(KEY, cfg)
    data = make_gaussian_dataset(KEY, SUBMODEL_N * 16)
    batches = stack_shards(partition_iid(KEY, data, SUBMODEL_N))
    part = jnp.ones((SUBMODEL_N,), jnp.float32)
    masked = CompressionPlan("low25", density=0.25, quant="fp8_e5m2")
    plans = {"masked": masked, "width": masked.as_width_sliced()}
    payload = {path: round_time(params, plan, PROFILES["low"],
                                16)["payload_bytes"]
               for path, plan in plans.items()}
    rows, times = [], {}
    for path, plan in plans.items():
        fn = _cohort_step_jit(MLP_MODEL.loss_fn, plan, "fedsgd", 5, 0.1,
                              None)
        g, _, l_sum, _ = fn(params, batches, part, ())      # compile
        jax.block_until_ready(g)
        t0 = time.perf_counter()
        for _ in range(SUBMODEL_STEPS):
            g, _, l_sum, _ = fn(params, batches, part, ())
        jax.block_until_ready(g)
        times[path] = (time.perf_counter() - t0) / SUBMODEL_STEPS * 1e6
        derived = (f"loss={float(l_sum) / SUBMODEL_N:.4f};"
                   f"payload_bytes={payload[path]:.0f}")
        if path == "width":
            derived += (f";speedup_vs_masked="
                        f"{times['masked'] / times['width']:.1f}x;"
                        f"payload_vs_masked="
                        f"{payload['masked'] / payload['width']:.1f}x")
        rows.append((f"fl/submodel_{path}_{SUBMODEL_N}", times[path],
                     derived))
    return rows


def _submodel_pallas_rows() -> list[tuple]:
    """Fused prefix-block aggregation vs the sequential scatter on a
    STRUCTURED fleet (the ISSUE-7 acceptance config): the scan engine at
    256 clients / 4 width-sliced plans / 50 rounds, agg="sequential"
    (per-tier ``scatter_accumulate`` chain) vs agg="pallas" (one
    ``structured_scatter`` kernel pass per leaf, DESIGN.md §15). Same
    warm+timed protocol as the fl/engine_* rows; the two trajectories
    are bit-identical (pinned by tests/test_structured.py), so the
    derived losses must match."""
    from repro.core.engine import ScanEngine
    spec = _fleet_spec(ENGINE_N)
    clients = spec.build_clients()
    scenario = FLScenario(fleet=spec, local=LocalTraining(submodel="width"))
    rows, rps = [], {}
    for path, agg in (("scan", "sequential"), ("fused", "pallas")):
        srv = _mlp_server(scenario, clients=clients)
        eng = ScanEngine(srv, chunk_rounds=ENGINE_ROUNDS, agg=agg)
        t0 = time.perf_counter()
        warm = eng.run(ENGINE_ROUNDS + 1)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.run(ENGINE_ROUNDS)
        us = (time.perf_counter() - t0) / ENGINE_ROUNDS * 1e6
        rps[path] = 1e6 / us
        derived = (f"rounds_per_sec={rps[path]:.1f};"
                   f"agg_backend={eng.agg_backend};"
                   f"compile_s={compile_s:.2f};"
                   f"loss_round51={warm[-1]['loss']:.4f}")
        if path == "fused":
            derived += f";speedup_vs_scan={rps['fused'] / rps['scan']:.2f}x"
        rows.append((f"fl/submodel_pallas_{path}_{ENGINE_N}", us, derived))
    return rows


FAULT_N = 256
FAULT_ROUNDS = 25
FAULT_CHURN = 0.1
FAULT_CORRUPT = 0.01


def _fault_rows() -> list[tuple]:
    """Fault-injection overhead (the ISSUE-9 acceptance config): clean
    vs 10% churn + 1% corrupted uploads + finite-guard quarantine, both
    arms mode=fedavg through the scan engine's sequential-aggregation
    path (upload faults need the per-coordinate coverage denominator,
    which the fused pallas backends don't carry). Same warm+timed
    protocol as the fl/engine_* rows; the overhead ratio is the record's
    ``fault_overhead`` and must stay <= 1.10."""
    from repro.core.engine import ScanEngine
    from repro.core.faults import FaultPolicy
    spec = _fleet_spec(FAULT_N)
    clients = spec.build_clients()
    local = LocalTraining(mode="fedavg", local_steps=2, local_lr=0.1)
    arms = (
        ("clean", FLScenario(fleet=spec, local=local)),
        ("faulty", FLScenario(fleet=spec, local=local,
                              faults=FaultPolicy(seed=9,
                                                 churn_rate=FAULT_CHURN,
                                                 corrupt_rate=FAULT_CORRUPT))),
    )
    rows, us = [], {}
    for path, scenario in arms:
        srv = _mlp_server(scenario, clients=clients)
        eng = ScanEngine(srv, chunk_rounds=FAULT_ROUNDS, agg="sequential")
        t0 = time.perf_counter()
        warm = eng.run(FAULT_ROUNDS + 1)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        recs = eng.run(FAULT_ROUNDS)
        us[path] = (time.perf_counter() - t0) / FAULT_ROUNDS * 1e6
        derived = (f"rounds_per_sec={1e6 / us[path]:.1f};"
                   f"compile_s={compile_s:.2f};"
                   f"loss_round{FAULT_ROUNDS + 1}={warm[-1]['loss']:.4f}")
        if path == "faulty":
            n_corr = sum(r["n_corrupt"] for r in warm + recs)
            n_part = sum(r["n_participants"] for r in recs)
            derived += (f";overhead_vs_clean={us['faulty'] / us['clean']:.3f}x;"
                        f"churn={FAULT_CHURN};corrupt={FAULT_CORRUPT};"
                        f"n_corrupt={n_corr};"
                        f"participants_per_round={n_part / FAULT_ROUNDS:.1f}")
        rows.append((f"fl/fault_{path}_{FAULT_N}", us[path], derived))
    return rows


SHARD_N = 100_000
SHARD_EDGES = 8
SHARD_ROUNDS = 10


def _shard_rows() -> list[tuple]:
    """Sharded hierarchical fleet runtime (DESIGN.md §16, the ISSUE-8
    acceptance config): a 100k-client / 4-plan / 8-edge-group topology
    fleet through the scan engine, unsharded (one device) vs sharded
    over the edge mesh (``shard_fleet`` — placement only, the program
    and trajectory are identical; the forced host devices set up at
    module import stand in for real accelerators). Timing excludes the
    one-off chunk compile, as in the fl/engine_* rows. The derived
    cross_shard_bytes is the ANALYTIC per-round edge→hub traffic — a
    function of plans and edge count only, independent of the 100k
    client count (pinned by tests/test_topology.py)."""
    from repro.core.engine import ScanEngine
    from repro.core.topology import make_edge_mesh, shard_fleet
    spec = FleetSpec.cycling(SCALE_TIERS, SHARD_N, samples_per_client=16,
                             edges=SHARD_EDGES)
    scenario = FLScenario(fleet=spec)
    clients = spec.build_clients()
    mesh = make_edge_mesh(SHARD_EDGES)
    xbytes = _shard_xbytes()
    rows, rps = [], {}
    for path in ("scan", "mesh"):
        srv = _mlp_server(scenario, clients=clients)
        if path == "mesh":
            shard_fleet(srv, mesh)
        eng = ScanEngine(srv, chunk_rounds=SHARD_ROUNDS)
        t0 = time.perf_counter()
        warm = eng.run(SHARD_ROUNDS + 1)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.run(SHARD_ROUNDS)
        us = (time.perf_counter() - t0) / SHARD_ROUNDS * 1e6
        rps[path] = 1e6 / us
        derived = (f"rounds_per_sec={rps[path]:.2f};"
                   f"edges={SHARD_EDGES};"
                   f"mesh_devices={mesh.devices.size if path == 'mesh' else 1};"
                   f"cross_shard_bytes={xbytes:.0f};"
                   f"compile_s={compile_s:.2f};"
                   f"loss_round{SHARD_ROUNDS + 1}={warm[-1]['loss']:.4f}")
        if path == "mesh":
            derived += (f";scaling_efficiency="
                        f"{rps['mesh'] / rps['scan']:.2f}")
        rows.append((f"fl/shard_{path}_{SHARD_N}", us, derived))
    return rows


def _shard_xbytes() -> float:
    """The shard tier's analytic edge→hub bytes per round — host-only
    shape arithmetic on the fleet's distinct plans."""
    from repro.core.topology import cross_shard_bytes
    plans = []
    for t in SCALE_TIERS:
        if DEVICE_TIERS[t] not in plans:
            plans.append(DEVICE_TIERS[t])
    return cross_shard_bytes(mlp.init(KEY, mlp_config()), plans,
                             SHARD_EDGES)


ASYNC_N = 256
ASYNC_ROUNDS = 50
ASYNC_BUFFER = 64
# speed-heterogeneous profile mix: the sync round blocks on the Pi-Zero
# class tier, which is exactly what the async runtime stops paying for
ASYNC_PROFILES = ("hub", "mid", "mid", "low")


def _async_rows() -> list[tuple]:
    """Async vs sync-wait on the 256-client / 4-plan hub/mid/low fleet:
    virtual-clock seconds to reach the sync baseline's round-50 loss."""
    spec = _fleet_spec(ASYNC_N, profiles=ASYNC_PROFILES)
    clients = spec.build_clients()
    rows = []

    sync = _mlp_server(FLScenario(fleet=spec), clients=clients)
    us, rec = _timed_rounds(sync, ASYNC_ROUNDS - 1)
    target = rec["loss"]
    sim_sync = sum(r["round_wall_time"] for r in sync.history)
    rows.append((f"fl/async_syncwait_{ASYNC_N}", us,
                 f"loss_round50={target:.4f};sim_T={sim_sync:.3f}s"))

    asy = _mlp_server(
        FLScenario(fleet=spec,
                   timing=AsyncBuffered(buffer_size=ASYNC_BUFFER,
                                        staleness_exp=0.5)),
        clients=clients)
    asy.step()                                   # compile
    t0 = time.perf_counter()
    sim_async, n_win = None, 1
    # window losses are per-buffer means (noisier than full-fleet means),
    # so the crossing check uses a 4-window moving average
    cap = ASYNC_ROUNDS * ASYNC_N // ASYNC_BUFFER * 4
    while n_win < cap:
        rec = asy.step()
        n_win += 1
        recent = [r["loss"] for r in asy.history[-4:]]
        if len(recent) == 4 and sum(recent) / 4 <= target:
            sim_async = rec["t"]
            break
    us_a = (time.perf_counter() - t0) / (n_win - 1) * 1e6
    stale = [r["staleness_mean"] for r in asy.history]
    derived = (f"sim_T_to_loss={sim_async:.3f}s;"
               f"sim_speedup={sim_sync / sim_async:.1f}x"
               if sim_async is not None
               else f"target_not_reached_in_{n_win}_windows")
    rows.append((f"fl/async_buf{ASYNC_BUFFER}_{ASYNC_N}", us_a,
                 derived + f";windows={n_win};"
                 f"staleness_mean={sum(stale) / len(stale):.2f}"))
    return rows


ASYNC_SCAN_WINDOWS = 50


def _async_scan_rows() -> list[tuple]:
    """Window-scan engine vs eager async windows at 256 clients / 4
    plans / buffer 64 (the ISSUE-6 acceptance config). As with the sync
    engine rows, timing excludes the one-off chunk compile (reported in
    the derived column): the engine's measured run reuses the cached
    program, the steady-state regime it exists for.

    Protocol note: the eager row measures a FRESH schedule's cost —
    one warm-up window, then 50 timed windows that still include the
    eager path's per-group-structure jit traces, because a fresh async
    run always pays them (window group signatures vary, unlike the
    sync engine's structurally identical rounds). ``jax.clear_caches``
    pins that protocol regardless of which bench sections ran earlier
    in the process. Once every structure has been seen, the eager path
    amortizes to ~6 ms/window of pure dispatch overhead — the engine's
    ~1.5 ms/window still beats that steady state ~4x (DESIGN.md §14)."""
    from repro.core.engine import WindowScanEngine
    jax.clear_caches()
    spec = _fleet_spec(ASYNC_N, profiles=ASYNC_PROFILES)
    clients = spec.build_clients()
    scenario = FLScenario(fleet=spec,
                          timing=AsyncBuffered(buffer_size=ASYNC_BUFFER,
                                               staleness_exp=0.5))
    rows = []

    eager = _mlp_server(scenario, clients=clients)
    eager.step()                                 # compile
    t0 = time.perf_counter()
    for _ in range(ASYNC_SCAN_WINDOWS):
        rec_e = eager.step()
    us_eager = (time.perf_counter() - t0) / ASYNC_SCAN_WINDOWS * 1e6
    rows.append((f"fl/async_scan_eager_{ASYNC_N}", us_eager,
                 f"windows_per_sec={1e6 / us_eager:.1f};"
                 f"loss_w51={rec_e['loss']:.4f}"))

    srv = _mlp_server(scenario, clients=clients)
    eng = WindowScanEngine(srv, chunk_windows=ASYNC_SCAN_WINDOWS)
    t0 = time.perf_counter()
    # warm-up covers the same 51 windows as the eager row (1 compile
    # window + 50 timed there), so the derived losses are the SAME
    # window's record — equal because the trajectories are bit-identical
    warm = eng.run(ASYNC_SCAN_WINDOWS + 1)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.run(ASYNC_SCAN_WINDOWS)
    us = (time.perf_counter() - t0) / ASYNC_SCAN_WINDOWS * 1e6
    rows.append((f"fl/async_scan_engine_{ASYNC_N}", us,
                 f"windows_per_sec={1e6 / us:.1f};"
                 f"speedup_vs_eager={us_eager / us:.1f}x;"
                 f"compile_s={compile_s:.2f};"
                 f"loss_w51={warm[-1]['loss']:.4f}"))
    return rows


def run() -> list[tuple]:
    rows = []
    tiers = ("hub", "high", "mid", "low")

    for mode in ("fedsgd", "fedavg"):
        srv = _mlp_server(FLScenario(
            fleet=FleetSpec(tiers=tiers, n_samples=1600),
            local=LocalTraining(mode=mode, local_steps=5, local_lr=1.0),
            runtime="client"))
        us, rec = _timed_rounds(srv, 30)
        rows.append((f"fl/round_{mode}", us,
                     f"loss_after_30={rec['loss']:.4f};"
                     f"upload_bytes={rec['total_upload_bytes']:.0f}"))

    rows += _scaling_rows()
    rows += _api_overhead_rows()
    rows += _engine_rows()
    rows += _async_rows()
    rows += _async_scan_rows()
    rows += _submodel_rows()
    rows += _submodel_pallas_rows()
    rows += _fault_rows()
    rows += _shard_rows()

    gcfg = get_smoke_config("granite-3-2b")
    gmodel = get_model(gcfg)
    gparams = gmodel.init(KEY)
    for tier in ("hub", "mid", "embedded"):
        t = round_time(gparams, DEVICE_TIERS[tier], PROFILES[tier], 256)
        rows.append((f"fl/eq1_{tier}", t["T"] * 1e6,
                     f"T_local={t['T_local']:.3f}s;T_up={t['T_upload']:.3f}s;"
                     f"T_down={t['T_download']:.3f}s;"
                     f"payload={t['payload_bytes']:.0f}B"))

    for arch in ("granite-3-2b", "granite-moe-1b-a400m", "zamba2-2.7b"):
        acfg = get_smoke_config(arch)
        amodel = get_model(acfg)
        opt = optim.adamw(3e-3)
        state = TrainState.create(amodel, opt, KEY)
        step = jax.jit(make_hetero_train_step(amodel, opt,
                                              default_tier_plans(4)))
        batch = {"tokens": jax.random.randint(KEY, (4, 2, 33), 0,
                                              acfg.vocab_size)}
        state, m0 = step(state, batch)   # compile
        t0 = time.perf_counter()
        loss0 = float(m0["loss"])
        for _ in range(5):
            state, m = step(state, batch)
        jax.block_until_ready(state)
        us = (time.perf_counter() - t0) / 5 * 1e6
        rows.append((f"fl/tierstep_{arch}", us,
                     f"loss_delta_5steps={loss0 - float(m['loss']):.4f}"))
    return rows


def _commit_hash() -> tuple:
    """(HEAD sha, dirty-tree flag) of the checkout the bench ACTUALLY ran
    in. ``git rev-parse HEAD`` is asked first — not ``GITHUB_SHA`` — so a
    locally regenerated record carries the vintage of the tree that
    produced the numbers rather than whatever CI env var leaked into the
    shell; the porcelain dirty flag marks records produced mid-edit.
    tests/test_bench_record.py pins both fields on the committed record."""
    import os
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def _git(*args):
        return subprocess.run(["git", *args], capture_output=True,
                              text=True, check=True, cwd=root).stdout

    try:
        sha = _git("rev-parse", "HEAD").strip()
        dirty = bool(_git("status", "--porcelain").strip())
        return sha, dirty
    except Exception:
        return os.environ.get("GITHUB_SHA", "unknown"), False


def emit_json(path: str) -> dict:
    """The machine-readable perf record CI tracks from PR 4 on: the
    fl/engine_* rows (the ISSUE-4 acceptance numbers), from PR 5 the
    fl/submodel_* rows (masked vs width-sliced cohort step), from PR 6
    the fl/async_scan_* rows (window-scan async engine vs eager
    windows), from PR 7 the fl/submodel_pallas_* rows (fused
    prefix-block aggregation vs sequential scatter on the structured
    fleet), and from PR 8 the fl/shard_* rows (100k-client sharded
    hierarchical fleet, DESIGN.md §16), and from PR 9 the fl/fault_*
    rows (fault machinery overhead vs the clean scan path, DESIGN.md
    §17), plus commit provenance (HEAD
    sha + dirty flag), written to ``path``. Runs ONLY those sections —
    cheap enough for every CI run; ``make bench-fl`` is the local entry
    point."""
    import json
    import platform
    rows = (_engine_rows() + _async_scan_rows() + _submodel_rows()
            + _submodel_pallas_rows() + _fault_rows() + _shard_rows())
    by_name = {name: {"us_per_call": us, "derived": derived}
               for name, us, derived in rows}

    def _rps(name):
        return 1e6 / by_name[f"fl/engine_{name}_{ENGINE_N}"]["us_per_call"]

    def _wps(name):
        return 1e6 / by_name[
            f"fl/async_scan_{name}_{ASYNC_N}"]["us_per_call"]

    def _sub_us(name):
        return by_name[f"fl/submodel_{name}_{SUBMODEL_N}"]["us_per_call"]

    def _srps(name):
        return 1e6 / by_name[
            f"fl/submodel_pallas_{name}_{ENGINE_N}"]["us_per_call"]

    def _shrps(name):
        return 1e6 / by_name[f"fl/shard_{name}_{SHARD_N}"]["us_per_call"]

    def _fus(name):
        return by_name[f"fl/fault_{name}_{FAULT_N}"]["us_per_call"]

    commit, dirty = _commit_hash()
    record = {
        "kind": "fl_bench",
        "commit": commit,
        "dirty": dirty,
        "backend": jax.default_backend(),
        "python": platform.python_version(),
        "config": {"clients": ENGINE_N, "plans": len(SCALE_TIERS),
                   "rounds": ENGINE_ROUNDS,
                   "async_buffer": ASYNC_BUFFER,
                   "async_windows": ASYNC_SCAN_WINDOWS,
                   "shard_clients": SHARD_N, "shard_edges": SHARD_EDGES,
                   "shard_devices": len(jax.devices()),
                   "shard_rounds": SHARD_ROUNDS,
                   "fault_clients": FAULT_N, "fault_rounds": FAULT_ROUNDS},
        "rounds_per_sec": {"eager": _rps("eager"), "scan": _rps("scan"),
                           "pallas": _rps("pallas")},
        "rounds_per_sec_structured": {"scan": _srps("scan"),
                                      "fused": _srps("fused")},
        "rounds_per_sec_sharded": {"scan": _shrps("scan"),
                                   "mesh": _shrps("mesh")},
        "windows_per_sec": {"eager": _wps("eager"),
                            "scan": _wps("engine")},
        "speedup_scan_vs_eager": _rps("scan") / _rps("eager"),
        "speedup_async_scan_vs_eager": _wps("engine") / _wps("eager"),
        "speedup_width_vs_masked_step": _sub_us("masked") / _sub_us("width"),
        "speedup_structured_fused_vs_scan": _srps("fused") / _srps("scan"),
        "scaling_efficiency": _shrps("mesh") / _shrps("scan"),
        "rounds_per_sec_faults": {"clean": 1e6 / _fus("clean"),
                                  "faulty": 1e6 / _fus("faulty")},
        "fault_overhead": _fus("faulty") / _fus("clean"),
        "cross_shard_bytes": _shard_xbytes(),
        "rows": by_name,
    }
    with open(path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    return record


if __name__ == "__main__":
    import sys

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if "--json" in sys.argv:
        out = sys.argv[sys.argv.index("--json") + 1]
        rec = emit_json(out)
        print(f"wrote {out}: "
              f"scan {rec['rounds_per_sec']['scan']:.1f} rounds/s, "
              f"{rec['speedup_scan_vs_eager']:.1f}x vs eager; "
              f"async scan {rec['windows_per_sec']['scan']:.1f} windows/s, "
              f"{rec['speedup_async_scan_vs_eager']:.1f}x vs eager; "
              f"structured fused "
              f"{rec['rounds_per_sec_structured']['fused']:.1f} rounds/s, "
              f"{rec['speedup_structured_fused_vs_scan']:.2f}x vs scan "
              f"@ {rec['config']['clients']} clients; "
              f"sharded {rec['rounds_per_sec_sharded']['mesh']:.2f} rounds/s "
              f"@ {rec['config']['shard_clients']} clients / "
              f"{rec['config']['shard_edges']} edges, "
              f"eff {rec['scaling_efficiency']:.2f}; "
              f"fault overhead {rec['fault_overhead']:.3f}x")
    else:
        for name, us, derived in run():
            print(f"{name},{us:.1f},{derived}")
