"""FL simulation — the paper's full system loop with an 8-device
heterogeneous IoT fleet, expressed as declarative ``FLScenario`` specs
(DESIGN.md §11): each experiment is ONE frozen spec composed of policy
objects (fleet x local training x upload x participation x timing), and
``simulate()`` assembles + drives the right runtime. Compared here:

  1. uncompressed FedSGD (McMahan et al. baseline — all devices big enough)
  2. hetero-compressed FedSGD (our mask-aware aggregation)
  3. hetero-compressed FedAvg (5 local steps, compressed-space training)
  4. fp8 upload quantization with error feedback

and reporting the paper's Eq. (1) per-round wall time + upload bytes,
then the cohort-vectorized runtime (DESIGN.md §9) and the at-scale
scenarios it unlocks — partial participation, a straggler deadline,
masked vs structured width-sliced tiers (DESIGN.md §13: the same tier
budgets spent as real smaller dense sub-models instead of full-shape
masks), and the asynchronous staleness-aware runtime (DESIGN.md §10)
where buffered aggregation stops the slow tiers from gating the
virtual clock.

  PYTHONPATH=src python examples/hetero_fl_sim.py
"""
import jax

from repro.fl import (AsyncBuffered, FleetSpec, FLScenario, LocalTraining,
                      ParticipationPolicy, SyncDrop, UploadPolicy, simulate)
from repro.models import mlp
from repro.data import make_gaussian_dataset
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

ROUNDS = 60
FLEET = ("hub", "high", "high", "mid", "mid", "low", "low", "embedded")

# non-IID (label-skew Dirichlet) split for the faithful per-client loop;
# the cohort/async runtimes stack each cohort's shards for vmap and
# truncate ragged shards to the common floor, so they use equal IID
# shards to keep every sample in play
NONIID = FleetSpec(tiers=FLEET, n_samples=4000, partition="dirichlet",
                   alpha=0.5)
IID = FleetSpec(tiers=FLEET, n_samples=4000)
VAL = make_gaussian_dataset(jax.random.PRNGKey(9), 1000)


def run(name, scenario):
    """One declarative experiment: simulate() builds the runtime the
    scenario's policies call for (per-client loop, cohort, or async)."""
    res = simulate(scenario, ROUNDS)
    rec = res.final
    acc = float(mlp.accuracy(res.params, VAL["x"], VAL["y"]))
    extra = (f"virtual_t={rec.t:.3f}s "
             f"staleness={rec.staleness_mean:.1f}/{rec.staleness_max}"
             if rec.t is not None else
             f"round_wall={rec.round_wall_time:.3f}s "
             + (f"participants={rec.n_participants}/{scenario.fleet.n_clients} "
                f"dropped={rec.n_dropped}"
                if rec.n_participants is not None else
                f"upload={rec.total_upload_bytes / 1e3:.1f}kB"))
    print(f"{name:28s} loss={rec.loss:.4f} val_acc={acc:.3f} {extra}")
    return acc


print(f"fleet: {list(FLEET)}\n")
run("fedsgd (all-hub baseline)",
    FLScenario(fleet=FleetSpec(tiers=("hub",) * len(FLEET), n_samples=4000,
                               partition="dirichlet"),
               runtime="client"))
run("fedsgd hetero-compressed", FLScenario(fleet=NONIID, runtime="client"))
run("fedavg hetero-compressed",
    FLScenario(fleet=NONIID, runtime="client",
               local=LocalTraining(mode="fedavg", local_steps=5,
                                   local_lr=1.0)))
run("fedsgd hetero + fp8 upload+EF",
    FLScenario(fleet=NONIID, runtime="client",
               upload=UploadPolicy(quant="fp8_e4m3", error_feedback=True)))
print("\nnote: the compressed fleet trains the SAME global model while the "
      "low tiers ship 4-25x smaller payloads (the paper's Eq. 1 win).")

print("\ncohort-vectorized runtime (one vmapped dispatch per plan, "
      "DESIGN.md §9):")
run("cohort fedsgd (IID shards)", FLScenario(fleet=IID))
run("cohort + 50% participation",
    FLScenario(fleet=IID, participation=ParticipationPolicy(fraction=0.5,
                                                            seed=1)))
run("cohort + 5ms deadline drop",
    FLScenario(fleet=IID, timing=SyncDrop(deadline=0.005)))

print("\nmasked emulation vs structured width-sliced sub-models "
      "(DESIGN.md §13): same tier budgets, but submodel='width' cuts "
      "REAL smaller dense models\nout of the global one (a 0.25 tier "
      "trains a ceil(0.25*d) wide sub-network) and the server "
      "scatter-aggregates per coordinate:")
from repro.fl import scenario_census

MASKED = FLScenario(fleet=IID)
WIDTH = FLScenario(fleet=IID, local=LocalTraining(submodel="width"))
run("cohort fedsgd masked tiers", MASKED)
run("cohort fedsgd width-sliced", WIDTH)
for name, sc in (("masked", MASKED), ("width-sliced", WIDTH)):
    cen = scenario_census(sc)
    low = next(r for r in cen["tiers"] if r["tier"] == "low")
    print(f"  {name:12s} per-round upload "
          f"{cen['total_upload_bytes_per_round'] / 1e3:6.1f}kB   "
          f"low-tier T_local={low['T_local'] * 1e3:.3f}ms "
          f"payload={low['payload_bytes']:.0f}B")

print("\nasync staleness-aware runtime (virtual clock + buffered "
      "aggregation, DESIGN.md §10):")
run("async buffer=4, a=0.5",
    FLScenario(fleet=IID, timing=AsyncBuffered(buffer_size=4,
                                               staleness_exp=0.5)))
run("async buffer=2 + jitter",
    FLScenario(fleet=IID,
               timing=AsyncBuffered(buffer_size=2, staleness_exp=0.5,
                                    time_jitter=0.2),
               participation=ParticipationPolicy(seed=1)))

print("\nmulti-round scan engine (whole chunks of rounds compiled into "
      "one donated-buffer program, DESIGN.md §12):")
import time

from repro.fl import ScanEngine

eager = simulate(FLScenario(fleet=IID), ROUNDS)
scan = simulate(FLScenario(fleet=IID), ROUNDS, engine="scan")
identical = all(
    bool((a == b).all())
    for a, b in zip(jax.tree.leaves(eager.params), jax.tree.leaves(scan.params)))
# steady-state on BOTH paths (warmed servers, no fleet build / compile):
# the engine's regime is many rounds, where the one-off compile amortizes
t0 = time.perf_counter()
for _ in range(ROUNDS):
    eager.server.round()
t_eager = time.perf_counter() - t0
engine = ScanEngine(scan.server, chunk_rounds=ROUNDS)
engine.run(ROUNDS)                               # compile
t0 = time.perf_counter()
engine.run(ROUNDS)
t_scan = time.perf_counter() - t0
print(f"eager loop: {ROUNDS / t_eager:6.1f} rounds/s    "
      f"scan engine: {ROUNDS / t_scan:6.1f} rounds/s (steady state)")
print(f"trajectories bit-identical: {identical} — a drop-in replacement; "
      f"fl/engine_* benches the 256-client config (>5x there)")
