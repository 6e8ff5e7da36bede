"""Quickstart: heterogeneous-device federated learning in ~20 lines.

One declarative ``FLScenario`` (DESIGN.md §11) describes the whole
experiment — a six-device IoT fleet (server hub -> fp8 edge -> pruned
tiers -> MCU-class) jointly training ONE global model, each tier on its
own compressed variant, merged by the mask-aware aggregator — and
``simulate()`` assembles the cohort-vectorized runtime and runs it.

  PYTHONPATH=src python examples/quickstart.py
"""
from repro.compile_cache import enable_compile_cache
from repro.fl import FleetSpec, FLScenario, LocalTraining, simulate

enable_compile_cache()

scenario = FLScenario(
    fleet=FleetSpec(tiers=("hub", "high", "mid", "mid", "low", "embedded"),
                    n_samples=1800),
    local=LocalTraining(mode="fedavg", local_steps=5, local_lr=1.0),
)
print("tiers:", {t: c for (t, _), c in scenario.fleet.counts().items()})

# engine="scan" compiles all 30 rounds into ONE donated-buffer program
# (DESIGN.md §12) — same trajectory as the eager loop, bit for bit
result = simulate(scenario, rounds=30, engine="scan")

for rec in result.records[4::5]:
    print(f"round {rec.step:3d}  global-model loss {rec.loss:.4f}  "
          f"round_wall {rec.round_wall_time * 1e3:.2f}ms")
print(f"done — one global model from 6 differently-compressed devices; "
      f"simulated {result.sim_time:.2f}s of fleet time, "
      f"{sum(r.total_upload_bytes for r in result.records) / 1e3:.0f}kB uploaded")
