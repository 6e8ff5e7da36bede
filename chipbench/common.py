"""Shared pieces of the chip benchmark: where its files live, how a cell
is looked up by name, the seed, the table of peaks, the compile-event
listener, and the comparison printout.

Nothing here imports the program under test (``repro``); the drivers do.
"""
from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class BenchError(Exception):
    """A cell, file or device the benchmark cannot run with."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str):
    """Import a driver or metric reader from its file. Metric files are
    named after metrics (``mfu.fl.py``), which no import statement can
    name, so they are loaded by path."""
    if not path.is_file():
        raise BenchError(f"no such file: {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, name: str) -> dict:
    """The cell's entry in BENCHMARK.json, with its configuration file,
    its traffic file and the metrics it reports resolved by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = configs[cell["config"]]
    model = load_json(ROOT / config["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def applies(metric, among=None):
        if "workloads" in metric:
            return name in metric["workloads"]
        if among is not None:
            return metric["moves"] in among
        return True

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if applies(m, e2e_names)]
    return {"name": name, "chips": cell["chips"], "config": model,
            "traffic": traffic, "end_to_end": e2e, "per_layer": per_layer}


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed of up to 64 bits.
    ``PRNGKey`` alone drops the bits above 32 (2**33 + 5 and 5 give one
    key); below 2**32 this is ``PRNGKey(seed)`` itself, the key the
    program's own fleet build uses (`FleetSpec.build_clients`)."""
    import jax
    if seed < 0:
        raise BenchError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    if seed >> 32:
        key = jax.random.fold_in(key, seed >> 32)
    return key


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of this kind. A kind that is not in
    ``peaks.json`` is an error, never a default."""
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"chipbench/peaks.json; known: {sorted(table)}")
    return table[device_kind]


class CompileWatch:
    """Union of JAX's compile events (trace, lowering, backend compile;
    persistent-cache reads included) on the ``perf_counter`` clock.
    Nested jits report nested events, so seconds are a union, not a sum."""

    def __init__(self):
        self.spans: list[tuple[float, float]] = []

    def install(self) -> None:
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name in _COMPILE_EVENTS:
            end = time.perf_counter()
            self.spans.append((end - secs, end))

    def count_between(self, t0: float, t1: float) -> int:
        return sum(1 for s, e in self.spans if s >= t0 and e <= t1)

    def seconds_between(self, t0: float, t1: float) -> float:
        total, reach = 0.0, t0
        for start, end in sorted(self.spans):
            start = max(start, reach)
            end = min(end, t1)
            if end > start:
                total += end - start
                reach = end
        return total


def device_info(devices) -> dict:
    dev = devices[0]
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def log(*parts) -> None:
    print("chipbench:", *parts, file=sys.stderr, flush=True)


def gap(prog: float, ref: float, scale: float) -> float:
    """|prog - ref| / scale, with a non-finite program value reading inf."""
    if not math.isfinite(prog):
        return math.inf
    return abs(prog - ref) / scale


def leaf_norm_gaps(prog_norms: dict, ref_norms: dict,
                   ref_grad_norms: dict | None = None) -> tuple[float, str,
                                                                list[str]]:
    """Worst leaf's gap between the program's norm and the reference's,
    each against the larger of that leaf's reference norm and the median
    leaf's. With ``ref_grad_norms``, leaves whose reference gradient is
    under a thousandth of the median leaf's are left out: they move under
    Adam by round-off alone. Returns (gap, worst leaf, leaves left out)."""
    names = sorted(ref_norms)
    ref_sorted = sorted(ref_norms[n] for n in names)
    median = ref_sorted[len(ref_sorted) // 2]
    skipped = []
    if ref_grad_norms is not None:
        gs = sorted(ref_grad_norms.values())
        g_med = gs[len(gs) // 2]
        skipped = [n for n in names if ref_grad_norms[n] < 1e-3 * g_med]
    worst, worst_name = 0.0, ""
    for n in names:
        if n in skipped:
            continue
        g = gap(prog_norms[n], ref_norms[n], max(ref_norms[n], median, 1e-30))
        if g > worst or not worst_name:
            worst, worst_name = g, n
    return worst, worst_name, skipped
