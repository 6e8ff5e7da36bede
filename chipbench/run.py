#!/usr/bin/env python3
"""Chip benchmark: one cell of BENCHMARK.json, one run.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell's entry in
BENCHMARK.json names its configuration (``chipbench/configs/``) and its
traffic mix (``chipbench/traffic/<traffic>.json``), which names the
driver (``chipbench/drivers/<driver>.py``) that builds the system under
test and runs the measured window; each per-layer metric is read by
``chipbench/metrics/<metric>.py``, and the limits the run's comparison is
held to are in ``chipbench/limits/<cell>.json``.

A run sets up (inputs and weights from the seed, compile, warm-up, the
first steps the reference follows), measures for ``--seconds``, reads the
device's memory peak, frees the program's state and compares what the
first steps produced with the plain reference. With ``--trace 1`` the
window runs under the profiler and the per-layer metrics are printed in
place of the end-to-end ones. The last line of standard output is one
JSON object; the last lines of standard error are the numbers compared,
each beside its limit. Without a TPU, or with fewer devices than the
cell asks for, it prints no result and exits 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench.common import (BENCH, BenchError, CompileWatch,  # noqa: E402
                              device_info, find_cell, load_json, load_module,
                              log, peaks)

TRACE_DIR = ROOT / ".chipbench_trace"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chip_devices(chips: int):
    """The cell's devices; a run never falls back to the CPU."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise BenchError(f"no TPU: JAX's backend is {backend!r}")
    devices = jax.devices()
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def enable_cache() -> str:
    """The program's persistent compilation cache, at the fixed path it
    chooses inside the checkout (or ``$JAX_COMPILATION_CACHE_DIR``), with
    every program kept, so that a cell's second run compiles nothing."""
    import jax

    from repro.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def load_driver(cell: dict, seed: int):
    name = cell["traffic"]["driver"]
    mod = load_module(BENCH / "drivers" / f"{name}.py", f"chipbench_driver_{name}")
    return mod.Driver(cell, seed)


def compare(readings: dict, limits: dict) -> dict:
    """Each number compared, with its limit; a missing or non-finite
    reading fails."""
    out = {}
    for name, limit in limits.items():
        v = readings.get(name, math.inf)
        out[name] = {"value": v, "limit": limit,
                     "ok": bool(math.isfinite(v) and v <= limit)}
    return out


def read_per_layer(cell: dict, ctx: dict) -> dict:
    out = {}
    for m in cell["per_layer"]:
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py",
                          "chipbench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, devices,
        limits: dict, t_start: float = T_START) -> dict:
    """One run of a cell on ``devices``; returns the result object."""
    import jax
    watch = CompileWatch()
    watch.install()
    driver = load_driver(cell, seed)
    driver.setup()
    setup_s = time.perf_counter() - t_start

    def span(name):
        return jax.profiler.TraceAnnotation(name) if trace else contextlib.nullcontext()

    if trace:
        driver.wrap_spans(span)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    w0 = time.perf_counter()
    try:
        with span("chipbench.window"):
            res = driver.window(seconds, span)
    finally:
        if trace:
            jax.profiler.stop_trace()
    w1 = time.perf_counter()
    log("compiles_in_window", watch.count_between(w0, w1),
        "compile_s_in_window", watch.seconds_between(w0, w1))
    device = device_info(devices)
    log("peak_bytes_in_use", device["memory_peak_bytes"])
    driver.release()
    gc.collect()
    t_ref = time.perf_counter()
    readings = driver.readings()
    t_end = time.perf_counter()
    log("reference_s", t_end - t_ref, "compile_s", watch.seconds_between(t_ref, t_end),
        "readings", readings)
    checks = compare(readings, limits)
    correct = all(c["ok"] for c in checks.values()) and res["failed"] == 0
    result = {"correct": correct, "attempted": res["attempted"],
              "failed": res["failed"]}
    breakdown = None
    if trace:
        from chipbench import trace as tr
        summary = tr.summarize(*tr.read_events(tr.find_xplane(str(TRACE_DIR))))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        ctx = {"trace": summary, "counters": res["counters"],
               "elapsed": res["elapsed"], "chips": len(devices),
               "peaks": peaks(device["kind"])}
        result["metrics"] = read_per_layer(cell, ctx)
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
    else:
        values = dict(res["end_to_end"], setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell["end_to_end"]}
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in checks.items()}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        bench = load_json(ROOT / "BENCHMARK.json")
        cell = find_cell(bench, args.workload)
        limits = load_json(BENCH / "limits" / f"{args.workload}.json")["limits"]
        devices = chip_devices(cell["chips"])
    except (BenchError, OSError, KeyError, ValueError) as e:
        print(f"chipbench: {type(e).__name__}: {e}", file=sys.stderr)
        return 3
    log("compile_cache", enable_cache())
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices,
                 limits)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
