#!/usr/bin/env python3
"""Readings for a cell's limits, on the chip at the cell's own size.

    python chipbench/calibrate.py --workload <cell> --seeds 1 2 3 ... [--controls 3]

One process: for each seed the driver sets up (which runs the program's
first steps), frees the program's state and reads the gaps of the plain
reference; for the first ``--controls`` seeds it also reads the control
(the reference one precision below the configuration's) and the
half-batch fault (the reference with every second participant or
sequence left out) against the reference. One JSON line per reading. The
benchmark's own runs never run this; the limits in
``chipbench/limits/<cell>.json`` are set from what it prints.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench.common import find_cell, load_json  # noqa: E402
from chipbench.run import chip_devices, enable_cache, load_driver  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    cell = find_cell(load_json(ROOT / "BENCHMARK.json"), args.workload)
    chip_devices(cell["chips"])
    enable_cache()
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        driver = load_driver(cell, seed)
        driver.setup()
        t1 = time.perf_counter()
        driver.release()
        gc.collect()
        kinds = [("program", {})]
        if i < args.controls:
            kinds += [("control", {"control": True}),
                      ("half", {"fault": "half"})]
        for kind, kw in kinds:
            t2 = time.perf_counter()
            r = driver.readings(**kw)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": kind, **r, "setup_s": t1 - t0,
                              "reading_s": time.perf_counter() - t2}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
