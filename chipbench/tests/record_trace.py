#!/usr/bin/env python3
"""Record the small chip trace that ``test_trace.py`` reads.

    python chipbench/tests/record_trace.py <out.xplane.pb>

On one TPU: the full-participation FL cell cut to 2000 clients, three
engine calls inside the harness's window span, under the profiler. The
trace holds the device's ``XLA Ops`` (the Pallas aggregation kernel among
them) and the harness's host spans.
"""
from __future__ import annotations

import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def main(out: str) -> int:
    import jax

    from chipbench import trace
    from chipbench.common import find_cell, load_json
    from chipbench.run import chip_devices, load_driver
    cell = find_cell(load_json(ROOT / "BENCHMARK.json"), "fl.mlp.100k.full")
    chip_devices(1)
    cell["traffic"]["clients"] = 2000
    driver = load_driver(cell, 1)
    driver.setup()
    driver.wrap_spans(jax.profiler.TraceAnnotation)
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("fl.chunk"):
                driver.call()
    jax.profiler.stop_trace()
    pathlib.Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(trace.find_xplane(tmp), out)
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
