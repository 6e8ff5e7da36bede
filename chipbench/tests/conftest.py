"""Small cells for the CPU: the benchmark's own cells with the fleet or
the model cut so a test run can hold them. Run by name:
``python -m pytest chipbench/tests`` (tier-1 collects ``tests/`` only)."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench.common import BENCH, find_cell, load_json  # noqa: E402

SMALL_DECODER = dict(hidden_size=128, intermediate_size=256,
                     num_attention_heads=4, num_key_value_heads=2,
                     vocab_size=512, num_hidden_layers=2)


def small_cell(name: str) -> dict:
    cell = find_cell(load_json(ROOT / "BENCHMARK.json"), name)
    if cell["traffic"]["driver"] == "fl_round":
        cell["traffic"]["clients"] = 400
    else:
        cell["config"].update(SMALL_DECODER)
        cell["traffic"].update(positions=32, batches=8)
    return cell


def limits(name: str) -> dict:
    return load_json(BENCH / "limits" / f"{name}.json")["limits"]


@pytest.fixture
def cpu_devices():
    import jax
    return jax.devices("cpu")[:1]
