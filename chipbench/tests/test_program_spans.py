"""The program's own host spans (``ScanEngine``'s ``fl.engine.*``) in the
trace reduction: the innermost span names each idle gap, so the engine's
spans take the idle time from the harness's spans around them; the
``idle_share.fl.*`` readers on made-up summaries; and a chip trace
recorded with the spans in place (``data/spans.xplane.pb``, made by
``record_trace.py``)."""
import pathlib

import pytest

from chipbench import trace
from chipbench.common import BENCH, load_module

DATA = pathlib.Path(__file__).parent / "data" / "spans.xplane.pb"
PHASES = ("fl.engine.masks", "fl.engine.inputs", "fl.engine.dispatch",
          "fl.engine.sync")
READERS = {"idle_share.fl.masks": "fl.engine.masks",
           "idle_share.fl.inputs": "fl.engine.inputs",
           "idle_share.fl.sync": "fl.engine.sync"}
MS = 1_000_000


def _reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py",
                       "test_metric_" + name.replace(".", "_"))


def _made_up_summary():
    """One call of 100 ms: the harness's spans around the engine's
    methods (``fl.host_masks``, ``fl.chunk_dispatch``) and around the
    call (``fl.chunk``), the engine's spans inside them."""
    devices = {0: [("fusion.1", 30 * MS, 45 * MS),
                   ("fusion.2", 60 * MS, 70 * MS)]}
    spans = [("chipbench.window", 0, 100 * MS),
             ("fl.chunk", 0, 100 * MS),
             ("fl.host_masks", 0, 10 * MS),
             ("fl.engine.masks", 1 * MS, 9 * MS),
             ("fl.engine.inputs", 10 * MS, 40 * MS),
             ("fl.engine.dispatch", 40 * MS, 50 * MS),
             ("fl.chunk_dispatch", 41 * MS, 49 * MS),
             ("fl.engine.sync", 50 * MS, 100 * MS)]
    return trace.summarize(devices, spans)


def test_program_spans_take_the_idle_time():
    s = _made_up_summary()
    gaps = dict(s["idle_gaps"])
    # idle: [0, 30] (masks, then inputs), [45, 50] (dispatch), [50, 60]
    # and [70, 100] (sync). The harness's method wraps keep only what
    # lies outside the engine's span inside them; the harness's fl.chunk
    # names nothing, since the engine's spans cover the call
    assert gaps == pytest.approx({"fl.host_masks": 0.002,
                                  "fl.engine.masks": 0.008,
                                  "fl.engine.inputs": 0.020,
                                  "fl.chunk_dispatch": 0.004,
                                  "fl.engine.dispatch": 0.001,
                                  "fl.engine.sync": 0.040})
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"])


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_its_span(name):
    s = _made_up_summary()
    want = {"fl.engine.masks": 8.0, "fl.engine.inputs": 20.0,
            "fl.engine.sync": 40.0}[READERS[name]]
    assert _reader(name).read({"trace": s}) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_without_its_span_reads_none(name):
    s = _made_up_summary()
    s["idle_gaps"] = [[n, v] for n, v in s["idle_gaps"]
                      if n != READERS[name]]
    assert _reader(name).read({"trace": s}) is None
    assert _reader(name).read({"trace": None}) is None


def test_recorded_program_spans():
    devices, spans = trace.read_events(str(DATA))
    for phase in PHASES:
        # three engine calls in the recording's window
        assert sum(1 for n, _, _ in spans if n == phase) == 3
    s = trace.summarize(devices, spans)
    assert sum(v for _, v in s["idle_gaps"]) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-9)
    calls_s = sum(e - b for n, b, e in spans if n == "fl.chunk") * 1e-9
    gaps = dict(s["idle_gaps"])
    assert gaps.get("fl.chunk", 0.0) < 0.05 * calls_s
    for name in READERS:
        assert _reader(name).read({"trace": s}) > 0
