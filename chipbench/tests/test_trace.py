"""The trace reduction: interval arithmetic on made-up events, and the
whole reduction on a small trace recorded on one TPU v5e
(``data/small.xplane.pb``, made by ``record_trace.py``)."""
import pathlib

import pytest

from chipbench import trace

DATA = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"


def test_union_and_subtract():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]


def test_summary_of_made_up_events():
    ms = 1_000_000
    devices = {0: [("fusion.1", 10 * ms, 30 * ms),
                   ("all-reduce.2", 25 * ms, 50 * ms),
                   ("fusion.3", 70 * ms, 90 * ms),
                   ("fusion.4", 95 * ms, 120 * ms)]}
    spans = [("chipbench.window", 0, 100 * ms),
             ("fl.chunk", 0, 60 * ms),
             ("fl.chunk_dispatch", 50 * ms, 60 * ms),
             ("fl.chunk", 60 * ms, 100 * ms)]
    s = trace.summarize(devices, spans)
    assert s["window_s"] == pytest.approx(0.100)
    # busy: [10, 50], [70, 90] and [95, 100], clipped to the window
    assert s["busy_s"] == pytest.approx(0.065)
    # the all-reduce runs alone over [30, 50]
    assert s["collective_only_s"] == pytest.approx(0.020)
    assert s["op_s"]["fusion.4"] == pytest.approx(0.005)
    gaps = dict(s["idle_gaps"])
    # [0, 10] in a chunk, [50, 60] in its dispatch, [60, 70] and [90, 95]
    # in the next chunk
    assert gaps == pytest.approx({"fl.chunk": 0.025,
                                  "fl.chunk_dispatch": 0.010})
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"])


@pytest.mark.skipif(not DATA.exists(), reason="no recorded trace")
def test_recorded_chip_trace():
    devices, spans = trace.read_events(str(DATA))
    assert list(devices) == [0] and devices[0]
    names = {n for n, _, _ in spans}
    assert {"chipbench.window", "fl.chunk", "fl.chunk_dispatch",
            "fl.host_masks"} <= names
    s = trace.summarize(devices, spans)
    assert 0 < s["busy_s"] < s["window_s"]
    assert sum(v for _, v in s["idle_gaps"]) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-9)
    # the Pallas aggregation kernel: one call per weight matrix per round,
    # 6 matrices x 2 rounds x 3 calls
    kernel = [n for n, _, _ in devices[0] if n.startswith("%grad_aggregate_raw")]
    assert len(kernel) == 36
    assert trace.op_seconds(s, r"^grad_aggregate_raw ") > 0
    # self times: the round loop's own time excludes its body's ops
    assert sum(s["op_s"].values()) <= s["busy_s"] * (1 + 1e-9)
