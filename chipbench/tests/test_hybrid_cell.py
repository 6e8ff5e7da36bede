"""The hybrid cell at a size a CPU test can hold, in float32 so that the
program and its float32 reference agree to round-off: the program passes
the cell's limits, the fp8 control and the half fault (the second half
of every sequence left out of the loss) do not, and the per-layer
readers find what they read or report nothing."""
import importlib.util

import pytest

from chipbench.common import BENCH, ROOT, find_cell, load_json
from chipbench.run import compare, load_driver
from conftest import limits

NAME = "decoder.granite-4.0-h-micro.t4.mesh4"
SMALL = dict(hidden_size=128, intermediate_size=256,
             shared_intermediate_size=256, num_attention_heads=4,
             num_key_value_heads=2, vocab_size=512, num_hidden_layers=3,
             layer_types=["mamba", "attention", "mamba"], mamba_n_heads=8,
             mamba_d_head=32, mamba_d_state=16, compute_dtype="float32")


def small_cell():
    cell = find_cell(load_json(ROOT / "BENCHMARK.json"), NAME)
    cell["config"].update(SMALL)
    cell["traffic"].update(positions=32, batches=4)
    return cell


@pytest.fixture(scope="module")
def driver():
    d = load_driver(small_cell(), 2 ** 33 + 17)
    d.setup()
    d.release()
    return d


def test_program_passes_control_and_half_fault_do_not(driver):
    lim = limits(NAME)
    program = compare(driver.readings(), lim)
    assert all(c["ok"] for c in program.values()), program
    for kw in ({"control": True}, {"fault": "half"}):
        other = compare(driver.readings(**kw), lim)
        assert not all(c["ok"] for c in other.values()), (kw, other)


def test_ssd_ops_are_named_from_the_compiled_step(driver):
    assert driver.ssd_ops


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_readers_report_nothing_without_their_source():
    trace = {"busy_s": 1.0, "window_s": 2.0, "devices": 1,
             "collective_only_s": 0.0, "op_s": {"fusion f32[8]": 0.5}}
    ssd = _reader("ssd_share.hybrid")
    assert ssd({"trace": trace, "counters": {}}) is None
    assert ssd({"trace": trace,
                "counters": {"ssd_op_names": ["while f32[2]"]}}) is None
    assert ssd({"trace": trace,
                "counters": {"ssd_op_names": ["fusion f32[8]"]}}) == 50.0
    coll = _reader("collective_share.hybrid")
    assert coll({"trace": trace}) is None
    four = dict(trace, devices=4, op_s={"fusion f32[8]": 0.5,
                                        "all-reduce bf16[8,4]": 0.25,
                                        "all-reduce f32[]": 0.25})
    assert coll({"trace": four}) == 25.0
