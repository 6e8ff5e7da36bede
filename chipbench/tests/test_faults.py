"""A run whose timed path is broken underneath must come out not
correct: the harness is driven past its look for a chip, on the CPU, at
a size a test can hold, with the program patched at the point where each
fault would arise."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import run as bench_run
from conftest import limits, small_cell

FL_CELLS = ["fl.mlp.100k.full"]
DECODER = "decoder.granite-3-2b.t4"


def _run(name, devices, seed=3):
    return bench_run.run(small_cell(name), seed, 0.5, False, devices,
                         limits(name))


def _state_unchanged_fl(monkeypatch):
    from repro.core.engine import ScanEngine
    body = ScanEngine._round_body

    def frozen(self, carry, x, datas):
        return carry, body(self, carry, x, datas)[1]
    monkeypatch.setattr(ScanEngine, "_round_body", frozen)


def _half_batch_fl(monkeypatch):
    """Every second client of each cohort never reports; the engine's
    own participation count then takes the mean over the rest."""
    from repro.core.engine import ScanEngine
    body = ScanEngine._round_body

    def halved(self, carry, x, datas):
        part = tuple(p * (jnp.arange(p.shape[-1]) % 2 == 0) for p in x["part"])
        return body(self, carry, dict(x, part=part), datas)
    monkeypatch.setattr(ScanEngine, "_round_body", halved)


def _state_unchanged_decoder(monkeypatch):
    import repro.core
    make = repro.core.make_hetero_train_step

    def frozen(*a, **kw):
        step = make(*a, **kw)
        return lambda state, batch: (state, step(state, batch)[1])
    monkeypatch.setattr(repro.core, "make_hetero_train_step", frozen)


def _half_batch_decoder(monkeypatch):
    import repro.core
    make = repro.core.make_hetero_train_step

    def halved(*a, **kw):
        step = make(*a, **kw)
        return lambda state, batch: step(
            state, {"tokens": batch["tokens"][:, :1]})
    monkeypatch.setattr(repro.core, "make_hetero_train_step", halved)


@pytest.mark.parametrize("name", FL_CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged_fl, _half_batch_fl])
def test_fl_fault_is_not_correct(name, fault, monkeypatch, cpu_devices):
    assert _run(name, cpu_devices)["correct"]
    fault(monkeypatch)
    res = _run(name, cpu_devices)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [_state_unchanged_decoder,
                                   _half_batch_decoder])
def test_decoder_fault_is_not_correct(fault, monkeypatch, cpu_devices):
    fault(monkeypatch)
    res = _run(DECODER, cpu_devices)
    assert not res["correct"], res["checks"]
