"""Ahead-of-time compiles of the runtime's Pallas kernels, and of one
scan-engine round chunk, for a described TPU v5e (no chip attached).

Nothing runs: these tests show that the TPU compiler accepts the real
Mosaic kernels at real widths (tile alignment, scoped VMEM) and that the
compiled round chunk holds them. The kernels are steered off interpret
mode here, in the test, because this process's backend is the CPU.
"""
import os
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import optim
from repro.configs.paper_mlp import config as mlp_config
from repro.core.engine import ScanEngine
from repro.core.scenario import (FleetSpec, FLScenario, LocalTraining,
                                 build_server)
from repro.kernels.grad_aggregate import ops as ga_ops
from repro.kernels.structured_scatter import ops as ss_ops
from repro.models import mlp


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def mosaic_kernels(monkeypatch):
    """Kernels lowered as Mosaic, and no persistent compile cache: a
    compile for a described chip is written there but cannot be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(ga_ops, "_auto_interpret", lambda: False)
    monkeypatch.setattr(ss_ops, "_auto_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def test_grad_aggregate_compiles(one_chip):
    t, shape = 4, (256, 256)
    g = _spec((t,) + shape, one_chip)
    w = _spec((t,), one_chip)
    fn = jax.jit(lambda g, m, w, wd: ga_ops.grad_aggregate(g, m, w,
                                                           w_den=wd))
    text = fn.lower(g, g, w, w).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape,widths", [
    ((1024, 4096), (1.0, 0.5, 0.25, 0.125)),
    ((2048, 2048), (1.0, 0.8, 0.6, 0.5, 0.25, 0.125)),
])
def test_structured_scatter_compiles_at_decoder_widths(one_chip, shape,
                                                       widths):
    """Three or more tiers of (256, 1024) f32 blocks overflow v5e's
    16 MiB of scoped VMEM; the block must shrink with the tier count."""
    locs = [tuple(max(1, int(d * w)) for d in shape) for w in widths]
    gs = tuple(_spec(loc, one_chip) for loc in locs)
    w = _spec((len(widths),), one_chip)
    fn = jax.jit(lambda gs, ms, w, wd: ss_ops.structured_scatter(
        list(gs), list(ms), w, wd, out_shape=shape))
    text = fn.lower(gs, gs, w, w).compile().as_text()
    assert "tpu_custom_call" in text


def test_scan_chunk_compiles_with_structured_kernel(one_chip):
    """One round chunk of the width-sliced 256-client fleet under
    agg="pallas": the compiled program holds the Mosaic kernel."""
    rounds = 2
    spec = FleetSpec.cycling(("hub", "high", "mid", "low"), 256,
                             samples_per_client=16)
    srv = build_server(FLScenario(fleet=spec,
                                  local=LocalTraining(submodel="width")),
                       types.SimpleNamespace(loss_fn=mlp.loss_fn),
                       optim.sgd(1.0),
                       mlp.init(jax.random.PRNGKey(0), mlp_config()))
    eng = ScanEngine(srv, chunk_rounds=rounds, agg="pallas")
    assert eng.agg_backend == "pallas_structured"
    # the chunk's arguments, as ScanEngine._run_chunk builds them for a
    # clean flat fleet
    carry, xs, datas = eng._stage_inputs(0, rounds, eng._host_masks(rounds))
    args = jax.tree.map(lambda x: _spec(x.shape, one_chip, x.dtype),
                        (carry, xs, datas))
    compiled = eng._chunk.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
