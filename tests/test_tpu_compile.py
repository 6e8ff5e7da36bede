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


def test_granite_hybrid_cell_step_compiles_in_16_gib_a_chip(topo):
    """The benchmark cell's step: granite-4.0-h-micro's published layers
    0-9 at published widths, 4 tiers x 1 x 4096 tokens, f32 state and
    AdamW donated, sharded over a (data 1, model 4) mesh of the described
    v5e:2x2 as ``sharded_train_step`` shards it. It fits each chip's 16 GiB,
    and no collective gathers an activation: the Mamba2 projections are
    laid out by head, so no rank gathers z, x, B, C or dt."""
    import re

    import numpy as np
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.core.compression import default_tier_plans
    from repro.launch.train import sharded_train_step
    from repro.models import get_model

    full = get_config("granite-4.0-h-micro")
    cfg = full.replace(num_layers=10, layer_types=full.layer_types[:10])
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    model = get_model(cfg)
    opt = optim.adamw(3e-4)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = {"params": params, "opt": jax.eval_shape(opt.init, params),
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    step, state_sh, batch_sh = sharded_train_step(
        model, opt, default_tier_plans(4), mesh, state)
    args = (jax.tree.map(lambda x, s: _spec(x.shape, s, x.dtype), state,
                         state_sh),
            {"tokens": _spec((4, 1, 4097), batch_sh, jnp.int32)})
    compiled = step.lower(*args).compile()
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert per_chip < 16 * 2 ** 30, mem
    big = []
    for line in compiled.as_text().splitlines():
        m = re.search(r"= (.*?) all-gather(-start)?\(", line)
        if m and any(np.prod([int(d) for d in dims.split(",") if d])
                     >= 4096 * 64
                     for dims in re.findall(r"\[([\d,]*)\]", m.group(1))):
            big.append(line.strip()[:200])
    assert not big, big
