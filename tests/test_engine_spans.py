"""ScanEngine's host phases in a profile: each chunk call opens
``fl.engine.masks``, ``fl.engine.inputs``, ``fl.engine.dispatch`` and
``fl.engine.sync`` on the calling thread, in that order and without
overlap; they nest inside spans opened around the engine's methods from
outside; and a run under the profiler computes what a run without it
does."""
import glob
import os
import types

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro import optim
from repro.configs.paper_mlp import config
from repro.core.engine import ScanEngine
from repro.core.scenario import FleetSpec, FLScenario, build_server
from repro.models import mlp

PHASES = ("fl.engine.masks", "fl.engine.inputs", "fl.engine.dispatch",
          "fl.engine.sync")
CHUNKS, CHUNK_ROUNDS = 2, 2


def _engine() -> ScanEngine:
    spec = FleetSpec.cycling(("hub", "high", "mid", "low"), 64,
                             samples_per_client=16)
    srv = build_server(FLScenario(fleet=spec),
                       types.SimpleNamespace(loss_fn=mlp.loss_fn),
                       optim.sgd(1.0),
                       mlp.init(jax.random.PRNGKey(0), config()))
    return ScanEngine(srv, chunk_rounds=CHUNK_ROUNDS, agg="sequential")


def _wrap(eng: ScanEngine) -> None:
    """Open a span around two engine methods from outside, as a caller
    that times them does."""
    for attr, name in (("_host_masks", "fl.host_masks"),
                       ("_chunk", "fl.chunk_dispatch")):
        def wrapped(*a, _fn=getattr(eng, attr), _name=name, **kw):
            with TraceAnnotation(_name):
                return _fn(*a, **kw)
        setattr(eng, attr, wrapped)


def _profiled_run(eng: ScanEngine, log_dir) -> list:
    """Run the chunks under the profiler; returns the ``fl.`` host events
    as (thread line, name, start_ns, end_ns), sorted by start."""
    jax.profiler.start_trace(str(log_dir))
    try:
        eng.run(CHUNKS * CHUNK_ROUNDS)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            events += [((plane.name, li), ev.name, ev.start_ns,
                        ev.start_ns + ev.duration_ns)
                       for ev in line.events if ev.name.startswith("fl.")]
    return sorted(events, key=lambda e: e[2])


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    # each engine's first chunk compiles, outside the profiler: a compile
    # under the profiler's Python tracer takes several times as long
    plain, traced = _engine(), _engine()
    plain.run((1 + 2 * CHUNKS) * CHUNK_ROUNDS)
    traced.run(CHUNK_ROUNDS)
    out = {"plain": plain, "traced": traced,
           "events": _profiled_run(traced, tmp_path_factory.mktemp("own"))}
    out["jitted"] = traced._chunk
    _wrap(traced)
    out["wrapped_events"] = _profiled_run(traced,
                                          tmp_path_factory.mktemp("wrapped"))
    return out


def _inside(inner, outer) -> bool:
    return outer[2] <= inner[2] and inner[3] <= outer[3]


def _check_phases(runs):
    ev = [e for e in runs["events"] if e[1] in PHASES]
    assert [e[1] for e in ev] == list(PHASES) * CHUNKS
    assert len({e[0] for e in ev}) == 1
    assert all(a[3] <= b[2] for a, b in zip(ev, ev[1:]))


def _check_outer_wraps(runs):
    ev = runs["wrapped_events"]
    by = {n: [e for e in ev if e[1] == n]
          for n in PHASES + ("fl.host_masks", "fl.chunk_dispatch")}
    assert all(len(v) == CHUNKS for v in by.values())
    # the innermost span names the time: the engine's own masks span sits
    # inside the outside wrap of the method, the outside wrap of the
    # jitted chunk inside the engine's dispatch span
    for m, h in zip(by["fl.engine.masks"], by["fl.host_masks"]):
        assert _inside(m, h)
    for c, d in zip(by["fl.chunk_dispatch"], by["fl.engine.dispatch"]):
        assert _inside(c, d)
    assert callable(getattr(runs["jitted"], "lower", None))


def _check_same_results(runs):
    plain, traced = runs["plain"], runs["traced"]
    assert traced.server.history == plain.server.history
    for a, b in zip(jax.tree.leaves(traced.server.params),
                    jax.tree.leaves(plain.server.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("check", [_check_phases, _check_outer_wraps,
                                   _check_same_results],
                         ids=["phases_in_order", "inside_outer_wraps",
                              "profiler_changes_nothing"])
def test_engine_host_spans(runs, check):
    check(runs)
