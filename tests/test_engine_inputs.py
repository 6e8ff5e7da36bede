"""ScanEngine's input staging: the chunk's ``xs`` is built on the host in
the program's dtypes, each per-client input packed across cohorts into
one array, and moved in one ``jax.device_put``. Each cohort's columns of
a packed leaf must equal, in dtype, shape, value and placement, what
sending that cohort's leaf on its own with ``jnp.asarray(numpy, dtype)``
gave, and one chunk call must make exactly one transfer there."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs.paper_mlp import config
from repro.core import engine as engine_mod
from repro.core.engine import ScanEngine
from repro.core.faults import FaultPolicy
from repro.core.scenario import (FleetSpec, FLScenario, ParticipationPolicy,
                                 build_server)
from repro.core.topology import make_edge_mesh, scatter_part, shard_fleet
from repro.models import mlp

TIERS = ("hub", "high", "mid", "low")
R = 3


def _fleet(n=16, **kw):
    return FleetSpec.cycling(TIERS, n, samples_per_client=16, **kw)


SCENARIOS = {
    "flat": FLScenario(
        fleet=_fleet(),
        participation=ParticipationPolicy(fraction=0.5, seed=11)),
    "faults": FLScenario(
        fleet=_fleet(),
        participation=ParticipationPolicy(fraction=0.7, seed=7),
        faults=FaultPolicy(seed=5, period=4, duty_cycle=0.75,
                           dropout_rate=0.25, corrupt_rate=0.3)),
    "topology": FLScenario(
        fleet=_fleet(16, edges=4),
        participation=ParticipationPolicy(fraction=0.5, seed=11)),
}


def _engine(name: str) -> ScanEngine:
    srv = build_server(SCENARIOS[name],
                       types.SimpleNamespace(loss_fn=mlp.loss_fn),
                       optim.sgd(1.0),
                       mlp.init(jax.random.PRNGKey(0), config()))
    if name == "topology":
        shard_fleet(srv, make_edge_mesh(4))
    return ScanEngine(srv, chunk_rounds=R)


def _per_leaf_xs(eng: ScanEngine, step0: int, masks) -> dict:
    """The chunk's ``xs`` as it was staged before the single transfer:
    one ``jnp.asarray`` (and, under a mesh, one ``device_put``) a leaf."""
    srv = eng.server
    parts, wparts, _, _, corrs = masks
    n_co = len(srv.cohorts)
    xs = {"step": jnp.asarray(np.arange(step0, step0 + R), jnp.int32),
          "has": jnp.asarray([any(p.any() for p in parts[r])
                              for r in range(R)])}
    if srv.faults is not None and not eng._topology:
        xs["wpart"] = tuple(
            jnp.asarray(np.stack([wparts[r][ci] for r in range(R)]),
                        jnp.float32) for ci in range(n_co))
    if eng._fault_uploads:
        offs = np.cumsum([0] + [c.size for c in srv.cohorts])
        xs["corrupt"] = tuple(
            jnp.asarray(np.stack([corrs[r][ci] for r in range(R)]),
                        jnp.float32) for ci in range(n_co))
        xs["uid"] = tuple(
            jnp.asarray(np.stack(
                [(step0 + r) * srv.n_clients
                 + np.arange(offs[ci], offs[ci + 1]) for r in range(R)]),
                jnp.int32) for ci in range(n_co))
    if eng._topology:
        xs["part"] = tuple(
            jnp.asarray(np.stack([scatter_part(c, parts[r][ci])
                                  for r in range(R)]))
            for ci, c in enumerate(srv.cohorts))
        xs["count"] = tuple(
            jnp.asarray(np.stack([np.bincount(c.edge_index[parts[r][ci]],
                                              minlength=c.n_edges)
                                  for r in range(R)]), jnp.float32)
            for ci, c in enumerate(srv.cohorts))
        if srv.mesh is not None:
            sh = jax.sharding.NamedSharding(
                srv.mesh, jax.sharding.PartitionSpec(None, "data"))
            xs["part"] = tuple(jax.device_put(p, sh) for p in xs["part"])
            xs["count"] = tuple(jax.device_put(c, sh) for c in xs["count"])
    else:
        xs["part"] = tuple(
            jnp.asarray(np.stack([parts[r][ci] for r in range(R)]),
                        jnp.float32) for ci in range(n_co))
    return xs


def _assert_same(got, ref):
    got, ref = np.asarray(got), np.asarray(ref)
    assert got.dtype == ref.dtype and got.shape == ref.shape
    assert np.array_equal(got, ref)


@pytest.mark.parametrize("name", ["flat", "faults", "topology"])
def test_staged_inputs_equal_per_leaf_transfers(name):
    eng = _engine(name)
    eng.run(R)  # one chunk first, so staging starts mid-run
    step0 = eng.server.step
    masks = eng._host_masks(R)
    _, xs, _ = eng._stage_inputs(step0, R, masks)
    ref = _per_leaf_xs(eng, step0, masks)
    assert sorted(xs) == sorted(ref)
    if name == "faults":
        assert {"wpart", "corrupt", "uid"} <= set(xs)
    if name == "topology":
        assert eng.server.mesh is not None and "count" in xs
    assert all(isinstance(v, jax.Array) for v in xs.values())
    for k in ("step", "has"):
        _assert_same(xs[k], ref[k])
    # every other input is one leaf for all cohorts: each cohort's
    # columns (its clients, or its grid rows) are what it was sent alone
    widths = [c.cap if name == "topology" else c.size
              for c in eng.server.cohorts]
    ends = np.cumsum(widths)
    for k in sorted(set(xs) - {"step", "has"}):
        assert xs[k].sharding.is_equivalent_to(ref[k][0].sharding,
                                               xs[k].ndim)
        assert xs[k].committed == ref[k][0].committed
        host = np.asarray(xs[k])
        for ci, (w, end) in enumerate(zip(widths, ends)):
            got = host[..., ci] if k == "count" else host[..., end - w:end]
            _assert_same(got, ref[k][ci])


class _Counting(types.ModuleType):
    """A stand-in for a module that counts calls to some of its
    functions while ``active`` and forwards everything else."""

    def __init__(self, mod, names):
        super().__init__(mod.__name__)
        self._mod, self.calls, self.active = mod, dict.fromkeys(names, 0), False
        for n in names:
            setattr(self, n, self._counted(n, getattr(mod, n)))

    def _counted(self, name, fn):
        def call(*a, **kw):
            if self.active:
                self.calls[name] += 1
            return fn(*a, **kw)
        return call

    def __getattr__(self, name):
        return getattr(self._mod, name)


def test_one_chunk_call_makes_one_transfer(monkeypatch):
    eng = _engine("flat")
    eng.run(R)  # the first call copies the carry it may not donate
    jax_c = _Counting(jax, ["device_put"])
    jnp_c = _Counting(jnp, ["asarray", "array"])
    monkeypatch.setattr(engine_mod, "jax", jax_c)
    monkeypatch.setattr(engine_mod, "jnp", jnp_c)
    stage = eng._stage_inputs

    def counted_stage(*a, **kw):
        jax_c.active = jnp_c.active = True
        try:
            return stage(*a, **kw)
        finally:
            jax_c.active = jnp_c.active = False

    monkeypatch.setattr(eng, "_stage_inputs", counted_stage)
    recs = eng.run(R)
    assert len(recs) == R
    assert jax_c.calls == {"device_put": 1}
    assert jnp_c.calls == {"asarray": 0, "array": 0}
