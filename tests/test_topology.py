"""Fleet topology (DESIGN.md §16): the static spec, the edge grids, the
split-client-axis aggregation invariance the hub combine rests on, and
the bitwise identity of sharded vs unsharded execution.

The multi-device cases need >1 host device:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 make test   # or
    make test-shard

— with one device they skip (the placement program is the same one; the
identity they pin is that extra devices change nothing).
"""
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import optim
from repro.configs.paper_mlp import config
from repro.core.aggregation import finalize, scatter_accumulate, zeros_like_acc
from repro.core.compression import (DEVICE_TIERS, compress_params, slice_tree,
                                    submodel_spec)
from repro.core.compression.quantization import fake_quant_ste
from repro.core.engine import ScanEngine
from repro.core.federated import build_cohorts
from repro.core.scenario import (AsyncBuffered, FleetSpec, FLScenario,
                                 LocalTraining, ParticipationPolicy,
                                 SyncDrop, UploadPolicy, build_server,
                                 scenario_census, simulate)
from repro.core.topology import (EdgeCohort, FleetTopology,
                                 build_edge_cohorts, cross_shard_bytes,
                                 make_edge_mesh, scatter_part, shard_fleet)
from repro.models import mlp

TIERS = ("hub", "high", "mid", "low")
MODEL = types.SimpleNamespace(loss_fn=mlp.loss_fn)
PARAMS = mlp.init(jax.random.PRNGKey(3), config())


def _bit_identical(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(bool(jnp.all(x == y))
                                      for x, y in zip(la, lb))


# ------------------------------------------------------------- the spec

class TestFleetTopology:
    def test_contiguous_shapes(self):
        t = FleetTopology.contiguous(10, 3)
        assert t.n_edges == 3 and t.n_clients == 10
        assert t.edges == ((0, 1, 2, 3), (4, 5, 6), (7, 8, 9))

    def test_round_robin_spreads_plans(self):
        t = FleetTopology.round_robin(8, 4)
        assert t.edges == ((0, 4), (1, 5), (2, 6), (3, 7))

    def test_validation(self):
        with pytest.raises(ValueError, match="at least one edge"):
            FleetTopology(())
        with pytest.raises(ValueError, match="empty"):
            FleetTopology(((0, 1), ()))
        with pytest.raises(ValueError, match="two edge groups"):
            FleetTopology(((0, 1), (1, 2)))
        with pytest.raises(ValueError, match="negative"):
            FleetTopology(((-1, 0),))
        FleetTopology(((2, 0), (1,))).validate(3)       # any order is fine
        with pytest.raises(ValueError, match="fleet has"):
            FleetTopology(((0, 1),)).validate(3)        # under-covers
        with pytest.raises(ValueError, match="fleet has"):
            FleetTopology(((0, 5),)).validate(2)        # gap

    def test_json_round_trip_and_hash(self):
        t = FleetTopology.contiguous(10, 3)
        t2 = FleetTopology.from_dict(json.loads(json.dumps(t.to_dict())))
        assert t2 == t and hash(t2) == hash(t)

    def test_edge_of(self):
        t = FleetTopology(((3, 1), (0, 2)))
        assert t.edge_of() == {3: 0, 1: 0, 0: 1, 2: 1}


# ----------------------------------------------------------- edge grids

def _fleet(n=16, edges=4, **kw):
    return FleetSpec.cycling(TIERS, n, samples_per_client=8,
                             edges=edges, **kw)


class TestEdgeGrids:
    def test_grid_shapes_and_values(self):
        spec = _fleet(16, 4)
        clients = spec.build_clients()
        cohorts = build_edge_cohorts(clients, spec.topology)
        assert len(cohorts) == len(TIERS)           # one grid per plan
        flat = {c.id: c for c in clients}
        for cohort in cohorts:
            assert isinstance(cohort, EdgeCohort)
            assert cohort.n_edges == 4
            lead = next(iter(cohort.data.values())).shape[:2]
            assert lead == (cohort.n_edges, cohort.cap)
            # every client's shard sits at its (edge, row) cell, exactly
            for i, cid in enumerate(cohort.client_ids):
                e, r = cohort.edge_index[i], cohort.row_index[i]
                for k, grid in cohort.data.items():
                    assert np.array_equal(np.asarray(grid)[e, r],
                                          np.asarray(flat[cid].data[k]))

    def test_flat_metadata_preserved(self):
        spec = _fleet(16, 4)
        clients = spec.build_clients()
        grids = build_edge_cohorts(clients, spec.topology)
        flats = build_cohorts(clients)
        for g, f in zip(grids, flats):
            assert g.plan == f.plan
            assert g.client_ids == f.client_ids
            assert g.profile_names == f.profile_names

    def test_scatter_part_hits_cells_only(self):
        spec = _fleet(16, 4)
        cohort = build_edge_cohorts(spec.build_clients(), spec.topology)[0]
        part = np.zeros(cohort.size, bool)
        part[::2] = True
        grid = scatter_part(cohort, part)
        assert grid.shape == (cohort.n_edges, cohort.cap)
        assert grid.sum() == part.sum()             # padding cells stay 0
        for i in range(cohort.size):
            assert grid[cohort.edge_index[i], cohort.row_index[i]] == part[i]


# --------------------------- split-client-axis aggregation invariance

def _contribs(seed, counts, struct, quantize=False):
    """Per-shard cohort-form contributions (g_sum, count) for one plan —
    what each edge gateway forwards to the hub."""
    leaves, treedef = jax.tree_util.tree_flatten(struct)
    out = []
    for k, c in zip(jax.random.split(jax.random.PRNGKey(seed), len(counts)),
                    counts):
        ks = jax.random.split(k, len(leaves))
        gl = [4.0 * jax.random.normal(kk, p.shape, jnp.float32)
              for kk, p in zip(ks, leaves)]
        g = jax.tree_util.tree_unflatten(treedef, gl)
        if quantize:
            g = jax.tree.map(lambda x: fake_quant_ste(x, 4, 3), g)
        out.append((g, jnp.float32(c)))
    return out


def _partials_vs_chain(struct, contribs, masks, spec, weight, dense_den):
    """The invariance the hub rests on: each shard's partial accumulator
    (built from exact zeros) element-wise combined in fixed shard order
    is BITWISE the single-device chain over the same shards. Exactness
    hangs on the +0.0 accumulator inits: the first add into +0 never
    flips a sign bit, so each partial IS its contribution and the
    combine's add tree is literally the chain's."""
    chain = zeros_like_acc(struct, dense_den=dense_den)
    for g, count in contribs:
        chain = scatter_accumulate(chain, g, masks, spec, weight, count)

    combined = None
    for g, count in contribs:
        partial = scatter_accumulate(
            zeros_like_acc(struct, dense_den=dense_den),
            g, masks, spec, weight, count)
        combined = partial if combined is None else jax.tree.map(
            jnp.add, combined, partial)
    assert _bit_identical(chain, combined)
    assert _bit_identical(finalize(chain), finalize(combined))


SHARD_COUNTS = st.lists(st.integers(0, 7), min_size=1, max_size=4)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), SHARD_COUNTS)
def test_shard_partials_masked(seed, counts):
    plan = DEVICE_TIERS["mid"]
    _, masks = compress_params(PARAMS, plan)
    contribs = _contribs(seed, counts, PARAMS)
    _partials_vs_chain(PARAMS, contribs, masks, None,
                       jnp.float32(plan.weight), dense_den=False)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), SHARD_COUNTS)
def test_shard_partials_structured_width_sliced(seed, counts):
    plan = DEVICE_TIERS["low"].as_width_sliced()
    spec = submodel_spec(PARAMS, plan.width)
    local = slice_tree(PARAMS, spec)
    _, masks = compress_params(local, plan.inner())
    contribs = _contribs(seed, counts, local)
    _partials_vs_chain(PARAMS, contribs, masks, spec,
                       jnp.float32(plan.weight), dense_den=True)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), SHARD_COUNTS)
def test_shard_partials_quantized_uploads(seed, counts):
    plan = DEVICE_TIERS["mid"]
    _, masks = compress_params(PARAMS, plan)
    contribs = _contribs(seed, counts, PARAMS, quantize=True)
    _partials_vs_chain(PARAMS, contribs, masks, None,
                       jnp.float32(plan.weight), dense_den=False)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), SHARD_COUNTS)
def test_empty_shards_are_exact_identity(seed, counts):
    """Interspersed exact-zero shards (empty edges, padding rows) leave
    the chain bitwise untouched — the property that lets every grid span
    all E edges unconditionally."""
    plan = DEVICE_TIERS["mid"]
    _, masks = compress_params(PARAMS, plan)
    contribs = _contribs(seed, counts, PARAMS)
    zero = (jax.tree.map(jnp.zeros_like, PARAMS), jnp.float32(0.0))
    withz = [zero]
    for c in contribs:
        withz += [c, zero]
    w = jnp.float32(plan.weight)
    a = zeros_like_acc(PARAMS, dense_den=False)
    for g, count in contribs:
        a = scatter_accumulate(a, g, masks, None, w, count)
    b = zeros_like_acc(PARAMS, dense_den=False)
    for g, count in withz:
        b = scatter_accumulate(b, g, masks, None, w, count)
    assert _bit_identical(a, b)


# ------------------------------------------- scenario / server threading

SCENARIOS = {
    "sync_wait": FLScenario(
        fleet=_fleet(16, 4),
        participation=ParticipationPolicy(fraction=0.5, seed=11)),
    "sync_drop": FLScenario(fleet=_fleet(16, 4),
                            timing=SyncDrop(deadline=0.004)),
    "fedavg": FLScenario(
        fleet=_fleet(8, 4),
        local=LocalTraining(mode="fedavg", local_steps=3, local_lr=0.5)),
    "quant_ef": FLScenario(
        fleet=_fleet(8, 4),
        upload=UploadPolicy(quant="fp8_e4m3", error_feedback=True),
        participation=ParticipationPolicy(fraction=0.6, seed=5)),
    "width": FLScenario(fleet=_fleet(8, 4),
                        local=LocalTraining(submodel="width")),
}


def _server(name):
    return build_server(SCENARIOS[name], MODEL, optim.sgd(1.0), PARAMS)


class TestScenarioThreading:
    def test_fleet_spec_round_trip(self):
        spec = _fleet(16, 4)
        d = json.loads(json.dumps(spec.to_dict()))
        assert d["topology"] == {"edges": [[0, 1, 2, 3], [4, 5, 6, 7],
                                           [8, 9, 10, 11], [12, 13, 14, 15]]}
        spec2 = FleetSpec.from_dict(d)
        assert spec2 == spec and hash(spec2) == hash(spec)

    def test_topology_must_cover_fleet(self):
        with pytest.raises(ValueError, match="fleet has"):
            FleetSpec(tiers=TIERS * 2, n_samples=64,
                      topology=FleetTopology.contiguous(16, 4))

    def test_rejected_combinations(self):
        with pytest.raises(ValueError, match="per-client"):
            FLScenario(fleet=_fleet(16, 4), runtime="client")
        with pytest.raises(ValueError, match="sync-only"):
            FLScenario(fleet=_fleet(16, 4),
                       timing=AsyncBuffered(buffer_size=4))

    def test_build_server_makes_edge_grids(self):
        srv = _server("sync_wait")
        assert all(isinstance(c, EdgeCohort) for c in srv.cohorts)
        assert srv.topology == SCENARIOS["sync_wait"].fleet.topology

    def test_engine_rejects_pallas(self):
        with pytest.raises(ValueError, match="pallas"):
            ScanEngine(_server("sync_wait"), agg="pallas")

    def test_shard_fleet_rejects_flat_server(self):
        sc = FLScenario(fleet=FleetSpec.cycling(TIERS, 8,
                                                samples_per_client=8))
        srv = build_server(sc, MODEL, optim.sgd(1.0), PARAMS)
        with pytest.raises(ValueError, match="topology server"):
            shard_fleet(srv)


# ------------------------------------------------ trajectory identities

@pytest.mark.parametrize("name", [
    "sync_wait",
    "sync_drop",
    pytest.param("fedavg", marks=pytest.mark.slow),
    pytest.param("quant_ef", marks=pytest.mark.slow),
    "width",
])
def test_scan_engine_bit_identical_to_eager(name):
    """Topology fleets ride the scan engine like flat fleets do: the
    compiled grid rounds must reproduce the eager grid rounds' params /
    opt_state / records to the bit. The topology engine's wall/bytes
    records are host float64 (the verbatim eager expressions), so record
    equality here is exact, not approximate."""
    scenario = SCENARIOS[name]
    eager = simulate(scenario, 5)
    scan = simulate(scenario, 5, engine="scan", chunk_rounds=2)
    assert _bit_identical(eager.params, scan.params)
    assert _bit_identical(eager.opt_state, scan.opt_state)
    assert [r.loss for r in eager.records] == [r.loss for r in scan.records]
    for re, rs in zip(eager.records, scan.records):
        assert re.n_participants == rs.n_participants
        assert re.n_dropped == rs.n_dropped
        assert re.round_wall_time == rs.round_wall_time
        assert re.total_upload_bytes == rs.total_upload_bytes


multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >=2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@multi_device
@pytest.mark.parametrize("name", ["sync_wait", "fedavg", "quant_ef",
                                  "width"])
@pytest.mark.parametrize("engine", ["eager", "scan"])
def test_sharded_bit_identical_to_unsharded(name, engine):
    """The acceptance bar (ISSUE 8): sharding is data placement only —
    the same program over mesh-placed edge grids must reproduce the
    unsharded ``simulate()`` trajectory to the bit, eager and compiled,
    across sync-wait, fedavg, quant+EF and width-sliced fleets."""
    scenario = SCENARIOS[name]
    mesh = make_edge_mesh(4)
    assert mesh.devices.size >= 2
    un = simulate(scenario, 4, engine=engine)
    sh = simulate(scenario, 4, engine=engine, mesh=mesh)
    assert _bit_identical(un.params, sh.params)
    assert _bit_identical(un.opt_state, sh.opt_state)
    assert [r.loss for r in un.records] == [r.loss for r in sh.records]


@multi_device
def test_shard_fleet_places_edge_axis():
    """The placement contract: cohort grids sharded over ``"data"`` on
    the edge axis, params replicated, and the server remembers its
    mesh."""
    srv = _server("sync_wait")
    mesh = make_edge_mesh(4)
    shard_fleet(srv, mesh)
    assert srv.mesh is mesh
    for c in srv.cohorts:
        for leaf in jax.tree.leaves(c.data):
            assert leaf.sharding.spec[0] == "data"
    for leaf in jax.tree.leaves(srv.params):
        assert all(s is None for s in leaf.sharding.spec)


# --------------------------------------------------- census and traffic

class TestCensusAndTraffic:
    def test_census_edge_groups(self):
        c = scenario_census(SCENARIOS["width"])
        assert c["n_edges"] == 4
        assert len(c["edge_groups"]) == 4
        assert sum(g["clients"] for g in c["edge_groups"]) == 8
        for g in c["edge_groups"]:
            assert g["active_params_max"] > 0
            assert g["round_wall_time"] > 0
            assert g["uplink_bytes"] > 0

    def test_cross_shard_bytes_independent_of_client_count(self):
        """The traffic model's point: edge->hub bytes depend on plans
        and edge count, never on how many devices hang off each
        gateway."""
        small = scenario_census(FLScenario(fleet=_fleet(16, 4)))
        big = scenario_census(FLScenario(fleet=_fleet(64, 4)))
        assert (small["cross_shard_bytes_per_round"]
                == big["cross_shard_bytes_per_round"])
        more_edges = scenario_census(FLScenario(fleet=_fleet(64, 8)))
        assert (more_edges["cross_shard_bytes_per_round"]
                == 2 * small["cross_shard_bytes_per_round"])

    def test_cross_shard_bytes_structured_is_smaller(self):
        plans = [DEVICE_TIERS[t] for t in TIERS]
        full = cross_shard_bytes(PARAMS, plans, 4)
        sliced = cross_shard_bytes(
            PARAMS, [p.as_width_sliced() for p in plans], 4)
        assert sliced < full
