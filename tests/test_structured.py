"""Structured sub-model compression (DESIGN.md §13): width-sliced local
models, shape-true Eq. (1), and coverage-counted scatter aggregation.

The acceptance bars: at width=1.0 the structured path reproduces the
masked cohort trajectory BIT-identically; ``scatter_accumulate`` matches
the dense masked reference at matched coordinates; the scan engine
compiles structured cohorts to the same trajectory as the eager loop;
payloads shrink by the sliced parameter count.
"""
import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import optim
from repro.configs.paper_mlp import config
from repro.core.aggregation import (accumulate_cohort, finalize,
                                    scatter_accumulate, zeros_like_acc)
from repro.core.compression import (CompressionPlan, DEVICE_TIERS,
                                    active_param_count, compress_params,
                                    expand_masks, expand_update,
                                    payload_bits, plan_arrays,
                                    slice_submodel, slice_tree,
                                    submodel_spec)
from repro.core.federated import Client, CohortFLServer
from repro.core.heterogeneity import PROFILES, round_time
from repro.core.scenario import (FleetSpec, FLScenario, LocalTraining,
                                 ParticipationPolicy, UploadPolicy,
                                 build_server, scenario_census, simulate)
from repro.data import make_gaussian_dataset, partition_iid
from repro.models import mlp

KEY = jax.random.PRNGKey(0)
MODEL = types.SimpleNamespace(loss_fn=mlp.loss_fn)


def _bit_identical(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(bool(jnp.all(x == y))
                                      for x, y in zip(la, lb))


# ------------------------------------------------------------ slicing

def test_slice_shapes_follow_ceil_rule_and_preserve_io_dims():
    """MLP 5->10x5->2 at width 0.25: hidden dims slice to ceil(0.25*10)=3,
    the model input (5) and output (2) dims are preserved, biases follow
    their layer's out-slice, the classifier bias stays full."""
    params = mlp.init(KEY, config())
    sub, spec = slice_submodel(params, 0.25)
    ws = [lp["w"].shape for lp in sub["layers"]]
    bs = [lp["b"].shape for lp in sub["layers"]]
    assert ws == [(5, 3), (3, 3), (3, 3), (3, 3), (3, 3), (3, 2)]
    assert bs == [(3,), (3,), (3,), (3,), (3,), (2,)]
    # the sub-model is a real model: same features in, same classes out
    assert mlp.apply(sub, jnp.ones((4, 5))).shape == (4, 2)


def test_slice_is_prefix_of_global():
    params = mlp.init(KEY, config())
    sub, spec = slice_submodel(params, 0.5)
    for s, p in zip(jax.tree.leaves(sub), jax.tree.leaves(params)):
        idx = tuple(slice(0, k) for k in s.shape)
        assert bool(jnp.all(s == p[idx]))


def test_width_one_is_identity():
    params = mlp.init(KEY, config())
    sub, spec = slice_submodel(params, 1.0)
    assert spec.is_identity
    for s, p in zip(jax.tree.leaves(sub), jax.tree.leaves(params)):
        assert s is p                       # same objects, not copies


def test_router_and_free_1d_leaves_pass_through():
    k = jax.random.PRNGKey(1)
    p = {"a": {"w": jax.random.normal(k, (8, 8))},
         "b": {"w": jax.random.normal(k, (8, 8))},
         "c": {"w": jax.random.normal(k, (8, 4))},
         "ln": jnp.ones((8,)),                       # no matrix sibling
         "moe": {"router": {"w": jax.random.normal(k, (8, 4))}}}
    sub, spec = slice_submodel(p, 0.5)
    assert sub["moe"]["router"]["w"].shape == (8, 4)  # excluded
    assert sub["ln"].shape == (8,)                    # not co-sliced
    assert sub["a"]["w"].shape == (8, 4)              # first: rows kept
    assert sub["b"]["w"].shape == (4, 4)
    assert sub["c"]["w"].shape == (4, 4)              # last: cols kept


def test_single_matrix_model_rejects_width_slicing():
    """A one-matrix model has no interior dim to cut (its in/out dims
    are preserved), so width < 1.0 must raise instead of silently
    training the full model at a dropped budget."""
    one = {"w": jnp.zeros((16, 16))}
    with pytest.raises(ValueError, match="interior dimension"):
        submodel_spec(one, 0.25)
    assert submodel_spec(one, 1.0).is_identity    # full width stays legal
    # ceil-rounding a sliceable axis back to full size is NOT an error
    two = {"layers": [{"w": jnp.zeros((10, 10))}, {"w": jnp.zeros((10, 10))}]}
    assert submodel_spec(two, 0.99).is_identity


def test_scan_pallas_runs_structured_fleets_fused_without_warning():
    """The bugfix this PR exists for: ``agg="pallas"`` on a structured
    fleet used to warn and silently fall back to the sequential scatter.
    It now routes through the fused prefix-block kernel, records the
    backend it actually used, and stays bitwise with the eager loop."""
    import warnings
    scenario = FLScenario(
        fleet=FleetSpec.cycling(("hub", "mid"), 4, samples_per_client=8),
        local=LocalTraining(submodel="width"))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        res = simulate(scenario, 2, engine="scan_pallas")
    assert not [w for w in caught
                if "scatter" in str(w.message) or "sequential" in str(w.message)]
    assert res.agg_backend == "pallas_structured"
    eager = simulate(scenario, 2)
    assert eager.agg_backend == "sequential"
    assert _bit_identical(eager.params, res.params)


def test_expand_update_is_slice_adjoint():
    """expand_update is the exact transpose of slice_tree: autodiff
    through slicing produces the same zero-padded cotangent."""
    params = mlp.init(KEY, config())
    sub, spec = slice_submodel(params, 0.5)
    g_sub = jax.tree.map(lambda x: jnp.full(x.shape, 2.0), sub)
    expanded = expand_update(g_sub, spec, params)
    # autodiff: d/dp sum(2 * slice(p)) == expand(2 * ones_sub)
    auto = jax.grad(
        lambda p: sum(2.0 * jnp.sum(x)
                      for x in jax.tree.leaves(slice_tree(p, spec))))(params)
    assert _bit_identical(expanded, auto)
    # and slicing the expansion recovers the sub-update exactly
    assert _bit_identical(slice_tree(expanded, spec), g_sub)


def test_compress_params_structured_shape_contract():
    """cparams at LOCAL shapes, masks at GLOBAL shapes (coverage ∧ inner
    mask; prefix coverage vectors for co-sliced biases)."""
    params = mlp.init(KEY, config())
    plan = CompressionPlan("x", density=0.5, quant="fp8_e4m3", width=0.5)
    cp, masks = compress_params(params, plan)
    sub, spec = slice_submodel(params, 0.5)
    for c, s in zip(jax.tree.leaves(cp), jax.tree.leaves(sub)):
        assert c.shape == s.shape
    flat_m = jax.tree.leaves(masks)
    flat_p = jax.tree.leaves(params)
    for i, (m, p) in enumerate(zip(flat_m, flat_p)):
        if spec.slices[i] is None and p.ndim < 2:
            assert np.shape(m) == ()          # excluded, uncovered: scalar
            continue
        assert m.shape == p.shape
        # nothing outside the slice is covered
        loc = spec.local_shape(i)
        outside = np.asarray(m).copy()
        outside[tuple(slice(0, k) for k in loc)] = 0.0
        assert not outside.any()
    # a co-sliced bias mask is a prefix coverage vector
    b_mask = masks["layers"][0]["b"]
    assert b_mask.tolist() == [1.0] * 5 + [0.0] * 5


def test_plan_width_validation_and_helpers():
    with pytest.raises(ValueError, match="width"):
        CompressionPlan("x", width=0.0)
    with pytest.raises(ValueError, match="width"):
        CompressionPlan("x", width=1.5)
    p = CompressionPlan("mid", density=0.5, quant="bf16")
    s = p.as_width_sliced()
    assert s.structured and s.width == 0.5 and s.density == 1.0
    assert s.as_width_sliced() is s           # idempotent
    # inner() is the WITHIN-slice plan: width stripped, density untouched
    assert s.inner() == dataclasses.replace(s, width=None)
    assert not s.inner().structured
    with pytest.raises(ValueError, match="tier-scanned"):
        plan_arrays([s])


# ---------------------------------------------- scatter aggregation

def test_scatter_accumulate_matches_dense_masked_reference():
    """The acceptance bar: scattering a sub-shaped (update, mask) equals
    accumulating the zero-padded dense twins — bitwise, coordinate for
    coordinate — through the shared accumulate/finalize chain."""
    params = mlp.init(KEY, config())
    plans = [CompressionPlan("a", width=0.5, weight=1.5),
             CompressionPlan("b", width=0.25, density=0.5, weight=2.0)]
    counts = [3.0, 2.0]
    key = jax.random.PRNGKey(3)

    acc_s = zeros_like_acc(params, dense_den=True)
    acc_d = zeros_like_acc(params, dense_den=True)
    for plan, count in zip(plans, counts):
        key, k = jax.random.split(key)
        spec = submodel_spec(params, plan.width)
        sub = slice_tree(params, spec)
        g_sub = jax.tree.map(lambda p: jax.random.normal(k, p.shape), sub)
        _, m_sub = compress_params(sub, plan.inner())
        w, c = jnp.float32(plan.weight), jnp.float32(count)
        acc_s = scatter_accumulate(acc_s, g_sub, m_sub, spec, w, c)
        # dense reference: pad the update, lift the masks, accumulate
        m_full = expand_masks(m_sub, spec, params)
        g_full = expand_update(g_sub, spec, params)
        acc_d = accumulate_cohort(acc_d, g_full, m_full, w, c)
    assert _bit_identical(acc_s[0], acc_d[0])
    assert _bit_identical(acc_s[1], acc_d[1])
    assert _bit_identical(finalize(acc_s), finalize(acc_d))


def test_scatter_and_masked_cohorts_share_one_accumulator():
    """A mixed fleet: one masked cohort through accumulate_cohort, one
    sliced cohort through scatter_accumulate, into the SAME accumulators.
    Uncovered coordinates get only the masked tier's update; doubly
    covered ones average per-coordinate."""
    params = {"layers": [{"w": jnp.zeros((4, 4))},
                         {"w": jnp.zeros((4, 4))},
                         {"w": jnp.zeros((4, 4))}]}
    acc = zeros_like_acc(params, dense_den=True)
    ones = jax.tree.map(jnp.ones_like, params)
    acc = accumulate_cohort(acc, jax.tree.map(lambda x: 2.0 * x, ones),
                            ones, jnp.float32(1.0), jnp.float32(1.0))
    spec = submodel_spec(params, 0.5)
    sub = slice_tree(params, spec)
    acc = scatter_accumulate(acc, jax.tree.map(lambda x: jnp.full(x.shape, 6.0), sub),
                             jax.tree.map(jnp.ones_like, sub), spec,
                             jnp.float32(1.0), jnp.float32(1.0))
    agg = finalize(acc)
    mid = np.asarray(agg["layers"][1]["w"])
    np.testing.assert_array_equal(mid[:2, :2], 4.0)   # (2+6)/2
    np.testing.assert_array_equal(mid[2:, 2:], 2.0)   # masked tier only
    # staleness discount is numerator-only through the scatter path too
    acc2 = scatter_accumulate(zeros_like_acc(params, dense_den=True),
                              jax.tree.map(lambda x: jnp.full(x.shape, 6.0), sub),
                              jax.tree.map(jnp.ones_like, sub), spec,
                              jnp.float32(1.0), jnp.float32(1.0),
                              staleness_weight=jnp.float32(0.5))
    assert float(finalize(acc2)["layers"][1]["w"][0, 0]) == 3.0


# ------------------------------------------------ runtime parity

def _fleet(plans, n_samples=128):
    data = make_gaussian_dataset(KEY, n_samples)
    shards = partition_iid(KEY, data, len(plans))
    return [Client(i, p, shards[i], profile_name="mid")
            for i, p in enumerate(plans)]


def _run(plans, optimizer, rounds=4, **kw):
    srv = CohortFLServer.from_clients(
        _fleet(plans), model=MODEL, optimizer=optimizer,
        params=mlp.init(KEY, config()), **kw)
    for _ in range(rounds):
        srv.round()
    return srv


@pytest.mark.parametrize("opt_name,kw", [
    ("sgd", {}),
    ("adam", dict(sample_fraction=0.5, seed=7)),
    pytest.param("sgd", dict(mode="fedavg", local_steps=3, local_lr=0.5),
                 marks=pytest.mark.slow),
    pytest.param("sgd", dict(upload_quant="fp8_e4m3", error_feedback=True),
                 marks=pytest.mark.slow),
])
def test_width_one_structured_trajectory_bit_identical_to_masked(opt_name, kw):
    """The tentpole's correctness anchor: width=1.0 routes through the
    structured code path (slice -> compress-within-slice -> scatter) yet
    must reproduce the masked cohort trajectory to the bit, across
    optimizers, partial participation, fedavg and quant+EF."""
    mk = {"sgd": lambda: optim.sgd(1.0), "adam": lambda: optim.adam(0.05)}
    plans_m = [DEVICE_TIERS["hub"], DEVICE_TIERS["mid"],
               DEVICE_TIERS["low"], DEVICE_TIERS["high"]]
    plans_w = [dataclasses.replace(p, width=1.0) for p in plans_m]
    a = _run(plans_m, mk[opt_name](), **kw)
    b = _run(plans_w, mk[opt_name](), **kw)
    assert b.any_structured and not a.any_structured
    assert _bit_identical(a.params, b.params)
    assert _bit_identical(a.opt_state, b.opt_state)
    assert [h["loss"] for h in a.history] == [h["loss"] for h in b.history]


WIDTH_SCENARIOS = {
    "fedsgd": FLScenario(
        fleet=FleetSpec.cycling(("hub", "high", "mid", "low"), 16,
                                samples_per_client=16),
        local=LocalTraining(submodel="width"),
        participation=ParticipationPolicy(fraction=0.5, seed=11)),
    "quant_ef": FLScenario(
        fleet=FleetSpec.cycling(("hub", "mid", "low"), 6,
                                samples_per_client=16),
        local=LocalTraining(submodel="width"),
        upload=UploadPolicy(quant="fp8_e4m3", error_feedback=True)),
    "fedavg": FLScenario(
        fleet=FleetSpec.cycling(("hub", "mid", "low"), 6,
                                samples_per_client=16),
        local=LocalTraining(mode="fedavg", local_steps=3, local_lr=0.5,
                            submodel="width")),
}


@pytest.mark.parametrize("name", [
    "fedsgd",
    pytest.param("quant_ef", marks=pytest.mark.slow),
    pytest.param("fedavg", marks=pytest.mark.slow),
])
def test_scan_engine_bit_identical_for_structured_cohorts(name):
    """Structured cohorts ride the donated scan carry (sub-shaped EF,
    in-body scatter) and must still match the eager loop bit for bit —
    on BOTH engine aggregation backends: the sequential scatter and the
    fused prefix-block Pallas kernel (DESIGN.md §15)."""
    scenario = WIDTH_SCENARIOS[name]
    eager = simulate(scenario, 5)
    scan = simulate(scenario, 5, engine="scan", chunk_rounds=2)
    fused = simulate(scenario, 5, engine="scan_pallas", chunk_rounds=2)
    assert eager.server.any_structured
    assert scan.agg_backend == "sequential"
    assert fused.agg_backend == "pallas_structured"
    for other in (scan, fused):
        assert _bit_identical(eager.params, other.params)
        assert _bit_identical(eager.opt_state, other.opt_state)
        assert [r.loss for r in eager.records] == [r.loss
                                                   for r in other.records]


def test_fused_scatter_handles_mixed_masked_and_sliced_fleet():
    """A fleet mixing full-coverage (width=1.0, identity spec) and
    sliced tiers: the full tiers ride the same kernel tier axis as
    plain adds, and the whole round stays bitwise with eager."""
    scenario = FLScenario(
        fleet=FleetSpec.cycling(("hub", "high", "low"), 6,
                                samples_per_client=16),
        local=LocalTraining(submodel="width"))
    eager = simulate(scenario, 4)
    fused = simulate(scenario, 4, engine="scan_pallas", chunk_rounds=2)
    widths = {c.plan.width for c in eager.server.cohorts}
    assert 1.0 in widths and len(widths) > 1      # genuinely mixed
    assert fused.agg_backend == "pallas_structured"
    assert _bit_identical(eager.params, fused.params)
    assert _bit_identical(eager.opt_state, fused.opt_state)


def test_structured_sub_shaped_ef_buffers():
    """EF residuals for a structured cohort live at the SLICED shapes —
    that is the memory win the tentpole claims."""
    scenario = WIDTH_SCENARIOS["quant_ef"]
    res = simulate(scenario, 2)
    params = res.params
    for cohort in res.server.cohorts:
        assert cohort.ef_buffer is not None
        sub, _ = slice_submodel(params, cohort.plan.width)
        for e, s in zip(jax.tree.leaves(cohort.ef_buffer),
                        jax.tree.leaves(sub)):
            assert e.shape == (cohort.size,) + s.shape


def test_client_loop_matches_cohort_for_structured_fleet():
    """The client-granular FLServer supports structured plans through
    full-shape zero-padding (grads via autodiff, fedavg deltas via
    expand_update) — at full participation its per-round losses must
    match the cohort runtime's scatter path."""
    spec = FleetSpec.cycling(("hub", "mid", "low"), 6, samples_per_client=16)
    for mode in ("fedsgd", "fedavg"):
        local = LocalTraining(mode=mode, local_steps=2, local_lr=0.5,
                              submodel="width")
        loop = simulate(FLScenario(fleet=spec, local=local,
                                   runtime="client"), 3)
        cohort = simulate(FLScenario(fleet=spec, local=local), 3)
        np.testing.assert_allclose(loop.losses, cohort.losses, rtol=2e-5)
        for a, b in zip(jax.tree.leaves(loop.params),
                        jax.tree.leaves(cohort.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=2e-6)


@pytest.mark.slow
def test_client_loop_structured_quant_ef_residuals_stay_in_coverage():
    """FLServer structured + upload quant + EF: the client-granular
    path's residuals ride at FULL shape (its grads are zero-padded), so
    a sliced tier's residual must be exactly zero outside its coverage
    — quantization error can only accumulate where updates flow."""
    spec = FleetSpec(tiers=("hub", "mid", "low"), n_samples=96)
    res = simulate(FLScenario(fleet=spec, runtime="client",
                              local=LocalTraining(submodel="width"),
                              upload=UploadPolicy(quant="fp8_e4m3",
                                                  error_feedback=True)), 4)
    assert all(np.isfinite(r.loss) for r in res.records)
    low = res.server.clients[2]                    # width 0.25 tier
    assert low.plan.structured
    s = submodel_spec(res.params, low.plan.width)
    flat_e = jax.tree.leaves(low.ef_buffer)
    flat_p = jax.tree.leaves(res.params)
    touched = 0
    for i, (e, p) in enumerate(zip(flat_e, flat_p)):
        assert e.shape == p.shape                  # full-shape residual
        if s.slices[i] is None:
            continue
        outside = np.asarray(e).copy()
        outside[tuple(slice(0, k) for k in s.slices[i])] = 0.0
        assert not outside.any()
        touched += 1
    assert touched


def test_async_structured_reduces_to_sync_at_full_buffer():
    """AsyncFLServer's structured scatter branch, pinned by the §10
    equivalence limit: buffer_size == n_clients with the staleness
    discount off consumes exactly one fresh upload per client per
    window, reproducing the sync-wait cohort trajectory."""
    from repro.core.scenario import AsyncBuffered
    spec = FleetSpec.cycling(("hub", "mid", "low"), 6, samples_per_client=16)
    local = LocalTraining(submodel="width")
    sync = simulate(FLScenario(fleet=spec, local=local), 4)
    asy = simulate(FLScenario(fleet=spec, local=local,
                              timing=AsyncBuffered(buffer_size=6,
                                                   staleness_exp=0.0)), 4)
    assert asy.server.n_versions_live >= 1
    for a, b in zip(jax.tree.leaves(sync.params),
                    jax.tree.leaves(asy.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_structured_low_tier_loses_no_global_coordinates():
    """A fleet mixing a full-width hub and a 0.25-width tier: every
    global coordinate still receives updates (the hub covers what the
    slice misses), and training reduces the loss."""
    plans = [DEVICE_TIERS["hub"], DEVICE_TIERS["low"].as_width_sliced()]
    srv = _run(plans, optim.sgd(1.0), rounds=8)
    assert srv.history[-1]["loss"] < srv.history[0]["loss"]


# -------------------------------------------------- scenario layer

def test_scenario_submodel_roundtrips_and_validates():
    sc = WIDTH_SCENARIOS["fedsgd"]
    back = FLScenario.from_dict(json.loads(json.dumps(sc.to_dict())))
    assert back == sc and back.local.submodel == "width"
    # old wire format (no submodel key) defaults to masked
    d = sc.local.to_dict()
    d.pop("submodel")
    assert LocalTraining.from_dict(d).submodel == "mask"
    with pytest.raises(ValueError, match="submodel"):
        LocalTraining(submodel="depth")


def test_build_server_width_converts_plans_without_mutating_clients():
    sc = WIDTH_SCENARIOS["fedsgd"]
    clients = sc.fleet.build_clients()
    plans_before = [c.plan for c in clients]
    srv = build_server(sc, MODEL, optim.sgd(1.0), mlp.init(KEY, config()),
                       clients=clients)
    assert all(c.plan.structured for c in srv.cohorts)
    assert [c.plan for c in clients] == plans_before   # caller's list intact
    assert {c.plan.width for c in srv.cohorts} == {1.0, 0.5, 0.25}


def test_census_reports_sliced_payloads():
    spec = FleetSpec(tiers=("hub", "mid", "low"), n_samples=300)
    masked = scenario_census(FLScenario(fleet=spec))
    width = scenario_census(FLScenario(fleet=spec,
                                       local=LocalTraining(submodel="width")))
    json.dumps(width)
    assert (width["total_upload_bytes_per_round"]
            < masked["total_upload_bytes_per_round"])


# ------------------------------------------------------ Eq. (1)

def test_eq1_uses_sliced_counts():
    """T_local/T_upload/T_download shrink by the actual sliced parameter
    counts; the payload equals payload_bits of the structured plan."""
    params = mlp.init(KEY, config())
    masked = CompressionPlan("m", density=0.25)
    sliced = masked.as_width_sliced()
    t_m = round_time(params, masked, PROFILES["low"], 64)
    t_s = round_time(params, sliced, PROFILES["low"], 64)
    assert t_s["T_local"] < t_m["T_local"]
    assert t_s["T_upload"] < t_m["T_upload"]
    assert t_s["payload_bytes"] == payload_bits(params, sliced) / 8
    # T_local ratio equals the active-param ratio exactly
    assert t_s["T_local"] / t_m["T_local"] == pytest.approx(
        active_param_count(params, sliced) / active_param_count(params, masked))


def _deep_tree(dim=128, n_layers=6):
    """Bias-free tower with tiny boundary layers, so the width-w vs
    density-w^2 payload comparison is dominated by interior slices."""
    k = jax.random.PRNGKey(0)
    dims = [2] + [dim] * n_layers + [2]
    return {"layers": [{"w": jax.random.normal(k, (i, o))}
                       for i, o in zip(dims[:-1], dims[1:])]}


@settings(max_examples=25, deadline=None)
@given(st.floats(0.2, 1.0))
def test_width_w_payload_consistent_with_density_w_squared(width):
    """The structured/masked budget correspondence: a width-w slice keeps
    ~w^2 of each interior matrix, so its Eq. (1) payload must track a
    density-w^2 masked plan (up to ceil rounding and the preserved
    input/output dims)."""
    params = _deep_tree()
    structured = CompressionPlan("s", width=width)
    masked = CompressionPlan("m", density=width * width)
    ps = payload_bits(params, structured)
    pm = payload_bits(params, masked)
    assert ps == pytest.approx(pm, rel=0.12)
    # and the structured payload is EXACTLY the sliced count at 32 bits
    spec = submodel_spec(params, width)
    assert ps == spec.local_size() * 32.0
