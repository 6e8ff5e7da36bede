"""Cohort-vectorized runtime (DESIGN.md §9): equivalence with the
per-client loop, partial participation, straggler policies, EF buffers."""
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro import optim
from repro.configs.paper_mlp import config
from repro.core.compression import DEVICE_TIERS
from repro.core.federated import (Client, CohortFLServer, FLServer,
                                  build_cohorts)
from repro.core.heterogeneity import PROFILES, cohort_round_time, round_time
from repro.data import make_gaussian_dataset, partition_iid, stack_shards
from repro.models import mlp

KEY = jax.random.PRNGKey(42)
MODEL = types.SimpleNamespace(loss_fn=functools.partial(mlp.loss_fn))
FLEET = ("hub", "high", "mid", "low", "mid", "low")
N_SAMPLES = 768                # divisible by len(FLEET): equal-size shards,
                                # so stack_shards truncates nothing and the
                                # cohort path sees identical data to the loop


def _fleet(tiers=FLEET, n_samples=N_SAMPLES):
    data = make_gaussian_dataset(KEY, n_samples)
    shards = partition_iid(KEY, data, len(tiers))
    return [Client(i, DEVICE_TIERS[t], shards[i], profile_name=t)
            for i, t in enumerate(tiers)]


def _max_diff(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _servers(mode="fedsgd", **kw):
    params = mlp.init(KEY, config())
    loop = FLServer(model=MODEL, optimizer=optim.sgd(1.0), clients=_fleet(),
                    params=params, mode=mode, **kw)
    coh = CohortFLServer.from_clients(
        _fleet(), model=MODEL, optimizer=optim.sgd(1.0), params=params,
        mode=mode, **kw)
    return loop, coh


# ------------------------------------------------------- equivalence

@pytest.mark.parametrize("mode,kw", [
    ("fedsgd", {}),
    pytest.param("fedavg", {"local_steps": 3, "local_lr": 0.5},
                 marks=pytest.mark.slow),
    pytest.param("fedsgd", {"upload_quant": "fp8_e4m3",
                            "error_feedback": True},
                 marks=pytest.mark.slow),
])
def test_cohort_round_matches_per_client_loop(mode, kw):
    """The vectorized round must reproduce the per-client loop's params
    (up to f32 reduction-order noise) for a mixed-plan fleet."""
    loop, coh = _servers(mode, **kw)
    for _ in range(2):
        rl, rc = loop.round(), coh.round()
    assert _max_diff(loop.params, coh.params) < 1e-5
    assert rl["loss"] == pytest.approx(rc["loss"], abs=1e-5)
    assert rl["round_wall_time"] == pytest.approx(rc["round_wall_time"],
                                                 rel=1e-6)
    assert rl["total_upload_bytes"] == pytest.approx(
        rc["total_upload_bytes"], rel=1e-6)


def test_build_cohorts_groups_by_plan():
    cohorts = build_cohorts(_fleet())
    assert len(cohorts) == 4                     # 4 distinct plans in FLEET
    assert sum(c.size for c in cohorts) == len(FLEET)
    ids = sorted(i for c in cohorts for i in c.client_ids)
    assert ids == list(range(len(FLEET)))
    for c in cohorts:
        assert next(iter(c.data.values())).shape[0] == c.size


def test_stack_shards_truncates_to_common_floor():
    shards = [{"x": jnp.ones((5, 3)), "y": jnp.zeros((5,))},
              {"x": jnp.ones((9, 3)), "y": jnp.zeros((9,))}]
    stacked = stack_shards(shards)
    assert stacked["x"].shape == (2, 5, 3)
    assert stacked["y"].shape == (2, 5)


@functools.lru_cache(maxsize=1)
def _time_params():
    return mlp.init(KEY, config())


@settings(max_examples=20, deadline=None)
@given(st.lists(st.sampled_from(sorted(PROFILES)), min_size=1, max_size=6),
       st.sampled_from(sorted(DEVICE_TIERS)),
       st.integers(min_value=1, max_value=1024),
       st.integers(min_value=1, max_value=8),
       st.booleans())
def test_cohort_round_time_parity_hypothesis(profile_names, tier, n_samples,
                                             local_steps, per_client_ns):
    """Property: under arbitrary profile/plan draws, the vectorized
    Eq. (1) arrays must match the scalar round_time leaf-for-leaf —
    including payload_bytes — for scalar AND per-client n_samples."""
    params = _time_params()
    plan = DEVICE_TIERS[tier]
    profs = [PROFILES[p] for p in profile_names]
    ns = ([n_samples + 3 * i for i in range(len(profs))] if per_client_ns
          else n_samples)
    vec = cohort_round_time(params, plan, profs, ns, local_steps)
    assert all(v.shape == (len(profs),) for v in vec.values())
    for i, p in enumerate(profs):
        n_i = ns[i] if per_client_ns else n_samples
        ref = round_time(params, plan, p, n_i, local_steps)
        for k in ("T_local", "T_upload", "T_global", "T_download", "T",
                  "payload_bytes"):
            assert vec[k][i] == pytest.approx(ref[k], rel=1e-12), (k, i)


def test_cohort_round_time_matches_scalar_round_time():
    params = mlp.init(KEY, config())
    plan = DEVICE_TIERS["mid"]
    profs = [PROFILES["hub"], PROFILES["low"]]
    vec = cohort_round_time(params, plan, profs, 128, local_steps=3)
    for i, p in enumerate(profs):
        ref = round_time(params, plan, p, 128, local_steps=3)
        for k in ("T_local", "T_upload", "T_global", "T_download", "T",
                  "payload_bytes"):
            assert vec[k][i] == pytest.approx(ref[k], rel=1e-12)


# ----------------------------------------- partial participation

def test_forced_participation_equals_loop_over_subset():
    """A pinned participation mask must equal the per-client loop run on
    exactly the participating clients."""
    coh = CohortFLServer.from_clients(
        _fleet(), model=MODEL, optimizer=optim.sgd(1.0),
        params=mlp.init(KEY, config()))
    part = [np.zeros(c.size, bool) for c in coh.cohorts]
    keep_ids = []
    for ci, c in enumerate(coh.cohorts):         # first client of each cohort
        part[ci][0] = True
        keep_ids.append(c.client_ids[0])
    rec = coh.round(participation=part)
    assert rec["n_participants"] == len(coh.cohorts)

    sub = [c for c in _fleet() if c.id in keep_ids]
    loop = FLServer(model=MODEL, optimizer=optim.sgd(1.0), clients=sub,
                    params=mlp.init(KEY, config()))
    loop.round()
    assert _max_diff(loop.params, coh.params) < 1e-5


def test_sample_fraction_limits_participants():
    coh = CohortFLServer.from_clients(
        _fleet(), model=MODEL, optimizer=optim.sgd(1.0),
        params=mlp.init(KEY, config()), sample_fraction=0.5, seed=7)
    seen = set()
    for _ in range(6):
        rec = coh.round()
        assert rec["n_participants"] == 3        # round(0.5 * 6)
        seen.add(rec["loss"])
    assert len(seen) > 1                         # different subsets sampled


def test_empty_round_leaves_params_untouched():
    coh = CohortFLServer.from_clients(
        _fleet(), model=MODEL, optimizer=optim.sgd(1.0),
        params=mlp.init(KEY, config()))
    p0 = coh.params
    rec = coh.round(participation=[np.zeros(c.size, bool)
                                   for c in coh.cohorts])
    assert rec["n_participants"] == 0
    assert rec["loss"] is None            # empty round: no NaN sentinel
    assert _max_diff(p0, coh.params) == 0.0


def test_all_dropped_round_is_bit_identical_noop_that_advances_step():
    """A deadline below every tier's round time drops the whole fleet:
    params AND opt_state must be bit-identical (no optimizer step ran on
    a zero accumulator), the loss None, and the step counter still
    advances — pins the empty-round path of CohortFLServer.round."""
    times = _tier_times()
    coh = CohortFLServer.from_clients(
        _fleet(), model=MODEL, optimizer=optim.adam(0.1),
        params=mlp.init(KEY, config()), straggler="drop",
        deadline=min(times.values()) / 2)
    p0 = jax.tree.map(np.asarray, coh.params)
    s0 = jax.tree.map(np.asarray, coh.opt_state)
    rec = coh.round()
    assert rec["n_participants"] == 0
    assert rec["n_dropped"] == len(FLEET)
    assert rec["loss"] is None            # empty round: no NaN sentinel
    assert rec["step"] == 1 and coh.step == 1       # clock still advances
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(coh.params)):
        np.testing.assert_array_equal(a, np.asarray(b))
    for a, b in zip(jax.tree.leaves(s0), jax.tree.leaves(coh.opt_state)):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_seed_determinism_of_sampled_rounds():
    """Same seed => identical history over sampled rounds; a different
    seed samples different subsets and diverges."""
    def hist(seed):
        srv = CohortFLServer.from_clients(
            _fleet(), model=MODEL, optimizer=optim.sgd(1.0),
            params=mlp.init(KEY, config()), sample_fraction=0.5, seed=seed)
        for _ in range(5):
            srv.round()
        return srv.history

    assert hist(3) == hist(3)
    assert hist(3) != hist(4)


# ------------------------------------------- straggler / deadline

def _tier_times():
    params = mlp.init(KEY, config())
    return {t: round_time(params, DEVICE_TIERS[t], PROFILES[t],
                          N_SAMPLES // len(FLEET))["T"] for t in set(FLEET)}


def test_deadline_drops_stragglers():
    times = _tier_times()
    # deadline between the fastest and slowest tier's analytic round time
    cut = (max(times.values()) + min(times.values())) / 2
    slow_tiers = {t for t, v in times.items() if v > cut}
    coh = CohortFLServer.from_clients(
        _fleet(), model=MODEL, optimizer=optim.sgd(1.0),
        params=mlp.init(KEY, config()), straggler="drop", deadline=cut)
    rec = coh.round()
    expect_dropped = sum(1 for t in FLEET if t in slow_tiers)
    assert rec["n_dropped"] == expect_dropped > 0
    assert rec["n_participants"] == len(FLEET) - expect_dropped
    assert rec["round_wall_time"] == cut         # server waits out deadline


def test_wait_policy_blocks_on_slowest():
    times = _tier_times()
    coh = CohortFLServer.from_clients(
        _fleet(), model=MODEL, optimizer=optim.sgd(1.0),
        params=mlp.init(KEY, config()), straggler="wait")
    rec = coh.round()
    assert rec["n_dropped"] == 0
    assert rec["round_wall_time"] == pytest.approx(max(times.values()),
                                                   rel=1e-6)


def test_drop_requires_deadline():
    with pytest.raises(ValueError):
        CohortFLServer.from_clients(
            _fleet(), model=MODEL, optimizer=optim.sgd(1.0),
            params=mlp.init(KEY, config()), straggler="drop")


# --------------------------------- error feedback across rounds

def test_ef_buffer_matches_param_dtype():
    """Lazily-initialized cohort EF buffers must adopt the param leaf
    dtype (they were hardcoded float32, breaking bf16 fleets)."""
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16),
                          mlp.init(KEY, config()))
    coh = CohortFLServer.from_clients(
        _fleet(tiers=("mid", "low")), model=MODEL, optimizer=optim.sgd(1.0),
        params=params, upload_quant="fp8_e4m3", error_feedback=True)
    coh.round()
    for c in coh.cohorts:
        assert c.ef_buffer is not None
        for p, e in zip(jax.tree.leaves(params),
                        jax.tree.leaves(c.ef_buffer)):
            assert e.dtype == p.dtype == jnp.bfloat16
            assert e.shape == (c.size,) + p.shape


def test_ef_buffer_survives_non_participation():
    coh = CohortFLServer.from_clients(
        _fleet(tiers=("mid", "mid", "low")), model=MODEL,
        optimizer=optim.sgd(1.0), params=mlp.init(KEY, config()),
        upload_quant="fp8_e4m3", error_feedback=True)
    full = [np.ones(c.size, bool) for c in coh.cohorts]
    coh.round(participation=full)                # seed all residuals
    big = max(range(len(coh.cohorts)), key=lambda i: coh.cohorts[i].size)
    ef_before = coh.cohorts[big].ef_buffer
    assert ef_before is not None

    part = [m.copy() for m in full]
    part[big][0] = False                         # bench client 0 of cohort
    coh.round(participation=part)
    ef_after = coh.cohorts[big].ef_buffer
    bench = [float(jnp.max(jnp.abs(a[0] - b[0])))
             for a, b in zip(jax.tree.leaves(ef_before),
                             jax.tree.leaves(ef_after))]
    ran = [float(jnp.max(jnp.abs(a[1] - b[1])))
           for a, b in zip(jax.tree.leaves(ef_before),
                           jax.tree.leaves(ef_after))]
    assert max(bench) == 0.0                     # benched residual untouched
    assert max(ran) > 0.0                        # participant's updated
