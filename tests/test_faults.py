"""Fault-injection layer + resilient runtimes (DESIGN.md §17): policy
validation and JSON round-trip, stateless host mask semantics, device
inject/guard/clip invariants, NaN-never-reaches-params (property),
retrying scheduler heap == materializer identity, graceful
zero-participant rounds, min-1 participation, and eager==scan
bit-identity under faults."""
import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro import optim
from repro.configs.paper_mlp import config
from repro.core.compression import DEVICE_TIERS
from repro.core.faults import (FaultPolicy, availability_mask, clip_updates,
                               corrupt_mask, corrupt_seq_mask, dropout_mask,
                               finite_guard, inject_corruption)
from repro.core.federated import Client, CohortFLServer
from repro.core.scenario import (AsyncBuffered, FleetSpec, FLScenario,
                                 LocalTraining, ParticipationPolicy,
                                 SyncDrop, SyncWait, UploadPolicy,
                                 scenario_census, simulate)
from repro.core.schedule import RetrySpec, VirtualClockScheduler, \
    materialize_windows
from repro.data import make_gaussian_dataset, partition_iid
from repro.models import mlp

KEY = jax.random.PRNGKey(42)
MODEL = types.SimpleNamespace(loss_fn=functools.partial(mlp.loss_fn))
TIERS = ("hub", "high", "mid", "low", "mid", "low")
FLEET = FleetSpec.cycling(TIERS, 6, samples_per_client=16)


def _max_diff(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _all_finite(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(tree))


# ------------------------------------------------------------- the policy

class TestFaultPolicy:
    def test_validation(self):
        with pytest.raises(ValueError, match="duty_cycle"):
            FaultPolicy(period=4, duty_cycle=0.0)
        with pytest.raises(ValueError, match="churn_rate"):
            FaultPolicy(churn_rate=1.0)
        with pytest.raises(ValueError, match="corrupt_kind"):
            FaultPolicy(corrupt_rate=0.1, corrupt_kind="zeros")
        with pytest.raises(ValueError, match="corrupt_frac"):
            FaultPolicy(corrupt_frac=0.0)
        with pytest.raises(ValueError, match="clip_norm"):
            FaultPolicy(clip_norm=0.0)
        with pytest.raises(ValueError, match="period"):
            FaultPolicy(period=-1)
        with pytest.raises(ValueError, match="rejoin_after"):
            FaultPolicy(rejoin_after=0)

    def test_properties(self):
        assert FaultPolicy(period=4, duty_cycle=0.5).traces_availability
        assert FaultPolicy(churn_rate=0.1).traces_availability
        assert not FaultPolicy(dropout_rate=0.5).traces_availability
        assert FaultPolicy(corrupt_rate=0.1).touches_uploads
        assert FaultPolicy(clip_norm=1.0).touches_uploads
        assert not FaultPolicy(dropout_rate=0.5).touches_uploads

    def test_hashable_and_json_round_trip(self):
        flt = FaultPolicy(seed=3, period=5, duty_cycle=0.6, churn_rate=0.1,
                          dropout_rate=0.2, corrupt_rate=0.05,
                          corrupt_kind="bitflip", corrupt_frac=0.5,
                          clip_norm=2.0)
        assert hash(flt) == hash(FaultPolicy.from_dict(flt.to_dict()))
        wire = json.loads(json.dumps(flt.to_dict()))
        assert FaultPolicy.from_dict(wire) == flt

    def test_scenario_round_trip_and_validation(self):
        sc = FLScenario(fleet=FLEET,
                        faults=FaultPolicy(period=4, duty_cycle=0.5,
                                           corrupt_rate=0.1))
        wire = json.loads(json.dumps(sc.to_dict()))
        assert FLScenario.from_dict(wire) == sc
        # clean scenarios serialize without a faults key at all
        assert "faults" not in FLScenario(fleet=FLEET).to_dict()
        with pytest.raises(ValueError, match="round-indexed"):
            FLScenario(fleet=FLEET,
                       timing=AsyncBuffered(buffer_size=2),
                       faults=FaultPolicy(period=4, duty_cycle=0.5))
        with pytest.raises(ValueError, match="hierarchical"):
            FLScenario(fleet=FleetSpec.cycling(TIERS, 8, edges=2,
                                               samples_per_client=16),
                       faults=FaultPolicy(corrupt_rate=0.1))

    def test_census_reports_fault_block(self):
        sc = FLScenario(fleet=FLEET,
                        faults=FaultPolicy(period=4, duty_cycle=0.5,
                                           churn_rate=0.1,
                                           dropout_rate=0.1,
                                           retry_backoff=0.5))
        c = scenario_census(sc)
        f = c["faults"]
        assert 0.0 < f["availability_expected"] < 1.0
        assert f["expected_participants_per_round"] <= sc.fleet.n_clients
        assert f["max_retry_delay_s"] == 0.5 * (1 + 2 + 4)


# ------------------------------------------------ host masks (stateless)

class TestHostMasks:
    def test_diurnal_duty_cycle_exact(self):
        flt = FaultPolicy(seed=7, period=5, duty_cycle=0.6)
        up = np.stack([availability_mask(flt, 32, s) for s in range(5)])
        # each client is up exactly ceil(0.6 * 5) = 3 of every 5 rounds
        assert (up.sum(axis=0) == 3).all()

    def test_churn_keeps_crashed_clients_dark(self):
        flt = FaultPolicy(seed=11, churn_rate=0.3, rejoin_after=3)
        rng_crash = [np.random.default_rng([11, 12, r]).random(16) < 0.3
                     for r in range(20)]
        for step in range(3, 20):
            up = availability_mask(flt, 16, step)
            for c in range(16):
                dark = any(rng_crash[r][c]
                           for r in range(step - 2, step + 1))
                assert up[c] == (not dark)

    def test_masks_are_stateless_and_replayable(self):
        flt = FaultPolicy(seed=3, period=4, duty_cycle=0.5, churn_rate=0.2,
                          dropout_rate=0.3, corrupt_rate=0.4)
        for fn in (availability_mask, dropout_mask, corrupt_mask):
            a = [fn(flt, 24, s) for s in (5, 2, 9)]
            b = [fn(flt, 24, s) for s in (9, 5, 2)]    # any order
            assert (a[0] == b[1]).all() and (a[1] == b[2]).all() \
                and (a[2] == b[0]).all()

    def test_corrupt_seq_mask_is_per_upload_pure(self):
        flt = FaultPolicy(seed=5, corrupt_rate=0.5)
        seqs = np.arange(40)
        flags = corrupt_seq_mask(flt, seqs)
        perm = np.random.default_rng(0).permutation(40)
        assert (corrupt_seq_mask(flt, seqs[perm]) == flags[perm]).all()
        assert 0 < flags.sum() < 40


# -------------------------------------------------- device-side pipeline

class TestDevicePipeline:
    def _updates(self, n=4):
        k = jax.random.PRNGKey(0)
        return {"w": jax.random.normal(k, (n, 8, 4)),
                "b": jax.random.normal(jax.random.fold_in(k, 1), (n, 4))}

    def test_inject_poisons_only_flagged_rows(self):
        u = self._updates()
        flt = FaultPolicy(seed=0, corrupt_rate=1.0, corrupt_kind="nan")
        corrupt = jnp.asarray([1.0, 0.0, 1.0, 0.0])
        out = inject_corruption(u, corrupt, jnp.arange(4), flt)
        for leaf, orig in zip(jax.tree.leaves(out), jax.tree.leaves(u)):
            assert bool(jnp.all(jnp.isnan(leaf[0])))
            assert bool(jnp.all(leaf[1] == orig[1]))    # untouched, bitwise
            assert bool(jnp.all(leaf[3] == orig[3]))

    def test_partial_corruption_is_uid_keyed(self):
        u = self._updates()
        flt = FaultPolicy(seed=0, corrupt_rate=1.0, corrupt_kind="inf",
                          corrupt_frac=0.5)
        ones = jnp.ones(4)
        a = inject_corruption(u, ones, jnp.arange(4), flt)
        b = inject_corruption(u, ones, jnp.arange(4), flt)
        assert _max_diff_nan_safe(a, b) == 0.0
        c = inject_corruption(u, ones, jnp.arange(4) + 100, flt)
        # different uids -> a different element subset (same counts-ish)
        same = all(bool(jnp.all(jnp.isposinf(x) == jnp.isposinf(y)))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(c)))
        assert not same

    def test_bitflip_wrecks_the_exponent(self):
        u = {"w": jnp.asarray([[0.5, -2.0, 3.0, 1.5]], jnp.float32)}
        flt = FaultPolicy(seed=0, corrupt_rate=1.0, corrupt_kind="bitflip")
        out = inject_corruption(u, jnp.ones(1), jnp.zeros(1, jnp.int32), flt)
        w = np.asarray(out["w"][0], np.float64)
        orig = np.asarray(u["w"][0], np.float64)
        # xor of the exponent MSB: |x| < 2 blows up ~2^128, |x| >= 2
        # collapses to denormals/zero — either way the value is wrecked
        ratio = np.abs(w) / np.abs(orig)
        assert ((ratio > 1e30) | (ratio < 1e-30) | ~np.isfinite(w)).all()

    def test_finite_guard_quarantines_and_counts(self):
        u = {"w": jnp.asarray([[1.0, jnp.nan, jnp.inf, -2.0]])}
        zeroed, cov = finite_guard(u)
        assert zeroed["w"].tolist() == [[1.0, 0.0, 0.0, -2.0]]
        assert cov["w"].tolist() == [[1.0, 0.0, 0.0, 1.0]]
        clean = self._updates()
        z, c = finite_guard(clean)
        assert _max_diff(z, clean) == 0.0               # bitwise transparent
        assert all(bool(jnp.all(x == 1.0)) for x in jax.tree.leaves(c))

    def test_clip_updates(self):
        big = {"w": jnp.full((1, 4), 10.0)}             # ||.|| = 20
        out = clip_updates(big, 2.0)
        assert jnp.allclose(jnp.sqrt(jnp.sum(out["w"] ** 2)), 2.0)
        small = {"w": jnp.asarray([[0.1, -0.2, 0.05, 0.0]])}
        assert _max_diff(clip_updates(small, 2.0), small) == 0.0  # scale 1.0
        zero = {"w": jnp.zeros((1, 4))}
        assert _max_diff(clip_updates(zero, 2.0), zero) == 0.0    # 0-safe


def _max_diff_nan_safe(a, b):
    out = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        eq = (x == y) | (jnp.isnan(x) & jnp.isnan(y))
        out = max(out, float(jnp.max(jnp.where(eq, 0.0, 1.0))))
    return out


# -------------------------------------------- retrying scheduler (async)

class TestRetry:
    def test_delay_bounds(self):
        spec = RetrySpec(drop_rate=1.0, backoff=0.25, max_retries=3, seed=0)
        # every attempt lost -> the full exponential ladder, final lands
        assert spec.delay(0, 0) == 0.25 * (1 + 2 + 4)
        assert RetrySpec(0.0, 0.25, 3).delay(0, 0) == 0.0
        assert RetrySpec(0.9, 0.25, 0).delay(0, 0) == 0.0


@settings(deadline=None, max_examples=20)
@given(st.integers(2, 8), st.floats(0.1, 1.0), st.integers(0, 10_000),
       st.sampled_from([0.1, 0.4, 0.8]))
def test_retry_heap_matches_materializer(n, frac, seed, rate):
    """SATELLITE: the window materializer stays element-wise identical
    to the event heap when a FaultPolicy's retry model delays uploads
    (same per-(seed, client, dispatch) delay, same float adds)."""
    rng = np.random.default_rng(seed)
    times = rng.uniform(0.5, 10.0, n).tolist()
    K = max(1, min(n, int(round(frac * n))))
    retry = RetrySpec(drop_rate=rate, backoff=0.5, max_retries=4, seed=seed)
    sched = VirtualClockScheduler(times, K, seed=seed, jitter=0.1,
                                  retry=retry)
    plan = materialize_windows(sched, 8)
    for w, win in zip(range(8), sched.trace(8)):
        assert plan.t[w] == win.t
        assert list(plan.client[w]) == [u.client for u in win.uploads]
        assert list(plan.upload_t[w]) == [u.t for u in win.uploads]
        assert list(plan.upload_seq[w]) == [u.seq for u in win.uploads]


# --------------------------------------------------- runtime end-to-end

def _clients():
    data = make_gaussian_dataset(KEY, 96)
    shards = partition_iid(KEY, data, len(TIERS))
    return [Client(i, DEVICE_TIERS[t], shards[i], profile_name=t)
            for i, t in enumerate(TIERS)]


class TestRuntimeSemantics:
    def test_inert_policy_matches_clean_trajectory(self):
        """A FaultPolicy with every axis off takes the clean code paths:
        params bitwise equal to faults=None."""
        base = FLScenario(fleet=FLEET,
                          participation=ParticipationPolicy(fraction=0.7,
                                                            seed=3))
        inert = FLScenario(fleet=FLEET,
                           participation=ParticipationPolicy(fraction=0.7,
                                                             seed=3),
                           faults=FaultPolicy(seed=9))
        a = simulate(base, 4, init_seed=1)
        b = simulate(inert, 4, init_seed=1)
        assert _max_diff(a.params, b.params) == 0.0

    def test_zero_participant_round_is_graceful(self):
        srv = CohortFLServer.from_clients(_clients(), model=MODEL,
                                          optimizer=optim.sgd(0.1),
                                          params=mlp.init(KEY, config()),
                                          faults=FaultPolicy(seed=0))
        p0 = jax.tree.map(jnp.array, srv.params)
        none = [np.zeros(c.size, bool) for c in srv.cohorts]
        rec = srv.round(participation=none)
        assert rec["loss"] is None                  # no NaN sentinel
        assert rec["n_participants"] == 0
        assert _max_diff(srv.params, p0) == 0.0     # params untouched
        rec2 = srv.round()                          # next round recovers
        assert rec2["loss"] is not None and np.isfinite(rec2["loss"])

    def test_min_one_participant(self):
        """SATELLITE: ParticipationPolicy guarantees >= 1 sampled client
        whenever fraction > 0 (the max(1, round(...)) floor)."""
        srv = CohortFLServer.from_clients(_clients(), model=MODEL,
                                          optimizer=optim.sgd(0.1),
                                          params=mlp.init(KEY, config()),
                                          sample_fraction=0.01)
        for s in range(5):
            rng = np.random.default_rng([0, s])
            masks = srv._sample_participation(rng)
            assert sum(int(m.sum()) for m in masks) == 1
        with pytest.raises(ValueError, match="fraction"):
            ParticipationPolicy(fraction=0.0)

    def test_dropouts_burn_wall_clock_but_upload_nothing(self):
        flt = FaultPolicy(seed=1, dropout_rate=0.5)
        sc = FLScenario(fleet=FLEET, faults=flt)
        res = simulate(sc, 6, init_seed=1)
        total_do = sum(r.n_dropouts for r in res.records)
        assert total_do > 0
        clean = simulate(FLScenario(fleet=FLEET), 6, init_seed=1)
        for rf, rc in zip(res.records, clean.records):
            # everyone is dispatched (full participation), so the wall
            # clock matches the clean run even though fewer upload
            assert rf.round_wall_time == rc.round_wall_time
            assert rf.n_participants == 6 - rf.n_dropouts

    def test_guard_off_proves_injection_is_real(self):
        flt = FaultPolicy(seed=0, corrupt_rate=1.0, corrupt_kind="nan",
                          finite_guard=False)
        res = simulate(FLScenario(fleet=FLEET, faults=flt), 2, init_seed=1)
        assert not _all_finite(res.params)

    def test_async_corruption_guarded(self):
        flt = FaultPolicy(seed=2, dropout_rate=0.3, retry_backoff=0.5,
                          corrupt_rate=0.5, corrupt_kind="inf")
        sc = FLScenario(fleet=FLEET,
                        timing=AsyncBuffered(buffer_size=2,
                                             staleness_exp=0.5),
                        faults=flt)
        res = simulate(sc, 8, init_seed=1)
        assert _all_finite(res.params)
        assert sum(r.n_corrupt for r in res.records) > 0
        # retries delay uploads: virtual time runs later than clean
        clean = simulate(FLScenario(
            fleet=FLEET, timing=AsyncBuffered(buffer_size=2,
                                              staleness_exp=0.5)),
            8, init_seed=1)
        assert res.records[-1].t > clean.records[-1].t


@settings(deadline=None, max_examples=6)
@given(st.integers(0, 10_000), st.sampled_from(["nan", "inf", "bitflip"]),
       st.sampled_from([1.0, 0.4]))
def test_corruption_never_reaches_params(seed, kind, frac):
    """PROPERTY: with the finite guard on, corrupted uploads never
    propagate NaN/Inf into the global params."""
    flt = FaultPolicy(seed=seed, corrupt_rate=0.6, corrupt_kind=kind,
                      corrupt_frac=frac,
                      clip_norm=5.0 if kind == "bitflip" else None)
    sc = FLScenario(fleet=FLEET,
                    local=LocalTraining(mode="fedavg", local_steps=2,
                                        local_lr=0.1),
                    faults=flt)
    res = simulate(sc, 3, init_seed=seed % 7)
    assert _all_finite(res.params)
    assert sum(r.n_corrupt for r in res.records) > 0


# ------------------------------------------- engines stay bit-identical

class TestEngineParity:
    def _cmp(self, sc, rounds):
        e = simulate(sc, rounds, init_seed=3, engine="eager")
        s = simulate(sc, rounds, init_seed=3, engine="scan")
        assert _max_diff(e.params, s.params) == 0.0
        for a, b in zip(e.records, s.records):
            assert (a.n_participants, a.n_dropped, a.n_dropouts,
                    a.n_corrupt, a.loss is None) == \
                   (b.n_participants, b.n_dropped, b.n_dropouts,
                    b.n_corrupt, b.loss is None)
            if a.loss is not None:
                assert a.loss == b.loss

    def test_scan_matches_eager_sync_faults(self):
        self._cmp(FLScenario(
            fleet=FLEET,
            local=LocalTraining(mode="fedavg", local_steps=2, local_lr=0.1),
            upload=UploadPolicy(quant="fp8_e4m3", error_feedback=True),
            participation=ParticipationPolicy(fraction=0.7, seed=7),
            faults=FaultPolicy(seed=5, period=4, duty_cycle=0.75,
                               churn_rate=0.15, dropout_rate=0.25,
                               corrupt_rate=0.3)), 5)

    def test_scan_matches_eager_deadline_faults(self):
        self._cmp(FLScenario(
            fleet=FLEET, timing=SyncDrop(deadline=0.05),
            faults=FaultPolicy(seed=5, period=3, duty_cycle=0.67,
                               dropout_rate=0.2, corrupt_rate=0.3,
                               corrupt_kind="bitflip", clip_norm=1.0)), 5)

    def test_scan_matches_eager_async_faults(self):
        self._cmp(FLScenario(
            fleet=FLEET,
            timing=AsyncBuffered(buffer_size=3, staleness_exp=0.5),
            upload=UploadPolicy(quant="fp8_e4m3", error_feedback=True),
            faults=FaultPolicy(seed=5, dropout_rate=0.2, retry_backoff=0.5,
                               corrupt_rate=0.3, corrupt_kind="inf")), 6)

    def test_pallas_backend_rejects_upload_faults(self):
        sc = FLScenario(fleet=FLEET,
                        faults=FaultPolicy(seed=1, corrupt_rate=0.2))
        from repro.core.engine import ScanEngine
        from repro.core.scenario import build_server
        srv = build_server(sc, MODEL, optim.sgd(0.1), mlp.init(KEY, config()))
        with pytest.raises(ValueError, match="coverage"):
            ScanEngine(srv, agg="pallas")
