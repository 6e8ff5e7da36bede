"""Async staleness-aware runtime (DESIGN.md §10): virtual-clock event
ordering vs a pure-Python reference simulator, sync-wait equivalence at
full buffer, staleness-discount semantics, version GC, determinism."""
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro import optim
from repro.configs.paper_mlp import config
from repro.core.aggregation import accumulate_cohort, finalize, zeros_like_acc
from repro.core.compression import DEVICE_TIERS
from repro.core.federated import AsyncFLServer, Client, CohortFLServer
from repro.core.schedule import (VirtualClockScheduler, dispatch_time,
                                 materialize_windows, schedule_census)
from repro.data import make_gaussian_dataset, partition_iid
from repro.models import mlp

KEY = jax.random.PRNGKey(42)
MODEL = types.SimpleNamespace(loss_fn=functools.partial(mlp.loss_fn))
FLEET = ("hub", "high", "mid", "low", "mid", "low")
N_SAMPLES = 768                     # equal shards -> exact stacking parity


def _fleet(tiers=FLEET, n_samples=N_SAMPLES):
    data = make_gaussian_dataset(KEY, n_samples)
    shards = partition_iid(KEY, data, len(tiers))
    return [Client(i, DEVICE_TIERS[t], shards[i], profile_name=t)
            for i, t in enumerate(tiers)]


def _max_diff(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# --------------------------------------------- event ordering (property)

def _reference_windows(times, buffer_size, n_windows, seed=0, jitter=0.0):
    """List-scan reference simulator: no heap, the same semantics spelled
    out naively — repeatedly pick the (t, seq)-smallest in-flight upload."""
    active, disp = [], [0] * len(times)
    seq, version = 0, 0

    def launch(client, start):
        nonlocal seq
        k = disp[client]
        disp[client] += 1
        active.append((start + dispatch_time(times[client], jitter,
                                             seed, client, k),
                       seq, client, version))
        seq += 1

    for c in range(len(times)):
        launch(c, 0.0)
    wins = []
    for _ in range(n_windows):
        ups = []
        for _ in range(buffer_size):
            u = min(active)                  # lexicographic: (t, seq, ...)
            active.remove(u)
            ups.append(u)
        t_agg = ups[-1][0]
        wins.append((t_agg, version, tuple(ups)))
        version += 1
        for _, _, c, _ in ups:
            launch(c, t_agg)
    return wins


@settings(deadline=None, max_examples=30)
@given(st.integers(2, 10), st.floats(0.1, 1.0), st.integers(0, 10_000),
       st.sampled_from([0.0, 0.1, 0.5]))
def test_scheduler_matches_reference(n, frac, seed, jitter):
    """Same seed => identical apply order (times, sequence, versions)."""
    rng = np.random.default_rng(seed)
    times = rng.uniform(0.5, 10.0, n).tolist()
    buffer_size = max(1, min(n, int(round(frac * n))))
    sched = VirtualClockScheduler(times, buffer_size, seed=seed,
                                  jitter=jitter)
    got = sched.trace(12)
    ref = _reference_windows(times, buffer_size, 12, seed=seed,
                             jitter=jitter)
    for w, (t, v, ups) in zip(got, ref):
        assert w.t == t and w.version == v
        assert tuple((u.t, u.seq, u.client, u.version)
                     for u in w.uploads) == ups


# ----------------------- window materialization (DESIGN.md §14 tentpole)

def _plan_equals_trace(plan, wins):
    """Element-wise identity between a WindowPlan and the heap's Windows:
    exact float times (same dispatch_time draws), clients, sequence
    numbers, versions and stalenesses, column for column."""
    assert plan.n_windows == len(wins)
    for w, win in enumerate(wins):
        assert plan.t[w] == win.t
        assert list(plan.client[w]) == [u.client for u in win.uploads]
        assert list(plan.upload_t[w]) == [u.t for u in win.uploads]
        assert list(plan.upload_seq[w]) == [u.seq for u in win.uploads]
        assert (list(plan.upload_version[w])
                == [u.version for u in win.uploads])
        assert tuple(plan.staleness[w]) == win.stalenesses


@settings(deadline=None, max_examples=30)
@given(st.integers(1, 10), st.floats(0.1, 1.0), st.integers(0, 10_000),
       st.sampled_from([0.0, 0.1, 0.5]))
def test_materialized_plan_matches_heap(n, frac, seed, jitter):
    """The lexsort materializer and the event heap are independent
    implementations of the same schedule: same (times, buffer_size,
    seed, jitter) => element-wise identical windows, bit-equal float
    times included — and materializing must not advance the scheduler."""
    rng = np.random.default_rng(seed)
    times = rng.uniform(0.5, 10.0, n).tolist()
    buffer_size = max(1, min(n, int(round(frac * n))))
    sched = VirtualClockScheduler(times, buffer_size, seed=seed,
                                  jitter=jitter)
    warm = seed % 3                     # plans may start mid-schedule
    if warm:
        sched.trace(warm)
    before = (sched.version, sched._seq, list(sched._dispatches),
              sorted(sched._heap))
    plan = materialize_windows(sched, 10)
    assert (sched.version, sched._seq, list(sched._dispatches),
            sorted(sched._heap)) == before
    assert plan.version0 == warm
    _plan_equals_trace(plan, sched.trace(10))
    # end_version is the post-trace in-flight state, and max_version_lag
    # reaches every version the ring must serve
    assert sorted(plan.end_version) == sorted(v for *_x, v in sched._heap)
    assert plan.max_version_lag >= int(plan.staleness.max())
    assert (plan.version0 + plan.n_windows - plan.end_version.min()
            <= plan.max_version_lag)


def test_materialized_plan_breaks_arrival_ties_by_seq():
    """Identical round times make every arrival a tie: both paths must
    fall back to dispatch sequence order, column for column."""
    sched = VirtualClockScheduler([1.0] * 5, buffer_size=2, seed=3)
    plan = materialize_windows(sched, 8)
    assert all(np.all(np.diff(row) > 0) for row in plan.upload_seq)
    _plan_equals_trace(plan, sched.trace(8))


def test_materialized_plan_single_client_fleet():
    """One client, buffer 1: every window is that client's next upload,
    always fresh (staleness 0), version lag never exceeds 1."""
    sched = VirtualClockScheduler([2.5], buffer_size=1, seed=1, jitter=0.2)
    plan = materialize_windows(sched, 6)
    assert np.all(plan.client == 0)
    assert np.all(plan.staleness == 0)
    assert plan.max_version_lag <= 1
    _plan_equals_trace(plan, sched.trace(6))


def test_materialize_validates_n_windows():
    sched = VirtualClockScheduler([1.0, 2.0], buffer_size=1)
    with pytest.raises(ValueError, match="n_windows"):
        materialize_windows(sched, 0)


def test_scheduler_validates_buffer_size():
    with pytest.raises(ValueError):
        VirtualClockScheduler([1.0, 2.0], buffer_size=3)
    with pytest.raises(ValueError):
        VirtualClockScheduler([1.0, 2.0], buffer_size=0)
    with pytest.raises(ValueError):
        VirtualClockScheduler([], buffer_size=1)


def test_census_staleness_zero_at_full_buffer():
    c = schedule_census([1.0, 2.0, 3.0], buffer_size=3, n_windows=5)
    assert c["staleness_max"] == 0
    assert c["updates_per_s"] == pytest.approx(c["sync_updates_per_s"])
    c2 = schedule_census([1.0, 1.0, 100.0], buffer_size=1, n_windows=30)
    assert c2["updates_per_s"] > c2["sync_updates_per_s"]  # no blocking


# -------------------------------------- sync-wait equivalence (tentpole)

def test_full_buffer_no_discount_matches_sync_wait():
    """buffer_size == n_clients + discount off: every window is one full
    synchronous round on the live version — the trajectory must reproduce
    CohortFLServer's sync-wait run to numerical tolerance."""
    params = mlp.init(KEY, config())
    sync = CohortFLServer.from_clients(
        _fleet(), model=MODEL, optimizer=optim.sgd(1.0), params=params,
        straggler="wait")
    asy = AsyncFLServer.from_clients(
        _fleet(), model=MODEL, optimizer=optim.sgd(1.0), params=params,
        buffer_size=len(FLEET), staleness_exp=0.0)
    t_cum = 0.0
    for _ in range(3):
        rs, ra = sync.round(), asy.step()
        t_cum += rs["round_wall_time"]
        assert ra["loss"] == pytest.approx(rs["loss"], abs=1e-6)
        assert ra["staleness_max"] == 0
        assert ra["t"] == pytest.approx(t_cum, rel=1e-9)
        assert ra["total_upload_bytes"] == pytest.approx(
            rs["total_upload_bytes"], rel=1e-9)
    assert _max_diff(sync.params, asy.params) < 1e-6


# ---------------------------------------------- staleness discount

def test_staleness_weight_scales_numerator_only():
    """(1+s)^-a damps the update magnitude; the denominator keeps the
    undiscounted mask weight so a lone stale group does not cancel out."""
    params = {"w": jnp.ones((2, 2))}
    g = {"w": jnp.full((2, 2), 2.0)}
    m = {"w": jnp.ones((2, 2))}
    one = jnp.float32(1.0)
    plain = finalize(accumulate_cohort(
        zeros_like_acc(params), g, m, one, one))
    damped = finalize(accumulate_cohort(
        zeros_like_acc(params), g, m, one, one,
        staleness_weight=jnp.float32(0.25)))
    np.testing.assert_allclose(np.asarray(damped["w"]),
                               0.25 * np.asarray(plain["w"]))


def test_stale_group_downweighted_vs_fresh():
    """In a mixed buffer, a stale group's gradient moves the aggregate
    less than the same gradient uploaded fresh."""
    params = {"w": jnp.ones((2, 2))}
    m = {"w": jnp.ones((2, 2))}
    fresh = {"w": jnp.zeros((2, 2))}
    stale = {"w": jnp.full((2, 2), 4.0)}
    one = jnp.float32(1.0)

    def mix(lam):
        acc = zeros_like_acc(params)
        acc = accumulate_cohort(acc, fresh, m, one, one)
        acc = accumulate_cohort(acc, stale, m, one, one,
                                staleness_weight=jnp.float32(lam))
        return float(finalize(acc)["w"][0, 0])

    assert mix(0.25) < mix(1.0)          # discount shrinks stale influence


def test_async_records_staleness_and_bounded_versions():
    srv = AsyncFLServer.from_clients(
        _fleet(), model=MODEL, optimizer=optim.sgd(1.0),
        params=mlp.init(KEY, config()), buffer_size=2, staleness_exp=0.5)
    srv.run(12)
    assert any(r["staleness_max"] > 0 for r in srv.history)
    # version store never outgrows the fleet (+1 for the live version)
    assert all(r["n_versions_live"] <= srv.n_clients + 1
               for r in srv.history)
    assert srv.n_versions_live <= srv.n_clients + 1


# ---------------------------------------------- virtual-time advantage

def test_async_reaches_sync_loss_in_less_virtual_time():
    """On a speed-heterogeneous fleet the buffered async runtime reaches
    the sync-wait baseline's validation loss in less simulated wall-clock
    (the whole point: stragglers stop gating the global clock)."""
    val = make_gaussian_dataset(jax.random.PRNGKey(9), 512)
    params = mlp.init(KEY, config())

    def val_loss(p):
        return float(mlp.loss_fn(p, val))

    sync = CohortFLServer.from_clients(
        _fleet(), model=MODEL, optimizer=optim.sgd(1.0), params=params,
        straggler="wait")
    t_sync = 0.0
    for _ in range(8):
        t_sync += sync.round()["round_wall_time"]
    target = val_loss(sync.params)

    asy = AsyncFLServer.from_clients(
        _fleet(), model=MODEL, optimizer=optim.sgd(1.0), params=params,
        buffer_size=2, staleness_exp=0.5)
    t_async = None
    for _ in range(200):
        rec = asy.step()
        if val_loss(asy.params) <= target:
            t_async = rec["t"]
            break
    assert t_async is not None, "async never reached the sync loss"
    assert t_async < t_sync


# ---------------------------------------------- determinism / plumbing

def test_async_seed_determinism_and_divergence():
    def hist(seed):
        srv = AsyncFLServer.from_clients(
            _fleet(), model=MODEL, optimizer=optim.sgd(1.0),
            params=mlp.init(KEY, config()), buffer_size=2,
            staleness_exp=0.5, time_jitter=0.3, seed=seed)
        srv.run(6)
        return srv.history

    assert hist(5) == hist(5)
    assert hist(5) != hist(6)


def test_cohort_server_redirects_async_policy():
    with pytest.raises(ValueError, match="AsyncFLServer"):
        CohortFLServer.from_clients(
            _fleet(), model=MODEL, optimizer=optim.sgd(1.0),
            params=mlp.init(KEY, config()), straggler="async")


def test_async_validates_knobs():
    with pytest.raises(ValueError):
        AsyncFLServer.from_clients(
            _fleet(), model=MODEL, optimizer=optim.sgd(1.0),
            params=mlp.init(KEY, config()), buffer_size=len(FLEET) + 1)
    with pytest.raises(ValueError):
        AsyncFLServer.from_clients(
            _fleet(), model=MODEL, optimizer=optim.sgd(1.0),
            params=mlp.init(KEY, config()), staleness_exp=-1.0)


@pytest.mark.slow
def test_async_fedavg_full_buffer_matches_sync():
    params = mlp.init(KEY, config())
    kw = dict(mode="fedavg", local_steps=3, local_lr=0.5)
    sync = CohortFLServer.from_clients(
        _fleet(), model=MODEL, optimizer=optim.sgd(1.0), params=params,
        straggler="wait", **kw)
    asy = AsyncFLServer.from_clients(
        _fleet(), model=MODEL, optimizer=optim.sgd(1.0), params=params,
        buffer_size=len(FLEET), staleness_exp=0.0, **kw)
    for _ in range(2):
        rs, ra = sync.round(), asy.step()
        assert ra["loss"] == pytest.approx(rs["loss"], abs=1e-6)
    assert _max_diff(sync.params, asy.params) < 1e-5
