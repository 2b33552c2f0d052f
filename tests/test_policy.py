"""Carbon policy subsystem: reactive-through-interface bit-parity with the
pre-subsystem (PR 3) trajectories, host-vs-scan equivalence for the
green-window planner and SLO deferral, priority-queue invariants,
deadline-miss accounting, and the forecast green-window extraction
helper."""
import dataclasses
import hashlib

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import forecast
from repro.core import policy as P
from repro.core.simulator import (JobSchedule, SimConfig, generate_jobs,
                                  pareto_frontier, simulate_fleet,
                                  simulate_fleet_scan, sweep_policies,
                                  synthetic_lifecycle_fleet)

BASE = SimConfig(epochs=24, seed=3, arrival_rate=6.0, mean_duration_h=6.0,
                 shortlist=16, history_h=48, horizon_h=8)
MIXED = SimConfig(epochs=36, seed=11, arrival_rate=8.0, mean_duration_h=10.0,
                  shortlist=32, history_h=48, horizon_h=12,
                  migration_budget=2, deferrable_frac=0.3,
                  outage=(0, 12, 6), flash_crowd=(20, 3, 2.5))

COUNTERS = ("rank_sweeps", "arrivals_placed", "jobs_completed",
            "jobs_dropped", "jobs_deferred", "migrations", "evictions",
            "deadline_misses", "defer_delay_h")


def _run_both(cfg, n=96, chips=64, jobs=None, pad=False):
    fleet, traces, ridx = synthetic_lifecycle_fleet(n, cfg,
                                                    chips_per_node=chips)
    jobs = jobs if jobs is not None else generate_jobs(cfg)
    host = simulate_fleet(fleet, traces, ridx, cfg, jobs=jobs)
    scan = simulate_fleet_scan(fleet, traces, ridx, cfg, jobs=jobs,
                               pad_plan=pad)
    return host, scan, jobs


def _assert_equivalent(host, scan):
    np.testing.assert_array_equal(host.node_log, scan.node_log)
    np.testing.assert_array_equal(host.first_node, scan.first_node)
    np.testing.assert_array_equal(host.start_epoch, scan.start_epoch)
    for f in COUNTERS:
        assert getattr(host, f) == getattr(scan, f), f
    assert scan.emissions_g == pytest.approx(host.emissions_g, rel=1e-4)


def _jobs(arrive, chips, dur, deferrable, deadline=None, value=None):
    return JobSchedule(
        arrive=np.asarray(arrive, np.int64),
        chips=np.asarray(chips, np.int64),
        duration=np.asarray(dur, np.int64),
        load=np.asarray(chips, np.float64),
        deferrable=np.asarray(deferrable, bool),
        deadline=None if deadline is None else np.asarray(deadline,
                                                          np.int64),
        value=None if value is None else np.asarray(value, np.float32))


# ---------------------------------------------------------------------------
# reactive through the Policy interface == the pre-subsystem trajectories
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg,digest,counters", [
    (BASE, "0141b64da0651227",
     dict(rank_sweeps=23, arrivals_placed=117, jobs_completed=96,
          jobs_dropped=0, jobs_deferred=0, migrations=0, evictions=0)),
    # rank_sweeps counts the engine's work, not the trajectory: ranking
    # only nodes with room for the smallest arrival (placement, point 2)
    # took MIXED's from 106 to 41 with the same placements
    (MIXED, "0e6437d00c3ba558",
     dict(rank_sweeps=41, arrivals_placed=385, jobs_completed=214,
          jobs_dropped=18, jobs_deferred=253, migrations=47,
          evictions=41)),
])
def test_reactive_policy_is_bit_identical_to_pr3(cfg, digest, counters):
    """Golden snapshot captured on the PR 3 tree before the policy
    subsystem existed: the default (reactive) policy routed through the
    new interface must reproduce placements and counters exactly, on both
    drivers."""
    host, scan, _ = _run_both(cfg)
    got = hashlib.sha256(np.concatenate(
        [host.node_log, host.first_node]).tobytes()).hexdigest()[:16]
    assert got == digest
    for k, v in counters.items():
        assert getattr(host, k) == v, k
    _assert_equivalent(host, scan)


def test_default_policy_is_reactive():
    assert SimConfig().policy == P.REACTIVE
    assert P.REACTIVE.migration == "reactive"
    assert P.REACTIVE.deferral == "reactive"
    assert P.REACTIVE.defer_green_factor == 0.95


# ---------------------------------------------------------------------------
# host-vs-scan equivalence for the new policies
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,pcfg", [
    ("green_window", P.green_window()),
    ("slo", P.slo_deferral(value_weight=0.7, deadline_hi=8)),
    ("slo_tiny_queue", P.slo_deferral(queue_cap=2, deadline_hi=8)),
    ("combined", P.PolicyConfig(migration="lookahead", deferral="slo")),
])
def test_policy_scan_matches_host(name, pcfg):
    cfg = dataclasses.replace(MIXED, deferrable_frac=0.5, policy=pcfg)
    host, scan, _ = _run_both(cfg)
    _assert_equivalent(host, scan)


def test_planner_gates_migrations():
    """The green-window gate batches moves: far fewer migrations than the
    reactive policy on the same stream, never exceeding the budget."""
    re_cfg = dataclasses.replace(MIXED, outage=None)
    gw_cfg = dataclasses.replace(re_cfg, policy=P.green_window())
    re, _, jobs = _run_both(re_cfg)
    gw, _, _ = _run_both(gw_cfg, jobs=jobs)
    assert re.migrations > 0
    assert gw.migrations <= re.migrations
    assert gw.migrations <= re_cfg.migration_budget * re_cfg.epochs


def test_planner_without_forecast_degrades_to_reactive():
    """w2 = 0 disables the forecast path; the look-ahead planner must then
    take the exact reactive migration decisions."""
    from repro.core.ranking import RankWeights
    w = RankWeights(w1=1.0, w2=0.0, w3=0.05, w4=0.05)
    re_cfg = dataclasses.replace(MIXED, weights=w)
    gw_cfg = dataclasses.replace(re_cfg, policy=P.green_window())
    re, _, jobs = _run_both(re_cfg)
    gw, gw_scan, _ = _run_both(gw_cfg, jobs=jobs)
    np.testing.assert_array_equal(re.node_log, gw.node_log)
    assert re.migrations == gw.migrations
    _assert_equivalent(gw, gw_scan)


# ---------------------------------------------------------------------------
# SLO queue invariants (deterministic constructions)
# ---------------------------------------------------------------------------


def _slo_cfg(**kw):
    base = dict(epochs=16, seed=0, arrival_rate=0.0, history_h=48,
                horizon_h=8, shortlist=8, defer_max_h=6)
    base.update(kw)
    return SimConfig(**base)


def test_slo_deadline_forces_placement():
    """defer_green_factor=10 makes every in-window epoch 'green later', so
    a deferrable job waits out its ENTIRE slack and must start exactly at
    its deadline epoch (arrive + slack)."""
    cfg = _slo_cfg(policy=P.slo_deferral(10.0))
    jobs = _jobs([2, 2], [8, 8], [2, 2], [True, False],
                 deadline=[4, 0], value=[1.0, 1.0])
    host, scan, _ = _run_both(cfg, n=16, chips=64, jobs=jobs)
    assert host.start_epoch[0] == 2 + 4      # rode the queue to deadline
    assert host.start_epoch[1] == 2          # non-deferrable: immediate
    assert host.deadline_misses == 0
    assert host.defer_delay_h == 4
    _assert_equivalent(host, scan)


def test_slo_queue_capacity_prioritizes_cheap_flexible_work():
    """Two jobs compete for a queue of one: the LOW-value job wins the
    slot (cheap batch work rides green windows); the high-value job is
    forced to place immediately."""
    cfg = _slo_cfg(policy=P.slo_deferral(10.0, value_weight=0.0,
                                         queue_cap=1))
    jobs = _jobs([3, 3], [8, 8], [2, 2], [True, True],
                 deadline=[4, 4], value=[5.0, 0.25])
    host, scan, _ = _run_both(cfg, n=16, chips=64, jobs=jobs)
    assert host.start_epoch[0] == 3          # high value: overflow, now
    assert host.start_epoch[1] == 3 + 4      # low value: rode the queue
    _assert_equivalent(host, scan)


def test_slo_value_weight_places_urgent_work_immediately():
    """With a strong value model, the high-value job's green threshold
    collapses (thresh = f * exp(-w*value)) so it places on arrival while
    the cheap job still waits for green hours."""
    cfg = _slo_cfg(policy=P.slo_deferral(10.0, value_weight=8.0))
    jobs = _jobs([3, 3], [8, 8], [2, 2], [True, True],
                 deadline=[4, 4], value=[4.0, 0.0])
    host, scan, _ = _run_both(cfg, n=16, chips=64, jobs=jobs)
    assert host.start_epoch[0] == 3
    assert host.start_epoch[1] == 3 + 4
    _assert_equivalent(host, scan)


def test_slo_unplaceable_job_misses_deadline():
    """A job larger than any node defers while its window lasts, then is
    dropped at the deadline and accounted as a deadline miss."""
    cfg = _slo_cfg(policy=P.slo_deferral(0.0))
    jobs = _jobs([2], [999], [2], [True], deadline=[3], value=[1.0])
    host, scan, _ = _run_both(cfg, n=8, chips=64, jobs=jobs)
    assert host.start_epoch[0] == -1
    assert host.jobs_dropped == 1
    assert host.deadline_misses == 1
    _assert_equivalent(host, scan)


def test_slo_horizon_end_queue_counts_as_misses():
    """Jobs still queued when the horizon ends never ran: dropped AND
    deadline-missed, on both drivers."""
    cfg = _slo_cfg(epochs=6, policy=P.slo_deferral(10.0))
    jobs = _jobs([4], [8], [2], [True], deadline=[6], value=[1.0])
    host, scan, _ = _run_both(cfg, n=8, chips=64, jobs=jobs)
    assert host.jobs_dropped == 1 and host.deadline_misses == 1
    _assert_equivalent(host, scan)


def test_slo_queue_order_key():
    """Admission key: value ascending, deadline DESCENDING, then job id."""
    value = np.asarray([1.0, 0.5, 0.5, 0.5], np.float32)
    deadline = np.asarray([9, 3, 7, 7], np.int64)
    jid = np.asarray([0, 1, 2, 3], np.int64)
    order = P.slo_queue_order(value, deadline, jid)
    np.testing.assert_array_equal(jid[order], [2, 3, 1, 0])


def test_defer_green_factor_threads_both_paths():
    """Satellite: the lifted green threshold genuinely parameterizes the
    deferral policy — factor 0 never defers, a huge factor always defers
    inside the window, identically on host and scan."""
    never = dataclasses.replace(
        BASE, deferrable_frac=1.0,
        policy=P.PolicyConfig(defer_green_factor=0.0))
    host, scan, _ = _run_both(never)
    assert host.jobs_deferred == scan.jobs_deferred == 0
    always = dataclasses.replace(
        BASE, deferrable_frac=1.0,
        policy=P.PolicyConfig(defer_green_factor=100.0))
    host2, scan2, _ = _run_both(always)
    assert host2.jobs_deferred > 0
    _assert_equivalent(host2, scan2)


def test_zero_defer_window_drops_without_misses():
    """defer_max_h == 0: deferrable jobs have no slack, so drops are NOT
    deadline misses — and the green-signal window clamps to one hour
    instead of reducing over an empty axis (a historical crash)."""
    cfg = SimConfig(epochs=10, seed=2, arrival_rate=10.0,
                    mean_duration_h=8.0, deferrable_frac=0.8,
                    defer_max_h=0, shortlist=8, history_h=24, horizon_h=6)
    host, scan, _ = _run_both(cfg, n=4, chips=64)
    assert host.jobs_dropped > 0
    assert host.deadline_misses == scan.deadline_misses == 0
    assert host.jobs_deferred == 0
    _assert_equivalent(host, scan)


def test_policy_config_rejects_unknown_kinds():
    with pytest.raises(ValueError, match="migration"):
        P.PolicyConfig(migration="psychic")
    with pytest.raises(ValueError, match="deferral"):
        P.PolicyConfig(deferral="never")


# ---------------------------------------------------------------------------
# hypothesis: random streams keep host/scan equivalence + accounting sane
# ---------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       rate=st.floats(1.0, 10.0),
       deferrable=st.floats(0.1, 1.0),
       vweight=st.floats(0.0, 3.0),
       qcap=st.integers(0, 4),
       budget=st.integers(0, 2),
       lookahead=st.booleans())
def test_policy_scan_matches_host_on_random_streams(seed, rate, deferrable,
                                                    vweight, qcap, budget,
                                                    lookahead):
    pcfg = P.PolicyConfig(
        migration="lookahead" if lookahead else "reactive",
        deferral="slo", value_weight=vweight, queue_cap=qcap,
        deadline_hi=5)
    cfg = dataclasses.replace(
        BASE, epochs=12, seed=seed, arrival_rate=rate,
        deferrable_frac=deferrable, migration_budget=budget,
        defer_max_h=4, history_h=24, horizon_h=6, policy=pcfg)
    host, scan, jobs = _run_both(cfg, n=24, chips=32, pad=True)
    _assert_equivalent(host, scan)
    # accounting invariants
    pol = P.Policy.for_jobs(pcfg, jobs.arrive, jobs.deferrable,
                            cfg.defer_max_h, jobs.deadline, jobs.value)
    started = host.start_epoch >= 0
    delay = host.start_epoch[started] - jobs.arrive[started]
    assert int(delay.sum()) == host.defer_delay_h
    assert np.all(delay <= pol.slack[started])      # deadlines respected
    assert host.deadline_misses <= int((pol.slack > 0).sum())


# ---------------------------------------------------------------------------
# forecast green-window extraction + Pareto harness
# ---------------------------------------------------------------------------


def test_green_window_signals_basic():
    fc = jnp.asarray(np.stack([np.full(8, 100.0),
                               np.linspace(400, 100, 8)]), jnp.float32)
    rpue = jnp.asarray([1.5, 1.0], jnp.float32)
    la_ci, gw_min = forecast.green_window_signals(fc, rpue, 4, 0.9)
    assert la_ci.shape == (2,) and gw_min.shape == ()
    # constant region: discount weights are normalized -> exactly the mean
    assert float(la_ci[0]) == pytest.approx(100.0, rel=1e-6)
    # window min rate over the first 4 hours only
    assert float(gw_min) == pytest.approx(
        min(100.0 * 1.5, float(fc[1, 3]) * 1.0), rel=1e-6)


def test_green_window_signals_clamps_short_horizon():
    """horizon < lookahead_h must clamp, not crash or read junk."""
    fc = jnp.asarray(np.linspace(300, 100, 6)[None, :], jnp.float32)
    rpue = jnp.asarray([2.0], jnp.float32)
    la_long, gw_long = forecast.green_window_signals(fc, rpue, 48, 0.9)
    la_all, gw_all = forecast.green_window_signals(fc, rpue, 6, 0.9)
    assert float(la_long[0]) == pytest.approx(float(la_all[0]), rel=1e-6)
    assert float(gw_long) == pytest.approx(float(gw_all), rel=1e-6)
    # empty-region +inf PUE rows can never win the window min
    fc2 = jnp.asarray(np.stack([np.full(6, 50.0), np.full(6, 1.0)]),
                      jnp.float32)
    rpue2 = jnp.asarray([1.0, np.inf], jnp.float32)
    _, gw2 = forecast.green_window_signals(fc2, rpue2, 4, 0.9)
    assert float(gw2) == pytest.approx(50.0, rel=1e-6)


def test_green_window_signals_batched_matches_per_epoch():
    rng = np.random.default_rng(0)
    fc = jnp.asarray(rng.uniform(50, 500, (5, 3, 12)), jnp.float32)
    rpue = jnp.asarray([1.1, 1.4, 1.6], jnp.float32)
    la_b, gw_b = forecast.green_window_signals(fc, rpue, 8, 0.9)
    for t in range(5):
        la_t, gw_t = forecast.green_window_signals(fc[t], rpue, 8, 0.9)
        np.testing.assert_allclose(np.asarray(la_b[t]), np.asarray(la_t),
                                   rtol=1e-6)
        assert float(gw_b[t]) == pytest.approx(float(gw_t), rel=1e-6)


def test_pareto_frontier_monotone_and_non_dominated():
    recs = [
        {"policy": "a", "seed": 0, "avg_start_delay_h": 0.0,
         "emissions_g": 100.0, "miss_rate": 0.0},
        {"policy": "b", "seed": 0, "avg_start_delay_h": 1.0,
         "emissions_g": 90.0, "miss_rate": 0.01},
        {"policy": "dominated", "seed": 0, "avg_start_delay_h": 2.0,
         "emissions_g": 95.0, "miss_rate": 0.02},
        {"policy": "c", "seed": 0, "avg_start_delay_h": 3.0,
         "emissions_g": 80.0, "miss_rate": 0.03},
    ]
    front = pareto_frontier(recs)
    assert [p["policy"] for p in front] == ["a", "b", "c"]
    es = [p["emissions_g"] for p in front]
    assert es == sorted(es, reverse=True)


def test_sweep_policies_shapes_and_keys():
    cfg = SimConfig(epochs=12, seed=0, arrival_rate=4.0,
                    mean_duration_h=3.0, deferrable_frac=0.5,
                    defer_max_h=4, history_h=24, horizon_h=6, shortlist=8)
    recs = sweep_policies(
        cfg, {"reactive": P.REACTIVE,
              "slo": P.slo_deferral(deadline_hi=4)},
        n=16, seeds=(0, 1), chips_per_node=64, region=0)
    assert len(recs) == 4
    for r in recs:
        assert {"policy", "seed", "emissions_g", "migrations",
                "deadline_misses", "avg_start_delay_h",
                "miss_rate"} <= set(r)
        assert r["emissions_g"] > 0


def test_pad_plan_is_behavior_neutral():
    cfg = dataclasses.replace(MIXED, deferrable_frac=0.4,
                              policy=P.slo_deferral(deadline_hi=8))
    fleet, traces, ridx = synthetic_lifecycle_fleet(96, cfg,
                                                    chips_per_node=64)
    jobs = generate_jobs(cfg)
    a = simulate_fleet_scan(fleet, traces, ridx, cfg, jobs=jobs)
    b = simulate_fleet_scan(fleet, traces, ridx, cfg, jobs=jobs,
                            pad_plan=True)
    np.testing.assert_array_equal(a.node_log, b.node_log)
    np.testing.assert_array_equal(a.start_epoch, b.start_epoch)
    assert a.emissions_g == b.emissions_g
    assert a.deadline_misses == b.deadline_misses


# ---------------------------------------------------------------------------
# migration gain expressions
# ---------------------------------------------------------------------------


def test_migration_gain_reactive_formula():
    g = P.migration_gain(
        np, P.REACTIVE, rate_cur=np.array([300.0]),
        best_rate=np.array([100.0]), chips=np.array([8.0]),
        remaining=np.array([10.0]), e_kwh_h=0.5, ckpt=np.array([0.2]))
    assert g[0] == pytest.approx((300 - 100) * 0.5 * 8 * 10 - 0.2 * 300)


def test_migration_gain_lookahead_gate():
    pcfg = P.green_window(green_gate=1.2)
    kw = dict(rate_cur=np.array([300.0]), best_rate=np.array([150.0]),
              chips=np.array([8.0]), remaining=np.array([10.0]),
              e_kwh_h=0.5, ckpt=np.array([0.2]),
              src_la=np.array([280.0]), dst_la=np.array([100.0]))
    open_g = P.migration_gain(np, pcfg, gw_min=np.array([130.0]), **kw)
    shut_g = P.migration_gain(np, pcfg, gw_min=np.array([100.0]), **kw)
    assert open_g[0] == pytest.approx(
        (280 - 100) * 0.5 * 8 * 10 - 0.2 * 300)
    assert shut_g[0] == -np.inf       # 150 > 1.2 * 100: wait for the window
