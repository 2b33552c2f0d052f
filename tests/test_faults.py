"""Signal-fault injection + graceful degradation: the chaos suite.

Contracts (see ``repro.core.faults`` and ISSUE 6):

- **zero-fault bitwise equivalence**: ``faults=None`` and a zero-rate
  ``FaultConfig`` both reproduce the fault-free golden trajectories
  bit-for-bit (placement digests pinned in ``tests/test_policy.py``);
- **host-vs-scan parity under every fault stream**: both drivers read the
  identical materialized ``FaultPlan``, so placements and counters match
  exactly, emissions to f32 tolerance — same contract as
  ``tests/test_simulator_scan.py``, extended to chaos streams;
- **no job silently dropped**: every in-horizon job is completed, dropped,
  or still active/queued when the horizon ends — under any fault mix;
- **quarantine re-admission**: a flapped node returns to placement
  eligibility only after ``quarantine_h`` consecutive healthy hours;
- **safe mode**: stale-beyond-horizon signal freezes migrations;
- **outage windows**: the single-tuple form and the list form agree, and
  multiple windows evict independently.
"""
import dataclasses
import hashlib

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.faults import FaultConfig, fault_graph_key, plan_faults
from repro.core.simulator import (SimConfig, _outage_windows, generate_jobs,
                                  simulate_fleet, simulate_fleet_scan,
                                  synthetic_lifecycle_fleet)

BASE = SimConfig(epochs=24, seed=3, arrival_rate=6.0, mean_duration_h=6.0,
                 shortlist=16, history_h=48, horizon_h=8)

COUNTERS = ("rank_sweeps", "arrivals_placed", "jobs_completed",
            "jobs_dropped", "jobs_deferred", "migrations", "evictions",
            "migrations_failed", "jobs_active_end", "safe_epochs",
            "deadline_misses")


def _run_both(cfg, n=96, chips=64, jobs=None):
    fleet, traces, ridx = synthetic_lifecycle_fleet(n, cfg,
                                                    chips_per_node=chips)
    jobs = jobs if jobs is not None else generate_jobs(cfg)
    host = simulate_fleet(fleet, traces, ridx, cfg, jobs=jobs)
    scan = simulate_fleet_scan(fleet, traces, ridx, cfg, jobs=jobs)
    return host, scan, jobs


def _assert_equivalent(host, scan):
    np.testing.assert_array_equal(host.node_log, scan.node_log)
    np.testing.assert_array_equal(host.first_node, scan.first_node)
    for f in COUNTERS:
        assert getattr(host, f) == getattr(scan, f), f
    assert scan.emissions_g == pytest.approx(host.emissions_g, rel=1e-4)
    np.testing.assert_allclose(scan.emissions_series,
                               host.emissions_series, rtol=1e-4)


def _assert_conserved(r, jobs, cfg):
    """No job silently dropped: every in-horizon job is accounted for."""
    in_h = int((np.asarray(jobs.arrive) < cfg.epochs).sum())
    assert r.jobs_completed + r.jobs_dropped + r.jobs_active_end == in_h
    placed = r.first_node >= 0
    assert r.jobs_completed + r.jobs_active_end <= int(placed.sum())
    assert np.all(r.node_log[~placed] == -1)


# ---------------------------------------------------------------------------
# zero-fault bitwise equivalence
# ---------------------------------------------------------------------------


def test_zero_rate_faultconfig_is_bitwise_noop():
    """A FaultConfig with every rate at zero materializes exact no-op
    tensors: emissions (not just placements) match faults=None bitwise on
    both drivers."""
    h0, s0, _ = _run_both(BASE)
    hz, sz, _ = _run_both(dataclasses.replace(BASE, faults=FaultConfig()))
    np.testing.assert_array_equal(h0.node_log, hz.node_log)
    np.testing.assert_array_equal(s0.node_log, sz.node_log)
    assert hz.emissions_g == h0.emissions_g
    assert sz.emissions_g == s0.emissions_g
    np.testing.assert_array_equal(hz.emissions_series, h0.emissions_series)


MIXED = SimConfig(epochs=36, seed=11, arrival_rate=8.0,
                  mean_duration_h=10.0, shortlist=32, history_h=48,
                  horizon_h=12, migration_budget=2, deferrable_frac=0.3,
                  outage=(0, 12, 6), flash_crowd=(20, 3, 2.5))


@pytest.mark.parametrize("cfg,want", [
    (BASE, "0141b64da0651227"), (MIXED, "0e6437d00c3ba558")])
def test_zero_fault_runs_reproduce_golden_digests(cfg, want):
    """The pre-fault golden trajectories (pinned since PR 4 in
    tests/test_policy.py) survive the fault layer: both with faults=None
    and with a zero-rate FaultConfig, on both drivers.  MIXED also runs
    its single-tuple outage through the generalized window list."""
    for f in (None, FaultConfig()):
        host, scan, _ = _run_both(dataclasses.replace(cfg, faults=f))
        for r in (host, scan):
            got = hashlib.sha256(np.concatenate(
                [r.node_log, r.first_node]).tobytes()).hexdigest()[:16]
            assert got == want, (f, r is scan)


def test_fault_graph_key_rates_are_data():
    assert fault_graph_key(None) == (False, False, False)
    assert fault_graph_key(FaultConfig()) == (True, False, False)
    # rates, caps and backoffs never shape the graph
    assert fault_graph_key(FaultConfig(ci_dropout=0.9, stale_cap_h=4,
                                       telem_sigma=1.0, fc_dropout=0.5,
                                       safe_stale_h=3, mig_backoff_h=7)) \
        == (True, False, False)
    assert fault_graph_key(FaultConfig(mig_fail=0.1)) == (True, True, False)
    assert fault_graph_key(FaultConfig(flap_rate=0.1)) == (True, False,
                                                           True)


def test_faultconfig_validates_rates():
    with pytest.raises(ValueError, match="ci_dropout"):
        FaultConfig(ci_dropout=1.5)
    with pytest.raises(ValueError, match="fc_outage"):
        FaultConfig(fc_outage=((-1, 4),))


# ---------------------------------------------------------------------------
# host-vs-scan parity under every fault class
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,fcfg", [
    ("dropout_hold", FaultConfig(ci_dropout=0.5)),
    ("dropout_persistence", FaultConfig(ci_dropout=0.7, stale_cap_h=2)),
    ("noise_bias", FaultConfig(ci_dropout=0.3, telem_sigma=0.1,
                               telem_bias=0.05)),
    ("fc_outage", FaultConfig(fc_dropout=0.4, fc_outage=((2, 5),))),
    ("safe_mode", FaultConfig(ci_dropout=0.95, stale_cap_h=2,
                              safe_stale_h=3)),
])
def test_scan_matches_host_under_signal_faults(name, fcfg):
    cfg = dataclasses.replace(BASE, migration_budget=2,
                              deferrable_frac=0.3, faults=fcfg)
    host, scan, jobs = _run_both(cfg)
    _assert_equivalent(host, scan)
    _assert_conserved(host, jobs, cfg)


def test_scan_matches_host_under_migration_faults():
    cfg = dataclasses.replace(
        BASE, migration_budget=3, mean_duration_h=16.0,
        faults=FaultConfig(mig_fail=0.5, mig_backoff_h=2))
    host, scan, jobs = _run_both(cfg)
    assert host.migrations_failed > 0
    _assert_equivalent(host, scan)
    _assert_conserved(host, jobs, cfg)


def test_scan_matches_host_under_flapping():
    cfg = dataclasses.replace(
        BASE, faults=FaultConfig(flap_rate=0.03, flap_len_h=2,
                                 quarantine_h=3))
    host, scan, jobs = _run_both(cfg)
    assert host.evictions > 0
    _assert_equivalent(host, scan)
    _assert_conserved(host, jobs, cfg)


def test_scan_matches_host_under_everything():
    """All fault classes at once, on top of outage windows, a flash crowd
    and both non-reactive policies' knobs."""
    from repro.core.policy import slo_deferral
    cfg = dataclasses.replace(
        BASE, epochs=36, migration_budget=2, deferrable_frac=0.4,
        outage=[(0, 12, 6), (2, 4, 3)], flash_crowd=(20, 3, 2.5),
        policy=slo_deferral(),
        faults=FaultConfig(ci_dropout=0.6, stale_cap_h=2, safe_stale_h=4,
                           telem_sigma=0.1, fc_outage=((5, 4),),
                           fc_dropout=0.2, mig_fail=0.4, flap_rate=0.03,
                           quarantine_h=2))
    host, scan, jobs = _run_both(cfg)
    _assert_equivalent(host, scan)
    _assert_conserved(host, jobs, cfg)


# ---------------------------------------------------------------------------
# degradation semantics
# ---------------------------------------------------------------------------


def test_migration_failures_consume_budget_and_back_off():
    """mig_fail=1.0: every attempt fails, nothing ever moves, failures
    are counted, and the accounting never charges a failed move."""
    cfg = dataclasses.replace(
        BASE, migration_budget=3, mean_duration_h=16.0,
        faults=FaultConfig(mig_fail=1.0, mig_backoff_h=2))
    host, scan, _ = _run_both(cfg)
    assert host.migrations == 0
    assert host.migrations_failed > 0
    assert host.migration_cost_g == 0.0
    _assert_equivalent(host, scan)
    # the no-fault twin DOES migrate on this stream (the faults are the
    # only difference)
    clean, _, _ = _run_both(dataclasses.replace(cfg, faults=None))
    assert clean.migrations > 0


def test_safe_mode_freezes_migrations():
    """At 100% dropout past the staleness horizon the degraded operator
    stops moving jobs; the naive twin keeps migrating on garbage."""
    env = dict(ci_dropout=1.0, stale_cap_h=6)
    cfg_safe = dataclasses.replace(
        BASE, epochs=36, migration_budget=2, mean_duration_h=16.0,
        faults=FaultConfig(safe_stale_h=6, **env))
    cfg_naive = dataclasses.replace(cfg_safe,
                                    faults=FaultConfig(**env))
    host, scan, _ = _run_both(cfg_safe)
    assert host.safe_epochs > 0
    assert host.migrations == 0
    _assert_equivalent(host, scan)
    naive, _, _ = _run_both(cfg_naive)
    assert naive.safe_epochs == 0 and naive.migrations > 0


def test_quarantine_readmission_in_plan():
    """A flapped node is re-admitted exactly quarantine_h healthy hours
    after its spell ends — checked on the materialized plan."""
    fcfg = FaultConfig(seed=5, flap_rate=0.05, flap_len_h=3,
                       quarantine_h=4)
    rng = np.random.default_rng(0)
    traces = rng.random((3, 120)) + 0.5
    plan = plan_faults(fcfg, traces, np.zeros(8, np.int64), epochs=48,
                       history_h=48, budget=0, n_nodes=8)
    assert (~plan.node_up).any(), "stream produced no flaps"
    up, elig = plan.node_up, plan.eligible
    T, N = up.shape
    for n in range(N):
        for t in range(T):
            down_recent = (~up[max(t - 4, 0):t, n]).any()
            assert elig[t, n] == (up[t, n] and not down_recent), (t, n)


def test_quarantine_end_to_end_blocks_placement():
    """Single-region fleet: during a node's quarantine, placements avoid
    it on both drivers."""
    cfg = dataclasses.replace(
        BASE, faults=FaultConfig(seed=2, flap_rate=0.05, flap_len_h=2,
                                 quarantine_h=6))
    fleet, traces, ridx = synthetic_lifecycle_fleet(16, cfg,
                                                    chips_per_node=64,
                                                    region=0)
    jobs = generate_jobs(cfg)
    host = simulate_fleet(fleet, traces, ridx, cfg, jobs=jobs)
    scan = simulate_fleet_scan(fleet, traces, ridx, cfg, jobs=jobs)
    _assert_equivalent(host, scan)
    plan = plan_faults(cfg.faults, traces, ridx, cfg.epochs, cfg.history_h,
                       cfg.migration_budget, 16, cfg.seed)
    started = host.start_epoch >= 0
    ok = plan.eligible[host.start_epoch[started],
                       host.node_log[started].astype(np.int64)]
    # every first placement landed on a then-eligible node (node_log may
    # differ from the start node for migrated jobs — restrict to jobs
    # that never moved, which is all of them at migration_budget=0)
    assert ok.all()


def test_persistence_fallback_changes_decisions_only_after_cap():
    """stale_cap_h only matters once a region has been stale past the
    cap: at low dropout with a huge cap, hold-last and capped configs
    coincide."""
    f_hold = FaultConfig(seed=7, ci_dropout=0.2)
    f_cap = dataclasses.replace(f_hold, stale_cap_h=23)
    h1, _, _ = _run_both(dataclasses.replace(BASE, faults=f_hold))
    h2, _, _ = _run_both(dataclasses.replace(BASE, faults=f_cap))
    # with dropout 0.2 a >23h stale spell is ~1e-17 likely: identical
    np.testing.assert_array_equal(h1.node_log, h2.node_log)


# ---------------------------------------------------------------------------
# outage windows (satellite: list form)
# ---------------------------------------------------------------------------


def test_outage_windows_normalizer():
    assert _outage_windows(None) == ()
    assert _outage_windows((1, 2, 3)) == ((1, 2, 3),)
    assert _outage_windows([(1, 2, 3)]) == ((1, 2, 3),)
    assert _outage_windows([(1, 2, 3), (0, 4, 5)]) == ((1, 2, 3),
                                                       (0, 4, 5))
    assert _outage_windows(((1, 2, 3), (0, 4, 5))) == ((1, 2, 3),
                                                       (0, 4, 5))


def test_outage_single_tuple_equals_singleton_list():
    cfg_t = dataclasses.replace(BASE, outage=(0, 6, 6),
                                mean_duration_h=12.0)
    cfg_l = dataclasses.replace(cfg_t, outage=[(0, 6, 6)])
    ht, st_, _ = _run_both(cfg_t)
    hl, sl, _ = _run_both(cfg_l)
    np.testing.assert_array_equal(ht.node_log, hl.node_log)
    assert ht.emissions_g == hl.emissions_g
    np.testing.assert_array_equal(st_.node_log, sl.node_log)
    assert st_.evictions == sl.evictions


def test_outage_multiple_windows():
    cfg = dataclasses.replace(BASE, outage=[(0, 2, 4), (1, 10, 4)],
                              mean_duration_h=12.0)
    host, scan, jobs = _run_both(cfg)
    assert host.evictions > 0
    _assert_equivalent(host, scan)
    _assert_conserved(host, jobs, cfg)


# ---------------------------------------------------------------------------
# scan-slot sizing + actionable overflow error (satellite)
# ---------------------------------------------------------------------------


def test_scan_slots_override_widens_plan():
    from repro.core.simulator import Policy, _scan_plan
    jobs = generate_jobs(BASE)
    pol = Policy.for_jobs(BASE.policy, jobs.arrive, jobs.deferrable,
                          BASE.defer_max_h, jobs.deadline, jobs.value)
    base_slots = _scan_plan(BASE, jobs, pol).slots
    wide = _scan_plan(dataclasses.replace(BASE,
                                          scan_slots=base_slots + 17),
                      jobs, pol)
    assert wide.slots == base_slots + 17
    # the override can only widen — a low value falls back to the bound
    assert _scan_plan(dataclasses.replace(BASE, scan_slots=1),
                      jobs, pol).slots == base_slots


def test_slot_overflow_error_reports_capacity_epoch_and_override():
    """The sound bound makes real overflow unreachable, so the message is
    exercised on a doctored (carry, ys): it must name the capacity S, the
    first offending epoch, and a concrete scan_slots workaround."""
    from repro.core.simulator import _scan_result

    class _Plan:
        slots, a_max, d_cap, rel_cap, m_evict = 7, 3, 2, 4, 0

    class _Run:
        cfg, jobs, plan = BASE, generate_jobs(BASE), _Plan()

    T = BASE.epochs
    carry = [None] * 5 + [0.0, 0.0, np.int32(2)]
    ys = [np.zeros(T, np.int64) for _ in range(16)]
    ys[13] = np.asarray([0] * 5 + [1] * (T - 5))   # cumulative overflow
    with pytest.raises(RuntimeError) as e:
        _scan_result(_Run(), carry, ys)
    msg = str(e.value)
    assert "S=7" in msg
    assert "at epoch 5" in msg
    assert "SimConfig(scan_slots=9)" in msg


# ---------------------------------------------------------------------------
# forecast persistence fallback (unit)
# ---------------------------------------------------------------------------


def test_persistence_forecast_tiles_last_day():
    import jax.numpy as jnp
    from repro.core.forecast import persistence_forecast
    hist = jnp.arange(72, dtype=jnp.float32)
    out = np.asarray(persistence_forecast(hist, 30))
    want = np.concatenate([np.arange(48, 72), np.arange(48, 54)])
    np.testing.assert_array_equal(out, want.astype(np.float32))
    # short history: tiles whatever exists
    short = jnp.asarray([3.0, 5.0])
    np.testing.assert_array_equal(
        np.asarray(persistence_forecast(short, 5)),
        np.asarray([3.0, 5.0, 3.0, 5.0, 3.0], np.float32))


# ---------------------------------------------------------------------------
# hypothesis chaos property
# ---------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       dropout=st.floats(0.0, 1.0),
       cap=st.integers(0, 6),
       sigma=st.floats(0.0, 0.3),
       mig_fail=st.floats(0.0, 1.0),
       flap=st.floats(0.0, 0.05),
       safe_h=st.integers(0, 6),
       budget=st.integers(0, 3))
def test_chaos_parity_and_conservation(seed, dropout, cap, sigma, mig_fail,
                                       flap, safe_h, budget):
    cfg = dataclasses.replace(
        BASE, epochs=12, seed=seed, history_h=24, horizon_h=6,
        migration_budget=budget, deferrable_frac=0.3, defer_max_h=3,
        faults=FaultConfig(seed=seed, ci_dropout=dropout, stale_cap_h=cap,
                           telem_sigma=sigma, mig_fail=mig_fail,
                           flap_rate=flap, flap_len_h=2, quarantine_h=2,
                           safe_stale_h=safe_h, fc_dropout=dropout / 2))
    host, scan, jobs = _run_both(cfg, n=24, chips=32)
    _assert_equivalent(host, scan)
    _assert_conserved(host, jobs, cfg)
    _assert_conserved(scan, jobs, cfg)
