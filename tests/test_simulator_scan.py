"""Scan-compiled simulator core: equivalence with the host-loop oracle.

The contract (see ``simulate_fleet_scan``): per-job placements
(``node_log``/``first_node``) and every integer counter match the host loop
EXACTLY; emissions/migration-cost accounting matches to float32
accumulation tolerance (the host loop accounts in float64 numpy).  Edge
coverage: job-table exhaustion, all-nodes-unhealthy epochs, zero-arrival
epochs, deferral takebacks, the Pallas kernel path, and hypothesis property
tests over random event streams (skipped via the stub when hypothesis is
missing)."""
import dataclasses

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.ranking import RankWeights
from repro.core.simulator import (SimConfig, JobSchedule, generate_jobs,
                                  simulate_fleet, simulate_fleet_scan,
                                  synthetic_lifecycle_fleet)

BASE = SimConfig(epochs=24, seed=3, arrival_rate=6.0, mean_duration_h=6.0,
                 shortlist=16, history_h=48, horizon_h=8)

COUNTERS = ("rank_sweeps", "arrivals_placed", "jobs_completed",
            "jobs_dropped", "jobs_deferred", "migrations", "evictions")


def _run_both(cfg, n=96, chips=64, jobs=None, ridx=None):
    fleet, traces, r = synthetic_lifecycle_fleet(n, cfg,
                                                 chips_per_node=chips)
    ridx = r if ridx is None else ridx
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
    assert scan.migration_cost_g == pytest.approx(host.migration_cost_g,
                                                  rel=1e-4, abs=1e-6)
    np.testing.assert_allclose(scan.emissions_series,
                               host.emissions_series, rtol=1e-4)


# ---------------------------------------------------------------------------
# scenario matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,cfg", [
    ("base", BASE),
    ("full_engine", dataclasses.replace(BASE, engine="full")),
    ("cfp_only", dataclasses.replace(
        BASE, weights=RankWeights(w1=1.0, w2=0.0, w3=0.0, w4=0.0))),
    ("deferral", dataclasses.replace(BASE, deferrable_frac=1.0,
                                     defer_max_h=4)),
    ("migration", dataclasses.replace(BASE, migration_budget=5,
                                      mean_duration_h=20.0)),
    ("always_on", dataclasses.replace(BASE, power_off_idle=False)),
    ("jobs_past_horizon", dataclasses.replace(BASE, mean_duration_h=40.0)),
    ("everything", dataclasses.replace(
        BASE, outage=(1, 6, 6), deferrable_frac=0.3, migration_budget=2,
        flash_crowd=(10, 3, 3.0))),
])
def test_scan_matches_host(name, cfg):
    host, scan, _ = _run_both(cfg)
    _assert_equivalent(host, scan)


def test_scan_matches_host_interleaved_lifecycle():
    """The acceptance-shaped stream: interleaved arrivals, releases,
    migrations, evictions and deferrals through one trajectory."""
    cfg = dataclasses.replace(BASE, epochs=36, migration_budget=2,
                              deferrable_frac=0.2, outage=(0, 12, 6),
                              flash_crowd=(20, 3, 2.5))
    host, scan, _ = _run_both(cfg, n=192, chips=128)
    assert host.migrations > 0 and host.evictions > 0
    assert host.jobs_deferred > 0 and host.jobs_completed > 0
    _assert_equivalent(host, scan)


def test_scan_throughput_counts_one_sweep_per_epoch():
    """The scanned shortlist engine keeps the host's sweep economy: the
    eager epoch-initial sweep is counted exactly like the host's lazy one."""
    host, scan, _ = _run_both(BASE)
    assert scan.rank_sweeps == host.rank_sweeps
    assert scan.rank_sweeps <= 2 * BASE.epochs


# ---------------------------------------------------------------------------
# static-shape edges: exhaustion, unhealthy fleets, empty epochs
# ---------------------------------------------------------------------------


def test_scan_job_table_exhaustion():
    """Arrivals far beyond fleet capacity: drops accounted identically and
    the fixed-capacity slot table never overflows (a violation raises)."""
    cfg = dataclasses.replace(BASE, arrival_rate=20.0, chips_lo=32,
                              chips_hi=64)
    host, scan, jobs = _run_both(cfg, n=4, chips=64)
    assert host.jobs_dropped > jobs.n // 2
    _assert_equivalent(host, scan)


def test_scan_all_nodes_unhealthy_epochs():
    """An outage covering every node: mass eviction, zero placements
    during the window, drops for non-deferrable arrivals."""
    cfg = dataclasses.replace(BASE, outage=(0, 6, 6), mean_duration_h=12.0)
    fleet, traces, ridx = synthetic_lifecycle_fleet(32, cfg,
                                                    chips_per_node=64)
    ridx0 = np.zeros_like(ridx)        # every node in the outaged region
    jobs = generate_jobs(cfg)
    host = simulate_fleet(fleet, traces, ridx0, cfg, jobs=jobs)
    scan = simulate_fleet_scan(fleet, traces, ridx0, cfg, jobs=jobs)
    assert host.evictions > 0 and host.jobs_dropped > 0
    in_window = (jobs.arrive >= 6) & (jobs.arrive < 12)
    assert np.all(host.first_node[in_window & ~jobs.deferrable] == -1)
    _assert_equivalent(host, scan)


def test_scan_zero_arrival_epochs():
    host, scan, _ = _run_both(dataclasses.replace(BASE, arrival_rate=0.0))
    assert host.arrivals_placed == scan.arrivals_placed == 0
    _assert_equivalent(host, scan)


def test_scan_empty_schedule():
    empty = JobSchedule(arrive=np.zeros(0, np.int64),
                        chips=np.zeros(0, np.int64),
                        duration=np.zeros(0, np.int64),
                        load=np.zeros(0),
                        deferrable=np.zeros(0, bool))
    host, scan, _ = _run_both(BASE, jobs=empty)
    assert scan.emissions_g == pytest.approx(host.emissions_g, rel=1e-4)
    assert scan.jobs_completed == scan.jobs_dropped == 0


def test_scan_rejects_host_only_engines():
    for engine in ("blind", "spread"):
        with pytest.raises(ValueError, match="host-only"):
            simulate_fleet_scan(
                *synthetic_lifecycle_fleet(8, BASE, chips_per_node=16)[:3],
                dataclasses.replace(BASE, engine=engine))


def test_scan_kernel_path_matches_host_kernel_path():
    """use_kernel=True routes the scanned epoch sweeps through the fused
    Pallas two-sweep kernel (interpret mode on CPU) — same trajectory as
    the host loop running the same kernel."""
    cfg = dataclasses.replace(BASE, epochs=8, arrival_rate=4.0,
                              shortlist=8, use_kernel=True)
    host, scan, _ = _run_both(cfg, n=64, chips=64)
    _assert_equivalent(host, scan)


# ---------------------------------------------------------------------------
# property tests over random event streams
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       rate=st.floats(0.0, 12.0),
       duration=st.floats(1.0, 20.0),
       budget=st.integers(0, 3),
       deferrable=st.floats(0.0, 1.0),
       outage=st.booleans())
def test_scan_matches_host_on_random_streams(seed, rate, duration, budget,
                                             deferrable, outage):
    cfg = dataclasses.replace(
        BASE, epochs=12, seed=seed, arrival_rate=rate,
        mean_duration_h=duration, migration_budget=budget,
        deferrable_frac=deferrable, defer_max_h=3,
        outage=(seed % 3, 4, 4) if outage else None,
        history_h=24, horizon_h=6)
    host, scan, _ = _run_both(cfg, n=24, chips=32)
    _assert_equivalent(host, scan)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_scan_totals_reconcile(seed):
    """Conservation on random streams: every job is placed-or-dropped-or-
    still-running/deferred, and chips flow back (completions monotone in
    horizon length would need a second run; here we check accounting)."""
    cfg = dataclasses.replace(BASE, seed=seed, epochs=16,
                              deferrable_frac=0.5, defer_max_h=3)
    fleet, traces, ridx = synthetic_lifecycle_fleet(24, cfg,
                                                    chips_per_node=32)
    jobs = generate_jobs(cfg)
    scan = simulate_fleet_scan(fleet, traces, ridx, cfg, jobs=jobs)
    in_horizon = int((jobs.arrive < cfg.epochs).sum())
    still_running = in_horizon - scan.jobs_completed - scan.jobs_dropped
    assert still_running >= 0
    placed = scan.first_node >= 0
    assert scan.jobs_completed <= placed.sum()
    assert np.all(scan.node_log[~placed] == -1)
