"""Batched ensemble simulator: vmapped-vs-sequential bit parity.

``simulate_fleet_ensemble`` executes a (seed x policy) grid of scanned
trajectories as one ``vmap``-of-``lax.scan`` program per graph bucket.
The contract mirrors the scanned core's own equivalence bar (PR 3/4):
per-job placements (``node_log``/``first_node``/``start_epoch``) and
every integer counter match ``simulate_fleet_scan`` run member-by-member
EXACTLY; emissions match to the scanned core's f32 accounting tolerance
(bitwise-equal on every tested stream so far).  Coverage: interleaved
arrival/release/migration/deferral/eviction streams, the PR 4 golden
digests, ragged ensembles (different job counts / plan shapes padded into
one bucket), multi-bucket calls with order preservation, the SLO queue
cap as a traced scalar, and hypothesis property streams."""
import dataclasses
import hashlib

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import jax

from repro.core import policy as P
from repro.core.simulator import (SimConfig, generate_jobs,
                                  simulate_fleet_ensemble,
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


def _run_spec(cfg, n=96, chips=64, region=None):
    fleet, traces, ridx = synthetic_lifecycle_fleet(n, cfg,
                                                    chips_per_node=chips,
                                                    region=region)
    return (fleet, traces, ridx, cfg, generate_jobs(cfg))


def _assert_member_parity(seq, ens):
    assert len(seq) == len(ens)
    for i, (a, b) in enumerate(zip(seq, ens)):
        np.testing.assert_array_equal(a.node_log, b.node_log,
                                      err_msg=f"member {i} node_log")
        np.testing.assert_array_equal(a.first_node, b.first_node,
                                      err_msg=f"member {i} first_node")
        np.testing.assert_array_equal(a.start_epoch, b.start_epoch,
                                      err_msg=f"member {i} start_epoch")
        for f in COUNTERS:
            assert getattr(a, f) == getattr(b, f), (i, f)
        assert b.emissions_g == pytest.approx(a.emissions_g, rel=1e-4)
        assert b.migration_cost_g == pytest.approx(a.migration_cost_g,
                                                   rel=1e-4, abs=1e-6)
        np.testing.assert_allclose(b.emissions_series, a.emissions_series,
                                   rtol=1e-4)


def _both(runs, **kw):
    seq = [simulate_fleet_scan(f, t, r, c, jobs=j, pad_plan=True)
           for f, t, r, c, j in runs]
    ens = simulate_fleet_ensemble(runs, **kw)
    _assert_member_parity(seq, ens)
    return seq, ens


# ---------------------------------------------------------------------------
# parity across policy mixes and interleaved streams
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,pcfg", [
    ("reactive", P.REACTIVE),
    ("green_window", P.green_window()),
    ("slo", P.slo_deferral(value_weight=0.7, deadline_hi=8)),
    ("combined", P.PolicyConfig(migration="lookahead", deferral="slo")),
])
def test_ensemble_matches_sequential_per_policy(name, pcfg):
    """Seed ensembles of one policy (one graph bucket) on the mixed
    stream: arrivals + releases + migrations + deferrals + outage
    evictions, bit-identical per lane."""
    runs = [_run_spec(dataclasses.replace(MIXED, seed=s,
                                          deferrable_frac=0.5, policy=pcfg))
            for s in (11, 12, 13)]
    _both(runs)


def test_ensemble_golden_digest_matches_pr4():
    """The PR 3/4 golden trajectory, reproduced through the ensemble
    path: one vmap lane must still hash to the committed digest."""
    ens = simulate_fleet_ensemble([_run_spec(BASE), _run_spec(MIXED)])
    digests = [hashlib.sha256(np.concatenate(
        [r.node_log, r.first_node]).tobytes()).hexdigest()[:16]
        for r in ens]
    assert digests == ["0141b64da0651227", "0e6437d00c3ba558"]


def test_ensemble_single_member_and_order():
    """E=1 works, and a multi-bucket call returns results in input order
    (buckets execute grouped, results are re-scattered)."""
    specs = [_run_spec(BASE),
             _run_spec(dataclasses.replace(
                 MIXED, policy=P.slo_deferral(deadline_hi=8),
                 deferrable_frac=0.5)),
             _run_spec(dataclasses.replace(BASE, seed=4)),
             _run_spec(dataclasses.replace(BASE, epochs=12))]
    solo = simulate_fleet_ensemble(specs[:1])
    assert len(solo) == 1
    seq, ens = _both(specs)
    # distinct schedules => distinct job counts; order must be preserved
    assert [len(r.node_log) for r in ens] == [s[4].n for s in specs]


def test_ensemble_ragged_grid_shares_padded_bucket():
    """Members with different arrival rates (hence different job counts,
    slot bounds and arrival buffers) still stack: shapes are the
    member-wise maxima of the pad-bucketed plans, and the padding lanes
    are exact no-ops."""
    runs = [_run_spec(dataclasses.replace(BASE, seed=s, arrival_rate=r))
            for s, r in ((1, 2.0), (2, 9.0), (3, 17.0))]
    _both(runs)


def test_ensemble_threshold_grid_is_one_bucket():
    """A defer_green_factor grid reaches the graph only through the
    traced ``green_factor`` scalar (PolicyConfig.graph_key pins it), so
    the grid shares one compiled trajectory AND the factor still bites:
    factor 0 never defers, a huge factor defers inside the window."""
    cfg = dataclasses.replace(BASE, deferrable_frac=1.0)
    runs = [_run_spec(dataclasses.replace(
        cfg, policy=P.PolicyConfig(defer_green_factor=f)))
        for f in (0.0, 0.95, 100.0)]
    keys = {P.PolicyConfig(defer_green_factor=f).graph_key()
            for f in (0.0, 0.95, 100.0)}
    assert len(keys) == 1
    seq, ens = _both(runs)
    assert ens[0].jobs_deferred == 0
    assert ens[2].jobs_deferred > 0


def test_ensemble_slo_queue_caps_stay_semantic():
    """SLO members with different queue caps share a bucket (the cap is
    the traced ``q_cap`` scalar over a shared buffer width) and each lane
    keeps its own admission semantics."""
    cfg = dataclasses.replace(MIXED, outage=None, deferrable_frac=0.8)
    runs = [_run_spec(dataclasses.replace(
        cfg, policy=P.slo_deferral(10.0, queue_cap=q, deadline_hi=8)))
        for q in (1, 3, 0)]        # 0 -> sound bound (widest)
    _both(runs)


def test_ensemble_rejects_host_only_engines():
    cfg = dataclasses.replace(BASE, engine="blind")
    with pytest.raises(ValueError, match="scanned core"):
        simulate_fleet_ensemble([_run_spec(cfg)])


def test_sweep_policies_ensemble_matches_sequential_records():
    """The rewired sweep harness: ensemble=True and ensemble=False must
    produce identical records (same placements => same counters; f32
    emissions agree bitwise on the tested streams, else the sweep would
    not be a drop-in replacement)."""
    cfg = SimConfig(epochs=12, seed=0, arrival_rate=4.0,
                    mean_duration_h=3.0, deferrable_frac=0.5,
                    defer_max_h=4, history_h=24, horizon_h=6, shortlist=8)
    grid = {"reactive": P.REACTIVE,
            "slo": P.slo_deferral(deadline_hi=4),
            "slo_w2": P.slo_deferral(value_weight=2.0, deadline_hi=4)}
    a = sweep_policies(cfg, grid, n=16, seeds=(0, 1), chips_per_node=64,
                       region=0, ensemble=True)
    b = sweep_policies(cfg, grid, n=16, seeds=(0, 1), chips_per_node=64,
                       region=0, ensemble=False)
    assert a == b


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="sharding over E needs >1 device")
def test_ensemble_shard_over_devices_matches():
    runs = [_run_spec(dataclasses.replace(BASE, seed=s))
            for s in (1, 2, 3, 4)]
    seq = [simulate_fleet_scan(f, t, r, c, jobs=j, pad_plan=True)
           for f, t, r, c, j in runs]
    ens = simulate_fleet_ensemble(runs, shard=True)
    _assert_member_parity(seq, ens)


# ---------------------------------------------------------------------------
# Pallas kernel lanes in the batched ensemble
# ---------------------------------------------------------------------------


def test_ensemble_kernel_lanes_match_scan_driver_on_mixed_stream():
    """use_kernel=True members run the batched (stalled-lanes x node-tiles)
    Pallas sweep — one launch per round — and every lane must stay
    bit-identical to the per-lane scan driver running the sequential
    kernel (interpret mode on CPU): same digests, counters, sweep
    counts on the full mixed arrival/release/migration/deferral/
    eviction stream."""
    cfg = dataclasses.replace(MIXED, use_kernel=True, shortlist=16)
    runs = [_run_spec(dataclasses.replace(cfg, seed=s), n=64)
            for s in (11, 12)]
    seq, ens = _both(runs)
    digests = [hashlib.sha256(np.concatenate(
        [r.node_log, r.first_node]).tobytes()).hexdigest()[:16]
        for r in ens]
    want = [hashlib.sha256(np.concatenate(
        [r.node_log, r.first_node]).tobytes()).hexdigest()[:16]
        for r in seq]
    assert digests == want


def test_ensemble_kernel_lanes_thread_custom_energy():
    """Custom EnergyModel scalars + marginal weight reach the batched
    kernel's per-lane en blocks: kernel ensemble lanes still match the
    scan driver, and the marginal weight changes placements."""
    from repro.core.energy import EnergyModel
    from repro.core.ranking import RankWeights
    cfg = dataclasses.replace(
        BASE, epochs=12, use_kernel=True, shortlist=8,
        energy=EnergyModel(idle_frac=0.25, embodied_g_per_node_h=90.0),
        weights=RankWeights(marginal=0.2))
    runs = [_run_spec(dataclasses.replace(cfg, seed=s), n=48)
            for s in (3, 4)]
    seq, ens = _both(runs)
    plain = simulate_fleet_ensemble(
        [_run_spec(dataclasses.replace(
            cfg, seed=3, energy=EnergyModel(),
            weights=RankWeights()), n=48)])
    assert not np.array_equal(ens[0].node_log, plain[0].node_log)


# ---------------------------------------------------------------------------
# ("e", "n") node-axis sharding
# ---------------------------------------------------------------------------


def test_ensemble_shard_en_single_device_is_noop():
    """shard="en" on one device degenerates to the unsharded program —
    bit-identical results (the mesh helper returns a 1x1 mesh and
    _shard_over_e leaves the buffers alone)."""
    runs = [_run_spec(dataclasses.replace(BASE, seed=s)) for s in (1, 2)]
    plain = simulate_fleet_ensemble(runs)
    en = simulate_fleet_ensemble(runs, shard="en")
    _assert_member_parity(plain, en)


def test_ensemble_mesh_factors_devices():
    """ensemble_mesh splits devices ensemble-axis-first (communication-
    free), node axis takes the leftover factor; both axes stick to exact
    divisors."""
    from repro.distributed.sharding import ensemble_mesh
    devs = jax.devices() * 8          # fake an 8x device list
    m = ensemble_mesh(4, 1024, devs[:8])
    assert m.axis_names == ("e", "n")
    assert dict(zip(m.axis_names, m.devices.shape)) == {"e": 4, "n": 2}
    # E indivisible by anything > 1 -> everything goes to the node axis
    m = ensemble_mesh(3, 1024, devs[:4])
    assert m.devices.shape == (3, 1)
    m = ensemble_mesh(7, 1024, devs[:4])
    assert m.devices.shape == (1, 4)
    # single device: 1x1, callers treat as "don't shard"
    assert ensemble_mesh(4, 1024, devs[:1]).devices.size == 1


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="node-axis sharding needs >1 device")
def test_ensemble_shard_en_over_devices_matches():
    runs = [_run_spec(dataclasses.replace(BASE, seed=s), n=128)
            for s in (1, 2)]
    seq = [simulate_fleet_scan(f, t, r, c, jobs=j, pad_plan=True)
           for f, t, r, c, j in runs]
    ens = simulate_fleet_ensemble(runs, shard="en")
    _assert_member_parity(seq, ens)


# ---------------------------------------------------------------------------
# hypothesis: random grids keep per-lane equivalence
# ---------------------------------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       rates=st.lists(st.floats(1.0, 9.0), min_size=2, max_size=3),
       deferrable=st.floats(0.1, 1.0),
       slo=st.booleans(),
       budget=st.integers(0, 2))
def test_ensemble_matches_sequential_on_random_grids(seed, rates,
                                                     deferrable, slo,
                                                     budget):
    pcfg = P.slo_deferral(deadline_hi=5) if slo else P.REACTIVE
    runs = []
    for i, rate in enumerate(rates):
        cfg = dataclasses.replace(
            BASE, epochs=12, seed=seed + i, arrival_rate=rate,
            deferrable_frac=deferrable, migration_budget=budget,
            defer_max_h=4, history_h=24, horizon_h=6, policy=pcfg)
        runs.append(_run_spec(cfg, n=24, chips=32))
    _both(runs)
