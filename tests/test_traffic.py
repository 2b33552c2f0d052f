"""Sub-epoch traffic subsystem: router host-vs-scan bit-exact parity on
mixed streams, M/M/c queueing-model monotonicity (property-based),
routing conservation (routed == offered == req stream; per-tenant request
gCO2 sums to the fleet serving total), zero-QPS streams as bitwise no-ops
against the PR 7 golden digests, and the one-compiled-bucket guarantee
for a (latency-SLO x router-greenness) grid."""
import dataclasses
import hashlib

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import router
from repro.core.policy import PolicyConfig
from repro.core.simulator import (SimConfig, _bucket_key, _prepare_scan_run,
                                  generate_jobs, simulate_fleet,
                                  simulate_fleet_ensemble,
                                  simulate_fleet_scan,
                                  synthetic_lifecycle_fleet)
from repro.core.traffic import (REQ_CAP, TrafficConfig, plan_traffic,
                                traffic_graph_key, validate_qps_weights)

BASE = SimConfig(epochs=24, seed=3, arrival_rate=6.0, mean_duration_h=6.0,
                 shortlist=16, history_h=48, horizon_h=8)
MIXED = SimConfig(epochs=36, seed=11, arrival_rate=8.0, mean_duration_h=10.0,
                  shortlist=32, history_h=48, horizon_h=12,
                  migration_budget=2, deferrable_frac=0.3,
                  outage=(0, 12, 6), flash_crowd=(20, 3, 2.5))
TRAFFIC = TrafficConfig(req_rate=20000.0, n_svc=4, flash_rate=0.05,
                        mu_per_chip=0.1)
# a saturated stream: ~75% chip occupancy forces serving replicas across
# carbon classes so the greenness blend actually redistributes load
DENSE = SimConfig(epochs=24, seed=3, arrival_rate=16.0,
                  mean_duration_h=10.0, shortlist=16, history_h=48,
                  horizon_h=8, chips_lo=8, chips_hi=32)


def _with_traffic(cfg, tcfg=TRAFFIC, **pol):
    policy = dataclasses.replace(cfg.policy, **pol) if pol else cfg.policy
    return dataclasses.replace(cfg, traffic=tcfg, policy=policy)


def _run_both(cfg, n=96, chips=64, jobs=None):
    fleet, traces, ridx = synthetic_lifecycle_fleet(n, cfg,
                                                    chips_per_node=chips)
    jobs = jobs if jobs is not None else generate_jobs(cfg)
    host = simulate_fleet(fleet, traces, ridx, cfg, jobs=jobs)
    scan = simulate_fleet_scan(fleet, traces, ridx, cfg, jobs=jobs)
    return host, scan


def _digest(res):
    return hashlib.sha256(np.concatenate(
        [res.node_log, res.first_node]).tobytes()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# traffic plan: seeded, traced data, zero-rate no-op
# ---------------------------------------------------------------------------


def test_plan_traffic_seeded_and_capped():
    tc = TrafficConfig(req_rate=500.0, flash_rate=0.1, noise_sigma=0.2)
    a = plan_traffic(tc, 48, 7)
    b = plan_traffic(tc, 48, 7)
    np.testing.assert_array_equal(a.req, b.req)
    assert a.req.dtype == np.int32
    assert a.req.min() >= 0 and a.req.max() <= REQ_CAP
    c = plan_traffic(tc, 48, 8)
    assert not np.array_equal(a.req, c.req)


def test_zero_rate_plan_is_all_zero():
    tc = TrafficConfig(req_rate=0.0, flash_rate=0.5, noise_sigma=1.0)
    assert int(plan_traffic(tc, 64, 3).req.sum()) == 0


def test_graph_key_only_carries_service_count():
    assert traffic_graph_key(None) == 0
    a = TrafficConfig(req_rate=100.0, n_svc=3)
    b = TrafficConfig(req_rate=9999.0, n_svc=3, flash_rate=0.4,
                      serve_frac=0.9, mu_per_chip=7.0)
    assert traffic_graph_key(a) == traffic_graph_key(b) == 3


def test_validate_qps_weights():
    with pytest.raises(ValueError):
        validate_qps_weights(None)
    with pytest.raises(ValueError):
        validate_qps_weights(np.full(40000, 1, np.int32))
    validate_qps_weights(np.ones(8, np.int32))


# ---------------------------------------------------------------------------
# M/M/c queueing model
# ---------------------------------------------------------------------------


@settings(max_examples=50, deadline=None)
@given(c=st.integers(1, 64), mu=st.floats(0.05, 5.0),
       util=st.floats(0.01, 0.95))
def test_mmc_p99_monotone_in_load_and_chips(c, mu, util):
    lam = util * c * mu
    lo = float(router.mmc_p99(c, mu, lam * 0.5))
    hi = float(router.mmc_p99(c, mu, lam))
    assert hi >= lo
    assert lo >= 1.0 / mu - 1e-9               # never below service time
    assert hi >= float(router.mmc_p50(c, mu, lam))
    # more chips at the same offered load never hurts
    assert float(router.mmc_p99(c + 1, mu, lam)) <= hi + 1e-9


@settings(max_examples=50, deadline=None)
@given(c=st.integers(1, 64), mu=st.floats(0.05, 5.0),
       slo_mult=st.floats(1.05, 20.0))
def test_lambda_caps_feasible_and_monotone(c, mu, slo_mult):
    slo = slo_mult / mu
    caps = router.lambda_caps(c, mu, slo)
    assert caps.shape == (c + 1,) and caps.dtype == np.int32
    assert caps[0] == 0
    assert np.all(np.diff(caps) >= 0)          # more chips, more capacity
    # the cap actually meets the SLO under the same model
    if caps[c] > 0:
        p99 = float(router.mmc_p99(c, mu, caps[c] / 3600.0))
        assert p99 <= slo * (1.0 + 1e-6)


def test_lambda_caps_infeasible_slo_is_zero():
    # SLO below the bare service time: no rate is feasible
    caps = router.lambda_caps(16, 1.0, 0.5)
    assert int(caps.sum()) == 0


def test_erlang_c_known_value():
    # M/M/1: C(1, a) == a (textbook identity)
    for a in (0.1, 0.5, 0.9):
        assert abs(float(router.erlang_c(1, a)) - a) < 1e-12


# ---------------------------------------------------------------------------
# route_epoch semantics (host reference)
# ---------------------------------------------------------------------------


def test_route_epoch_greenness_extremes():
    svc = np.zeros(4, np.int32)
    jid = np.arange(4, dtype=np.int32)
    w = np.ones(4, np.int32)
    cap = np.full(4, 100, np.int32)
    carbon = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
    r0, o0 = router.route_epoch(np, req_t=np.int32(200), svc=svc, jid=jid,
                                weight=w, cap=cap, carbon=carbon, n_svc=1,
                                greenness=np.float32(0.0))
    np.testing.assert_array_equal(r0, [50, 50, 50, 50])   # even split
    r1, _ = router.route_epoch(np, req_t=np.int32(200), svc=svc, jid=jid,
                               weight=w, cap=cap, carbon=carbon, n_svc=1,
                               greenness=np.float32(1.0))
    np.testing.assert_array_equal(r1, [100, 100, 0, 0])   # water-fill
    assert int(o0[0]) == 200 and int(o0[1]) == 0


def test_route_epoch_blend_respects_caps():
    # the green share fills RESIDUAL capacity: no lane exceeds its cap
    # from the blend itself (only the carbon-blind even baseline can)
    svc = np.zeros(4, np.int32)
    jid = np.arange(4, dtype=np.int32)
    w = np.ones(4, np.int32)
    cap = np.full(4, 100, np.int32)
    carbon = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
    r, _ = router.route_epoch(np, req_t=np.int32(200), svc=svc, jid=jid,
                              weight=w, cap=cap, carbon=carbon, n_svc=1,
                              greenness=np.float32(0.5))
    assert int(r.sum()) == 200
    assert np.all(r <= cap)


def test_route_epoch_overload_spills_to_greenest_feasible():
    svc = np.zeros(3, np.int32)
    jid = np.arange(3, dtype=np.int32)
    w = np.ones(3, np.int32)
    cap = np.array([0, 10, 10], np.int32)      # lane 0 infeasible
    carbon = np.array([1.0, 2.0, 3.0], np.float32)
    r, _ = router.route_epoch(np, req_t=np.int32(100), svc=svc, jid=jid,
                              weight=w, cap=cap, carbon=carbon, n_svc=1,
                              greenness=np.float32(1.0))
    assert int(r.sum()) == 100
    assert int(r[1]) == 90                      # greenest FEASIBLE lane
    assert int(r[0]) == 0


def test_route_epoch_weighted_offered_split():
    svc = np.array([0, 0, 1, 1], np.int32)
    jid = np.arange(4, dtype=np.int32)
    w = np.array([3, 3, 1, 1], np.int32)
    cap = np.full(4, 10**6, np.int32)
    carbon = np.ones(4, np.float32)
    _, o = router.route_epoch(np, req_t=np.int32(800), svc=svc, jid=jid,
                              weight=w, cap=cap, carbon=carbon, n_svc=2,
                              greenness=np.float32(1.0))
    assert int(o[0]) == 600 and int(o[1]) == 200
    assert int(o[:2].sum()) == 800


def test_route_epoch_no_active_lanes():
    svc = np.full(3, -1, np.int32)
    r, o = router.route_epoch(np, req_t=np.int32(500), svc=svc,
                              jid=np.arange(3, dtype=np.int32),
                              weight=np.zeros(3, np.int32),
                              cap=np.zeros(3, np.int32),
                              carbon=np.zeros(3, np.float32), n_svc=2,
                              greenness=np.float32(1.0))
    assert int(r.sum()) == 0 and int(o.sum()) == 0


# ---------------------------------------------------------------------------
# host-vs-scan parity on mixed streams
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [BASE, MIXED, DENSE],
                         ids=["base", "mixed", "dense"])
def test_traffic_parity_host_vs_scan(cfg):
    """Request counters and routing decisions are BIT-EXACT between the
    f64 host loop and the f32 scanned core; the float request metrics
    match to the emissions tolerance."""
    cfg = _with_traffic(cfg, router_slo_s=12.0, router_greenness=0.75)
    host, scan = _run_both(cfg)
    assert host.req_served == scan.req_served > 0
    assert host.req_offered == scan.req_offered
    assert host.p99_violations == scan.p99_violations
    np.testing.assert_allclose(scan.req_gco2, host.req_gco2, rtol=1e-4)
    np.testing.assert_allclose(scan.req_p99_s, host.req_p99_s, rtol=1e-3)
    assert _digest(host) == _digest(scan)


def test_traffic_parity_under_faults():
    """Routing decisions read the OBSERVED (degraded) CI and stay
    bit-exact across drivers; accounting reads ground truth."""
    from repro.core.faults import FaultConfig
    cfg = _with_traffic(dataclasses.replace(
        MIXED, faults=FaultConfig(ci_dropout=0.2, telem_sigma=0.1)))
    host, scan = _run_both(cfg)
    assert host.req_served == scan.req_served > 0
    assert host.p99_violations == scan.p99_violations
    np.testing.assert_allclose(scan.req_gco2, host.req_gco2, rtol=1e-4)


# ---------------------------------------------------------------------------
# conservation
# ---------------------------------------------------------------------------


def test_request_conservation_and_tenant_attribution():
    cfg = _with_traffic(dataclasses.replace(DENSE, n_tenants=3),
                        router_slo_s=12.0, router_greenness=1.0)
    host, scan = _run_both(cfg, n=48)
    tplan = plan_traffic(cfg.traffic, cfg.epochs, cfg.seed)
    # every offered request is routed somewhere (spill guarantees it
    # whenever the service has >= 1 active replica)
    assert host.req_served == host.req_offered
    # the offered stream is the traffic plan (weights always > 0 here
    # because the saturated stream keeps every service populated)
    assert host.req_offered == int(tplan.req.sum())
    for r in (host, scan):
        assert r.tenant_request_g is not None
        assert r.tenant_request_g.shape == (4,)
        assert r.tenant_request_g[-1] == 0.0   # spare bin structurally 0
        np.testing.assert_allclose(r.tenant_request_g.sum(), r.req_gco2,
                                   rtol=1e-5)
    # request carbon is an attribution slice, never added to emissions
    base_host, _ = _run_both(dataclasses.replace(cfg, traffic=None), n=48)
    assert host.emissions_g == base_host.emissions_g


# ---------------------------------------------------------------------------
# zero-QPS == bitwise no-op vs the PR 7 goldens
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg,digest", [
    (BASE, "0141b64da0651227"),
    (MIXED, "0e6437d00c3ba558"),
])
def test_zero_qps_reproduces_golden_digests(cfg, digest):
    """A configured-but-silent traffic layer (req_rate == 0) must leave
    the placement trajectory bitwise identical on BOTH drivers, and so
    must traffic=None."""
    zero = TrafficConfig(req_rate=0.0, n_svc=2)
    for c in (cfg, _with_traffic(cfg, zero)):
        host, scan = _run_both(c)
        assert _digest(host) == digest
        assert _digest(scan) == digest
    host, _ = _run_both(_with_traffic(cfg, zero))
    assert host.req_served == host.req_offered == 0
    assert host.req_gco2 == 0.0 and host.p99_violations == 0


def test_serving_trajectory_placement_invariant():
    """The router never feeds back into placement: a LOUD traffic layer
    also preserves the golden digest (capacity is shared by
    construction — replicas serve on the chips placement allocated)."""
    host, scan = _run_both(_with_traffic(BASE))
    assert _digest(host) == "0141b64da0651227"
    assert _digest(scan) == "0141b64da0651227"


# ---------------------------------------------------------------------------
# one compiled bucket for the (slo x greenness) grid + frontier shape
# ---------------------------------------------------------------------------


def test_slo_greenness_grid_shares_one_bucket():
    fleet, traces, ridx = synthetic_lifecycle_fleet(48, DENSE,
                                                    chips_per_node=64)
    keys = set()
    runs = []
    for slo in (10.5, 12.0, 18.0):
        for g in (0.0, 0.5, 1.0):
            cfg = _with_traffic(DENSE, router_slo_s=slo,
                                router_greenness=g)
            runs.append((fleet, traces, ridx, cfg))
            keys.add(_bucket_key(_prepare_scan_run(fleet, traces, ridx,
                                                   cfg, pad_plan=True)))
    assert len(keys) == 1
    res = simulate_fleet_ensemble(runs)
    # ensemble members match the solo scan bit-exactly on the counters
    solo = simulate_fleet_scan(*runs[4])
    assert (res[4].req_served, res[4].req_offered,
            res[4].p99_violations) == \
           (solo.req_served, solo.req_offered, solo.p99_violations)
    # greenness monotonically trades carbon against modeled p99 at a
    # fixed SLO (the Pareto frontier the serving bench gates on)
    by_g = {g: r for (_, _, _, c), r in zip(runs, res)
            if c.policy.router_slo_s == 12.0
            for g in [c.policy.router_greenness]}
    gpr = {g: r.req_gco2 / max(r.req_served, 1) for g, r in by_g.items()}
    assert gpr[1.0] < gpr[0.5] < gpr[0.0]
