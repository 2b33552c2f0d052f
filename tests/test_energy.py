"""Unified EnergyModel: default-model golden-digest parity with the PR 6
baselines on both drivers, marginal-weight-0 bit-identity with the
historical total-CFP ranking, per-tenant attribution conservation
(host and scan), embodied-amortization monotonicity, the
one-compiled-bucket guarantee for an (idle x embodied x marginal)
calibration grid, and workload-calibrated power sanity."""
import dataclasses
import hashlib

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES
from repro.core.energy import DEFAULT_ENERGY, EnergyModel
from repro.core.fleet import synthetic_fleet
from repro.core.ranking import RankWeights, marginal_cfp
from repro.core.scheduler import place_jobs
from repro.core.simulator import (SimConfig, generate_jobs, simulate_fleet,
                                  simulate_fleet_scan,
                                  synthetic_lifecycle_fleet)

BASE = SimConfig(epochs=24, seed=3, arrival_rate=6.0, mean_duration_h=6.0,
                 shortlist=16, history_h=48, horizon_h=8)
MIXED = SimConfig(epochs=36, seed=11, arrival_rate=8.0, mean_duration_h=10.0,
                  shortlist=32, history_h=48, horizon_h=12,
                  migration_budget=2, deferrable_frac=0.3,
                  outage=(0, 12, 6), flash_crowd=(20, 3, 2.5))


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
# default model == historical constants, bit for bit
# ---------------------------------------------------------------------------


def test_default_model_matches_historical_constants():
    em = EnergyModel()
    assert em.e_kwh_h == 0.30625
    assert em.chip_kw == 0.25
    assert em.watts_per_chip == 306.25
    assert em.dyn_frac == 1.0 - 0.35
    from repro.core.carbon import job_energy_kwh
    for args in [(3600.0, 1, 1), (0.25 * 3600.0, 1, 64), (12.5, 800, 8)]:
        assert em.job_energy_kwh(*args) == job_energy_kwh(*args)


@pytest.mark.parametrize("cfg,digest", [
    (BASE, "0141b64da0651227"),
    (MIXED, "0e6437d00c3ba558"),
])
def test_explicit_default_energy_reproduces_golden_digests(cfg, digest):
    """An explicitly-passed default EnergyModel is bitwise the implicit
    one on BOTH drivers — the PR 4/6 trajectory digests are unchanged."""
    cfg = dataclasses.replace(cfg, energy=EnergyModel())
    host, scan = _run_both(cfg)
    assert _digest(host) == digest
    assert _digest(scan) == digest
    np.testing.assert_array_equal(host.node_log, scan.node_log)
    assert scan.emissions_g == pytest.approx(host.emissions_g, rel=1e-4)


def test_marginal_weight_zero_is_bit_identical():
    """Threading a traced default EnergyModel (marginal term present at
    weight 0) through the placement engines leaves scores and placements
    bitwise unchanged vs the energy=None historical path."""
    fleet = synthetic_fleet(512, seed=7)
    demands = jnp.asarray(np.random.default_rng(0).integers(1, 64, 128),
                          jnp.int32)
    for engine in ("shortlist", "full"):
        ref = place_jobs(fleet, demands, engine=engine)
        out = place_jobs(fleet, demands, engine=engine,
                         energy=DEFAULT_ENERGY.device())
        np.testing.assert_array_equal(np.asarray(ref.node),
                                      np.asarray(out.node))
        np.testing.assert_array_equal(
            np.asarray(ref.scores).view(np.int32),
            np.asarray(out.scores).view(np.int32))


def test_marginal_term_prefers_on_nodes():
    """With a positive marginal weight, the Eq. 1 variant charges waking
    an empty node its idle floor + embodied carbon, so placement shifts
    toward already-on nodes (the consolidation the SCHEDULE_WEIGHT bonus
    only approximates)."""
    cfp = jnp.asarray([100.0, 100.0], jnp.float32)
    chips = jnp.asarray([64, 64], jnp.int32)
    is_off = jnp.asarray([False, True])
    m = marginal_cfp(cfp, chips, 0.35, 0.65, is_off, embodied_g_h=50.0)
    assert float(m[0]) < float(m[1])       # on-node dynamic share wins
    # weight 0 never changes a ranking graph bucket
    assert RankWeights(marginal=0.4).graph_key() == RankWeights()


# ---------------------------------------------------------------------------
# per-tenant attribution
# ---------------------------------------------------------------------------


def test_tenant_attribution_conserves_host_and_scan():
    cfg = dataclasses.replace(MIXED, n_tenants=4)
    host, scan = _run_both(cfg)
    for res in (host, scan):
        assert res.tenant_emissions_g is not None
        assert res.tenant_emissions_g.shape == (5,)
    # host accounts in f64: conservation is exact to rounding
    np.testing.assert_allclose(host.tenant_emissions_g.sum(),
                               host.emissions_g, rtol=1e-12)
    # scan folds f32 per-epoch bins; same conservation to f32 tolerance
    np.testing.assert_allclose(scan.tenant_emissions_g.sum(),
                               scan.emissions_g, rtol=1e-5)
    # the idle-remainder bin is ~0 on this fully-occupied stream, so it
    # only carries accumulated rounding — compare with a total-scaled atol
    np.testing.assert_allclose(scan.tenant_emissions_g,
                               host.tenant_emissions_g, rtol=1e-3,
                               atol=1e-7 * host.emissions_g)
    # tenants run real jobs on this stream: every per-tenant bin is
    # positive and the idle remainder is nonnegative up to rounding
    assert (host.tenant_emissions_g[:-1] > 0).all()
    assert host.tenant_emissions_g[-1] >= -1e-9 * host.emissions_g


def test_tenant_column_required():
    cfg = dataclasses.replace(BASE, n_tenants=3)
    jobs = generate_jobs(BASE)            # drawn without tenants
    fleet, traces, ridx = synthetic_lifecycle_fleet(32, cfg,
                                                    chips_per_node=64)
    with pytest.raises(ValueError, match="tenant"):
        simulate_fleet(fleet, traces, ridx, cfg, jobs=jobs)
    with pytest.raises(ValueError, match="tenant"):
        simulate_fleet_scan(fleet, traces, ridx, cfg, jobs=jobs)


# ---------------------------------------------------------------------------
# embodied amortization
# ---------------------------------------------------------------------------

_TINY = SimConfig(epochs=8, seed=5, arrival_rate=2.0, mean_duration_h=4.0,
                  history_h=24, horizon_h=4)
_TINY_BASELINE = {}


def _tiny_emissions(embodied):
    key = float(embodied)
    if key not in _TINY_BASELINE:
        cfg = dataclasses.replace(
            _TINY, energy=EnergyModel(embodied_g_per_node_h=key))
        host, scan = _run_both(cfg, n=24, chips=32)
        assert scan.emissions_g == pytest.approx(host.emissions_g,
                                                 rel=1e-4)
        _TINY_BASELINE[key] = host.emissions_g
    return _TINY_BASELINE[key]


@settings(max_examples=8, deadline=None)
@given(e=st.floats(0.0, 200.0))
def test_embodied_amortization_monotone_in_node_on_hours(e):
    """Embodied carbon amortizes per node-ON-hour: with placements
    invariant (the term does not enter ranking at marginal weight 0),
    emissions grow EXACTLY linearly — slope = total node-on-hours — and
    hence monotonically in the embodied rate."""
    base = _tiny_emissions(0.0)
    on_hours = 24 * _TINY.epochs          # power_off_idle=False: all on
    got = _tiny_emissions(e)
    assert got == pytest.approx(base + e * on_hours, rel=1e-9)
    assert got >= base


# ---------------------------------------------------------------------------
# one compiled bucket for a calibration grid
# ---------------------------------------------------------------------------


def test_energy_grid_shares_one_ensemble_bucket():
    """An (idle-frac x embodied x marginal-weight) calibration grid rides
    entirely through traced data: every member hashes to the SAME
    ensemble graph bucket as the default config."""
    from repro.core.simulator import _bucket_key, _prepare_scan_run

    def key(cfg):
        fleet, traces, ridx = synthetic_lifecycle_fleet(
            32, cfg, chips_per_node=64)
        return _bucket_key(_prepare_scan_run(fleet, traces, ridx, cfg,
                                             generate_jobs(cfg)))

    ref = key(BASE)
    grid = [
        dataclasses.replace(BASE, energy=EnergyModel(idle_frac=i,
                                                     embodied_g_per_node_h=g),
                            weights=RankWeights(marginal=m))
        for i in (0.2, 0.35) for g in (0.0, 120.0) for m in (0.0, 0.3)
    ]
    assert all(key(cfg) == ref for cfg in grid)
    # ... and a migration-overhead grid too (the checkpoint cost is
    # traced data now, not a graph constant)
    assert key(dataclasses.replace(BASE, migration_overhead_h=0.7)) == ref


def test_kernel_path_threads_custom_energy():
    """Custom EnergyModel scalars + a nonzero marginal weight now flow
    into the Pallas sweep (the en_* SMEM block) instead of raising — and
    both drivers run the SAME kernel, so host vs scan trajectories stay
    bit-identical on placements."""
    cfg = dataclasses.replace(
        BASE, epochs=12, use_kernel=True, shortlist=8,
        energy=EnergyModel(idle_frac=0.25, embodied_g_per_node_h=90.0),
        weights=RankWeights(marginal=0.2))
    host, scan = _run_both(cfg, n=48, chips=64)
    np.testing.assert_array_equal(host.node_log, scan.node_log)
    np.testing.assert_array_equal(host.first_node, scan.first_node)
    assert scan.emissions_g == pytest.approx(host.emissions_g, rel=1e-4)
    # ... and the marginal weight genuinely reaches the kernel score: the
    # same stream placed with marginal=0 diverges
    base = _run_both(dataclasses.replace(cfg, weights=RankWeights()))[0]
    assert not np.array_equal(host.node_log, base.node_log)


# ---------------------------------------------------------------------------
# workload calibration
# ---------------------------------------------------------------------------


def test_workload_calibration_spans_configs():
    """Roofline-calibrated chip power stays inside [floor, 1] x nameplate
    and actually differentiates the assigned configs: a compute-bound
    train step draws more than a bandwidth-bound decode step."""
    em = EnergyModel()
    for arch in ARCHS.values():
        for shape in SHAPES.values():
            cal = em.for_workload(arch, shape)
            assert 0.3 * em.chip_power_w <= cal.chip_power_w \
                <= em.chip_power_w
    # attention-free mamba decode is bandwidth-bound (weight passes per
    # token) while its train step is compute-bound — distinct draws;
    # full-attention models stay compute-bound at 32k (quadratic term)
    train = em.for_workload(ARCHS["falcon-mamba-7b"], SHAPES["train_4k"])
    decode = em.for_workload(ARCHS["falcon-mamba-7b"], SHAPES["decode_32k"])
    assert train.chip_power_w > decode.chip_power_w
