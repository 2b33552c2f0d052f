"""Property + unit tests for the paper's core: Eq. 1 ranking, Eq. 2
accounting, forecasting, scenarios (the -85.68% headline), CPP projection."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import carbon, cpp, forecast, telemetry
from repro.core.ranking import RankWeights, maiz_ranking, rank_nodes
from repro.core.scenarios import run_paper_experiment

finite = st.floats(min_value=0.001, max_value=1e6, allow_nan=False)


# ---------------------------------------------------------------------------
# Eq. 2: CF = EC × PUE × CI
# ---------------------------------------------------------------------------


@settings(max_examples=200, deadline=None)
@given(ec=finite, pue=st.floats(1.0, 3.0), ci=st.floats(0.0, 2000.0))
def test_cf_formula_exact(ec, pue, ci):
    got = float(carbon.carbon_footprint(
        jnp.float64(ec) * 1.0, pue, ci))
    assert got == pytest.approx(ec * pue * ci, rel=1e-5)


@settings(max_examples=50, deadline=None)
@given(st.lists(st.floats(0.0, 1e4), min_size=2, max_size=48))
def test_emissions_linear_in_power(powers):
    p = jnp.asarray(powers, jnp.float32)
    ci = jnp.ones_like(p) * 300.0
    one = carbon.emissions_g(p, 1.2, ci)
    two = carbon.emissions_g(2 * p, 1.2, ci)
    assert float(two) == pytest.approx(2 * float(one), rel=1e-5, abs=1e-3)


def test_emissions_matches_hand_integral():
    power = jnp.asarray([1000.0, 2000.0])     # W for 1h each
    ci = jnp.asarray([100.0, 200.0])          # g/kWh
    got = float(carbon.emissions_g(power, 1.5, ci))
    assert got == pytest.approx(1.0 * 1.5 * 100 + 2.0 * 1.5 * 200)


# ---------------------------------------------------------------------------
# Eq. 1: MAIZ_RANKING
# ---------------------------------------------------------------------------


def _rand_terms(rng, n):
    return (jnp.asarray(rng.random(n) * 100),
            jnp.asarray(rng.random(n) * 100),
            jnp.asarray(rng.random(n)),
            jnp.asarray(rng.random(n)))


def test_ranking_prefers_lower_carbon(rng):
    cfp, fcfp, eff, sw = _rand_terms(rng, 32)
    # clone node 0 as node 1 but with strictly lower carbon terms
    cfp = cfp.at[1].set(cfp[0] * 0.5)
    fcfp = fcfp.at[1].set(fcfp[0] * 0.5)
    eff = eff.at[1].set(eff[0])
    sw = sw.at[1].set(sw[0])
    s = maiz_ranking(cfp, fcfp, eff, sw)
    assert float(s[1]) < float(s[0])


def test_ranking_prefers_higher_efficiency(rng):
    cfp, fcfp, eff, sw = _rand_terms(rng, 32)
    cfp = cfp.at[1].set(cfp[0]); fcfp = fcfp.at[1].set(fcfp[0])
    sw = sw.at[1].set(sw[0])
    eff = eff.at[1].set(eff[0] + 0.5)
    s = maiz_ranking(cfp, fcfp, eff, sw)
    assert float(s[1]) < float(s[0])


@settings(max_examples=50, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_ranking_scale_invariant_under_normalization(seed):
    rng = np.random.default_rng(seed)
    cfp, fcfp, eff, sw = _rand_terms(rng, 16)
    s1 = maiz_ranking(cfp, fcfp, eff, sw)
    s2 = maiz_ranking(cfp * 1000, fcfp * 1000, eff, sw)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)


def test_zero_weights_ignore_term(rng):
    cfp, fcfp, eff, sw = _rand_terms(rng, 16)
    w = RankWeights(w1=1.0, w2=0.0, w3=0.0, w4=0.0)
    s = maiz_ranking(cfp, fcfp, eff, sw, w)
    order, best = rank_nodes(s)
    assert int(best) == int(jnp.argmin(cfp))


def test_rank_nodes_excludes_invalid(rng):
    cfp, fcfp, eff, sw = _rand_terms(rng, 8)
    s = maiz_ranking(cfp, fcfp, eff, sw)
    valid = jnp.ones(8, bool).at[int(jnp.argmin(s))].set(False)
    _, best = rank_nodes(s, valid)
    assert bool(valid[int(best)])


# ---------------------------------------------------------------------------
# Forecast (FCFP)
# ---------------------------------------------------------------------------


def test_forecast_beats_persistence_on_average():
    skills = []
    for region in ("ES", "NL", "DE"):
        for t0 in (1800, 3500, 5200, 7000):
            ci = telemetry.hourly_ci(telemetry.REGIONS[region], hours=t0 + 48)
            skills.append(float(forecast.forecast_skill(
                jnp.asarray(ci[:t0]), jnp.asarray(ci[t0:t0 + 48]))))
    assert np.mean(skills) < 1.05


def test_forecast_shapes_and_positivity():
    ci = telemetry.hourly_ci(telemetry.REGIONS["DE"], hours=1000)
    fc, coef = forecast.fit_forecast(jnp.asarray(ci), 72)
    assert fc.shape == (72,)
    assert float(jnp.min(fc)) >= 0.0


@pytest.mark.parametrize("T", [3, 10, 23])
def test_forecast_short_history_stays_sane(T):
    """Histories under 24 h: no silent out-of-bounds residual gather, no
    near-collinear long-period harmonics — the forecast must stay within
    the neighborhood of the observed level, not blow up."""
    ci = telemetry.hourly_ci(telemetry.REGIONS["ES"], hours=T)
    fc, coef = forecast.fit_forecast(jnp.asarray(ci), 48)
    fc = np.asarray(fc)
    assert fc.shape == (48,)
    assert np.all(np.isfinite(fc))
    assert np.all(fc <= 3.0 * ci.max() + 1.0)
    # coef padded to the full basis width regardless of window support
    assert coef.shape == (1 + 2 * sum(forecast.HARMONICS),)


def test_forecast_constant_trace_is_constant():
    hist = jnp.full((100,), 321.0)
    fc, _ = forecast.fit_forecast(hist, 30)
    np.testing.assert_allclose(np.asarray(fc), 321.0, rtol=1e-4)


def test_forecast_horizon_beyond_one_day():
    """horizon > 24: the residual pattern recycles daily and decays."""
    ci = telemetry.hourly_ci(telemetry.REGIONS["NL"], hours=400)
    fc, _ = forecast.fit_forecast(jnp.asarray(ci), 120)
    fc = np.asarray(fc)
    assert fc.shape == (120,)
    assert np.all(np.isfinite(fc)) and np.all(fc >= 0.0)
    assert fc.max() < 3.0 * ci.max()


def test_forecast_skill_short_history_runs():
    ci = telemetry.hourly_ci(telemetry.REGIONS["DE"], hours=60)
    s = float(forecast.forecast_skill(jnp.asarray(ci[:12]),
                                      jnp.asarray(ci[12:36])))
    assert np.isfinite(s) and s > 0.0


# ---------------------------------------------------------------------------
# Scenarios: the paper's headline numbers
# ---------------------------------------------------------------------------


def test_scenario_c_reproduces_8568_percent():
    r = run_paper_experiment()
    assert r.reduction_pct["C"] == pytest.approx(85.68, abs=0.75)


def test_scenario_b_close_to_c_and_c_greener():
    """Paper: 'both scenarios B and C achieve similar reductions, C is more
    sustainable long-term.'"""
    r = run_paper_experiment()
    assert abs(r.reduction_pct["B"] - r.reduction_pct["C"]) < 3.0
    assert r.emissions_kg["C"] <= r.emissions_kg["B"]


def test_scenario_ordering_and_energy():
    r = run_paper_experiment()
    e = r.emissions_kg
    assert e["baseline"] > e["A"] > e["C"]          # shifting helps; off helps
    # A keeps every node on -> same energy as baseline; B/C power off 2 nodes
    assert r.energy_kwh["A"] == pytest.approx(r.energy_kwh["baseline"])
    assert r.energy_kwh["C"] < 0.5 * r.energy_kwh["baseline"]


def test_calibration_is_reentrant_and_leaves_regions_untouched():
    """calibrate_dip_depth threads candidate profiles through explicitly:
    the global REGIONS table is never mutated, even transiently."""
    import copy
    from repro.core.scenarios import calibrate_dip_depth
    before = copy.deepcopy(telemetry.REGIONS)
    d1 = calibrate_dip_depth(iters=3, hours=400)
    assert telemetry.REGIONS == before
    d2 = calibrate_dip_depth(iters=3, hours=400)   # reentrant: same answer
    assert d1 == d2
    assert 0.3 <= d1 <= 0.95


def test_traces_are_deterministic_and_calibrated():
    ci1, pue1 = telemetry.region_traces(hours=500)
    ci2, pue2 = telemetry.region_traces(hours=500)
    np.testing.assert_array_equal(ci1, ci2)
    full, _ = telemetry.region_traces()
    means = full.mean(axis=1)
    # ES (solar-rich, dips) lands below its 256 mean; NL/DE near theirs
    assert means[0] < 256
    assert means[1] == pytest.approx(386, rel=0.12)
    assert means[2] == pytest.approx(385, rel=0.12)


def test_power_trace_20s_sampling():
    node = telemetry.NodePower()
    util = np.array([0.0, 0.5, 1.0])
    on = np.array([1.0, 1.0, 0.0])
    p = telemetry.power_trace_20s(node, util, on)
    assert p.shape == (3 * 180,)
    kwh = telemetry.hourly_energy_kwh(p)
    assert kwh[2] == 0.0
    assert kwh[0] == pytest.approx(20 * 250 / 1000, rel=1e-6)
    assert kwh[1] == pytest.approx(20 * 325 / 1000, rel=0.05)


# ---------------------------------------------------------------------------
# CPP / EU-taxonomy projection (paper §5 arithmetic)
# ---------------------------------------------------------------------------


def test_projection_matches_paper_numbers():
    p = cpp.eu_taxonomy_projection()
    assert p.units_required == 27_686_054
    assert p.trees_equivalent == pytest.approx(90e6, rel=1e-6)
    assert p.cars_equivalent == pytest.approx(2.44e6, rel=1e-6)
    assert p.eco_costs_eur["human_health"] == pytest.approx(3.0e9)
    assert p.eco_costs_eur["eco_toxicity"] == pytest.approx(4.65e9)
    assert p.eco_costs_eur["carbon_footprint"] == pytest.approx(2.63e9)


def test_cpp_score():
    assert cpp.cpp_score(100.0, 20.0, 4.0) == pytest.approx(20.0)
