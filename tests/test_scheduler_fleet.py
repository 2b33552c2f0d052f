"""Scheduler/fleet properties: capacity safety, ranking-greedy placement,
scenario allocation invariants (hypothesis)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.fleet import synthetic_fleet
from repro.core.scheduler import SCENARIOS, place_jobs
from repro.core import telemetry


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000),
       n_jobs=st.integers(1, 12),
       n_nodes=st.integers(4, 64))
def test_placement_respects_capacity(seed, n_jobs, n_nodes):
    rng = np.random.default_rng(seed)
    fleet = synthetic_fleet(n_nodes, seed=seed)
    demands = jnp.asarray(rng.integers(1, 128, n_jobs), jnp.int32)
    pl = place_jobs(fleet, demands)
    nodes = np.asarray(pl.node)
    used = np.zeros(n_nodes)
    for j, nd in enumerate(nodes):
        if nd >= 0:
            used[nd] += int(demands[j])
    assert np.all(used <= np.asarray(fleet.capacity) + 1e-6)


def test_placement_prefers_best_ranked_node():
    fleet = synthetic_fleet(32, seed=7)
    scores = np.asarray(fleet.rank())
    cap = np.asarray(fleet.capacity)
    demand = 1
    feasible = np.where(cap >= demand)[0]
    best = feasible[np.argmin(scores[feasible])]
    pl = place_jobs(fleet, jnp.asarray([demand], jnp.int32))
    assert int(pl.node[0]) == int(best)


def test_oversized_job_unplaceable():
    fleet = synthetic_fleet(8, seed=1)
    pl = place_jobs(fleet, jnp.asarray([10_000], jnp.int32))
    assert int(pl.node[0]) == -1


def test_unhealthy_nodes_never_chosen():
    fleet = synthetic_fleet(64, seed=3)
    sick = ~np.asarray(fleet.healthy)
    if not sick.any():
        pytest.skip("no sick nodes in this fleet draw")
    pl = place_jobs(fleet, jnp.asarray([1] * 16, jnp.int32))
    for nd in np.asarray(pl.node):
        if nd >= 0:
            assert bool(fleet.healthy[nd])


@settings(max_examples=20, deadline=None)
@given(demand=st.floats(0.1, 3.0), hours=st.integers(24, 240))
def test_scenario_allocations_conserve_demand(demand, hours):
    ci, pue = telemetry.region_traces(hours=hours)
    for name, alloc in SCENARIOS.items():
        util, on = alloc(ci, pue, demand)
        # total dynamic demand preserved each hour
        np.testing.assert_allclose(util.sum(0), demand, rtol=1e-9)
        # work only lands on powered nodes
        assert np.all(util[on == 0.0] == 0.0)
        if name in ("B", "C"):
            assert np.all(on.sum(0) == 1.0)       # exactly one node on
        else:
            assert np.all(on == 1.0)


def test_scenario_c_tracks_best_effective_rate():
    ci, pue = telemetry.region_traces(hours=100)
    util, on = SCENARIOS["C"](ci, pue, 1.0)
    eff = ci * pue[:, None]
    chosen = util.argmax(axis=0)
    np.testing.assert_array_equal(chosen, eff.argmin(axis=0))
