"""The program's own instrumentation: the shortlist engines' walk counters
(``placement.WALK_COUNTS``), the batched engine's sweep rounds, and the
compiled simulator drivers' host spans and named device scopes.  None of
it may change a placement, a score or a sweep count."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import placement, scheduler
from repro.core.fleet import synthetic_fleet
from repro.core.simulator import (SimConfig, generate_jobs, program_texts,
                                  simulate_fleet_ensemble,
                                  simulate_fleet_scan,
                                  synthetic_lifecycle_fleet)
from test_placement import _random_event_stream

PROGRAM_SPANS = ("plan_build", "dispatch", "device_wait", "readback",
                 "result_assembly")


def _check_partition(r, demands):
    """Causes 1-3 are the sweeps; hits + sweeps + arrivals left unplaced
    without a sweep = arrivals."""
    wc = np.asarray(r.walk_counts)
    assert wc.shape == (4,) and wc.dtype == np.int32 and (wc >= 0).all()
    assert int(wc[1:].sum()) == int(r.n_sweeps)
    d = np.asarray(demands)
    node = np.asarray(r.node)
    arrivals = int((d > 0).sum())
    unplaced = int(((d > 0) & (node < 0)).sum())
    assert 0 <= arrivals - int(wc.sum()) <= unplaced
    return wc


@pytest.mark.parametrize("n,shortlist", [(7, 2), (64, 2), (64, 8),
                                         (1000, 32)])
def test_walk_counts_partition_sweeps_and_arrivals(n, shortlist):
    fleet = synthetic_fleet(n, seed=n + 1)
    rng = np.random.default_rng(n * 31 + shortlist)
    demands, nodes = _random_event_stream(fleet, rng, 96)
    d = jnp.asarray(demands, jnp.int32)
    v = jnp.asarray(nodes, jnp.int32)
    a = placement.place_lifecycle_shortlist(fleet, d, v, shortlist=shortlist)
    b = placement.place_lifecycle_full_rerank(fleet, d, v)
    np.testing.assert_array_equal(np.asarray(a.node), np.asarray(b.node))
    _check_partition(a, demands)
    assert b.walk_counts is None            # the oracle has no shortlist
    p = scheduler.place_events_jit(fleet, d, v, engine="shortlist",
                                   shortlist=shortlist)
    np.testing.assert_array_equal(np.asarray(p.walk_counts),
                                  np.asarray(a.walk_counts))


def test_walk_counts_see_every_cause():
    """Releases outside the shortlist (dirty), demands the shortlist
    cannot hold (no room), landings that lift the shortlist's scores above
    the bound (bound), and plain hits, in one stream; the eager first
    sweep counts as dirty."""
    fleet = synthetic_fleet(64, seed=65)
    rng = np.random.default_rng(64 * 31 + 2)
    demands, nodes = _random_event_stream(fleet, rng, 400, max_d=200)
    r = placement.place_lifecycle_shortlist(
        fleet, jnp.asarray(demands, jnp.int32),
        jnp.asarray(nodes, jnp.int32), shortlist=2)
    wc = _check_partition(r, demands)
    assert (wc > 0).all(), wc
    arr = jnp.asarray(rng.integers(1, 64, 48), jnp.int32)
    e = placement.place_lifecycle_shortlist(
        fleet, arr, jnp.full((48,), -1, jnp.int32), shortlist=4,
        eager_sweep=True)
    ewc = _check_partition(e, arr)
    assert ewc[1] == 1                      # only the first sweep is dirty


def _lanes(L, n=96, E=40, seed=0):
    rng = np.random.default_rng(seed)
    fleets = [synthetic_fleet(n, seed=seed + i) for i in range(L)]
    fleet = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *fleets)
    dem = rng.integers(1, 160, (L, E)).astype(np.int32)
    n_ev = rng.integers(E // 2, E + 1, L).astype(np.int32)
    return fleets, fleet, jnp.asarray(dem), jnp.asarray(n_ev)


@pytest.mark.parametrize("L", [1, 3])
def test_batched_walk_counts_match_the_sequential_engine(L):
    fleets, fleet, dem, n_ev = _lanes(L, seed=7 * L)
    node, cap, sweeps, wc, rounds = placement.place_lifecycle_batched(
        fleet, dem, shortlist=4, n_events=n_ev)
    wc = np.asarray(wc)
    assert wc.shape == (L, 4)
    for l, f in enumerate(fleets):
        r = placement.place_lifecycle_shortlist(
            f, dem[l], jnp.full(dem.shape[1:], -1, jnp.int32), shortlist=4,
            n_events=n_ev[l], eager_sweep=True)
        np.testing.assert_array_equal(np.asarray(node[l]),
                                      np.asarray(r.node))
        np.testing.assert_array_equal(wc[l], np.asarray(r.walk_counts))
        assert int(sweeps[l]) == int(r.n_sweeps) == int(wc[l, 1:].sum())
    # each round is one batched launch over all lanes: at least as many as
    # the busiest lane's sweeps, and at most one more, the last round of
    # the call, which sweeps whether or not a lane stalled
    rounds = int(rounds)
    assert int(np.max(sweeps)) <= rounds <= int(np.sum(sweeps)) + 1
    if L == 1:
        assert rounds - int(sweeps[0]) in (0, 1)
    full = placement.place_lifecycle_batched(fleet, dem, engine="full",
                                             n_events=n_ev)
    assert full[3] is None and full[4] is None


def _spec(cfg, n=64, chips=64):
    fleet, traces, ridx = synthetic_lifecycle_fleet(n, cfg,
                                                    chips_per_node=chips)
    return (fleet, traces, ridx, cfg, generate_jobs(cfg))


CFG = SimConfig(epochs=12, seed=5, arrival_rate=6.0, mean_duration_h=4.0,
                shortlist=8, history_h=48, horizon_h=8)


def test_sim_walk_counts_sum_to_rank_sweeps_and_agree_across_drivers():
    specs = [_spec(CFG), _spec(SimConfig(**{**CFG.__dict__, "seed": 6}))]
    seq = [simulate_fleet_scan(*s[:4], jobs=s[4], pad_plan=True)
           for s in specs]
    ens = simulate_fleet_ensemble(specs)
    for a, b in zip(seq, ens):
        assert sum(a.walk_counts[1:]) == a.rank_sweeps > 0
        assert a.walk_counts == b.walk_counts
        assert a.sweep_rounds is None
    # one bucket: both lanes share its rounds, one launch over both lanes
    # per round, each stalled lane's sweep taking one of them
    assert ens[0].sweep_rounds == ens[1].sweep_rounds
    assert max(r.rank_sweeps for r in ens) <= ens[0].sweep_rounds \
        <= sum(r.rank_sweeps for r in ens) + CFG.epochs
    oracle = SimConfig(**{**CFG.__dict__, "engine": "full"})
    r = simulate_fleet_scan(*_spec(oracle)[:4])
    assert r.walk_counts is None and r.sweep_rounds is None


def _host_spans(logdir, names):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for ln in plane.lines for e in ln.events
                    if e.name in names]
    return sorted(out, key=lambda s: s[1])


def test_scan_driver_spans_nest_in_order(tmp_path):
    spec = _spec(CFG)
    simulate_fleet_scan(*spec[:4], jobs=spec[4])        # compile first
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("call"):
        simulate_fleet_scan(*spec[:4], jobs=spec[4])
    jax.profiler.stop_trace()
    spans = _host_spans(tmp_path, ("call",) + PROGRAM_SPANS)
    assert [s[0] for s in spans] == ["call", *PROGRAM_SPANS]
    _, c0, c1 = spans[0]
    t = c0
    for _, s, e in spans[1:]:
        assert t <= s <= e <= c1                # inside the call, in turn
        t = e


def test_program_texts_carry_the_named_scopes():
    spec = _spec(CFG)
    texts = program_texts([spec], ensemble=False, pad_plan=False)
    assert len(texts) == 1 and texts[0].startswith("HloModule jit__scan")
    names = set(re.findall(r'op_name="([^"]*)"', texts[0]))
    parts = {p for n in names for p in n.split("/")}
    assert {"epoch_pre", "epoch_post", "placement_walk", "rank_sweep",
            "forecast"} <= parts
    ens = program_texts([spec, spec], ensemble=True, pad_plan=True)
    assert len(ens) == 1 and "jit__ensemble_trajectory" in ens[0]
    names = set(re.findall(r'op_name="([^"]*)"', ens[0]))
    parts = {p for n in names for p in n.split("/")}
    # the lane vmap names the epoch halves' scopes after itself
    assert {"vmap(epoch_pre)", "vmap(epoch_post)", "placement_walk",
            "rank_sweep", "forecast"} <= parts
