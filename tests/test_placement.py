"""Shortlist placement engine vs the O(J·N) oracle (bit-exact parity over
ragged N, ties, exhaustion), and the fused Pallas top-k vs ``jax.lax.top_k``
in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import placement
from repro.core.fleet import Fleet, synthetic_fleet
from repro.core.scheduler import place_jobs
from repro.kernels import ref
from repro.kernels.ops import maiz_ranking_fused, maiz_ranking_topk


def _uniform_fleet(n, chips=8, cap=8):
    """Every node identical -> every score ties exactly."""
    ones = jnp.ones((n,), jnp.float32)
    return Fleet(
        ci_now=300.0 * ones, ci_forecast=310.0 * ones, pue=1.2 * ones,
        power_kw=10.0 * ones,
        capacity=jnp.full((n,), cap, jnp.int32),
        healthy=jnp.ones((n,), bool),
        straggler_score=jnp.zeros((n,), jnp.float32),
        flops_per_j=1e9 * ones,
        chips_total=jnp.full((n,), chips, jnp.int32),
    )


def _assert_parity(fleet, demands, shortlist):
    a = placement.place_jobs_shortlist(fleet, demands, shortlist=shortlist)
    b = placement.place_jobs_full_rerank(fleet, demands)
    np.testing.assert_array_equal(np.asarray(a.node), np.asarray(b.node))
    np.testing.assert_array_equal(np.asarray(a.capacity),
                                  np.asarray(b.capacity))
    return a, b


# ---------------------------------------------------------------------------
# shortlist == full re-rank, bit-identical
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [5, 64, 1000, 1024, 1025, 2048, 3000])
@pytest.mark.parametrize("shortlist", [1, 4, 32])
def test_parity_ragged_n(n, shortlist):
    fleet = synthetic_fleet(n, seed=n)
    rng = np.random.default_rng(n)
    demands = jnp.asarray(rng.integers(1, 96, 48), jnp.int32)
    _assert_parity(fleet, demands, shortlist)


def test_parity_shortlist_larger_than_fleet():
    fleet = synthetic_fleet(17, seed=3)
    demands = jnp.asarray([4] * 24, jnp.int32)
    a, _ = _assert_parity(fleet, demands, shortlist=4096)
    assert int(a.n_sweeps) == 1     # full cover: never needs a re-sweep


def test_parity_under_exact_ties():
    """Identical nodes -> degenerate normalizers, all scores tie exactly;
    both paths must fill nodes in index order."""
    fleet = _uniform_fleet(100)
    demands = jnp.asarray([3] * 40, jnp.int32)
    a, _ = _assert_parity(fleet, demands, shortlist=8)
    # greedy + lowest-index tie-break: first job lands on node 0
    assert int(a.node[0]) == 0
    assert np.all(np.asarray(a.node) >= 0)


def test_parity_capacity_exhaustion_and_unplaceable():
    fleet = _uniform_fleet(6, chips=4, cap=4)
    # 6*4 = 24 chips total; demands overflow -> later jobs unplaceable
    demands = jnp.asarray([3] * 10, jnp.int32)
    a, _ = _assert_parity(fleet, demands, shortlist=2)
    assert np.asarray(a.node).min() == -1


def test_parity_all_infeasible():
    fleet = _uniform_fleet(32, cap=0)
    demands = jnp.asarray([1] * 5, jnp.int32)
    a, _ = _assert_parity(fleet, demands, shortlist=4)
    assert np.all(np.asarray(a.node) == -1)
    # impossible demands are rejected via the cap_max bound before the lazy
    # initial sweep ever runs: zero rank sweeps for an all-infeasible stream
    assert int(a.n_sweeps) == 0


def test_shortlist_reduces_sweeps():
    """The acceptance-shaped property: one rank per epoch, not per job."""
    fleet = synthetic_fleet(4096, seed=1)
    demands = jnp.asarray([64] * 128, jnp.int32)
    a, b = _assert_parity(fleet, demands, shortlist=64)
    assert int(b.n_sweeps) == 128
    assert int(a.n_sweeps) * 5 <= int(b.n_sweeps)


def test_scheduler_wrapper_engines_agree():
    fleet = synthetic_fleet(256, seed=9)
    demands = jnp.asarray([16] * 32, jnp.int32)
    a = place_jobs(fleet, demands, engine="shortlist", shortlist=16)
    b = place_jobs(fleet, demands, engine="full")
    np.testing.assert_array_equal(np.asarray(a.node), np.asarray(b.node))
    assert int(a.n_sweeps) < int(b.n_sweeps)
    with pytest.raises(ValueError):
        place_jobs(fleet, demands, engine="bogus")


def test_engine_kernel_path_matches_jnp():
    """Pallas-sweep engine == jnp-sweep engine on a padded ragged fleet."""
    fleet = synthetic_fleet(96, seed=5)
    demands = jnp.asarray([8] * 16, jnp.int32)
    a = placement.place_jobs_shortlist(fleet, demands, shortlist=8,
                                       use_kernel=True, interpret=True)
    b = placement.place_jobs_shortlist(fleet, demands, shortlist=8)
    np.testing.assert_array_equal(np.asarray(a.node), np.asarray(b.node))


# ---------------------------------------------------------------------------
# lifecycle events: interleaved arrivals / releases / migrations
# ---------------------------------------------------------------------------


def _assert_lifecycle_parity(fleet, demands, nodes, shortlist):
    demands = jnp.asarray(demands, jnp.int32)
    nodes = jnp.asarray(nodes, jnp.int32)
    a = placement.place_lifecycle_shortlist(fleet, demands, nodes,
                                            shortlist=shortlist)
    b = placement.place_lifecycle_full_rerank(fleet, demands, nodes)
    np.testing.assert_array_equal(np.asarray(a.node), np.asarray(b.node))
    np.testing.assert_array_equal(np.asarray(a.capacity),
                                  np.asarray(b.capacity))
    return a, b


def _random_event_stream(fleet, rng, n_events, max_d=96):
    """Arrivals interleaved with releases of previously-placed jobs,
    replayed against a host-side oracle to keep releases consistent."""
    cap = np.asarray(fleet.capacity).copy()
    healthy = np.asarray(fleet.healthy)
    # replicate frozen-normalizer scoring well enough to pick release
    # targets: releases must credit nodes that actually hold chips, so we
    # replay the full oracle incrementally on host
    live = []          # (node, chips) of placed jobs
    demands, nodes = [], []
    from repro.core.placement import frozen_ctx, _ctx_scores
    ctx = frozen_ctx(fleet)
    for _ in range(n_events):
        if live and rng.random() < 0.4:
            i = rng.integers(0, len(live))
            nd, ch = live.pop(int(i))
            demands.append(-ch)
            nodes.append(nd)
            cap[nd] += ch
        else:
            d = int(rng.integers(1, max_d))
            demands.append(d)
            nodes.append(-1)
            scores = np.asarray(_ctx_scores(jnp.asarray(cap), ctx,
                                            placement.RankWeights()))
            masked = np.where((cap >= d) & healthy, scores, np.inf)
            best = int(np.argmin(masked))
            if np.isfinite(masked[best]):
                cap[best] -= d
                live.append((best, d))
    return demands, nodes


@pytest.mark.parametrize("n", [7, 64, 1000, 1024, 2048])
@pytest.mark.parametrize("shortlist", [2, 8, 32])
def test_lifecycle_parity_interleaved(n, shortlist):
    fleet = synthetic_fleet(n, seed=n + 1)
    rng = np.random.default_rng(n * 31 + shortlist)
    demands, nodes = _random_event_stream(fleet, rng, 64)
    assert any(d < 0 for d in demands), "stream must contain releases"
    _assert_lifecycle_parity(fleet, demands, nodes, shortlist)


def test_lifecycle_parity_under_ties_and_exhaustion():
    """Identical nodes, capacity drained then released: the released node
    must become the argmin target again, bit-identically in both engines."""
    fleet = _uniform_fleet(16, chips=4, cap=4)
    # fill the fleet (16*4 chips), drop two jobs, then try again
    demands = [4] * 16 + [4, -4, -4, 4, 4, 4]
    nodes = [-1] * 16 + [-1, 3, 11, -1, -1, -1]
    a, _ = _assert_lifecycle_parity(fleet, demands, nodes, shortlist=4)
    out = np.asarray(a.node)
    assert out[16] == -1                    # fleet full: unplaceable
    # released nodes 3 and 11 are the only free ones; lowest index first
    assert out[19] == 3 and out[20] == 11
    assert out[21] == -1                    # drained again


def test_lifecycle_release_outside_shortlist_invalidates():
    """A release on a node the shortlist can't see must still be found by
    the next arrival (epoch invalidation, not a stale-bound win)."""
    fleet = _uniform_fleet(64, chips=8, cap=8)
    # shortlist=2 sees nodes {0, 1}; fill node 50 manually then release it
    demands = [8] * 64 + [-8, 8]
    nodes = [-1] * 64 + [50, -1]
    a, _ = _assert_lifecycle_parity(fleet, demands, nodes, shortlist=2)
    out = np.asarray(a.node)
    assert out[-2] == 50
    assert out[-1] == 50        # the freshly freed node is the only fit


def test_lifecycle_migration_pattern():
    """release(old) + arrival = migration; parity incl. landing back."""
    fleet = synthetic_fleet(256, seed=5)
    rng = np.random.default_rng(9)
    demands, nodes = [], []
    placed = []
    cap = np.asarray(fleet.capacity).copy()
    for d in rng.integers(1, 64, 24):
        demands.append(int(d)); nodes.append(-1); placed.append(int(d))
    # migrate 8 jobs: release somewhere legal, re-arrive
    for _ in range(8):
        d = placed.pop()
        feas = np.nonzero(cap >= 0)[0]
        src = int(feas[rng.integers(0, feas.size)])
        demands += [-d, d]
        nodes += [src, -1]
    _assert_lifecycle_parity(fleet, demands, nodes, shortlist=16)


def _full_greenest_fleet(n=1100, n_full=1030, sick=7):
    """The decision service's state at a small size: the best-scoring
    nodes (low intensity) are full, every later one has 4 of 8 chips
    free, and one best-scoring node has room but is out of service.  The
    first kernel tile holds no node with room and N is off the tile."""
    i = np.arange(n)
    ci = np.where(i < n_full, 50.0 + 0.01 * i, 300.0 + 2.0 * (i - n_full))
    cap = np.where(i < n_full, 0, 4)
    cap[sick] = 8
    ones = jnp.ones((n,), jnp.float32)
    return Fleet(
        ci_now=jnp.asarray(ci, jnp.float32),
        ci_forecast=jnp.asarray(ci * 1.05, jnp.float32),
        pue=1.2 * ones, power_kw=10.0 * ones,
        capacity=jnp.asarray(cap, jnp.int32),
        healthy=jnp.asarray(i != sick),
        straggler_score=jnp.zeros((n,), jnp.float32),
        flops_per_j=1e9 * ones,
        chips_total=jnp.full((n,), 8, jnp.int32))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_room_aware_shortlist_when_best_nodes_are_full(use_kernel):
    """The K best-scoring nodes have no room: the shortlist ranks only
    nodes with room for the call's smallest arrival, so arrivals are
    placed from it instead of sweeping once each, bit-identically to the
    oracle.  The stream releases chips on a full node outside the
    shortlist (it must win the next arrival), pads with no-ops, and
    fills the fleet until fewer than K+1 nodes have room and arrivals
    go unplaced; the sick node with room is never chosen."""
    fleet = _full_greenest_fleet()
    rng = np.random.default_rng(14)
    demands = [int(d) for d in rng.integers(1, 4, 30)] + [0, -8, 2]
    demands += [int(d) if d < 4 else 0 for d in rng.integers(1, 5, 200)]
    nodes = [-1] * 31 + [3] + [-1] * 201
    d = jnp.asarray(demands, jnp.int32)
    v = jnp.asarray(nodes, jnp.int32)
    a = placement.place_lifecycle_shortlist(
        fleet, d, v, shortlist=8, use_kernel=use_kernel, interpret=True)
    b = placement.place_lifecycle_full_rerank(fleet, d, v)
    np.testing.assert_array_equal(np.asarray(a.node), np.asarray(b.node))
    np.testing.assert_array_equal(np.asarray(a.capacity),
                                  np.asarray(b.capacity))
    out = np.asarray(a.node)
    assert out[32] == 3                 # the released full node wins
    assert 7 not in out[np.asarray(demands) > 0]
    assert (out[np.asarray(demands) > 0] == -1).any()   # the fleet filled
    wc = np.asarray(a.walk_counts)
    arrivals = sum(x > 0 for x in demands)
    assert wc[0] > 0
    assert int(a.n_sweeps) * 5 <= arrivals, (int(a.n_sweeps), wc)


def test_unhealthy_nodes_hard_masked():
    """Health is a hard feasibility constraint in both engines."""
    fleet = synthetic_fleet(128, seed=4)
    sick = ~np.asarray(fleet.healthy)
    if not sick.any():
        pytest.skip("no sick nodes in this draw")
    demands = jnp.asarray([1] * 64, jnp.int32)
    for engine in ("shortlist", "full"):
        pl = place_jobs(fleet, demands, engine=engine, shortlist=4)
        for nd in np.asarray(pl.node):
            if nd >= 0:
                assert bool(fleet.healthy[nd])


def test_scheduler_place_events_wrapper():
    from repro.core.scheduler import place_events
    fleet = synthetic_fleet(64, seed=2)
    demands = jnp.asarray([8, 8, -8, 8, 0], jnp.int32)
    first = placement.place_jobs_full_rerank(
        fleet, jnp.asarray([8], jnp.int32))
    n0 = int(first.node[0])
    nodes = jnp.asarray([-1, -1, n0, -1, -1], jnp.int32)
    a = place_events(fleet, demands, nodes, engine="shortlist", shortlist=8)
    b = place_events(fleet, demands, nodes, engine="full")
    np.testing.assert_array_equal(np.asarray(a.node), np.asarray(b.node))
    assert int(a.node[0]) == n0         # arrival 0 = same greedy choice
    assert int(a.node[2]) == n0         # release echoes its target
    assert int(a.node[4]) == -1         # no-op padding
    with pytest.raises(ValueError):
        place_events(fleet, demands, nodes, engine="bogus")


# ---------------------------------------------------------------------------
# fused Pallas top-k vs jax.lax.top_k oracle (interpret mode)
# ---------------------------------------------------------------------------


def _rand_inputs(rng, n):
    return (jnp.asarray(rng.random(n) * 100, jnp.float32),
            jnp.asarray(1 + rng.random(n), jnp.float32),
            jnp.asarray(rng.random(n) * 500, jnp.float32),
            jnp.asarray(rng.random(n) * 500, jnp.float32),
            jnp.asarray(rng.random(n), jnp.float32),
            jnp.asarray(rng.random(n), jnp.float32))


W = jnp.asarray([0.35, 0.25, 0.25, 0.15], jnp.float32)


@pytest.mark.parametrize("n,k", [(1024, 8), (1000, 16), (2048, 4),
                                 (5, 8), (1, 4), (2050, 3),
                                 (2048, 100)])   # k > MAX_TILE_K fallback
def test_pallas_topk_matches_lax_topk(n, k, rng):
    args = _rand_inputs(rng, n)
    scores, top_s, top_i = maiz_ranking_topk(*args, W, k=k, interpret=True)
    # scores against the pure-jnp oracle
    lohi = ref.term_lohi(*args)
    want, _, want_arg = ref.maiz_ranking_ref(*args, lohi, W)
    np.testing.assert_allclose(np.asarray(scores), np.asarray(want),
                               atol=1e-5)
    # tile-merged top-k against lax.top_k on the kernel's own scores:
    # exact equality required, tie-breaking included
    kk = min(k, n)
    assert top_s.shape == top_i.shape == (kk,)
    neg, idx = jax.lax.top_k(-scores, kk)
    np.testing.assert_array_equal(np.asarray(top_i), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(top_s), np.asarray(-neg))
    # k=1 head is the argmin
    assert int(top_i[0]) == int(want_arg)


def test_pallas_topk_tie_break_lowest_index():
    """Duplicate tiles -> exact score ties across tiles; the merge must keep
    the lower-index copy, matching lax.top_k / argmin semantics."""
    rng = np.random.default_rng(7)
    base = rng.random(1024).astype(np.float32)
    ci = np.tile(rng.random(1024).astype(np.float32), 2)
    n = 2048
    args = (jnp.asarray(np.tile(base, 2)), jnp.ones(n, jnp.float32),
            jnp.asarray(ci), jnp.asarray(ci),
            jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.float32))
    scores, top_s, top_i = maiz_ranking_topk(*args, W, k=8, interpret=True)
    neg, idx = jax.lax.top_k(-scores, 8)
    np.testing.assert_array_equal(np.asarray(top_i), np.asarray(idx))
    # both copies of a tied score appear, and the low-index copy leads
    ti, ts = np.asarray(top_i), np.asarray(top_s)
    for s in np.unique(ts):
        dup = ti[ts == s]
        np.testing.assert_array_equal(dup, np.sort(dup))
        assert dup[0] < 1024


def test_pallas_lohi_fused_prepass_matches_ref(rng):
    """Sweep-1 (fused term+min/max) == the jnp pre-pass, padding masked."""
    from repro.kernels.maizx_rank import TILE, maiz_lohi_pallas
    for n in (1024, 1000, 1):
        args = _rand_inputs(rng, n)
        pad = (-n) % TILE
        padded = tuple(jnp.pad(a, (0, pad)) for a in args)
        lohi = maiz_lohi_pallas(*padded, jnp.full((1, 1), n, jnp.int32),
                                interpret=True)
        np.testing.assert_allclose(np.asarray(lohi),
                                   np.asarray(ref.term_lohi(*args)),
                                   rtol=1e-6)


def test_fused_argmin_head_unchanged(rng):
    """maiz_ranking_fused keeps its (scores, best_score, best_node) API."""
    args = _rand_inputs(rng, 1500)
    scores, best_s, best_n = maiz_ranking_fused(*args, W, interpret=True)
    assert int(best_n) == int(jnp.argmin(scores))
    np.testing.assert_allclose(float(best_s), float(scores[int(best_n)]))
