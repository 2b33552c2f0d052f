"""Carbon-aware placement: scenario policies + fleet-scale greedy assignment.

Two levels, matching the paper:

1. **Scenario policies** (paper §4): given hourly CI traces for N nodes and a
   total dynamic demand, produce per-hour (util, on) matrices for the
   Baseline / A / B / C scenarios.  These drive the year-long emission
   simulation in ``scenarios.py``.

2. **Fleet placement** (our 1000+-node generalization): jobs with chip
   demands are greedily assigned to the best MAIZ-ranked node with free
   capacity, entirely on-device.  The heavy lifting lives in
   ``repro.core.placement``: a fused top-k shortlist engine that ranks once
   per decision epoch (O(N + J·K)) instead of once per job (O(J·N)), with
   the full re-rank path kept as the bit-exact test oracle.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import numpy as np

from repro.core import placement
from repro.core.energy import EnergyModel
from repro.core.fleet import Fleet
from repro.core.ranking import RankWeights

# ---------------------------------------------------------------------------
# Paper scenarios (hourly allocation over N nodes)
# ---------------------------------------------------------------------------


def baseline_alloc(ci: np.ndarray, pue: np.ndarray, demand: float
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Even spread, everything on, carbon-blind. ci: (N, T); pue: (N,);
    demand in node-equivalents of dynamic load. Returns (util, on) (N,T)."""
    N, T = ci.shape
    util = np.full((N, T), demand / N)
    return util, np.ones((N, T))


def _effective_rate(ci: np.ndarray, pue: np.ndarray) -> np.ndarray:
    """MAIZX ranks by carbon FOOTPRINT (Eq. 2), i.e. CI × PUE — the paper
    text loosely says "lowest carbon intensity"; CFP includes PUE."""
    return ci * pue[:, None]


def scenario_a_alloc(ci: np.ndarray, pue: np.ndarray, demand: float):
    """All compute to the best (lowest CI×PUE) node each hour; others stay
    ON (idle, 'available' per the paper)."""
    N, T = ci.shape
    best = _effective_rate(ci, pue).argmin(axis=0)
    util = np.zeros((N, T))
    util[best, np.arange(T)] = demand
    return util, np.ones((N, T))


def scenario_b_alloc(ci: np.ndarray, pue: np.ndarray, demand: float):
    """Concentrate on one FIXED node (carbon-blind), power the rest off."""
    N, T = ci.shape
    util = np.zeros((N, T))
    on = np.zeros((N, T))
    util[0], on[0] = demand, 1.0
    return util, on


def scenario_c_alloc(ci: np.ndarray, pue: np.ndarray, demand: float):
    """MAIZX active shifting: best CFP-rate node each hour, others OFF.

    Routed through the rolling lifecycle simulator
    (``simulator.paper_scenario_alloc``): one 1-epoch job per hour placed
    by the same engine that schedules multi-thousand-node fleets — the
    paper experiment is the N=3 / T=8760 special case of ``simulate_fleet``
    rather than a separate closed form."""
    from repro.core.simulator import paper_scenario_alloc
    return paper_scenario_alloc(ci, pue, demand)


SCENARIOS = {
    "baseline": baseline_alloc,
    "A": scenario_a_alloc,
    "B": scenario_b_alloc,
    "C": scenario_c_alloc,
}


# ---------------------------------------------------------------------------
# Fleet-scale greedy placement (jit, on-device)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Placement:
    node: jax.Array      # (J,) chosen node per job, -1 = unplaceable
    scores: jax.Array    # (N,) rank scores at FINAL occupancy (frozen lo/hi)
    n_sweeps: Optional[jax.Array] = None   # () int32 full rank sweeps
    # (4,) int32 per-arrival outcome counts of the shortlist engine
    # (``placement.WALK_COUNTS``); None for the full-rerank oracle
    walk_counts: Optional[jax.Array] = None


# Above this N/J the full re-rank's O(J·N) rescore traffic outweighs the
# shortlist engine's per-event loop overhead even on XLA:CPU; below it —
# the entire measured grid, N<=262144 x J<=256 — full re-rank is the
# faster CPU path (see _auto_engine and BENCH_placement.json "auto").
_AUTO_FULL_MAX_N_PER_JOB = 65536


def _auto_engine(n: int, j: int, use_kernel: bool = False) -> str:
    """Resolve ``engine="auto"``: pick the engine that is actually faster
    for this (backend, N, J) so default callers never fall off the
    shortlist engine's small-N cliff.

    The fused shortlist engine's win is measured in rank sweeps — the
    memory-bound currency on accelerators — so it stays the choice for
    any non-CPU backend.  On XLA:CPU, the engine's in-loop ``lax.top_k``
    lowers as a full sort under ``lax.cond`` (~50x slower, see
    ``repro.core.placement``), and the measured grid
    (BENCH_placement.json: N=4096 engine 112.8 ms vs full 5.6 ms/call at
    J=256; full faster at every point up to N=262144) shows the O(J·N)
    full re-rank winning everywhere a job list of realistic size is
    placed — the crossover only arrives when N/J grows past
    ``_AUTO_FULL_MAX_N_PER_JOB`` and per-job full sweeps become the
    bandwidth bottleneck.  ``use_kernel`` no longer forces the shortlist
    engine: on CPU the kernel runs in interpret mode, where the same
    cliff applies, so the N/J crossover decides (the kernel sweep plugs
    into either engine's epoch pre-pass)."""
    del use_kernel  # kept for API compat; no longer affects the choice
    if jax.default_backend() != "cpu":
        return "shortlist"
    return "shortlist" if n // max(j, 1) > _AUTO_FULL_MAX_N_PER_JOB \
        else "full"


def place_jobs(fleet: Fleet, demands: jax.Array,
               weights: RankWeights = RankWeights(),
               horizon_h: float = 1.0, *,
               engine: str = "auto", shortlist: int = 32,
               use_kernel: bool = False,
               interpret: Optional[bool] = None,
               energy: Optional[EnergyModel] = None) -> Placement:
    """Greedy: jobs in given order take the best-ranked node with capacity.

    demands: (J,) chips per job.  Capacity is decremented as jobs land and
    node power — hence CFP/FCFP — rises with occupancy
    (``Fleet.effective_power_kw``), so later jobs genuinely see the updated
    fleet.  Because a landing job perturbs exactly one node's score, the
    default ``engine="shortlist"`` ranks once per decision epoch against a
    tile-merged top-``shortlist`` and places in O(N + J·K);
    ``engine="full"`` is the O(J·N) per-job re-rank oracle the shortlist
    path is bit-identical to (see ``repro.core.placement``).
    ``use_kernel`` routes epoch sweeps through the fused Pallas kernel;
    ``interpret`` forces/disables its Pallas interpret mode (None = auto
    by backend).

    The win is measured in rank sweeps (the memory-bound quantity on TPU:
    5 vs 256 at N=65536, J=256 — see BENCH_placement.json).  On CPU with
    the jnp scoring path, per-job loop overhead exceeds the sweep savings
    at every measured size, so the default ``engine="auto"`` resolves to
    whichever engine is faster for this (backend, N, J) — see
    ``_auto_engine``; placements are bit-identical either way, only the
    ``n_sweeps`` accounting differs.
    """
    if engine == "auto":
        engine = _auto_engine(fleet.n, demands.shape[0], use_kernel)
    if engine == "shortlist":
        r = placement.place_jobs_shortlist(
            fleet, demands, weights, horizon_h, shortlist=shortlist,
            use_kernel=use_kernel, interpret=interpret, energy=energy)
    elif engine == "full":
        r = placement.place_jobs_full_rerank(fleet, demands, weights,
                                             horizon_h, energy=energy)
    else:
        raise ValueError(f"unknown placement engine: {engine!r}")
    return Placement(node=r.node, scores=r.scores, n_sweeps=r.n_sweeps,
                     walk_counts=r.walk_counts)


place_jobs_jit = jax.jit(place_jobs,
                         static_argnames=("engine", "shortlist",
                                          "use_kernel", "interpret"))


def place_events(fleet: Fleet, demands: jax.Array, nodes: jax.Array,
                 weights: RankWeights = RankWeights(),
                 horizon_h: float = 1.0, *,
                 engine: str = "auto", shortlist: int = 32,
                 use_kernel: bool = False,
                 interpret: Optional[bool] = None,
                 capacity: Optional[jax.Array] = None,
                 n_events: Optional[jax.Array] = None,
                 eager_sweep: bool = False,
                 energy: Optional[EnergyModel] = None) -> Placement:
    """Lifecycle placement over an interleaved event stream.

    ``demands[e] > 0`` is an arrival (greedily placed, like ``place_jobs``);
    ``demands[e] < 0`` releases ``-demands[e]`` chips back to ``nodes[e]``
    (a finished or migrating job); ``demands[e] == 0`` is no-op padding.
    Releases make scores *fall* mid-epoch, which the shortlist engine
    absorbs with release-aware epoch invalidation while staying bit-exact
    to the full-rerank oracle (``engine="full"``) — see
    ``repro.core.placement``.  This is the per-epoch entry point of the
    rolling fleet simulator (``repro.core.simulator``); the scan-compiled
    core (``simulator.simulate_fleet_scan``) drives the same engines inside
    ``lax.scan`` with pre-applied release credits.  The engine's scan-side
    event contract is exposed here too: ``capacity`` starts the event loop
    at a post-release snapshot while normalizers stay frozen at
    ``fleet.capacity``, ``n_events`` truncates the loop at the compacted
    event count, and ``eager_sweep`` hoists the epoch-initial sweep out of
    the loop (valid for release-free streams only — see
    ``placement.place_lifecycle_shortlist``).  ``interpret``
    forces/disables Pallas interpret mode for ``use_kernel=True``
    (None = auto by backend).  ``engine="auto"`` (default) resolves per
    ``_auto_engine`` — bit-identical placements either way."""
    if engine == "auto":
        engine = _auto_engine(fleet.n, demands.shape[0], use_kernel)
    if engine not in ("shortlist", "full"):
        raise ValueError(f"unknown placement engine: {engine!r}")
    with jax.named_scope("placement_walk"):
        if engine == "shortlist":
            r = placement.place_lifecycle_shortlist(
                fleet, demands, nodes, weights, horizon_h,
                shortlist=shortlist, use_kernel=use_kernel,
                interpret=interpret, capacity=capacity, n_events=n_events,
                eager_sweep=eager_sweep, energy=energy)
        else:
            r = placement.place_lifecycle_full_rerank(
                fleet, demands, nodes, weights, horizon_h,
                capacity=capacity, n_events=n_events, energy=energy)
    return Placement(node=r.node, scores=r.scores, n_sweeps=r.n_sweeps,
                     walk_counts=r.walk_counts)


place_events_jit = jax.jit(place_events,
                           static_argnames=("engine", "shortlist",
                                            "use_kernel", "interpret",
                                            "eager_sweep"))
