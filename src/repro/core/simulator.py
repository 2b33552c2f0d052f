"""Rolling multi-epoch fleet simulator: arrivals, departures, migration.

The paper's headline (§5, Scenario C: -85.68 % CO2) comes from *continuous*
operation — work shifts hour by hour as carbon intensity moves.  This module
advances a fleet through T hourly epochs.  Each epoch:

1. refreshes ``ci_now`` from per-region hourly traces and ``ci_forecast``
   from ``forecast.fit_forecast`` over the trailing ``history_h`` window
   (the FCFP source is the real forecaster, not a 24 h-mean oracle);
2. releases finished jobs (their chips return to their nodes — scores
   *fall*, which is why placement runs on the lifecycle engine with
   release-aware epoch invalidation, see ``repro.core.placement``);
3. optionally migrates the worst-placed running jobs when the carbon
   policy's gain beats the checkpoint/restore carbon cost
   (``migration_budget`` per epoch, cost model in gCO2 via
   ``carbon.job_energy_kwh``), and force-evicts jobs from outaged
   regions.  Migration gain and deferral decisions are pluggable through
   ``SimConfig.policy`` (``repro.core.policy``): the reactive parity
   oracle, the forecast-driven green-window planner (discounted
   look-ahead over the forecast tensor, moves gated into green windows),
   and SLO-aware deferral (deadline/value priority queue with
   deadline-miss accounting) — both drivers consume the same ``Policy``
   expressions, so host and scan cannot drift;
4. admits a stochastic-but-seeded arrival stream (diurnal modulation,
   optional flash crowds, deferrable batch jobs that wait for greener
   hours), placing every event through ONE lifecycle-engine call —
   releases batched ahead of arrivals so the whole epoch costs ~1 rank
   sweep;
5. accounts emissions: per-node energy from the affine utilization model
   (``core.energy.EnergyModel``: idle floor + dynamic power + amortized
   embodied carbon), idle nodes powered off when ``power_off_idle``,
   migration overhead charged at the source node's CI.

``engine="shortlist"`` and ``engine="full"`` produce bit-identical
trajectories (asserted by the lifecycle parity tests and the
``sim_scale`` bench).  Two carbon-blind comparators:

- ``engine="blind"``: lowest-index first-fit with the same idle power-off —
  a strong consolidator that isolates the *carbon-awareness* contribution;
- ``engine="spread"``: round-robin, every node always on — the paper's
  baseline scenario generalized to fleet scale (isolates awareness +
  consolidation + power-off together, the Scenario-C-vs-baseline framing).

``paper_scenario_alloc`` is the N=3 / T=8760 special case: one 1-epoch job
per hour carrying the paper's aggregate demand, CFP-only weights, idle
power-off — reproducing Scenario C's (util, on) matrices through the same
code path that runs 65k-node fleets (see ``scheduler.scenario_c_alloc``).

**Two drivers, one epoch graph.**  ``simulate_fleet`` is the host loop:
one jitted ``_epoch_step`` dispatch per epoch, python job bookkeeping —
the reference oracle.  ``simulate_fleet_scan`` compiles the WHOLE
trajectory as one ``lax.scan`` over a fixed-capacity job-slot table and
padded event buffers (``ScanPlan``), sharing ``_place_epoch`` and every
policy expression with the host path so placements and counters match the
oracle exactly (emissions to f32 tolerance; year-scale runs go from
minutes to seconds — see EXPERIMENTS.md §Scanned core and BENCH_sim.json's
``long_run``).

**Instrumentation.**  The compiled drivers open host spans
(``jax.profiler.TraceAnnotation``: ``plan_build``, ``dispatch``,
``device_wait``, ``readback``, ``result_assembly``), which cost nothing
unless a profiler trace is active, and name their device layers with
``jax.named_scope`` (``epoch_pre``, ``placement_walk``, ``rank_sweep``,
``epoch_post``, ``router``; ``forecast`` in ``forecast.fit_forecast``),
which changes only HLO metadata.  ``SimResult.walk_counts`` and
``sweep_rounds`` are always counted, like ``rank_sweeps``.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import forecast, telemetry
from repro.core import policy as policylib
from repro.core import router as routerlib
from repro.core.energy import DEFAULT_ENERGY, EnergyModel
from repro.core.faults import (FaultConfig, FaultPlan, fault_graph_key,
                               plan_faults)
from repro.core.traffic import (TrafficConfig, TrafficPlan, plan_traffic,
                                traffic_graph_key, validate_qps_weights)
from repro.core.fleet import Fleet
from repro.core.placement import (place_lifecycle_batched,
                                  place_lifecycle_full_rerank,
                                  place_lifecycle_shortlist)
from repro.core.policy import Policy, PolicyConfig
from repro.core.ranking import RankWeights

# job state machine
_PENDING, _ACTIVE, _DONE, _DROPPED = 0, 1, 2, 3


@dataclasses.dataclass(frozen=True)
class SimConfig:
    epochs: int = 168
    seed: int = 0
    weights: RankWeights = RankWeights()
    engine: str = "shortlist"       # shortlist | full | blind | spread
    shortlist: int = 64
    use_kernel: bool = False
    # Pallas interpret mode of the use_kernel sweep: None = by backend
    # (interpreted off-TPU), False = the compiled Mosaic kernel only
    interpret: Optional[bool] = None
    horizon_h: int = 24             # FCFP forecast horizon
    history_h: int = 336            # trailing window fed to fit_forecast
    # --- arrival process (seeded, deterministic) ---
    arrival_rate: float = 12.0      # mean arrivals / epoch
    diurnal: bool = True            # business-hours modulation
    flash_crowd: Optional[Tuple[int, int, float]] = None  # (t0, len, mult)
    # one (region, t0, len) window, or a list/tuple of such windows
    # (normalized by _outage_windows; the single-tuple form stays accepted)
    outage: Optional[Tuple[int, int, int]] = None
    mean_duration_h: float = 12.0
    chips_lo: int = 8
    chips_hi: int = 64
    deferrable_frac: float = 0.0    # batch jobs that can wait for green hours
    defer_max_h: int = 6
    # --- policy subsystem (migration + deferral, see repro.core.policy) ---
    policy: PolicyConfig = PolicyConfig()
    # --- signal faults + graceful degradation (see repro.core.faults) ---
    # None = perfect oracles (the historical behavior, bit-identical to
    # the pre-fault golden trajectories); a FaultConfig degrades every
    # signal the policies read while emission accounting stays on ground
    # truth.  Only fault_graph_key(faults) shapes the compiled scan.
    faults: Optional[FaultConfig] = None
    # --- request-level serving traffic (see repro.core.traffic/router) ---
    # None = no serving layer (the historical behavior, bit-identical to
    # the pre-traffic golden trajectories).  A TrafficConfig attaches a
    # seeded fleet-QPS stream: placed jobs with a ``svc_class`` become
    # replicas sharing the chip capacity placement allocated, and every
    # epoch the marginal-carbon router splits the offered requests across
    # them under ``policy.router_slo_s`` (see step 5 of the epoch).  Only
    # traffic_graph_key(traffic) — the service count — shapes the
    # compiled scan; rates/SLO/greenness are traced data.
    traffic: Optional[TrafficConfig] = None
    # manual override for the scanned core's job-table width (0 = the
    # sound ScanPlan bound); surfaced by the slot-overflow error message
    scan_slots: int = 0
    # --- migration ---
    migration_budget: int = 0       # max policy migrations / epoch
    migration_overhead_h: float = 0.05   # checkpoint+restore wall clock
    # --- power model ---
    # Two-part energy/carbon model (idle fraction, chip/host watts,
    # amortized embodied gCO2 per node-hour, marginal-CFP weight storage).
    # Threaded as TRACED data through both drivers and the placement
    # engines, so an (idle-frac x embodied x marginal) calibration grid
    # shares one compiled graph; the default reproduces the historical
    # constants bit-exactly.
    energy: EnergyModel = DEFAULT_ENERGY
    power_off_idle: bool = True     # nodes with no jobs draw zero
    # --- multi-tenant attribution ---
    # > 0 assigns each job a tenant id in [0, n_tenants) (drawn AFTER all
    # other job columns, so enabling attribution cannot perturb the
    # stream) and reports per-tenant emissions: each on-node's gCO2 is
    # split across resident jobs proportional to occupied chips; the
    # idle/rounding remainder lands in bin ``n_tenants`` so the bins sum
    # exactly to the fleet total.
    n_tenants: int = 0
    # Powered-off nodes get this straggler bonus so the SCHEDULE_WEIGHT
    # term biases toward consolidation: landing on an already-on node only
    # adds dynamic power, while waking an off node pays the idle floor too.
    # Pure greedy CFP ranking is anti-consolidating (occupancy raises a
    # node's footprint, pushing the next job to a fresh idle node) — at
    # IDLE_POWER_FRAC = 0.35 that spread costs more than the CI spread
    # saves.  0 disables.
    consolidate: float = 1.0

    @property
    def use_forecast(self) -> bool:
        return self.weights.w2 != 0.0


def _outage_windows(outage) -> Tuple[Tuple[int, int, int], ...]:
    """Normalize ``SimConfig.outage`` to a tuple of (region, t0, len)
    windows: ``None`` -> ``()``, the historical single tuple -> a 1-tuple,
    and any sequence of windows passes through.  Both drivers and the
    scanned core's static shapes consume only this canonical form."""
    if outage is None:
        return ()
    if len(outage) == 3 and all(
            isinstance(v, (int, np.integer)) for v in outage):
        return (tuple(int(v) for v in outage),)
    return tuple(tuple(int(v) for v in w) for w in outage)


@dataclasses.dataclass
class JobSchedule:
    """Struct-of-arrays over jobs, sorted by arrival epoch.

    ``deadline``/``value`` are the SLO-deferral columns (latest start
    slack in epochs and queue-priority value); ``None`` means the policy
    layer derives the reactive defaults (``defer_max_h`` slack for
    deferrable jobs, unit value) — see ``policy.Policy.for_jobs``.
    ``svc_class``/``qps_weight`` are the serving columns (which service a
    placed replica belongs to, and its share of that service's QPS);
    ``None`` or ``svc_class < 0`` means the job serves no requests."""
    arrive: np.ndarray      # (J,) epoch of arrival
    chips: np.ndarray       # (J,) chip demand
    duration: np.ndarray    # (J,) epochs of runtime
    load: np.ndarray        # (J,) float dynamic load (util accounting)
    deferrable: np.ndarray  # (J,) bool
    deadline: Optional[np.ndarray] = None   # (J,) start slack in epochs
    value: Optional[np.ndarray] = None      # (J,) f32 job value
    tenant: Optional[np.ndarray] = None     # (J,) tenant id (attribution)
    qps_weight: Optional[np.ndarray] = None  # (J,) i32 QPS share weight
    svc_class: Optional[np.ndarray] = None   # (J,) i32 service; -1 = none

    @property
    def n(self) -> int:
        return self.arrive.shape[0]


def generate_jobs(cfg: SimConfig) -> JobSchedule:
    """Seeded stochastic arrival stream: Poisson with diurnal modulation and
    an optional flash crowd; geometric durations; uniform chip demands."""
    rng = np.random.default_rng(np.uint64(cfg.seed) * np.uint64(977) + 13)
    t = np.arange(cfg.epochs)
    rate = np.full(cfg.epochs, float(cfg.arrival_rate))
    if cfg.diurnal:
        rate *= 1.0 + 0.4 * np.cos(2 * np.pi * (t % 24 - 14) / 24)
    if cfg.flash_crowd is not None:
        t0, length, mult = cfg.flash_crowd
        rate[t0:t0 + length] *= mult
    counts = rng.poisson(rate)
    arrive = np.repeat(t, counts)
    J = arrive.shape[0]
    chips = rng.integers(cfg.chips_lo, cfg.chips_hi + 1, J)
    # duration = 1 + Geometric(p), mean 1 + 1/p; p clamped into (0, 1] so
    # mean_duration_h in (1, 2) degrades to all-2-epoch jobs, not a crash
    p = min(1.0, 1.0 / max(cfg.mean_duration_h - 1.0, 1e-9))
    duration = 1 + rng.geometric(p, J) \
        if cfg.mean_duration_h > 1.0 else np.ones(J, np.int64)
    deferrable = rng.random(J) < cfg.deferrable_frac
    # SLO columns are drawn AFTER every reactive column so enabling the
    # SLO policy cannot perturb the reactive arrival stream (the committed
    # bench baselines and the PR 3 golden trajectories depend on it)
    deadline = value = None
    if cfg.policy.deferral == "slo":
        lo = max(cfg.policy.deadline_lo, 1)
        hi = max(cfg.policy.deadline_hi
                 if cfg.policy.deadline_hi > 0 else cfg.defer_max_h, lo)
        deadline = rng.integers(lo, hi + 1, J)
        value = rng.exponential(1.0, J).astype(np.float32)
    # tenant ids draw LAST (after reactive AND SLO columns) so turning on
    # attribution perturbs neither stream — same invariant as the SLO draw
    tenant = None
    if cfg.n_tenants > 0:
        tenant = rng.integers(0, cfg.n_tenants, J).astype(np.int32)
    # serving columns draw after EVERY other column (reactive, SLO,
    # tenant) so attaching a traffic layer perturbs none of the earlier
    # streams — the committed golden digests depend on this order
    qps_weight = svc_class = None
    if cfg.traffic is not None and cfg.traffic.n_svc > 0:
        tc = cfg.traffic
        serving = rng.random(J) < tc.serve_frac
        svc_class = np.where(serving, rng.integers(0, tc.n_svc, J),
                             -1).astype(np.int32)
        qps_weight = np.where(serving, rng.integers(1, tc.weight_hi + 1, J),
                              0).astype(np.int32)
    return JobSchedule(arrive=arrive, chips=chips.astype(np.int64),
                       duration=duration.astype(np.int64),
                       load=chips.astype(np.float64),
                       deferrable=deferrable, deadline=deadline,
                       value=value, tenant=tenant,
                       qps_weight=qps_weight, svc_class=svc_class)


@dataclasses.dataclass
class SimResult:
    emissions_g: float              # total, incl. migration overhead
    migration_cost_g: float
    rank_sweeps: int
    arrivals_placed: int            # arrival events landed (incl. re-placements)
    jobs_completed: int
    jobs_dropped: int
    jobs_deferred: int              # deferral decisions taken
    migrations: int
    evictions: int
    node_log: np.ndarray            # (J,) final node per job (-1 = dropped)
    first_node: np.ndarray          # (J,) first placement per job
    emissions_series: np.ndarray    # (T,) gCO2 per epoch
    deadline_misses: int = 0        # slack>0 jobs that never started in time
    defer_delay_h: int = 0          # sum of (start - arrive) over placements
    migrations_failed: int = 0      # actuation failures (budget consumed)
    jobs_active_end: int = 0        # still running when the horizon ends
    safe_epochs: int = 0            # epochs spent with policy frozen
    start_epoch: Optional[np.ndarray] = None  # (J,) first-placement epoch
    util: Optional[np.ndarray] = None   # (N, T) when record_matrices
    on: Optional[np.ndarray] = None
    # (n_tenants + 1,) gCO2 per tenant when cfg.n_tenants > 0; the last
    # bin is the unattributed idle/overhead remainder.  Bins sum exactly
    # to emissions_g (conservation by construction).
    tenant_emissions_g: Optional[np.ndarray] = None
    # --- request-serving layer (SimConfig.traffic; see core.router) ---
    req_served: int = 0             # requests routed onto replicas
    req_offered: int = 0            # requests offered to active services
    # request-attributed gCO2: an *attribution slice* of the node energy
    # already counted in emissions_g (NOT added on top — the traffic-free
    # and zero-QPS trajectories stay bitwise identical to the goldens)
    req_gco2: float = 0.0
    p99_violations: int = 0         # replica-epochs routed above lambda_max
    req_p99_s: float = 0.0          # request-weighted modeled p99 (s)
    # (n_tenants + 1,) request gCO2 per tenant (spare last bin stays 0);
    # bins sum exactly to req_gco2
    tenant_request_g: Optional[np.ndarray] = None
    # the compiled drivers' shortlist-engine arrival outcomes, summed over
    # epochs (placement.WALK_COUNTS; indices 1-3 sum to rank_sweeps); None
    # for the full-rerank engine and the host loop
    walk_counts: Optional[Tuple[int, int, int, int]] = None
    # batched sweep launches (over all lanes) of this lane's ensemble
    # bucket; None outside the batched ensemble
    sweep_rounds: Optional[int] = None


# ---------------------------------------------------------------------------
# jitted epoch step: slice traces -> forecast -> build fleet -> place events
# ---------------------------------------------------------------------------


def _place_epoch(pue, power_kw, chips_total, straggler, flops_per_j,
                 ci_now, ci_fc, cap_ctx, cap_start, healthy, demands, nodes,
                 statics, n_events=None, eager_sweep=False, energy=None):
    """Build the epoch Fleet and run the lifecycle placement engine.

    ``cap_ctx`` is the capacity snapshot the frozen normalizers see;
    ``cap_start`` is where the event loop begins.  The host loop passes the
    same array for both (releases stream through the engine); the scanned
    core pre-applies an epoch's leading releases as one scatter (they are
    commutative capacity edits on a dirty engine) and passes the
    post-release capacity as ``cap_start`` — identical final state, fewer
    loop iterations.  Returns ``(node, capacity, n_sweeps, walk_counts)``
    (``walk_counts`` None for the full-rerank engine)."""
    engine, shortlist, use_kernel, weights = statics[:4]
    interpret = statics[9]
    fleet = Fleet(ci_now=ci_now.astype(jnp.float32),
                  ci_forecast=ci_fc.astype(jnp.float32),
                  pue=pue, power_kw=power_kw, capacity=cap_ctx,
                  healthy=healthy, straggler_score=straggler,
                  flops_per_j=flops_per_j, chips_total=chips_total)
    with jax.named_scope("placement_walk"):
        if engine == "full":
            r = place_lifecycle_full_rerank(
                fleet, demands, nodes, weights, horizon_h=1.0,
                capacity=cap_start, n_events=n_events, energy=energy)
        else:
            r = place_lifecycle_shortlist(
                fleet, demands, nodes, weights, horizon_h=1.0,
                shortlist=shortlist, use_kernel=use_kernel,
                interpret=interpret, capacity=cap_start, n_events=n_events,
                eager_sweep=eager_sweep, energy=energy)
    return r.node, r.capacity, r.n_sweeps, r.walk_counts


def _epoch_core(traces, ridx, pue, power_kw, chips_total, straggler,
                flops_per_j, region_pue, t, cap, healthy, demands, nodes,
                fc_ok, statics, energy=None):
    """One simulator epoch on-device: slice the CI column, refresh the FCFP
    forecast, build the Fleet and run the lifecycle placement engine.
    ``straggler`` already carries the per-epoch consolidation bonus.

    ``traces`` is whatever CI the *policies* may read — the degraded
    observed trace under a ``FaultConfig``, ground truth otherwise (the
    callers keep emission accounting on ground truth either way).  When
    the statics' ``fc_fallback`` flag is set, the traced ``fc_ok`` scalar
    selects between the fitted forecast and the persistence-of-day
    fallback (``forecast.persistence_regions``) — a forecast-service
    outage is per-epoch data, not graph structure.

    The scanned core (``simulate_fleet_scan``) runs the same pieces —
    ``_place_epoch`` plus the identical CI/forecast expressions — inside
    ``lax.scan``, with the forecast batched over epochs up front (bitwise
    equal: it only depends on the static traces)."""
    (engine, shortlist, use_kernel, weights, horizon_h, history_h,
     use_forecast, defer_window, fc_fallback, _) = statics
    ci_now_r = jax.lax.dynamic_slice_in_dim(traces, t, 1, axis=1)[:, 0]
    ci_now = ci_now_r[ridx]
    if use_forecast:
        window = jax.lax.dynamic_slice_in_dim(
            traces, t - history_h, history_h, axis=1)
        fc, _ = forecast.forecast_regions(window, horizon_h, 0)  # (R, H)
        if fc_fallback:
            fc = jnp.where(fc_ok,
                           fc, forecast.persistence_regions(window,
                                                            horizon_h))
        ci_fc = jnp.mean(fc, axis=-1)[ridx]
        # greenest achievable CFP rate inside the deferral window, for the
        # deferrable-batch policy (min over regions and near-term hours);
        # the window is policy-derived (reactive: defer_max_h, SLO: the
        # largest per-job slack — see policy.Policy.defer_window).
        # Node-less regions are masked, not inf-multiplied: a clamped
        # 0.0 forecast times the +inf sentinel would be NaN
        fut_rate = jnp.min(jnp.where(
            jnp.isfinite(region_pue)[:, None],
            fc[:, :defer_window] * region_pue[:, None], jnp.inf))
    else:
        ci_fc = ci_now
        fut_rate = jnp.float32(jnp.inf)
    node, cap_out, n_sweeps, _ = _place_epoch(
        pue, power_kw, chips_total, straggler, flops_per_j, ci_now, ci_fc,
        cap, cap, healthy, demands, nodes, statics, energy=energy)
    cur_rate = jnp.min(jnp.where(healthy, ci_now * pue, jnp.inf))
    return node, cap_out, n_sweeps, ci_now, cur_rate, fut_rate


_epoch_step = jax.jit(_epoch_core, static_argnames=("statics",))


@functools.partial(jax.jit, static_argnames=("epochs", "history_h",
                                             "horizon_h", "lookahead_h",
                                             "discount", "fc_fallback"))
def _lookahead_signals(traces, region_pue, fc_ok, epochs, history_h,
                       horizon_h, lookahead_h, discount,
                       fc_fallback=False):
    """Green-window planner signals for ALL epochs in one batched call:
    the identical windowed-forecast graph the scanned core hoists as scan
    ``xs`` (it only depends on the static traces), reduced by
    ``forecast.green_window_signals``.  Returns ``(la_ci (T, R),
    la_dst (T,), gw_min (T,))`` — the discounted look-ahead CI per
    region, the greenest discounted region rate, and the greenest single
    upcoming moment (the green-window gate reference).  The host loop
    computes these once up front so its migration policy reads the same
    float32 forecast signals as the scanned core."""
    ts = jnp.arange(epochs, dtype=jnp.int32)
    wins = jax.vmap(lambda t: jax.lax.dynamic_slice_in_dim(
        traces, t, history_h, axis=1))(ts)
    fc = jax.vmap(
        lambda w: forecast.forecast_regions(w, horizon_h, 0)[0])(wins)
    if fc_fallback:
        fcp = jax.vmap(
            lambda w: forecast.persistence_regions(w, horizon_h))(wins)
        fc = jnp.where(fc_ok[:, None, None], fc, fcp)
    la_ci, gw_min = forecast.green_window_signals(
        fc, region_pue, lookahead_h, discount)
    la_dst = jnp.min(jnp.where(jnp.isfinite(region_pue)[None, :],
                               la_ci * region_pue[None, :], jnp.inf),
                     axis=-1)
    return la_ci, la_dst, gw_min


def _region_pue(n_regions: int, ridx: np.ndarray, pue) -> np.ndarray:
    """Representative PUE per region row; regions with no nodes get +inf so
    they can't win the deferral policy's "greenest upcoming hour" min.
    Shared by the host loop and the scanned core — the deferral policy's
    region-PUE convention must stay identical across drivers."""
    out = np.full(n_regions, np.inf)
    np.minimum.at(out, ridx, np.asarray(pue, np.float64))
    return out


def _pad_bucket(n: int) -> int:
    """Round the event count up to a small set of static sizes so the jitted
    epoch step compiles O(log) times, not O(T)."""
    b = 8
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# the simulator
# ---------------------------------------------------------------------------


def simulate_fleet(fleet0: Fleet, region_ci: np.ndarray, ridx: np.ndarray,
                   cfg: SimConfig, jobs: Optional[JobSchedule] = None,
                   record_matrices: bool = False) -> SimResult:
    """Advance ``fleet0`` (capacity = free chips at t=0) through
    ``cfg.epochs`` hourly epochs.

    ``region_ci`` is (R, history_h + epochs + margin) hourly CI; nodes map
    to regions via ``ridx``.  Epoch t reads column ``history_h + t`` as
    ``ci_now`` and feeds the trailing ``history_h`` window to the FCFP
    forecaster.  ``jobs`` defaults to ``generate_jobs(cfg)``.
    """
    N, T = fleet0.n, cfg.epochs
    jobs = jobs if jobs is not None else generate_jobs(cfg)
    J = jobs.n
    if cfg.engine not in ("shortlist", "full", "blind", "spread"):
        raise ValueError(f"unknown simulator engine: {cfg.engine!r}")
    blind = cfg.engine in ("blind", "spread")
    spread = cfg.engine == "spread"
    rr_ptr = [0]                            # round-robin pointer (spread)
    pol = Policy.for_jobs(cfg.policy, jobs.arrive, jobs.deferrable,
                          cfg.defer_max_h, jobs.deadline, jobs.value)
    slo = pol.slo
    q_cap = pol.queue_cap(T) if slo else 0
    planner = (pol.lookahead and cfg.migration_budget > 0 and not blind
               and cfg.use_forecast)
    green_factor = float(cfg.policy.defer_green_factor)
    outs = _outage_windows(cfg.outage)

    # fault streams: every policy decision reads the degraded OBSERVED
    # trace (including the jitted epoch step below); emission + migration
    # cost accounting stays on ground truth
    fplan: Optional[FaultPlan] = None
    if cfg.faults is not None:
        fplan = plan_faults(cfg.faults, np.asarray(region_ci, np.float64),
                            np.asarray(ridx), T, cfg.history_h,
                            cfg.migration_budget, N, cfg.seed)
    obs_ci = region_ci if fplan is None else fplan.obs_traces
    has_flaps = fplan is not None and fplan.has_flaps
    mig_block: Dict[int, Tuple[int, int]] = {}  # job -> (until, n_fails)
    mig_failed = 0

    traces = jnp.asarray(obs_ci, jnp.float32)
    ridx_d = jnp.asarray(ridx, jnp.int32)
    region_pue_d = jnp.asarray(
        _region_pue(region_ci.shape[0], ridx, fleet0.pue), jnp.float32)

    # host mirrors for policy + accounting (f64)
    pue_h = np.asarray(fleet0.pue, np.float64)
    power_h = np.asarray(fleet0.power_kw, np.float64)
    chips_total_h = np.asarray(fleet0.chips_total, np.int64)
    healthy0 = np.asarray(fleet0.healthy, bool)

    cap = fleet0.capacity
    cap_h = np.asarray(cap, np.int64)
    njobs = np.zeros(N, np.int64)          # running jobs per node
    load_on = np.zeros(N, np.float64)      # dynamic load per node

    # job table
    jnode = np.full(J, -1, np.int64)
    jfirst = np.full(J, -1, np.int64)
    jstart = np.full(J, -1, np.int64)
    jend = np.full(J, -1, np.int64)
    jstate = np.full(J, _PENDING, np.int8)
    ends: Dict[int, list] = {}
    by_arrival: Dict[int, list] = {}
    for j in range(J):
        by_arrival.setdefault(int(jobs.arrive[j]), []).append(j)
    deferred: Dict[int, list] = {}
    slo_queue: list = []                   # SLO priority queue (sorted)

    emissions = 0.0
    mig_cost_total = 0.0
    sweeps = placed = completed = dropped = deferred_n = 0
    migrations = evictions = misses = delay_h = 0
    series = np.zeros(T)
    util_m = np.zeros((N, T)) if record_matrices else None
    on_m = np.zeros((N, T)) if record_matrices else None

    fc_fallback = (fplan is not None and cfg.use_forecast and not blind)
    # weights enter the compiled graph through their canonical graph_key
    # (marginal pinned to 0): the live marginal weight rides as traced
    # data inside the EnergyModel, so a marginal-weight sweep shares one
    # compile — on the Pallas path too, where the en_* scalars are
    # threaded into the sweep kernel (see kernels.maizx_rank).
    em_host = cfg.energy
    em_dev = em_host.device(w_marginal=cfg.weights.marginal)
    statics = (cfg.engine, cfg.shortlist, cfg.use_kernel,
               cfg.weights.graph_key(),
               cfg.horizon_h, cfg.history_h,
               cfg.use_forecast and not blind,
               pol.defer_window(cfg.defer_max_h), fc_fallback,
               cfg.interpret)
    overhead_s = cfg.migration_overhead_h * 3600.0
    n_ten = int(cfg.n_tenants)
    if n_ten and jobs.tenant is None:
        raise ValueError("cfg.n_tenants > 0 requires jobs.tenant "
                         "(generate_jobs draws it when n_tenants is set)")
    ten = None if not n_ten else np.asarray(jobs.tenant, np.int64)
    tenant_g = np.zeros(n_ten + 1) if n_ten else None
    if planner:
        fc_ok_d = jnp.asarray(fplan.fc_ok) if fplan is not None \
            else jnp.ones(T, bool)
        la_ci_all, la_dst_all, gw_min_all = [
            np.asarray(x) for x in _lookahead_signals(
                traces, region_pue_d, fc_ok_d, T, cfg.history_h,
                cfg.horizon_h, cfg.policy.lookahead_h, cfg.policy.discount,
                fc_fallback)]

    # request-serving traffic: the router reads state AFTER the epoch's
    # placements settle (step 5b) and never feeds back into placement, so
    # every traffic-free metric above stays bitwise identical
    tcfg = cfg.traffic
    n_svc = traffic_graph_key(tcfg)
    req_served = req_offered = req_viol = 0
    req_g = p99_wsum = 0.0
    ten_req = None
    if n_svc > 0:
        validate_qps_weights(jobs.qps_weight)
        if jobs.svc_class is None:
            raise ValueError("SimConfig.traffic requires a JobSchedule "
                             "svc_class column (generate_jobs draws it "
                             "when cfg.traffic is set)")
        tplan = plan_traffic(tcfg, T, cfg.seed)
        svc_col = np.asarray(jobs.svc_class, np.int32)
        w_col = np.asarray(jobs.qps_weight, np.int32)
        c_max_r = int(np.max(jobs.chips, initial=1))
        # per-replica admissible rate: the M/M/c inversion runs ONCE here
        # in f64 and feeds both drivers as integer data (parity contract)
        lam_cap = routerlib.lambda_caps(c_max_r, tcfg.mu_per_chip,
                                        cfg.policy.router_slo_s)
        pue32 = np.asarray(fleet0.pue, np.float32)
        green32 = np.float32(cfg.policy.router_greenness)
        req_kwh = float(em_host.req_kwh(1.0 / tcfg.mu_per_chip))
        ten_req = np.zeros(n_ten + 1) if n_ten else None

    for t in range(T):
        a = cfg.history_h + t
        ci_col = region_ci[:, a][ridx]      # (N,) f64 TRUE (accounting)
        ci_obs_col = obs_ci[:, a][ridx]     # (N,) f64 observed (policy)
        fc_ok_t = bool(fplan.fc_ok[t]) if fplan is not None else True
        safe_t = bool(fplan.safe[t]) if fplan is not None else False
        healthy = healthy0.copy()
        for reg, t0, length in outs:
            if t0 <= t < t0 + length:
                healthy &= (ridx != reg)
        if has_flaps:
            healthy &= fplan.eligible[t]

        # ---- 1. end-of-life releases --------------------------------
        rel_jobs = [j for j in ends.pop(t, []) if jstate[j] == _ACTIVE]
        for j in rel_jobs:
            jstate[j] = _DONE
            completed += 1
            njobs[jnode[j]] -= 1
            load_on[jnode[j]] -= jobs.load[j]

        # ---- 2. forced evictions + migration policy -----------------
        active = np.where(jstate == _ACTIVE)[0]
        evict = active[~healthy[jnode[active]]] if (outs or has_flaps) \
            else np.empty(0, np.int64)
        mig: list = []
        if cfg.migration_budget > 0 and not blind and active.size:
            stay = active[healthy[jnode[active]]]
            free = cap_h.copy()
            # policy rates read the OBSERVED trace; the accounting below
            # charges the move at the true CI regardless
            rate = np.where(healthy, pue_h * ci_obs_col, np.inf)
            # best achievable CFP rate per distinct chip demand, O(C·N)
            best_rate: Dict[int, float] = {}
            for c in np.unique(jobs.chips[stay]):
                feas = rate[free >= c]
                best_rate[int(c)] = float(feas.min()) if feas.size else np.inf
            # per-chip-hour energy of a job (kWh): chips · board+host power
            e_kwh_h = em_host.e_kwh_h       # per chip per hour
            chips_arr = jobs.chips[stay]
            br_arr = np.array([best_rate[int(c)] for c in chips_arr]) \
                if stay.size else np.empty(0)
            la_kw = {}
            if planner:
                la_node = la_ci_all[t][ridx] * pue_h        # (N,) f64
                la_kw = dict(src_la=la_node[jnode[stay]],
                             dst_la=float(la_dst_all[t]),
                             gw_min=float(gw_min_all[t]))
            gain = policylib.migration_gain(
                np, cfg.policy,
                rate_cur=rate[jnode[stay]], best_rate=br_arr,
                chips=chips_arr,
                remaining=np.maximum(jend[stay] - t, 0),
                e_kwh_h=float(e_kwh_h),
                ckpt=np.asarray(em_host.job_energy_kwh(overhead_s, 1,
                                                       chips_arr)),
                **la_kw)
            if mig_block and stay.size:
                # retry-with-backoff: a job whose last actuation failed is
                # frozen out of the candidate sort until its backoff ends
                blocked = np.array([mig_block.get(int(j), (0, 0))[0] > t
                                    for j in stay])
                gain = np.where(blocked, -np.inf, gain)
            if safe_t:
                gain = policylib.degraded_gain(np, gain, safe_t)
            order = np.argsort(-gain, kind="stable")
            # attempt rank k draws fault stream mig_fail[t, k]: a failed
            # hypervisor command consumes its budget slot (the job stays
            # put, nothing charged) and doubles the job's retry backoff
            for k, i in enumerate(order[:cfg.migration_budget]):
                if not gain[i] > 0.0:
                    continue
                j = int(stay[i])
                if fplan is not None and k < fplan.mig_fail.shape[1] \
                        and fplan.mig_fail[t, k]:
                    nf = mig_block.get(j, (0, 0))[1] + 1
                    mig_block[j] = (t + cfg.faults.mig_backoff_h
                                    * (1 << min(nf - 1, 10)), nf)
                    mig_failed += 1
                    continue
                mig.append(j)
                mig_block.pop(j, None)
        migrations += len(mig)
        evictions += evict.size
        movers = list(evict) + mig
        for j in movers:
            njobs[jnode[j]] -= 1
            load_on[jnode[j]] -= jobs.load[j]
            if j in mig:
                mc = (float(em_host.job_energy_kwh(overhead_s, 1,
                                                   int(jobs.chips[j])))
                      * pue_h[jnode[j]] * ci_col[jnode[j]])
                mig_cost_total += mc
                if n_ten:       # overhead belongs to the moving tenant
                    tenant_g[ten[j]] += mc

        # ---- 3. new arrivals (+ deferral policy) --------------------
        arr_jobs = (slo_queue if slo else deferred.pop(t, [])) \
            + by_arrival.pop(t, [])
        # deferral decided after the jitted step computes rates; we peek
        # using the raw trace for the policy signal only when forecasting
        # is off-path (blind engine never defers)
        ev_d = ([-int(jobs.chips[j]) for j in rel_jobs]
                + [-int(jobs.chips[j]) for j in movers]
                + [int(jobs.chips[j]) for j in movers]
                + [int(jobs.chips[j]) for j in arr_jobs])
        ev_n = ([int(jnode[j]) for j in rel_jobs]
                + [int(jnode[j]) for j in movers]
                + [-1] * (len(movers) + len(arr_jobs)))
        E = _pad_bucket(max(len(ev_d), 1))
        dem = np.zeros(E, np.int32)
        tgt = np.full(E, -1, np.int32)
        dem[:len(ev_d)] = ev_d
        tgt[:len(ev_n)] = ev_n
        arr_off = len(rel_jobs) + 2 * len(movers)

        if blind:
            out, cap_h = _place_blind(dem, tgt, cap_h, healthy, rr_ptr,
                                      spread)
            cap = jnp.asarray(cap_h, fleet0.capacity.dtype)
            cur_rate = fut_rate = np.inf
        else:
            strag = jnp.asarray(
                np.asarray(fleet0.straggler_score, np.float64)
                + cfg.consolidate * (njobs == 0), jnp.float32)
            out, cap, n_sw, _, cur_rate, fut_rate = _epoch_step(
                traces, ridx_d, fleet0.pue, fleet0.power_kw,
                fleet0.chips_total, strag,
                fleet0.flops_per_j, region_pue_d, jnp.int32(a), cap,
                jnp.asarray(healthy), jnp.asarray(dem), jnp.asarray(tgt),
                jnp.asarray(fc_ok_t), statics, em_dev)
            out = np.asarray(out)
            cap_h = np.asarray(cap, np.int64)
            sweeps += int(n_sw)
            cur_rate, fut_rate = float(cur_rate), float(fut_rate)
            # safe mode: a stale fleet stops chasing green hours it can no
            # longer see — the inf future rate turns every wants_defer off
            fut_rate = float(policylib.degraded_future(np, fut_rate,
                                                       safe_t))

        # ---- 4. record outcomes -------------------------------------
        # deferrable jobs whose green hour is coming release their slot
        # again (we re-run them next epoch); done post-hoc so the event
        # stream stays identical across engines
        green_later = bool(policylib.wants_defer(fut_rate, cur_rate,
                                                 green_factor))
        keepset: set = set()
        if slo:
            # SLO deferral: queued/new jobs that want to wait compete for
            # the fixed-capacity priority queue (value asc, deadline desc,
            # jid — cheap flexible work rides green windows); overflow and
            # deadline-reached jobs place immediately.  The per-job green
            # comparison runs in float32 so it is bit-identical to the
            # scanned core's.
            cur32, fut32 = np.float32(cur_rate), np.float32(fut_rate)
            cand = []
            for i, j in enumerate(arr_jobs):
                if pol.slack[j] > 0 \
                        and (t - int(jobs.arrive[j])) < int(pol.slack[j]):
                    node = int(out[arr_off + i])
                    if node < 0 or bool(policylib.wants_defer(
                            fut32, cur32, pol.thresh[j])):
                        cand.append(j)
            slo_queue = []
            if cand:
                cj = np.asarray(cand, np.int64)
                order = policylib.slo_queue_order(pol.value[cj],
                                                  pol.deadline_ep[cj], cj)
                slo_queue = [int(cj[k]) for k in order[:q_cap]]
            keepset = set(slo_queue)
        redo_d, redo_n = [], []
        for i, j in enumerate(movers + arr_jobs):
            node = int(out[arr_off - len(movers) + i]) if i < len(movers) \
                else int(out[arr_off + (i - len(movers))])
            is_new = i >= len(movers)
            if is_new:
                if slo:
                    defer_now = j in keepset
                else:
                    defer_now = bool(jobs.deferrable[j]) \
                        and (t - int(jobs.arrive[j])) < cfg.defer_max_h \
                        and (green_later if node >= 0 else True)
                if defer_now:
                    if node >= 0:
                        # take the placement back: defer to next epoch
                        redo_d.append(-int(jobs.chips[j]))
                        redo_n.append(node)
                    if not slo:
                        deferred.setdefault(t + 1, []).append(j)
                    deferred_n += 1
                    continue
            if node < 0:
                jstate[j] = _DROPPED
                dropped += 1
                if is_new and pol.slack[j] > 0:
                    misses += 1
                continue
            if jstate[j] != _ACTIVE:       # first placement
                jstate[j] = _ACTIVE
                jend[j] = t + int(jobs.duration[j])
                ends.setdefault(int(jend[j]), []).append(j)
                if jfirst[j] < 0:
                    jfirst[j] = node
                jstart[j] = t
                delay_h += t - int(jobs.arrive[j])
            jnode[j] = node
            njobs[node] += 1
            load_on[node] += jobs.load[j]
            placed += 1
        if redo_d:
            E2 = _pad_bucket(len(redo_d))
            d2 = np.zeros(E2, np.int32)
            n2 = np.full(E2, -1, np.int32)
            d2[:len(redo_d)] = redo_d
            n2[:len(redo_n)] = redo_n
            if blind:
                _, cap_h = _place_blind(d2, n2, cap_h, healthy, rr_ptr,
                                        spread)
                cap = jnp.asarray(cap_h, fleet0.capacity.dtype)
            else:
                _, cap, _, _, _, _ = _epoch_step(
                    traces, ridx_d, fleet0.pue, fleet0.power_kw,
                    fleet0.chips_total, strag,
                    fleet0.flops_per_j, region_pue_d, jnp.int32(a), cap,
                    jnp.asarray(healthy), jnp.asarray(d2), jnp.asarray(n2),
                    jnp.asarray(fc_ok_t), statics, em_dev)
                cap_h = np.asarray(cap, np.int64)

        # ---- 5. emission accounting ---------------------------------
        # the spread comparator models the paper's baseline: all nodes on
        on = (njobs > 0) if cfg.power_off_idle and not spread \
            else np.ones(N, bool)
        occ = 1.0 - cap_h / np.maximum(chips_total_h, 1)
        energy_kwh = power_h * (em_host.idle_frac
                                + em_host.dyn_frac * occ) * on
        # two-part carbon: operational (Eq. 2) + amortized embodied per
        # on-node-hour; embodied == 0.0 adds exact zeros (bit-neutral)
        node_g = (energy_kwh * pue_h * ci_col
                  + em_host.embodied_g_per_node_h * on)
        series[t] = float(np.sum(node_g))
        emissions += series[t]
        if n_ten:
            # split each on-node's gCO2 across resident jobs proportional
            # to occupied chips; idle/rounding remainder -> last bin, so
            # the bins sum to series[t] exactly (conservation)
            act = np.where(jstate == _ACTIVE)[0]
            occ_chips = np.zeros(N)
            np.add.at(occ_chips, jnode[act], jobs.chips[act])
            share = node_g / np.maximum(occ_chips, 1.0)
            contrib = share[jnode[act]] * jobs.chips[act]
            np.add.at(tenant_g, ten[act], contrib)
            tenant_g[-1] += series[t] - float(contrib.sum())
        if record_matrices:
            util_m[:, t] = load_on
            on_m[:, t] = on.astype(np.float64)

        # ---- 5b. request routing + serving attribution --------------
        # lanes are the epoch's post-placement active jobs; the routing
        # DECISION reads the observed CI column (f32, as the scan core
        # does), the request-carbon ATTRIBUTION reads ground truth (f64)
        if n_svc > 0:
            act_r = np.where(jstate == _ACTIVE)[0]
            jn = jnode[act_r]
            ci_r32 = np.asarray(obs_ci[:, a], np.float32)
            carbon = pue32[jn] * ci_r32[ridx[jn]]
            chips_l = np.asarray(jobs.chips[act_r], np.int64)
            cap_l = lam_cap[np.minimum(chips_l, c_max_r)]
            routed, offered = routerlib.route_epoch(
                np, req_t=np.int32(tplan.req[t]), svc=svc_col[act_r],
                jid=act_r.astype(np.int32), weight=w_col[act_r],
                cap=cap_l, carbon=carbon, n_svc=n_svc, greenness=green32)
            req_served += int(routed.sum())
            req_offered += int(offered[:n_svc].sum())
            req_viol += int(((routed > cap_l)
                             & (svc_col[act_r] >= 0)).sum())
            g_lane = routed.astype(np.float64) * (
                req_kwh * pue_h[jn] * ci_col[jn])
            req_g += float(g_lane.sum())
            p99_l = routerlib.modeled_p99(np, routed, chips_l, c_max_r,
                                          tcfg.mu_per_chip)
            p99_wsum += float((routed.astype(np.float64) * p99_l).sum())
            if n_ten:
                np.add.at(ten_req, ten[act_r], g_lane)

    # jobs still waiting in the deferral queue when the horizon ends were
    # never run: account them as dropped (and as deadline misses — every
    # queued job has slack > 0) so totals reconcile with jobs.n
    for pending in list(deferred.values()) + [slo_queue]:
        for j in pending:
            if jstate[j] == _PENDING:
                jstate[j] = _DROPPED
                dropped += 1
                misses += 1

    emissions += mig_cost_total
    return SimResult(emissions_g=emissions, migration_cost_g=mig_cost_total,
                     rank_sweeps=sweeps, arrivals_placed=placed,
                     jobs_completed=completed, jobs_dropped=dropped,
                     jobs_deferred=deferred_n, migrations=migrations,
                     evictions=evictions, node_log=jnode, first_node=jfirst,
                     emissions_series=series, deadline_misses=misses,
                     defer_delay_h=delay_h, migrations_failed=mig_failed,
                     jobs_active_end=int((jstate == _ACTIVE).sum()),
                     safe_epochs=int(fplan.safe.sum())
                     if fplan is not None else 0,
                     start_epoch=jstart, util=util_m, on=on_m,
                     tenant_emissions_g=tenant_g,
                     req_served=req_served, req_offered=req_offered,
                     req_gco2=req_g, p99_violations=req_viol,
                     req_p99_s=p99_wsum / max(req_served, 1),
                     tenant_request_g=ten_req)


def _place_blind(dem: np.ndarray, tgt: np.ndarray, cap: np.ndarray,
                 healthy: np.ndarray, rr_ptr: list, spread: bool
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Carbon-blind lifecycle comparators: lowest-index first-fit
    (consolidating), or round-robin from a rotating pointer (spreading,
    the paper's baseline policy)."""
    cap = cap.copy()
    N = cap.shape[0]
    out = np.full(dem.shape[0], -1, np.int64)
    for e in range(dem.shape[0]):
        d = int(dem[e])
        if d < 0:
            cap[tgt[e]] -= d
            out[e] = tgt[e]
        elif d > 0:
            feas = np.nonzero((cap >= d) & healthy)[0]
            if not feas.size:
                continue
            if spread:
                nxt = feas[feas >= rr_ptr[0]]
                pick = int(nxt[0]) if nxt.size else int(feas[0])
                rr_ptr[0] = (pick + 1) % N
            else:
                pick = int(feas[0])
            out[e] = pick
            cap[pick] -= d
    return out, cap


# ---------------------------------------------------------------------------
# scan-compiled simulator core: the whole trajectory as ONE lax.scan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ScanPlan:
    """Static shapes for the scanned core, derived from the job schedule.

    Every per-epoch buffer is padded to a *sound* upper bound computed on
    the host, so ``lax.scan`` compiles one fixed-shape trajectory:

    - ``slots``: fixed-capacity job table size — interval bound on
      concurrently-active jobs (a job can hold chips only during
      ``[arrive, arrive + defer_slack + duration)``; drops/evictions only
      shrink activity windows, so the bound cannot be exceeded);
    - ``a_max`` / ``rel_cap`` / ``d_cap``: max new arrivals, end-of-life
      releases, and deferred-arrival carry in any epoch (sliding-window
      counts over the schedule);
    - ``m_evict``: eviction buffer — ``slots`` when outage windows or node
      flapping are configured (everything active could be evicted), else 0.

    The scanned core still counts any bound violation in
    ``overflow`` (belt and braces: a nonzero value is an internal error,
    raised after the scan)."""
    slots: int
    a_max: int
    d_cap: int
    rel_cap: int
    m_evict: int
    arr_ids: np.ndarray     # (T, a_max) int32 job ids arriving per epoch


def _scan_plan(cfg: SimConfig, jobs: JobSchedule, pol: Policy,
               pad: bool = False) -> ScanPlan:
    """Derive the scanned core's static shapes.  ``pad`` rounds every
    buffer up to ``_pad_bucket`` sizes — behavior-neutral (pads are exact
    no-ops) but it lets seed ensembles with slightly different schedules
    share one compiled trajectory, the decisive win for
    ``sweep_policies`` grids."""
    T = cfg.epochs
    arrive = np.asarray(jobs.arrive, np.int64)
    dur = np.asarray(jobs.duration, np.int64)
    slack = pol.slack           # (J,) per-job start slack (policy column)
    in_h = arrive < T           # jobs arriving past the horizon never run
    counts = np.bincount(arrive[in_h], minlength=T) if arrive.size else \
        np.zeros(T, np.int64)
    a_max = max(int(counts.max(initial=0)), 1)
    if pad:
        a_max = _pad_bucket(a_max)
    arr_ids = np.full((T, a_max), -1, np.int32)
    if arrive.size:
        # host by_arrival order: ascending job id within each epoch
        order = np.argsort(arrive, kind="stable")
        order = order[arrive[order] < T]
        ofs = np.concatenate([[0], np.cumsum(counts)])
        pos = np.arange(order.size) - ofs[arrive[order]]
        arr_ids[arrive[order], pos] = order
    hi = T + int((dur + slack).max(initial=0)) + 2
    diff = np.zeros(hi, np.int64)
    np.add.at(diff, arrive[in_h], 1)
    np.add.at(diff, (arrive + slack + dur)[in_h], -1)
    slots = max(int(np.cumsum(diff).max(initial=0)), a_max, 1,
                int(cfg.scan_slots))
    # EOL release epoch lies in [arrive + dur, arrive + dur + slack]
    rdiff = np.zeros(hi, np.int64)
    np.add.at(rdiff, np.minimum((arrive + dur)[in_h], hi - 1), 1)
    np.add.at(rdiff, np.minimum((arrive + dur + slack)[in_h] + 1, hi - 1),
              -1)
    rel_cap = max(int(np.cumsum(rdiff)[:T].max(initial=0)), 1)
    if pol.slo:
        # SLO: the carry IS the fixed-capacity priority queue
        d_cap = pol.queue_cap(T) if bool((slack[in_h] > 0).sum()) else 0
    else:
        # reactive deferral carry: the same occupancy bound, always sound
        # (the overflow counter turns any violation into a raised error)
        d_cap = policylib.sound_queue_bound(arrive, slack, T)
    if pad:
        slots = _pad_bucket(slots)
        rel_cap = _pad_bucket(rel_cap)
        if d_cap > 0 and not pol.slo:   # the SLO queue cap is semantic
            d_cap = _pad_bucket(d_cap)
    # flapping nodes force evictions exactly like outage windows do, so
    # either fault source sizes the eviction buffer
    flaps = cfg.faults is not None and cfg.faults.flap_rate > 0.0
    m_evict = slots if (_outage_windows(cfg.outage) or flaps) else 0
    return ScanPlan(slots=slots, a_max=a_max, d_cap=d_cap, rel_cap=rel_cap,
                    m_evict=m_evict, arr_ids=arr_ids)


def _traj_scan(arrs, statics, dims, ensemble: bool, mesh=None):
    """The whole trajectory as one ``lax.scan``: fixed-size slot table +
    padded event buffers around the shared ``_place_epoch`` epoch graph.

    The epoch body is split into ``epoch_pre`` (releases, evictions +
    migration policy, event-stream build) and ``epoch_post`` (outcome
    recording, deferral queues, emission accounting) around the
    placement event loop.  Both halves are loop-free masked tensor ops,
    so the batched ensemble (``ensemble=True``) maps them over a leading
    lane axis with a plain ``vmap`` and drives the hand-batched
    placement engine (``placement.place_lifecycle_batched``) in between
    — one compiled scan for the whole (seed x policy) grid, with O(N)
    sweep work per sweep-round instead of per event (vmapping the
    sequential engine would execute both ``lax.cond`` branches per
    event).  ``ensemble=False`` is the unchanged sequential core:
    identical ops, one trajectory.  ``mesh`` is the ensemble's device
    mesh when its buffers are sharded (see ``_shard_over_e``).

    Hot-path structure (all bitwise-neutral vs the host loop's per-epoch
    graph):
    - the FCFP forecast only depends on the static traces, so it is batched
      over all T epochs up front and fed to the scan as ``xs``;
    - an epoch's releases are commutative capacity edits on a dirty engine,
      so they are applied as one scatter and the engine starts at the
      post-release capacity (``_place_epoch``'s ``cap_start``) — the event
      loop only carries arrivals;
    - the migration policy's best-feasible-rate per chip demand exploits
      ``rate = pue · ci_region``: within a region the rate order is the
      static pue order, so a cummax of free capacity along that order plus
      a searchsorted replaces a fleet-wide scatter-min."""
    (T, S, a_max, d_cap, rel_cap, m_evict, budget, chips_max, history_h,
     defer_max_h, outage, power_off_idle, consolidate, n_ten,
     pcfg, fkey, n_svc) = dims
    faulty, fault_mig, fault_flap = fkey     # faults.fault_graph_key
    N = arrs["capacity"].shape[-1]
    engine, shortlist = statics[0], statics[1]
    weights = statics[3]
    horizon_h, use_forecast = statics[4], statics[6]
    defer_window = statics[7]
    fc_fallback = statics[8]
    budget = min(budget, S)     # can't migrate more jobs than can be active
    slo = pcfg.deferral == "slo"
    planner = pcfg.migration == "lookahead" and use_forecast and budget > 0
    m_cap = budget + m_evict
    n_narr = d_cap + a_max
    NARR = m_cap                # event stream: [mover arrivals | new]
    has_defer = d_cap > 0
    alloc_cap = min(S, n_narr)
    EV = m_cap + n_narr         # padded event-buffer width
    INT_MAX = jnp.int32(2 ** 31 - 1)
    arange_s = jnp.arange(S, dtype=jnp.int32)
    # the per-run EnergyModel rides through ``arrs`` as traced f32 data
    # (``en_*`` scalars, lowered host-side by ``_build_arrs``) — an
    # (idle-frac x embodied x marginal) calibration grid shares this one
    # compiled trajectory, on the Pallas path too (the kernel consumes
    # the same scalars through its en_* VMEM block).
    use_kernel, interpret = statics[2], statics[9]
    if slo:
        arange_e = jnp.arange(n_narr, dtype=jnp.int32)
        # effective queue capacity: a traced per-run scalar <= the static
        # buffer width d_cap, so ensemble members with different (semantic)
        # SLO queue caps share one compiled trajectory; the sequential
        # path passes q_cap == d_cap, making the mask an exact no-op
        arange_d = jnp.arange(d_cap, dtype=jnp.int32)
    ts = jnp.arange(T, dtype=jnp.int32)

    def take(arr, idx, valid, fill):
        """Masked gather that never reads a clamped junk lane."""
        v = arr[jnp.clip(idx, 0, arr.shape[0] - 1)]
        return jnp.where(valid, v, fill)

    def build_xs(arrs):
        """Hoisted forecast: identical per-window math as _epoch_core,
        vmapped over epochs (the windows depend only on the constant
        traces).  Per-trajectory — the ensemble vmaps it over lanes."""
        traces = arrs["traces"]
        xs = {"t": ts, "arr": arrs["arr_ids"]}
        if n_svc > 0:
            xs["req"] = arrs["tr_req"]
        if faulty:
            xs["safe"] = arrs["f_safe"]
            if fault_flap:
                xs["elig"] = arrs["f_elig"]
            if fault_mig and budget > 0:
                xs["mig_fail"] = arrs["f_mig_fail"][:, :budget]
        if use_forecast:
            wins = jax.vmap(lambda t: jax.lax.dynamic_slice_in_dim(
                traces, t, history_h, axis=1))(ts)
            fc = jax.vmap(
                lambda w: forecast.forecast_regions(w, horizon_h, 0)[0])(
                wins)
            if fc_fallback:
                # forecast-service outage epochs fall back to the
                # persistence-of-day forecast over the same observed
                # window (identical select as _epoch_core, batched)
                fcp = jax.vmap(lambda w: forecast.persistence_regions(
                    w, horizon_h))(wins)
                fc = jnp.where(arrs["f_fc_ok"][:, None, None], fc, fcp)
            xs["ci_fc_r"] = jnp.mean(fc, axis=-1)                 # (T, R)
            # node-less regions masked (their fc * inf sentinel would be
            # NaN when the clamped forecast is exactly 0)
            rp_ok = jnp.isfinite(arrs["region_pue"])
            fut = jnp.min(jnp.where(
                rp_ok[None, :, None],
                fc[:, :, :defer_window]
                * arrs["region_pue"][None, :, None],
                jnp.inf), axis=(1, 2))                            # (T,)
            xs["fut"] = policylib.degraded_future(
                jnp, fut, arrs["f_safe"]) if faulty else fut
            if planner:
                # green-window planner signals, batched over all epochs
                # (the host loop computes the same reduction via
                # ``_lookahead_signals`` so both drivers read identical
                # f32 forecast signals)
                la_ci, gw_min = forecast.green_window_signals(
                    fc, arrs["region_pue"], pcfg.lookahead_h,
                    pcfg.discount)
                xs["la_ci"] = la_ci                               # (T, R)
                xs["la_dst"] = jnp.min(
                    jnp.where(rp_ok[None, :],
                              la_ci * arrs["region_pue"][None, :],
                              jnp.inf), axis=-1)                  # (T,)
                xs["gw_min"] = gw_min                             # (T,)
        return xs

    @jax.named_scope("epoch_pre")
    def epoch_pre(arrs, carry, x):
        """Epoch parts 1-3: EOL releases, evictions + migration policy,
        and the compacted arrival-event stream — everything the placement
        engine consumes, plus the intermediates ``epoch_post`` needs."""
        traces, ridx = arrs["traces"], arrs["ridx"]
        pue = arrs["pue"]
        chips_d = arrs["chips"]
        (cap, njobs, slot_jid, slot_node, slot_end, defer_ids, mig_cost,
         overflow) = carry[:8]
        if fault_mig:
            mig_until, mig_nfail = carry[8], carry[9]
        else:
            mig_until = mig_nfail = None
        t, arr_row = x["t"], x["arr"]
        a = t + history_h
        healthy = arrs["healthy"]
        for reg, t0, length in outage:
            healthy = healthy & ~((t >= t0) & (t < t0 + length)
                                  & (ridx == reg))
        if fault_flap:
            healthy = healthy & x["elig"]
        ci_col_r = jax.lax.dynamic_slice_in_dim(traces, a, 1, axis=1)[:, 0]
        ci_col = ci_col_r[ridx]
        # decisions read the observed column (ci_col); accounting and
        # migration-cost charging read ground truth (the same tensor when
        # no faults are configured — the graph is unchanged)
        ci_true = jax.lax.dynamic_slice_in_dim(
            arrs["traces_true"], a, 1, axis=1)[:, 0][ridx] if faulty \
            else ci_col
        occupied = slot_jid >= 0

        # ---- 1. end-of-life releases (vector mask; on a dirty engine
        # releases are commutative capacity edits, so they are applied as
        # one scatter instead of consuming event-loop slots) ------------
        rel_mask = occupied & (slot_end == t)
        completed_t = jnp.sum(rel_mask.astype(jnp.int32))
        rel_idx = jnp.nonzero(rel_mask, size=rel_cap, fill_value=S)[0]
        rel_valid = rel_idx < S
        rel_node = take(slot_node, rel_idx, rel_valid, -1)
        rel_jid = take(slot_jid, rel_idx, rel_valid, -1)
        rel_chips = take(chips_d, jnp.maximum(rel_jid, 0), rel_valid, 0)
        njobs = njobs.at[jnp.where(rel_valid, rel_node, N)].add(
            -1, mode="drop")
        slot_jid = jnp.where(rel_mask, -1, slot_jid)
        overflow = overflow + jnp.maximum(completed_t - rel_cap, 0)

        # ---- 2. forced evictions + migration policy ------------------
        occupied2 = slot_jid >= 0
        node_healthy = take(healthy, slot_node, occupied2, False)
        stay_mask = occupied2 & node_healthy
        seg_slot, seg_ok = [], []
        evictions_t = jnp.int32(0)
        migrations_t = jnp.int32(0)
        failed_t = jnp.int32(0)
        mig_cost_t = jnp.float32(0.0)
        if m_evict > 0:
            evict_mask = occupied2 & ~node_healthy
            evictions_t = jnp.sum(evict_mask.astype(jnp.int32))
            ekey = jnp.where(evict_mask, slot_jid, INT_MAX)
            ekey_s, evict_slot = jax.lax.sort((ekey, arange_s), num_keys=1)
            seg_slot.append(evict_slot[:m_evict])
            seg_ok.append(ekey_s[:m_evict] < INT_MAX)
        if budget > 0:
            rate = jnp.where(healthy, pue * ci_col, jnp.inf)
            # best achievable CFP rate per chip demand, O(N + R·C):
            # within a region rate order == static pue order, so the first
            # prefix (in pue order) whose free-capacity cummax covers the
            # demand holds the region's min feasible rate
            perm, pue_sorted = arrs["mig_perm"], arrs["mig_pue"]
            capg = take(jnp.where(healthy, cap, -1), perm, perm < N, -1)
            cmax = jax.lax.cummax(capg, axis=1)
            cr = jnp.arange(chips_max + 1, dtype=jnp.int32)
            idx = jax.vmap(
                lambda row: jnp.searchsorted(row, cr, side="left"))(cmax)
            ok = idx < perm.shape[1]
            pb = jnp.take_along_axis(
                pue_sorted, jnp.clip(idx, 0, perm.shape[1] - 1), axis=1)
            best_ge = jnp.min(
                jnp.where(ok, pb * ci_col_r[:, None], jnp.inf), axis=0)
            s_chips = take(chips_d, jnp.maximum(slot_jid, 0), stay_mask, 0)
            br = best_ge[jnp.clip(s_chips, 0, chips_max)]
            rate_cur = take(rate, slot_node, stay_mask, jnp.inf)
            remaining = jnp.maximum(slot_end - t, 0).astype(jnp.float32)
            chips_f = s_chips.astype(jnp.float32)
            la_kw = {}
            if planner:
                la_node = x["la_ci"][ridx] * pue             # (N,) f32
                la_kw = dict(
                    src_la=take(la_node, slot_node, stay_mask,
                                jnp.float32(0.0)),
                    dst_la=x["la_dst"], gw_min=x["gw_min"])
            gain = policylib.migration_gain(
                jnp, pcfg, rate_cur=rate_cur, best_rate=br, chips=chips_f,
                remaining=remaining, e_kwh_h=arrs["en_ekwh"],
                ckpt=arrs["en_ckpt"] * chips_f,
                green_gate=arrs["green_gate"], **la_kw)
            if fault_mig:
                # retry-with-backoff: slots whose last actuation failed
                # are frozen out of the candidate sort until the backoff
                # ends (same -inf freeze as the host's mig_block dict)
                gain = jnp.where(stay_mask & (mig_until > t),
                                 -jnp.inf, gain)
            if faulty:
                gain = policylib.degraded_gain(jnp, gain, x["safe"])
            mk1 = jnp.where(stay_mask, -gain, jnp.inf)
            mk2 = jnp.where(stay_mask, slot_jid, INT_MAX)
            _, _, mig_slot = jax.lax.sort((mk1, mk2, arange_s), num_keys=2)
            mig_slot = mig_slot[:budget]
            mig_ok = stay_mask[mig_slot] & (gain[mig_slot] > 0.0)
            if fault_mig:
                # attempt rank k draws fault stream mig_fail[t, k]: the
                # failed command consumes its budget slot (the job stays
                # put, nothing charged) and doubles the retry backoff;
                # a later success resets the slot's backoff state
                fail = mig_ok & x["mig_fail"]
                mig_ok = mig_ok & ~x["mig_fail"]
                failed_t = jnp.sum(fail.astype(jnp.int32))
                nf1 = take(mig_nfail, mig_slot, fail, 0) + 1
                until = t + arrs["mig_backoff"] * (
                    jnp.int32(1) << jnp.minimum(nf1 - 1, 10))
                mig_until = mig_until.at[
                    jnp.where(fail, mig_slot, S)].set(until, mode="drop")
                mig_nfail = mig_nfail.at[
                    jnp.where(fail, mig_slot, S)].set(nf1, mode="drop")
                mig_until = mig_until.at[
                    jnp.where(mig_ok, mig_slot, S)].set(0, mode="drop")
                mig_nfail = mig_nfail.at[
                    jnp.where(mig_ok, mig_slot, S)].set(0, mode="drop")
            migrations_t = jnp.sum(mig_ok.astype(jnp.int32))
            mnode = jnp.clip(slot_node[mig_slot], 0, N - 1)
            mchip = chips_d[jnp.maximum(slot_jid[mig_slot], 0)]
            # per-mover overhead cost kept as a vector so attribution can
            # charge each migration to its mover's tenant
            mc_vec = jnp.where(
                mig_ok,
                arrs["en_ckpt"] * mchip.astype(jnp.float32)
                * pue[mnode] * ci_true[mnode], 0.0)
            mig_cost_t = jnp.sum(mc_vec)
            seg_slot.append(mig_slot)
            seg_ok.append(mig_ok)
        if m_cap > 0:
            mov_slot = jnp.concatenate(seg_slot)
            mov_ok = jnp.concatenate(seg_ok)
            mov_jid = take(slot_jid, mov_slot, mov_ok, -1)
            mov_old = take(slot_node, mov_slot, mov_ok, -1)
            mov_chips = take(chips_d, jnp.maximum(mov_jid, 0), mov_ok, 0)
            njobs = njobs.at[jnp.where(mov_ok, mov_old, N)].add(
                -1, mode="drop")
        else:
            mov_slot = mov_jid = mov_old = mov_chips = \
                jnp.zeros((0,), jnp.int32)
            mov_ok = jnp.zeros((0,), bool)

        # ---- 3. apply release credits, build the arrival stream -------
        strag = arrs["straggler"] + consolidate \
            * (njobs == 0).astype(jnp.float32)
        cap_start = cap.at[jnp.where(rel_valid, rel_node, N)].add(
            rel_chips, mode="drop").at[jnp.where(mov_ok, mov_old, N)].add(
            mov_chips, mode="drop")
        narr_jid = jnp.concatenate([defer_ids, arr_row]) if has_defer \
            else arr_row
        narr_chips = take(chips_d, jnp.maximum(narr_jid, 0),
                          narr_jid >= 0, 0)
        dem_full = jnp.concatenate([mov_chips, narr_chips])
        # compact the stream: pads are exact no-ops for the engine, so the
        # loop only walks the real arrivals (order preserved) and stops at
        # their count — the dominant CPU win for the scanned core
        ev_idx = jnp.nonzero(dem_full > 0, size=EV, fill_value=EV)[0]
        n_ev = jnp.sum((dem_full > 0).astype(jnp.int32))
        dem = take(dem_full, ev_idx, ev_idx < EV, 0)
        if use_forecast:
            ci_fc = x["ci_fc_r"][ridx]
            fut_rate = x["fut"]
        else:
            ci_fc = ci_col
            fut_rate = jnp.float32(jnp.inf)
        cur_rate = jnp.min(jnp.where(healthy, ci_col * pue, jnp.inf))
        mid = dict(cap_ctx=cap, ci_col=ci_col, ci_fc=ci_fc,
                   healthy=healthy, strag=strag, cap_start=cap_start,
                   dem=dem, n_ev=n_ev, ev_idx=ev_idx, fut_rate=fut_rate,
                   cur_rate=cur_rate, t=t, njobs=njobs,
                   slot_jid=slot_jid, slot_node=slot_node,
                   slot_end=slot_end, mov_slot=mov_slot, mov_jid=mov_jid,
                   narr_jid=narr_jid, narr_chips=narr_chips,
                   completed_t=completed_t, evictions_t=evictions_t,
                   migrations_t=migrations_t, mig_cost_t=mig_cost_t,
                   mig_cost=mig_cost, overflow=overflow,
                   ci_true=ci_true, failed_t=failed_t)
        if n_svc > 0:
            mid["req_t"] = x["req"]
        if budget > 0 and n_ten > 0:
            # mover tenants read pre-update slot_jid (still valid here);
            # mc_vec is zero for non-winning lanes so junk indices are
            # harmless under mode="drop" scatter-adds
            mid.update(mc_vec=mc_vec, mig_ten=arrs["tenant"][
                jnp.maximum(slot_jid[mig_slot], 0)])
        if fault_mig:
            mid.update(mig_until=mig_until, mig_nfail=mig_nfail)
        return mid

    @jax.named_scope("epoch_post")
    def epoch_post(arrs, mid, out_c, cap2, n_sw, walk_counts, rounds):
        """Epoch parts 4-5: scatter the compacted placements back, record
        mover/arrival outcomes, run the deferral queue admission, and
        account emissions — returns the scan (carry, ys).  The engine's
        ``walk_counts`` and ``rounds`` (None where the engine has none)
        pass through to ys."""
        pue, power_kw = arrs["pue"], arrs["power_kw"]
        chips_total = arrs["chips_total"]
        dur_d, arrive_d = arrs["duration"], arrs["arrive"]
        defer_d = arrs["deferrable"]
        t = mid["t"]
        ci_col, fut_rate = mid["ci_col"], mid["fut_rate"]
        cur_rate = mid["cur_rate"]
        njobs, slot_jid = mid["njobs"], mid["slot_jid"]
        slot_node, slot_end = mid["slot_node"], mid["slot_end"]
        mov_slot, mov_jid = mid["mov_slot"], mid["mov_jid"]
        narr_jid, narr_chips = mid["narr_jid"], mid["narr_chips"]
        overflow = mid["overflow"]
        out = jnp.full((EV,), -1, jnp.int32).at[mid["ev_idx"]].set(
            out_c, mode="drop")

        # ---- 4. record outcomes --------------------------------------
        green = policylib.wants_defer(fut_rate, cur_rate,
                                      arrs["green_factor"])
        placed_t = jnp.int32(0)
        dropped_t = jnp.int32(0)
        if m_cap > 0:
            mnode_new = out[:m_cap]
            mov_win = (mov_jid >= 0) & (mnode_new >= 0)
            mov_fail = (mov_jid >= 0) & (mnode_new < 0)
            slot_node = slot_node.at[jnp.where(mov_win, mov_slot, S)].set(
                mnode_new, mode="drop")
            slot_jid = slot_jid.at[jnp.where(mov_fail, mov_slot, S)].set(
                -1, mode="drop")
            njobs = njobs.at[jnp.where(mov_win, mnode_new, N)].add(
                1, mode="drop")
            placed_t += jnp.sum(mov_win.astype(jnp.int32))
            dropped_t += jnp.sum(mov_fail.astype(jnp.int32))
            ys_mov_node = jnp.where(mov_win, mnode_new, -1)
        else:
            ys_mov_node = jnp.zeros((0,), jnp.int32)
        nnode = out[NARR:]
        valid = narr_jid >= 0
        jsafe = jnp.maximum(narr_jid, 0)
        if has_defer and slo:
            slack_d, thresh_d = arrs["slack"], arrs["thresh"]
            value_d, deadline_d = arrs["value"], arrs["deadline"]
            # SLO deferral: candidates that want to wait (green for THEIR
            # value-tightened threshold, or unplaced, inside their own
            # slack window) compete for the fixed-capacity priority queue
            # on the shared (value asc, deadline desc, jid) key — same
            # admission and storage order as the host's lexsort
            in_win = (t - arrive_d[jsafe]) < slack_d[jsafe]
            can_defer = valid & (slack_d[jsafe] > 0) & in_win
            green_j = policylib.wants_defer(fut_rate, cur_rate,
                                            thresh_d[jsafe])
            want = can_defer & jnp.where(nnode >= 0, green_j, True)
            k1 = jnp.where(want, value_d[jsafe], jnp.inf)
            k2 = jnp.where(want, -deadline_d[jsafe], INT_MAX)
            k3 = jnp.where(want, narr_jid, INT_MAX)
            k1s, _, _, perm = jax.lax.sort((k1, k2, k3, arange_e),
                                           num_keys=3)
            sel_ok = jnp.isfinite(k1s[:d_cap]) & (arange_d < arrs["q_cap"])
            sel_idx = perm[:d_cap]
            defer_again = jnp.zeros((n_narr,), bool).at[
                jnp.where(sel_ok, sel_idx, n_narr)].set(True, mode="drop")
            takeback = defer_again & (nnode >= 0)
            cap2 = cap2.at[jnp.where(takeback, nnode, N)].add(
                narr_chips, mode="drop")
            deferred_t = jnp.sum(defer_again.astype(jnp.int32))
            # the queue carries in priority order (urgent overflow placed
            # this epoch, not dropped — no overflow accounting by design)
            defer_ids = jnp.where(sel_ok, narr_jid[sel_idx], -1)
        elif has_defer:
            in_win = (t - arrive_d[jsafe]) < defer_max_h
            can_defer = valid & defer_d[jsafe] & in_win
            takeback = can_defer & green & (nnode >= 0)
            defer_again = takeback | (can_defer & (nnode < 0))
            # taken-back placements release their chips again (the host
            # loop's redo call is a pure-release engine pass == scatter)
            cap2 = cap2.at[jnp.where(takeback, nnode, N)].add(
                narr_chips, mode="drop")
            deferred_t = jnp.sum(defer_again.astype(jnp.int32))
            didx = jnp.nonzero(defer_again, size=d_cap,
                               fill_value=n_narr)[0]
            defer_ids = take(narr_jid, didx, didx < n_narr, -1)
            overflow = overflow + jnp.maximum(deferred_t - d_cap, 0)
        else:
            takeback = defer_again = jnp.zeros(nnode.shape, bool)
            deferred_t = jnp.int32(0)
            defer_ids = jnp.full((d_cap,), -1, jnp.int32)
        place_new = valid & (nnode >= 0) & ~takeback
        drop_new = valid & (nnode < 0) & ~defer_again
        # a dropped job is a deadline miss only if it ever HAD start slack
        # (host counts via pol.slack > 0, which is defer_max_h-gated for
        # the reactive policy — mirror that, or the counters drift at
        # defer_max_h == 0)
        if slo:
            slackable = arrs["slack"][jsafe] > 0
        elif defer_max_h > 0:
            slackable = defer_d[jsafe]
        else:
            slackable = jnp.zeros(jsafe.shape, bool)
        miss_t = jnp.sum((drop_new & slackable).astype(jnp.int32))
        free_idx = jnp.nonzero(slot_jid < 0, size=alloc_cap,
                               fill_value=S)[0]
        rank = jnp.cumsum(place_new.astype(jnp.int32)) - 1
        tgt_slot = jnp.where(
            place_new & (rank < alloc_cap),
            free_idx[jnp.clip(rank, 0, alloc_cap - 1)], S)
        overflow = overflow + jnp.sum(
            (place_new & (tgt_slot >= S)).astype(jnp.int32))
        slot_jid = slot_jid.at[tgt_slot].set(narr_jid, mode="drop")
        slot_node = slot_node.at[tgt_slot].set(nnode, mode="drop")
        slot_end = slot_end.at[tgt_slot].set(t + dur_d[jsafe], mode="drop")
        njobs = njobs.at[jnp.where(place_new, nnode, N)].add(
            1, mode="drop")
        placed_t += jnp.sum(place_new.astype(jnp.int32))
        dropped_t += jnp.sum(drop_new.astype(jnp.int32))

        # ---- 5. emission accounting ----------------------------------
        # always at the TRUE carbon intensity — faults degrade what the
        # policies see, not what the grid actually emitted.  The operating
        # charge and the amortized embodied charge both gate on ``on``;
        # with the default model's embodied == 0 the added term is an
        # exact elementwise +0.0, so e_t stays bitwise historical.
        on = (njobs > 0) if power_off_idle else jnp.ones((N,), bool)
        occ = 1.0 - cap2.astype(jnp.float32) \
            / jnp.maximum(chips_total.astype(jnp.float32), 1.0)
        energy = power_kw * (arrs["en_idle"]
                             + arrs["en_dyn"] * occ) * on
        node_g = energy * pue * mid["ci_true"] \
            + arrs["en_embodied"] * on
        e_t = jnp.sum(node_g)
        if n_ten > 0:
            # per-tenant attribution from the POST-update slot tables:
            # each on-node's gCO2 is split across its resident jobs
            # proportionally to occupied chips; the idle/rounding
            # remainder lands in the extra bin n_ten (conservation by
            # construction, same split as the host loop's np.add.at)
            occ3 = slot_jid >= 0
            s_jid = jnp.maximum(slot_jid, 0)
            s_chips = jnp.where(
                occ3, arrs["chips"][s_jid], 0).astype(jnp.float32)
            occ_chips = jnp.zeros((N,), jnp.float32).at[
                jnp.where(occ3, slot_node, N)].add(s_chips, mode="drop")
            share = node_g / jnp.maximum(occ_chips, 1.0)
            contrib = jnp.where(
                occ3, share[jnp.clip(slot_node, 0, N - 1)] * s_chips, 0.0)
            ten_t = jnp.zeros((n_ten + 1,), jnp.float32).at[
                jnp.where(occ3, arrs["tenant"][s_jid], n_ten)].add(
                contrib, mode="drop")
            ten_t = ten_t.at[n_ten].add(e_t - jnp.sum(contrib))
            if budget > 0:
                # migration overhead is charged to the mover's tenant
                ten_t = ten_t.at[mid["mig_ten"]].add(
                    mid["mc_vec"], mode="drop")
        else:
            ten_t = jnp.zeros((1,), jnp.float32)

        if n_svc > 0:
            with jax.named_scope("router"):
                # ---- 5b. request routing + serving attribution -----------
                # lanes are the POST-update slot tables (the host routes over
                # the end-of-epoch active set); the routing DECISION reads
                # the observed CI column — mid["ci_col"] is degraded under
                # faults, exactly like every placement decision above — and
                # the request-carbon ATTRIBUTION reads ground truth.  All
                # arithmetic inside route_epoch is int32 except two pinned
                # f32 ops, so routed/offered match the host loop bit-exactly
                # (see repro.core.router).
                occ_r = slot_jid >= 0
                r_jid = jnp.maximum(slot_jid, 0)
                svc_l = jnp.where(occ_r, arrs["svc"][r_jid], -1)
                w_l = jnp.where(occ_r, arrs["qweight"][r_jid], 0)
                chips_l = jnp.where(occ_r, arrs["chips"][r_jid], 0)
                cap_l = jnp.where(
                    occ_r, arrs["lam_cap"][jnp.clip(chips_l, 0, chips_max)],
                    0)
                node_l = jnp.clip(slot_node, 0, N - 1)
                carbon_l = pue[node_l] * mid["ci_col"][node_l]
                routed, offered = routerlib.route_epoch(
                    jnp, req_t=mid["req_t"], svc=svc_l, jid=slot_jid,
                    weight=w_l, cap=cap_l, carbon=carbon_l, n_svc=n_svc,
                    greenness=arrs["greenness"])
                served_t = jnp.sum(routed)
                offered_t = jnp.sum(offered[:n_svc])
                viol_t = jnp.sum(((routed > cap_l)
                                  & (svc_l >= 0)).astype(jnp.int32))
                g_lane = routed.astype(jnp.float32) * (
                    arrs["en_reqkwh"] * (pue[node_l] * mid["ci_true"][node_l]))
                reqg_t = jnp.sum(g_lane)
                p99_l = routerlib.modeled_p99(jnp, routed, chips_l,
                                              chips_max, arrs["tr_mu"])
                p99w_t = jnp.sum(routed.astype(jnp.float32) * p99_l)
                if n_ten > 0:
                    tenreq_t = jnp.zeros((n_ten + 1,), jnp.float32).at[
                        jnp.where(occ_r, arrs["tenant"][r_jid], n_ten)].add(
                        g_lane, mode="drop")
                else:
                    tenreq_t = jnp.zeros((1,), jnp.float32)

        carry = (cap2, njobs, slot_jid, slot_node, slot_end, defer_ids,
                 mid["mig_cost"] + mid["mig_cost_t"], overflow)
        if fault_mig:
            # a reused slot belongs to a fresh job with no failure history
            carry = carry + (
                mid["mig_until"].at[tgt_slot].set(0, mode="drop"),
                mid["mig_nfail"].at[tgt_slot].set(0, mode="drop"))
        ys = (e_t, n_sw, mid["completed_t"], dropped_t, placed_t,
              deferred_t, mid["migrations_t"], mid["evictions_t"], miss_t,
              mov_jid, ys_mov_node,
              jnp.where(place_new, narr_jid, -1),
              jnp.where(place_new, nnode, -1),
              overflow, mid["failed_t"], ten_t, walk_counts, rounds)
        if n_svc > 0:
            ys = ys + (served_t, offered_t, viol_t, reqg_t, p99w_t,
                       tenreq_t)
        return carry, ys

    # traced EnergyModel twin for the placement engines ((L,) leaves in
    # the ensemble — the batched ctx builder vmaps over them); the Pallas
    # sweep consumes the same model via the en_* scalar block
    em_tr = EnergyModel(
        idle_frac=arrs["en_idle"], chip_power_w=arrs["en_chipw"],
        host_power_w=arrs["en_hostw"],
        embodied_g_per_node_h=arrs["en_embodied"],
        w_marginal=arrs["en_wmarg"], dyn_frac=arrs["en_dyn"])

    if not ensemble:
        xs = build_xs(arrs)

        def body(carry, x):
            mid = epoch_pre(arrs, carry, x)
            tgt = jnp.full((EV,), -1, jnp.int32)
            out_c, cap2, n_sw, wc = _place_epoch(
                arrs["pue"], arrs["power_kw"], arrs["chips_total"],
                mid["strag"], arrs["flops_per_j"], mid["ci_col"],
                mid["ci_fc"], mid["cap_ctx"], mid["cap_start"],
                mid["healthy"], mid["dem"], tgt, statics,
                n_events=mid["n_ev"], eager_sweep=True, energy=em_tr)
            return epoch_post(arrs, mid, out_c, cap2, n_sw, wc, None)

        init = (arrs["capacity"], jnp.zeros((N,), jnp.int32),
                jnp.full((S,), -1, jnp.int32), jnp.zeros((S,), jnp.int32),
                jnp.zeros((S,), jnp.int32),
                jnp.full((d_cap,), -1, jnp.int32),
                jnp.float32(0.0), jnp.int32(0))
        if fault_mig:
            init = init + (jnp.zeros((S,), jnp.int32),
                           jnp.zeros((S,), jnp.int32))
        return jax.lax.scan(body, init, xs)

    # --- batched ensemble: vmapped pre/post around the batched engine ---
    L = arrs["capacity"].shape[0]
    xs = jax.vmap(build_xs)(arrs)
    xs = jax.tree_util.tree_map(lambda a: jnp.moveaxis(a, 0, 1), xs)
    vpre = jax.vmap(epoch_pre)
    vpost = jax.vmap(epoch_post)

    def body(carry, x):
        mid = vpre(arrs, carry, x)
        # the same Fleet _place_epoch builds, with (L, N) leaves
        fleet = Fleet(ci_now=mid["ci_col"].astype(jnp.float32),
                      ci_forecast=mid["ci_fc"].astype(jnp.float32),
                      pue=arrs["pue"], power_kw=arrs["power_kw"],
                      capacity=mid["cap_ctx"], healthy=mid["healthy"],
                      straggler_score=mid["strag"],
                      flops_per_j=arrs["flops_per_j"],
                      chips_total=arrs["chips_total"])
        with jax.named_scope("placement_walk"):
            out_c, cap2, n_sw, wc, rounds = place_lifecycle_batched(
                fleet, mid["dem"], weights, horizon_h=1.0, engine=engine,
                shortlist=shortlist, use_kernel=use_kernel,
                interpret=interpret, capacity=mid["cap_start"],
                n_events=mid["n_ev"], energy=em_tr, mesh=mesh)
        if rounds is not None:      # one count for the bucket, per lane
            rounds = jnp.broadcast_to(rounds, (L,))
        return vpost(arrs, mid, out_c, cap2, n_sw, wc, rounds)

    init = (arrs["capacity"], jnp.zeros((L, N), jnp.int32),
            jnp.full((L, S), -1, jnp.int32), jnp.zeros((L, S), jnp.int32),
            jnp.zeros((L, S), jnp.int32),
            jnp.full((L, d_cap), -1, jnp.int32),
            jnp.zeros((L,), jnp.float32), jnp.zeros((L,), jnp.int32))
    if fault_mig:
        init = init + (jnp.zeros((L, S), jnp.int32),
                       jnp.zeros((L, S), jnp.int32))
    carry, ys = jax.lax.scan(body, init, xs)
    return carry, jax.tree_util.tree_map(
        lambda a: jnp.moveaxis(a, 0, 1), ys)


def _scan_traj_impl(arrs, statics, dims):
    return _traj_scan(arrs, statics, dims, ensemble=False)


_scan_trajectory = jax.jit(_scan_traj_impl,
                           static_argnames=("statics", "dims"))


@functools.partial(jax.jit, static_argnames=("statics", "dims", "mesh"),
                   donate_argnums=(0,))
def _ensemble_trajectory(arrs, statics, dims, mesh=None):
    """E stacked trajectories as ONE compiled program (see ``_traj_scan``
    with ``ensemble=True``).  The stacked input buffers are donated (they
    are rebuilt per call; the scan carries alias them on backends that
    support donation)."""
    return _traj_scan(arrs, statics, dims, ensemble=True, mesh=mesh)


@dataclasses.dataclass
class _ScanRun:
    """One prepared trajectory: schedule-derived plan + static graph key,
    ready to be built into scan inputs — alone (``simulate_fleet_scan``)
    or stacked into an ensemble bucket whose buffer dims are the
    member-wise maxima (``simulate_fleet_ensemble``)."""
    fleet0: Fleet
    region_ci: np.ndarray
    ridx: np.ndarray
    cfg: SimConfig
    jobs: JobSchedule
    pol: Policy
    plan: ScanPlan
    statics: tuple
    mig_nmax: int           # widest region (rows of the mig_perm table)
    fplan: Optional[FaultPlan] = None   # materialized fault streams
    tplan: Optional[TrafficPlan] = None  # materialized request stream


def _prepare_scan_run(fleet0: Fleet, region_ci: np.ndarray,
                      ridx: np.ndarray, cfg: SimConfig,
                      jobs: Optional[JobSchedule] = None,
                      pad_plan: bool = False) -> _ScanRun:
    if cfg.engine not in ("shortlist", "full"):
        raise ValueError(
            f"scanned core supports engine='shortlist'|'full', got "
            f"{cfg.engine!r} (blind/spread comparators are host-only)")
    jobs = jobs if jobs is not None else generate_jobs(cfg)
    if cfg.n_tenants and jobs.tenant is None:
        raise ValueError("SimConfig.n_tenants > 0 requires a JobSchedule "
                         "with a tenant column (generate_jobs draws one)")
    pol = Policy.for_jobs(cfg.policy, jobs.arrive, jobs.deferrable,
                          cfg.defer_max_h, jobs.deadline, jobs.value)
    plan = _scan_plan(cfg, jobs, pol, pad=pad_plan)
    fc_fallback = cfg.faults is not None and cfg.use_forecast
    # weights enter the statics via graph_key(): the live marginal weight
    # rides as traced data (arrs["en_wmarg"]), so a marginal-weight grid
    # shares one compiled trajectory
    statics = (cfg.engine, cfg.shortlist, cfg.use_kernel,
               cfg.weights.graph_key(),
               cfg.horizon_h, cfg.history_h, cfg.use_forecast,
               pol.defer_window(cfg.defer_max_h), fc_fallback,
               cfg.interpret)
    fplan = None
    if cfg.faults is not None:
        fplan = plan_faults(cfg.faults, np.asarray(region_ci, np.float64),
                            np.asarray(ridx), cfg.epochs, cfg.history_h,
                            cfg.migration_budget, fleet0.n, cfg.seed)
    tplan = None
    if traffic_graph_key(cfg.traffic) > 0:
        validate_qps_weights(jobs.qps_weight)
        if jobs.svc_class is None:
            raise ValueError("SimConfig.traffic requires a JobSchedule "
                             "svc_class column (generate_jobs draws it "
                             "when cfg.traffic is set)")
        tplan = plan_traffic(cfg.traffic, cfg.epochs, cfg.seed)
    sizes = np.bincount(np.asarray(ridx, np.int64),
                        minlength=region_ci.shape[0])
    return _ScanRun(fleet0=fleet0, region_ci=np.asarray(region_ci),
                    ridx=np.asarray(ridx), cfg=cfg, jobs=jobs, pol=pol,
                    plan=plan, statics=statics,
                    mig_nmax=max(int(sizes.max(initial=0)), 1),
                    fplan=fplan, tplan=tplan)


def _bucket_key(run: _ScanRun) -> tuple:
    """Everything that must match for two runs to share one compiled
    ensemble trajectory: the placement/forecast statics, graph-shaping
    config fields, array shapes, and the policy's canonical
    ``graph_key``.  The remaining ``dims`` entries are pure buffer
    sizes, maxed over the bucket by ``_shared_dims``."""
    cfg = run.cfg
    return (run.statics, cfg.epochs, run.fleet0.n, run.region_ci.shape,
            cfg.migration_budget, cfg.defer_max_h,
            _outage_windows(cfg.outage),
            cfg.power_off_idle, float(cfg.consolidate),
            cfg.n_tenants > 0, cfg.policy.graph_key(),
            fault_graph_key(cfg.faults), traffic_graph_key(cfg.traffic))


def _shared_dims(runs, pad: bool):
    """Shared jit-static ``dims`` for a bucket of runs: every static
    buffer size is the member-wise maximum — padding is an exact no-op
    for each member, by the same soundness argument as ``ScanPlan``'s
    own bounds (the SLO queue cap stays *semantic* through the traced
    ``q_cap`` scalar, so only its buffer widens).  Returns
    ``(dims, Jp, mig_nmax)``."""
    cfg = runs[0].cfg
    slots = max(r.plan.slots for r in runs)
    outs = _outage_windows(cfg.outage)
    fkey = fault_graph_key(cfg.faults)
    dims = (cfg.epochs, slots,
            max(r.plan.a_max for r in runs),
            max(r.plan.d_cap for r in runs),
            max(r.plan.rel_cap for r in runs),
            slots if (outs or fkey[2]) else 0,
            cfg.migration_budget,
            max(int(np.max(r.jobs.chips, initial=1)) for r in runs),
            cfg.history_h, cfg.defer_max_h, outs,
            cfg.power_off_idle, float(cfg.consolidate),
            max(r.cfg.n_tenants for r in runs),
            cfg.policy.graph_key(), fkey, traffic_graph_key(cfg.traffic))
    jp = max((_pad_bucket(max(r.jobs.n, 1)) if pad else max(r.jobs.n, 1))
             for r in runs)
    return dims, jp, max(r.mig_nmax for r in runs)


def _build_arrs(run: _ScanRun, dims: tuple, jp: int, mig_nmax: int):
    """Device inputs for ONE trajectory at the bucket's shared shapes.

    Padding conventions (all exact no-ops for the scan): padded jobs
    arrive past the horizon and are never touched; padded ``arr_ids``
    lanes carry the -1 sentinel; padded ``mig_perm`` columns carry the
    ``N`` sentinel with +inf pue.  The per-run policy knobs that reach
    the graph as data (``q_cap``/``green_factor``/``green_gate``) ride
    along as traced scalars."""
    fleet0, cfg, jobs, plan = run.fleet0, run.cfg, run.jobs, run.plan
    region_ci, ridx = run.region_ci, run.ridx
    N, T, J = fleet0.n, cfg.epochs, jobs.n
    a_max = dims[2]

    def jconst(x, fill, dtype):
        out = np.full(jp, fill, dtype)
        out[:J] = np.asarray(x, dtype)[:J]
        return jnp.asarray(out)

    region_pue = _region_pue(region_ci.shape[0], ridx, fleet0.pue)
    # static per-region pue-ascending node order for the migration
    # policy's best-feasible-rate computation (rate = pue · ci_region, so
    # within a region the rate order never changes)
    R = region_ci.shape[0]
    ridx_np = np.asarray(ridx, np.int64)
    pue_np = np.asarray(fleet0.pue, np.float32)
    sizes = np.bincount(ridx_np, minlength=R)
    mig_perm = np.full((R, mig_nmax), N, np.int32)    # N = padding sentinel
    mig_pue = np.full((R, mig_nmax), np.inf, np.float32)
    order = np.lexsort((pue_np, ridx_np))
    col = np.arange(order.size) \
        - np.concatenate([[0], np.cumsum(sizes)])[ridx_np[order]]
    mig_perm[ridx_np[order], col] = order
    mig_pue[ridx_np[order], col] = pue_np[order]
    arr_ids = np.full((T, a_max), -1, np.int32)
    arr_ids[:, :plan.a_max] = plan.arr_ids
    arrs = dict(
        mig_perm=jnp.asarray(mig_perm), mig_pue=jnp.asarray(mig_pue),
        traces=jnp.asarray(region_ci, jnp.float32),
        ridx=jnp.asarray(ridx, jnp.int32),
        region_pue=jnp.asarray(region_pue, jnp.float32),
        pue=fleet0.pue, power_kw=fleet0.power_kw,
        chips_total=fleet0.chips_total, flops_per_j=fleet0.flops_per_j,
        straggler=fleet0.straggler_score,
        healthy=jnp.asarray(fleet0.healthy, bool),
        capacity=fleet0.capacity.astype(jnp.int32),
        chips=jconst(jobs.chips, 0, np.int32),
        duration=jconst(jobs.duration, 1, np.int32),
        arrive=jconst(jobs.arrive, T + 1, np.int32),
        deferrable=jconst(jobs.deferrable, False, bool),
        arr_ids=jnp.asarray(arr_ids),
        q_cap=jnp.int32(plan.d_cap),
        green_factor=jnp.float32(cfg.policy.defer_green_factor),
        green_gate=jnp.float32(cfg.policy.green_gate),
    )
    # the EnergyModel, lowered to traced f32 scalars host-side — bitwise
    # the constants the scan core used to inline (en_ekwh/en_ckpt go
    # through the identical f64 op order before the single f32 round)
    em = cfg.energy
    arrs.update(
        en_idle=jnp.float32(em.idle_frac),
        en_dyn=jnp.float32(em.dyn_frac),
        en_chipw=jnp.float32(em.chip_power_w),
        en_hostw=jnp.float32(em.host_power_w),
        en_embodied=jnp.float32(em.embodied_g_per_node_h),
        en_wmarg=jnp.float32(cfg.weights.marginal),
        en_ekwh=jnp.float32(em.e_kwh_h),
        en_ckpt=jnp.float32(em.ckpt_kwh(cfg.migration_overhead_h)))
    if dims[13] > 0:
        ten = jobs.tenant if jobs.tenant is not None \
            else np.zeros(J, np.int32)
        arrs["tenant"] = jconst(ten, 0, np.int32)
    if run.fplan is not None:
        fp = run.fplan
        # decisions read the degraded observed trace; the true trace rides
        # along for emission/migration-cost accounting.  All fault streams
        # are DATA — only fault_graph_key decides which lanes exist, so a
        # whole dropout/staleness grid shares one compiled trajectory.
        arrs.update(
            traces=jnp.asarray(fp.obs_traces, jnp.float32),
            traces_true=jnp.asarray(region_ci, jnp.float32),
            f_fc_ok=jnp.asarray(fp.fc_ok),
            f_safe=jnp.asarray(fp.safe),
            mig_backoff=jnp.int32(cfg.faults.mig_backoff_h))
        if cfg.faults.mig_fail > 0.0:
            arrs["f_mig_fail"] = jnp.asarray(fp.mig_fail)
        if cfg.faults.flap_rate > 0.0:
            arrs["f_elig"] = jnp.asarray(fp.eligible)
    if run.pol.slo:
        arrs.update(
            slack=jconst(run.pol.slack, 0, np.int32),
            thresh=jconst(run.pol.thresh, 1.0, np.float32),
            value=jconst(run.pol.value, np.inf, np.float32),
            deadline=jconst(run.pol.deadline_ep, 0, np.int32))
    if dims[16] > 0:
        # request-serving traffic: the seeded QPS stream and the
        # host-built M/M/c admissible-rate table ride in as integer DATA
        # (byte-identical to what the host loop routed with — the bit-
        # exactness contract of repro.core.router), and the SLO/greenness
        # knobs as traced scalars, so a (slo x greenness) grid shares
        # this one compiled trajectory
        tc = cfg.traffic
        arrs.update(
            tr_req=jnp.asarray(run.tplan.req),
            svc=jconst(jobs.svc_class if jobs.svc_class is not None
                       else np.full(J, -1, np.int32), -1, np.int32),
            qweight=jconst(jobs.qps_weight if jobs.qps_weight is not None
                           else np.zeros(J, np.int32), 0, np.int32),
            lam_cap=jnp.asarray(routerlib.lambda_caps(
                dims[7], tc.mu_per_chip, cfg.policy.router_slo_s)),
            greenness=jnp.float32(cfg.policy.router_greenness),
            tr_mu=jnp.float32(tc.mu_per_chip),
            en_reqkwh=jnp.float32(em.req_kwh(1.0 / tc.mu_per_chip)))
    return arrs


def _scan_result(run: _ScanRun, carry, ys) -> SimResult:
    """Unpack one trajectory's (carry, ys) into a ``SimResult`` on the
    host (numpy inputs; the ensemble slices its member lane first)."""
    jobs, plan, T, J = run.jobs, run.plan, run.cfg.epochs, run.jobs.n
    defer_f, mig_cost_f, overflow_f = carry[5], carry[6], carry[7]
    ys = [_host(y) for y in ys]
    (e_t, n_sw, completed_t, dropped_t, placed_t, deferred_t, mig_t,
     evi_t, miss_t, mov_jid, mov_node, new_jid, new_node, ov_t,
     failed_t, ten_t) = ys[:16]
    if int(overflow_f) != 0:
        bad = int(np.argmax(ov_t > 0))   # first epoch whose cumulative
        raise RuntimeError(              # overflow count is nonzero
            f"scanned simulator overflowed its static job-slot capacity "
            f"S={plan.slots} at epoch {bad}: {int(overflow_f)} event(s) "
            f"beyond ScanPlan(slots={plan.slots}, a_max={plan.a_max}, "
            f"d_cap={plan.d_cap}, rel_cap={plan.rel_cap}, "
            f"m_evict={plan.m_evict}).  The sound bound should never be "
            f"exceeded — please report; as a workaround, rerun with "
            f"SimConfig(scan_slots={plan.slots + int(overflow_f)}) to "
            f"widen the job table")
    series = e_t.astype(np.float64)
    # replay the per-event placement log chronologically: within an epoch
    # movers precede new arrivals (host step-4 order); a job appears at
    # most once per epoch, so first/last occurrence give first/final node
    ev_jid = np.concatenate([mov_jid, new_jid], axis=1).ravel()
    ev_node = np.concatenate([mov_node, new_node], axis=1).ravel()
    mask = (ev_jid >= 0) & (ev_node >= 0)
    j_m, n_m = ev_jid[mask], ev_node[mask]
    node_log = np.full(J, -1, np.int64)
    first_node = np.full(J, -1, np.int64)
    uniq, first_idx = np.unique(j_m, return_index=True)
    first_node[uniq] = n_m[first_idx]
    uniq_r, last_idx = np.unique(j_m[::-1], return_index=True)
    node_log[uniq_r] = n_m[::-1][last_idx]
    # first placement always comes through the arrival stream, so the
    # per-epoch new-arrival log rows give start epochs (and thereby the
    # policy latency accounting: delay = start - arrive)
    ep_rows = np.repeat(np.arange(T, dtype=np.int64), new_jid.shape[1])
    nmask = (new_jid.ravel() >= 0) & (new_node.ravel() >= 0)
    start_epoch = np.full(J, -1, np.int64)
    uniq_s, first_s = np.unique(new_jid.ravel()[nmask], return_index=True)
    start_epoch[uniq_s] = ep_rows[nmask][first_s]
    started = start_epoch >= 0
    delay_h = int((start_epoch[started]
                   - np.asarray(jobs.arrive)[started]).sum())
    # jobs still waiting in the deferral queue never ran -> dropped (and
    # every queued job has slack > 0 -> a deadline miss)
    still_q = int((np.asarray(defer_f) >= 0).sum())
    dropped = int(dropped_t.sum()) + still_q
    mig_cost = float(mig_cost_f)
    tenant_g = None
    n_run = run.cfg.n_tenants
    if n_run:
        # per-epoch f32 bins, summed on host in f64; the shared buffer may
        # be wider than this member's tenant count — its extra bins are
        # structurally zero, and the idle/remainder bin sits last
        tg = ten_t.astype(np.float64).sum(axis=0)
        tenant_g = np.concatenate([tg[:n_run], tg[-1:]])
    wc_t, rounds_t = ys[16:18]
    req_kw = {}
    if len(ys) > 18:
        served_t, offered_t, viol_t, reqg_t, p99w_t, tenreq_t = ys[18:24]
        served = int(served_t.astype(np.int64).sum())
        req_kw = dict(
            req_served=served,
            req_offered=int(offered_t.astype(np.int64).sum()),
            p99_violations=int(viol_t.astype(np.int64).sum()),
            req_gco2=float(reqg_t.astype(np.float64).sum()),
            req_p99_s=float(p99w_t.astype(np.float64).sum())
            / max(served, 1))
        if n_run:
            tr = tenreq_t.astype(np.float64).sum(axis=0)
            req_kw["tenant_request_g"] = np.concatenate([tr[:n_run],
                                                         tr[-1:]])
    return SimResult(
        emissions_g=float(series.sum()) + mig_cost,
        migration_cost_g=mig_cost,
        rank_sweeps=int(n_sw.sum()),
        arrivals_placed=int(placed_t.sum()),
        jobs_completed=int(completed_t.sum()),
        jobs_dropped=dropped,
        jobs_deferred=int(deferred_t.sum()),
        migrations=int(mig_t.sum()),
        evictions=int(evi_t.sum()),
        node_log=node_log, first_node=first_node,
        emissions_series=series,
        deadline_misses=int(miss_t.sum()) + still_q,
        defer_delay_h=delay_h,
        migrations_failed=int(failed_t.sum()),
        jobs_active_end=int((np.asarray(carry[2]) >= 0).sum()),
        safe_epochs=int(run.fplan.safe.sum())
        if run.fplan is not None else 0,
        start_epoch=start_epoch,
        tenant_emissions_g=tenant_g,
        walk_counts=None if wc_t is None
        else tuple(int(c) for c in wc_t.astype(np.int64).sum(axis=0)),
        sweep_rounds=None if rounds_t is None
        else int(rounds_t.astype(np.int64).sum()), **req_kw)


def _host(x):
    """A device output on the host; None (a counter the engine lacks)
    stays None."""
    return None if x is None else np.asarray(x)


def simulate_fleet_scan(fleet0: Fleet, region_ci: np.ndarray,
                        ridx: np.ndarray, cfg: SimConfig,
                        jobs: Optional[JobSchedule] = None, *,
                        pad_plan: bool = False) -> SimResult:
    """``simulate_fleet`` with the epoch loop compiled as ONE ``lax.scan``.

    Same trajectory semantics as the host loop for
    ``engine in ("shortlist", "full")`` — arrivals, EOL releases, outage
    evictions, budget/cost-model migration, deferrable batch jobs — but the
    T-epoch loop is a single compiled scan over a fixed-capacity job table
    and padded event buffers (``ScanPlan``), so a year-scale trajectory
    costs one dispatch instead of T.  The carbon-blind comparators and
    ``record_matrices`` stay host-only.

    **Equivalence contract** (asserted by ``tests/test_simulator_scan.py``
    and the ``sim_scale`` bench): per-job placements (``node_log``,
    ``first_node``) and all integer counters are expected to match the host
    loop exactly; ``emissions_g`` / ``emissions_series`` /
    ``migration_cost_g`` match to float32 accumulation tolerance (the host
    loop accounts in float64 numpy; rtol 1e-4).  The placement decisions
    run the identical `_epoch_core` graph, and the engine's scoring path is
    barrier-pinned (see ``repro.core.placement``), so integer divergence
    can only come from f32-vs-f64 near-ties in the migration-gain ordering
    or the deferral green-hour comparison — none observed on the tested
    streams; a mismatch is a regression, not tolerance.

    ``pad_plan`` buckets every static buffer (and the job-table width) to
    ``_pad_bucket`` sizes — behavior-neutral, but seed ensembles with
    slightly different schedules then share one compiled trajectory."""
    run, dims, arrs = _scan_inputs(fleet0, region_ci, ridx, cfg, jobs,
                                   pad_plan)
    with _span("dispatch"):
        out = _scan_trajectory(arrs, run.statics, dims)
    with _span("device_wait"):
        carry, ys = jax.block_until_ready(out)
    with _span("readback"):
        carry = [np.asarray(c) for c in carry]
        ys = [_host(y) for y in ys]
    with _span("result_assembly"):
        return _scan_result(run, carry, ys)


def _span(name: str):
    """A host span on the profiler's clock; free unless a trace is on."""
    return jax.profiler.TraceAnnotation(name)


def _scan_inputs(fleet0, region_ci, ridx, cfg, jobs, pad_plan):
    """Plan build of one scanned trajectory: ``(run, dims, arrs)``."""
    with _span("plan_build"):
        run = _prepare_scan_run(fleet0, region_ci, ridx, cfg, jobs,
                                pad_plan)
        dims, jp, nmax = _shared_dims([run], pad_plan)
        return run, dims, _build_arrs(run, dims, jp, nmax)


_PARITY_COUNTERS = ("rank_sweeps", "arrivals_placed", "jobs_completed",
                    "jobs_dropped", "jobs_deferred", "migrations",
                    "evictions")


def scan_vs_host_parity(host: SimResult, scan: SimResult
                        ) -> Tuple[bool, float]:
    """The scanned core's equivalence contract (see
    ``simulate_fleet_scan``): placements and counters exact, f64-vs-f32
    accounting within rtol 1e-4.  Returns (holds, emissions rel. error)."""
    exact = (np.array_equal(host.node_log, scan.node_log)
             and np.array_equal(host.first_node, scan.first_node)
             and all(getattr(host, f) == getattr(scan, f)
                     for f in _PARITY_COUNTERS))
    rel = float(abs(host.emissions_g - scan.emissions_g)
                / max(abs(host.emissions_g), 1e-9))
    return bool(exact and rel <= 1e-4), rel


def simulate_fleet_ensemble(runs, *, pad_plan: bool = True,
                            shard=False) -> list:
    """Run an ensemble of trajectories as ONE compiled, ONE dispatched
    batched-``lax.scan`` program per graph bucket.

    ``runs`` is a sequence of ``(fleet0, region_ci, ridx, cfg)`` or
    ``(fleet0, region_ci, ridx, cfg, jobs)`` tuples — the exact argument
    tuples ``simulate_fleet_scan`` takes; the result list matches input
    order and is **bit-identical per trajectory** to calling
    ``simulate_fleet_scan`` on each member (placements and every integer
    counter exact, emissions to the scanned core's own f32 tolerance —
    asserted by ``tests/test_simulator_ensemble.py``).

    Members are grouped by graph key (``_bucket_key``: placement statics,
    epochs, fleet/trace shapes, graph-shaping config fields, and
    ``PolicyConfig.graph_key`` — so a threshold/value/queue-cap grid over
    one seed set is a single bucket); within a bucket every per-trajectory
    input is stacked on a leading E axis and buffer dims are the
    member-wise maxima (``pad_plan`` bucketing keeps those maxima shared
    across seeds).  The bucket then runs as one batched scan —
    ``vmap``-ed loop-free epoch halves around the hand-batched placement
    engine (``_traj_scan(ensemble=True)``) — so a whole grid costs one
    compile and one dispatch, its per-epoch element ops carry the E
    axis, and sweeps/sorts batch over lanes.  On wide-vector or
    multi-device hardware that axis is the throughput lever; on a single
    XLA:CPU device it measures dispatch-equivalent (see EXPERIMENTS.md
    §Ensemble for the numbers and the memory ceiling in E).

    ``shard=True`` additionally lays the E axis out across the available
    devices (largest divisor of E <= device count) via ``NamedSharding``,
    so the same compiled program runs data-parallel over the ensemble on
    multi-device CPU/TPU; on a single device it is a no-op.
    ``shard="en"`` uses the 2D ``("e", "n")`` mesh instead
    (``distributed.sharding.ensemble_mesh``): the leftover device factor
    splits the *node* axis of the (E, N) fleet buffers, for fleets that do
    not fit one device — the tile-local top-k merge is unchanged (XLA
    concatenates per-shard candidates before the ``lax.top_k``).

    ``use_kernel=True`` members run the batched Pallas sweep — one
    (stalled-lanes × node-tiles) kernel launch per placement round
    (``placement.place_lifecycle_batched``), per-lane bit-identical to
    the sequential scan driver (interpret mode on CPU, compiled on
    TPU).  XLA cannot partition a compiled Pallas kernel, so on a sharded
    ensemble the sweep runs per device under ``shard_map`` over the same
    mesh (``ops.maiz_ranking_topk_batched``)."""
    runs = list(runs)
    results: list = [None] * len(runs)
    for idxs, members, dims, stacked, mesh in _ensemble_buckets(
            runs, pad_plan, shard):
        with warnings.catch_warnings():
            # input donation is best-effort: only the lanes that alias a
            # scan carry are consumed, the rest warn — expected, not a bug
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            with _span("dispatch"):
                out = _ensemble_trajectory(stacked, members[0].statics,
                                           dims, mesh=mesh)
        with _span("device_wait"):
            carry, ys = jax.block_until_ready(out)
        with _span("readback"):
            carry = [np.asarray(c) for c in carry]
            ys = [_host(y) for y in ys]
        with _span("result_assembly"):
            for lane, (i, m) in enumerate(zip(idxs, members)):
                results[i] = _scan_result(
                    m, [c[lane] for c in carry],
                    [None if y is None else y[lane] for y in ys])
    return results


def _ensemble_buckets(runs, pad_plan: bool, shard):
    """Plan build of an ensemble, one graph bucket at a time: yields
    ``(member indices, prepared members, dims, stacked inputs, mesh)``."""
    with _span("plan_build"):
        preps = []
        for spec in runs:
            jobs = spec[4] if len(spec) > 4 else None
            preps.append(_prepare_scan_run(spec[0], spec[1], spec[2],
                                           spec[3], jobs, pad_plan))
        buckets: Dict[tuple, list] = {}
        for i, p in enumerate(preps):
            buckets.setdefault(_bucket_key(p), []).append(i)
    for idxs in buckets.values():
        with _span("plan_build"):
            members = [preps[i] for i in idxs]
            dims, jp, nmax = _shared_dims(members, pad_plan)
            built = [_build_arrs(m, dims, jp, nmax) for m in members]
            stacked = {k: jnp.stack([b[k] for b in built])
                       for k in built[0]}
            del built
            mesh = None
            if shard:
                stacked, mesh = _shard_over_e(
                    stacked, axes="en" if shard == "en" else "e")
        yield idxs, members, dims, stacked, mesh


def program_texts(runs, *, ensemble: bool, pad_plan: bool,
                  shard=False) -> list:
    """Optimized HLO text of each distinct program that
    ``simulate_fleet_ensemble(runs, pad_plan=..., shard=...)``
    (``ensemble=True``) or ``simulate_fleet_scan(*run, pad_plan=...)`` for
    each run (``ensemble=False``) dispatches, built by the same plan-build
    code and compiled through the same compilation cache (a hit where the
    process has run them).  Nothing runs.  Each instruction's
    ``op_name`` metadata carries the named scopes of the module
    docstring, so a profiler trace's operations can be mapped to them."""
    texts = []

    def add(fn, *args, **kw):
        text = fn.lower(*args, **kw).compile().as_text()
        if text not in texts:
            texts.append(text)

    if ensemble:
        for _, members, dims, stacked, mesh in _ensemble_buckets(
                list(runs), pad_plan, shard):
            add(_ensemble_trajectory, stacked, members[0].statics, dims,
                mesh=mesh)
    else:
        for spec in runs:
            run, dims, arrs = _scan_inputs(
                *spec[:4], spec[4] if len(spec) > 4 else None, pad_plan)
            add(_scan_trajectory, arrs, run.statics, dims)
    return texts


# the stacked buffers that carry the node axis in dim 1 — the only ones a
# ("e", "n") mesh partitions beyond the ensemble axis
_NODE_AXIS_KEYS = ("capacity", "pue", "power_kw", "chips_total",
                   "flops_per_j", "straggler", "healthy", "ridx")


def _shard_over_e(stacked, axes: str = "e"):
    """Lay the stacked ensemble buffers across devices.

    ``axes="e"``: partition the leading ensemble axis only (largest
    divisor of E <= the device count) — every input is batched on E, so
    the partition is communication-free.  ``axes="en"``: build the 2D
    ``("e", "n")`` mesh (``distributed.sharding.ensemble_mesh``) and
    additionally split the node axis of the (E, N) fleet buffers over the
    leftover device factor — for fleets that do not fit one device; XLA
    inserts the cross-shard collectives for the ``lax.top_k`` candidate
    merge and argmin reductions.  Either way a single device is a no-op.
    Returns the buffers and the mesh they were laid out on (None when
    nothing was sharded)."""
    devs = jax.devices()
    E = next(iter(stacked.values())).shape[0]
    P = jax.sharding.PartitionSpec
    if axes == "e":
        nd = max((d for d in range(1, len(devs) + 1) if E % d == 0),
                 default=1)
        if nd <= 1:
            return stacked, None
        mesh = jax.sharding.Mesh(np.array(devs[:nd]), ("e",))
        sh = jax.sharding.NamedSharding(mesh, P("e"))
        return {k: jax.device_put(v, sh) for k, v in stacked.items()}, mesh
    if axes != "en":
        raise ValueError(f"shard axes must be 'e' or 'en', got {axes!r}")
    from repro.distributed.sharding import ensemble_mesh
    mesh = ensemble_mesh(E, stacked["capacity"].shape[1], devs)
    if mesh.devices.size <= 1:
        return stacked, None
    return {k: jax.device_put(v, jax.sharding.NamedSharding(
        mesh, P("e", "n") if k in _NODE_AXIS_KEYS else P("e")))
        for k, v in stacked.items()}, mesh


# ---------------------------------------------------------------------------
# synthetic lifecycle fleet (traces + node arrays)
# ---------------------------------------------------------------------------


def synthetic_lifecycle_fleet(n: int, cfg: SimConfig,
                              chips_per_node: int = 256,
                              region: Optional[int] = None
                              ) -> Tuple[Fleet, np.ndarray, np.ndarray]:
    """(empty fleet, region CI traces, node->region map) for the simulator.

    Same statistical recipe as ``fleet.synthetic_fleet`` but capacity
    starts FULL (jobs arrive through the lifecycle) and the traces carry
    ``history_h`` hours of warm-up for the forecaster.  ``region`` pins
    every node into one region — the single-region setting where temporal
    shifting (deferral into green windows) is the only carbon lever,
    spatial arbitrage being off the table (see EXPERIMENTS.md §Policy)."""
    rng = np.random.default_rng(cfg.seed)
    regions = list(telemetry.REGIONS.values())
    ridx = rng.integers(0, len(regions), n) if region is None \
        else np.full(n, int(region))
    hours = cfg.history_h + cfg.epochs + cfg.horizon_h + 1
    traces = np.stack([telemetry.hourly_ci(r, hours=hours, seed=cfg.seed + i)
                       for i, r in enumerate(regions)])
    fleet = Fleet(
        ci_now=jnp.asarray(traces[ridx, cfg.history_h], jnp.float32),
        ci_forecast=jnp.asarray(traces[ridx, cfg.history_h], jnp.float32),
        pue=jnp.asarray(np.array([r.pue for r in regions])[ridx],
                        jnp.float32),
        power_kw=jnp.asarray(
            chips_per_node * cfg.energy.chip_kw
            * (1 + 0.1 * rng.random(n)), jnp.float32),
        capacity=jnp.full((n,), chips_per_node, jnp.int32),
        healthy=jnp.ones((n,), bool),
        straggler_score=jnp.asarray(
            np.abs(rng.normal(0, 0.05, n)), jnp.float32),
        flops_per_j=jnp.asarray(
            788e9 * (1 + 0.05 * rng.standard_normal(n)), jnp.float32),
        chips_total=jnp.full((n,), chips_per_node, jnp.int32),
    )
    return fleet, traces, ridx


# ---------------------------------------------------------------------------
# policy Pareto sweep harness
# ---------------------------------------------------------------------------


def sweep_policies(cfg: SimConfig, policies, *, n: int = 1024,
                   seeds=(0,), chips_per_node: int = 256,
                   region: Optional[int] = None, ensemble: bool = True,
                   shard: bool = False) -> list:
    """Run a seed ensemble per policy through the scanned core and return
    flat records for the carbon-vs-latency Pareto study.

    ``policies`` maps name -> ``PolicyConfig`` (dict or (name, cfg)
    pairs); each (policy, seed) pair re-derives the fleet, traces and job
    schedule from ``dataclasses.replace(cfg, seed=seed, policy=pcfg)``.
    With ``ensemble=True`` (default) the whole (policy x seed) grid runs
    through ``simulate_fleet_ensemble``: grid points whose policies share
    a ``graph_key`` become lanes of ONE batched scan — one compile, one
    dispatch per bucket — instead of one scan dispatch per point
    (threshold/value/queue-cap knobs live in traced per-job columns and
    per-run scalars).  ``ensemble=False`` keeps the sequential
    per-point ``simulate_fleet_scan`` path (the timing baseline of the
    ``ensemble`` bench block; results are bit-identical either way).
    Both use ``pad_plan=True`` bucketing so shapes are shared.  Latency
    is reported two ways: ``avg_start_delay_h`` (mean placement delay
    over started jobs) and ``miss_rate`` (deadline misses over
    slack-carrying jobs inside the horizon)."""
    items = policies.items() if isinstance(policies, dict) else policies
    fleet_cache: Dict[int, tuple] = {}   # fleet/traces depend on seed only
    runs, metas = [], []
    for name, pcfg in items:
        for seed in seeds:
            c = dataclasses.replace(cfg, seed=int(seed), policy=pcfg)
            if int(seed) not in fleet_cache:
                fleet_cache[int(seed)] = synthetic_lifecycle_fleet(
                    n, c, chips_per_node=chips_per_node, region=region)
            fleet, traces, ridx = fleet_cache[int(seed)]
            jobs = generate_jobs(c)
            runs.append((fleet, traces, ridx, c, jobs))
            metas.append((name, int(seed), c, jobs))
    if ensemble:
        rs = simulate_fleet_ensemble(runs, pad_plan=True, shard=shard)
    else:
        rs = [simulate_fleet_scan(f, t, ri, c, jobs=j, pad_plan=True)
              for f, t, ri, c, j in runs]
    records = []
    for (name, seed, c, jobs), r in zip(metas, rs):
        pol = Policy.for_jobs(c.policy, jobs.arrive, jobs.deferrable,
                              c.defer_max_h, jobs.deadline, jobs.value)
        in_h = np.asarray(jobs.arrive) < c.epochs
        slo_jobs = int(((pol.slack > 0) & in_h).sum())
        started = int((r.start_epoch >= 0).sum())
        records.append({
            "policy": name, "seed": seed, "n": n,
            "epochs": c.epochs, "jobs": int(jobs.n),
            "emissions_g": float(r.emissions_g),
            "migration_cost_g": float(r.migration_cost_g),
            "migrations": int(r.migrations),
            "completed": int(r.jobs_completed),
            "dropped": int(r.jobs_dropped),
            "deferred": int(r.jobs_deferred),
            "deadline_misses": int(r.deadline_misses),
            "defer_delay_h": int(r.defer_delay_h),
            "avg_start_delay_h": r.defer_delay_h / max(started, 1),
            "miss_rate": r.deadline_misses / max(slo_jobs, 1),
        })
    return records


def pareto_frontier(records: list, x: str = "avg_start_delay_h",
                    y: str = "emissions_g") -> list:
    """Seed-aggregate ``sweep_policies`` records per policy (mean) and
    return the non-dominated carbon/latency frontier, sorted by ``x``
    ascending — ``y`` is strictly decreasing along the result, so a
    well-formed frontier is monotone by construction (the bench gate
    checks exactly that on the emitted artifact)."""
    by: Dict[str, list] = {}
    for r in records:
        by.setdefault(r["policy"], []).append(r)
    pts = []
    for name, rs in by.items():
        p = {"policy": name,
             "seeds": sorted(r["seed"] for r in rs),
             "miss_rate": float(np.mean([r["miss_rate"] for r in rs]))}
        p[x] = float(np.mean([r[x] for r in rs]))
        p[y] = float(np.mean([r[y] for r in rs]))
        pts.append(p)
    pts.sort(key=lambda p: (p[x], p[y]))
    front, best = [], np.inf
    for p in pts:
        if p[y] < best:
            front.append(p)
            best = p[y]
    return front


# ---------------------------------------------------------------------------
# the paper experiment as a simulator special case
# ---------------------------------------------------------------------------

_PAPER_CHIPS = 60      # one unit = 60 servers; the job takes the whole node


def paper_scenario_alloc(ci: np.ndarray, pue: np.ndarray, demand: float
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """Scenario C (util, on) matrices via the rolling simulator.

    One 1-epoch job per hour carries the aggregate dynamic demand; weights
    are CFP-only, so with equal node power and an empty fleet the greedy
    engine lands each hour's job on argmin(CI x PUE) and powers everything
    else off — exactly the paper's active-shifting policy, but produced by
    the same lifecycle code path that runs multi-thousand-node fleets."""
    N, T = ci.shape
    cfg = SimConfig(epochs=T, seed=0,
                    weights=RankWeights(w1=1.0, w2=0.0, w3=0.0, w4=0.0),
                    engine="full", history_h=0, horizon_h=1,
                    migration_budget=0, power_off_idle=True)
    ones = jnp.ones((N,), jnp.float32)
    fleet = Fleet(
        ci_now=jnp.asarray(ci[:, 0], jnp.float32),
        ci_forecast=jnp.asarray(ci[:, 0], jnp.float32),
        pue=jnp.asarray(pue, jnp.float32),
        power_kw=ones,
        capacity=jnp.full((N,), _PAPER_CHIPS, jnp.int32),
        healthy=jnp.ones((N,), bool),
        straggler_score=jnp.zeros((N,), jnp.float32),
        flops_per_j=ones,
        chips_total=jnp.full((N,), _PAPER_CHIPS, jnp.int32),
    )
    jobs = JobSchedule(arrive=np.arange(T),
                       chips=np.full(T, _PAPER_CHIPS, np.int64),
                       duration=np.ones(T, np.int64),
                       load=np.full(T, float(demand)),
                       deferrable=np.zeros(T, bool))
    r = simulate_fleet(fleet, ci, np.arange(N), cfg, jobs=jobs,
                       record_matrices=True)
    return r.util, r.on
