"""Fused top-k shortlist placement: O(N + J·K) instead of O(J·N).

``place_jobs`` used to re-rank the full fleet once per job inside a
``fori_loop`` — a per-job O(N) sweep even though landing a job changes the
score of exactly one node.  This engine ranks once per *decision epoch*
instead:

1. **Frozen normalizers.**  A placement call computes the min-max lo/hi per
   Eq. 1 term once at entry and freezes them (normalization is calibration,
   not a per-evaluation statistic).  With frozen lo/hi, a node's score
   depends only on its OWN free capacity — power rises affinely with
   occupied chips (``Fleet.effective_power_kw``) — so placing a job changes
   exactly one score, recomputable in O(1).

2. **Shortlist + exactness bound.**  One O(N) sweep (the fused Pallas
   two-sweep kernel on TPU, stable-sorted jnp scores otherwise) yields the
   K-node shortlist plus the (K+1)-th best (score, index) pair — the
   *bound*.  Non-shortlist scores cannot change inside an epoch (only nodes
   that receive jobs change, and jobs only land on shortlist nodes), so as
   long as the shortlist's best capacity-feasible (score, index) beats the
   bound lexicographically, it IS the global argmin and the O(K) pick is
   exact.

   **Room rule.**  The sweep ranks only the *eligible* nodes,
   ``where(healthy, cap, 0) >= dmin`` with ``dmin`` the smallest positive
   demand among the call's arrivals; the others score +inf for the top-k
   alone (the placing argmin keeps its own ``(cap >= d) & healthy`` mask
   over the full scores, where an ineligible node, ``hcap < dmin <= d``,
   never wins either).  Without it a fleet whose best-scoring nodes are
   full sweeps once per arrival: every shortlist entry lacks room.  It is
   exact.  Every arrival has ``d >= dmin``, so an ineligible node cannot
   host any of them until its capacity rises; within an epoch that takes
   a release, which marks the epoch dirty outside the shortlist and is
   rescored in O(1) on it.  So "best feasible shortlist entry beats the
   bound" still certifies the global masked argmin.  With fewer than K+1
   eligible nodes the bound is +inf and the shortlist holds every
   eligible node, so a clean epoch with no feasible entry still means
   unplaceable; the +inf filler entries get the index N, which no release
   or arrival can take.  Health is static within a call, so an unhealthy
   node is ineligible throughout.  The rule reads only the call's
   capacities and demands: where every node has room for every demand,
   every node is eligible and the sweep is the unmasked one.

3. **Fallback sweeps.**  When the bound is violated — shortlist capacity
   exhausted for this demand, or every surviving entry outscored by the
   bound — the engine runs a fresh full sweep, places the current job from
   the full masked argmin (exact by construction) and opens the next epoch.
   Placing J jobs therefore costs a handful of O(N) sweeps plus O(J·K)
   shortlist work, not J sweeps.

``place_jobs_full_rerank`` is the O(J·N) oracle: per job, rescore the whole
fleet from current occupancy and take the masked argmin.  Bit-identical
placements are *guaranteed*, not just likely: every tie-break in the engine
(stable sort, ``lax.top_k``, in-shortlist argmin) resolves toward the lower
node index — the same rule as ``jnp.argmin`` — and the per-evaluation score
math is division-free elementwise mul/add with ``optimization_barrier`` at
every spot XLA could FMA-contract, so the O(1) single-node rescore computes
the exact same float32 as the O(N) sweep.  (XLA:CPU's vectorized f32 divide
is NOT bit-equal to its scalar divide, and contraction choices vary with
array shape — all reciprocals and cap-independent terms are therefore
precomputed once per call and shared by both paths.)  The parity tests in
``tests/test_placement.py`` assert exact equality, ties and ragged shapes
included.

**Lifecycle events (arrivals + releases + migrations).**  The rolling fleet
simulator (``repro.core.simulator``) interleaves job *departures* with
arrivals: a release credits chips back to a known node, so that node's
score *falls* mid-epoch.  The one-sided argument above ("scores only rise,
the stale bound stays a sound lower bound") no longer holds, so the
lifecycle engine (``place_lifecycle_shortlist``) adds release-aware epoch
invalidation:

- a release landing on a **shortlist** node is rescored in O(1) (exactly
  like a landing job — the entry's score simply falls, and non-shortlist
  scores are untouched, so the bound stays sound);
- a release landing on a **non-shortlist** node marks the epoch *dirty*:
  some score below the bound may now exist outside the shortlist, so the
  next arrival forces a fresh full sweep (which re-validates the bound and
  clears the flag).  ``cap_max`` — the no-sweep upper bound used to reject
  impossible demands — is raised to the released node's new free capacity,
  keeping it a sound upper bound in both directions.

Epochs also start dirty (lazy initial sweep): leading releases are pure
capacity edits, and the first arrival pays the one O(N) sweep for the
epoch.  A migration is exactly release(old node) + arrival, so batching an
epoch's releases ahead of its arrivals keeps the engine at ~1 sweep per
epoch regardless of how many jobs depart.  Bit-parity with the lifecycle
oracle (``place_lifecycle_full_rerank``) is preserved because every event
either reuses the exact shared scoring graph or triggers the same masked
argmin the oracle computes.

Because leading releases on a dirty engine are pure *commutative* capacity
edits (integer adds; ``cap_max`` is a running max whose final value is
order-independent), callers may batch them in any order — the scanned
simulator (``repro.core.simulator.simulate_fleet_scan``) relies on this to
feed fixed-layout padded event buffers from inside ``lax.scan``.  Both
engines are pure jax control flow (``lax.switch`` over the event sign +
``lax.cond`` for the sweep fallback), so they trace unchanged inside
``scan``/``vmap``; zero-demand events are exact no-ops, which makes
padding free.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.energy import DEFAULT_ENERGY, EnergyModel
from repro.core.fleet import IDLE_POWER_FRAC, Fleet
from repro.core.ranking import RankWeights


def rounding_pin(xp):
    """The f32 rounding pin for ``xp``-generic parity code: the
    ``optimization_barrier`` identity under jnp (which ``vmap`` batches
    natively), a plain identity on numpy.  The QPS router
    (``repro.core.router``) pins its greenness-blend multiply with this
    so the host and scanned drivers cannot diverge by operator fusion —
    the same discipline this module's scoring path applies at every
    mul→add seam.  Serving replicas draw on the same chip capacity this
    engine allocates, so the router's parity contract rides on the same
    pin."""
    if xp is jnp:
        return jax.lax.optimization_barrier
    return lambda x: x


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PlacementResult:
    node: jax.Array       # (J,) int32 chosen node per job; -1 = unplaceable
    scores: jax.Array     # (N,) scores at FINAL occupancy (frozen lo/hi)
    capacity: jax.Array   # (N,) free chips after all placements
    n_sweeps: jax.Array   # () int32: full O(N) decision sweeps performed
    # (4,) int32 ``WALK_COUNTS`` of the shortlist engine; None for the
    # full-rerank oracle, which has no shortlist
    walk_counts: Optional[jax.Array] = None


# What each arrival of a shortlist engine cost, by index into
# ``walk_counts``: placed from the shortlist with no sweep, or swept
# because (in this order of precedence) the epoch was dirty, no shortlist
# node had room and health for the demand, or the bound outscored the best
# feasible shortlist node.  Indices 1-3 partition ``n_sweeps`` exactly (the
# eager first sweep counts as dirty); arrivals that are unplaceable without
# a sweep count nowhere.
WALK_COUNTS = ("shortlist_hit", "sweep_dirty", "sweep_no_room",
               "sweep_bound")


def _lo_rcp(t):
    """(lo, 1/span) normalizer pair; degenerate span (<= 1e-12) -> rcp 0 so
    an information-free term contributes exactly 0 (see ranking._minmax)."""
    lo, hi = t.min(), t.max()
    span = hi - lo
    rcp = jnp.where(span > 1e-12, 1.0 / jnp.maximum(span, 1e-12), 0.0)
    return lo, rcp, hi


def frozen_ctx(fleet: Fleet, weights: RankWeights = RankWeights(),
               horizon_h: float = 1.0,
               energy: Optional[EnergyModel] = None) -> Dict[str, jax.Array]:
    """One-time per-placement context: cap-independent Eq. 1 pieces.

    ``a_now``/``a_fc`` are full-load CFP/FCFP rates (power·pue·ci·h); the
    efficiency and schedule terms don't depend on occupancy at all, so their
    weighted normalized sum collapses into the per-node ``static`` vector.
    All divisions happen here, once — the per-evaluation path is
    division-free (see module docstring).  ``lohi`` is the (4, 2) matrix the
    fused Pallas kernel consumes for the same normalization.

    ``energy`` threads the two-part :class:`EnergyModel` as traced data:
    idle/dynamic fractions replace the module constants, and the marginal-
    CFP term's context (``m_dyn``/``m_wake``, its frozen normalizer, the
    traced weight ``w_m``) is materialized.  ``energy=None`` with
    ``weights.marginal == 0`` reproduces the historical graph exactly —
    no marginal entries, constants inlined."""
    pk = fleet.power_kw * horizon_h
    a_now = pk * fleet.pue * fleet.ci_now
    a_fc = pk * fleet.pue * fleet.ci_forecast
    inv_total = 1.0 / jnp.maximum(fleet.chips_total.astype(jnp.float32), 1.0)
    eff = fleet.flops_per_j
    sched = fleet.sched_term

    def mm(x):
        lo, rcp, _ = _lo_rcp(x)
        return (x - lo) * rcp

    static = (weights.w3 * (1.0 - mm(eff)) + weights.w4 * mm(sched))

    em = energy
    if em is None and weights.marginal:
        em = DEFAULT_ENERGY.device(w_marginal=weights.marginal)
    idle_f = IDLE_POWER_FRAC if em is None else em.idle_frac
    dyn_f = (1.0 - IDLE_POWER_FRAC) if em is None else em.dyn_frac

    cap0 = fleet.capacity.astype(jnp.float32)
    factor0 = idle_f + dyn_f * (1.0 - cap0 * inv_total)
    cfp0, fcfp0 = a_now * factor0, a_fc * factor0
    lo_now, rcp_now, hi_now = _lo_rcp(cfp0)
    lo_fc, rcp_fc, hi_fc = _lo_rcp(fcfp0)
    lohi = jnp.stack([
        jnp.stack([lo_now, hi_now]), jnp.stack([lo_fc, hi_fc]),
        jnp.stack([eff.min(), eff.max()]),
        jnp.stack([sched.min(), sched.max()])])
    ctx = dict(a_now=a_now, a_fc=a_fc, inv_total=inv_total, static=static,
               idle_f=idle_f, dyn_f=dyn_f,
               lo_now=lo_now, rcp_now=rcp_now, lo_fc=lo_fc, rcp_fc=rcp_fc,
               lohi=lohi)
    if em is not None:
        # Marginal-CFP context: per-chip dynamic carbon for on nodes, the
        # two-part wake price (idle floor + amortized embodied carbon over
        # the horizon) for powered-off ones.  Normalizer frozen at entry
        # like every other term.  The term is always evaluated when these
        # entries exist; with traced ``w_m == 0`` it adds exactly +0.0.
        # ``lohi`` grows its fifth row so the generalized Pallas sweep
        # normalizes the in-kernel marginal term with the same frozen pair.
        ct_f = fleet.chips_total.astype(jnp.float32)
        emb_h = em.embodied_g_per_node_h * horizon_h
        m_dyn = a_now * inv_total * dyn_f
        m_wake = a_now * idle_f + emb_h
        mcfp0 = m_dyn + jnp.where(cap0 == ct_f, m_wake, 0.0)
        lo_m, rcp_m, hi_m = _lo_rcp(mcfp0)
        ctx.update(m_dyn=m_dyn, m_wake=m_wake, ct_f=ct_f,
                   emb_h=jnp.asarray(emb_h, jnp.float32),
                   lo_m=lo_m, rcp_m=rcp_m,
                   lohi=jnp.concatenate(
                       [lohi, jnp.stack([lo_m, hi_m])[None]]),
                   w_m=jnp.asarray(em.w_marginal, jnp.float32))
    return ctx


_GATHERED = ("a_now", "a_fc", "inv_total", "static",
             "m_dyn", "m_wake", "ct_f")


def _ctx_scores(cap, ctx, w: RankWeights):
    """Eq. 1 with frozen normalizers, elementwise over ``cap``'s shape.

    Division-free; the barriers pin rounding before every mul→add seam so a
    length-1 gather computes bit-identically to the full-fleet sweep."""
    bar = jax.lax.optimization_barrier
    capf = cap.astype(jnp.float32)
    occ = 1.0 - bar(capf * ctx["inv_total"])
    dyn = bar(ctx["dyn_f"] * occ)
    factor = ctx["idle_f"] + dyn
    cfp = bar(ctx["a_now"] * factor)
    fcfp = bar(ctx["a_fc"] * factor)
    t1 = bar(w.w1 * ((cfp - ctx["lo_now"]) * ctx["rcp_now"]))
    t2 = bar(w.w2 * ((fcfp - ctx["lo_fc"]) * ctx["rcp_fc"]))
    score = (t1 + t2) + ctx["static"]
    if "m_dyn" in ctx:
        # Select-then-add (no FMA contraction possible across the where);
        # score >= +0.0 always, so `score + 0.0` is bitwise `score` when
        # the traced weight is zero — the marginal term is bit-neutral.
        mcfp = ctx["m_dyn"] + jnp.where(capf == ctx["ct_f"],
                                        ctx["m_wake"], 0.0)
        score = score + bar(ctx["w_m"] * ((mcfp - ctx["lo_m"])
                                          * ctx["rcp_m"]))
    return score


def _one_score(cap_b, b, ctx, w: RankWeights):
    """Rescore node ``b`` (free chips ``cap_b``) in O(1) — bit-identical to
    ``_ctx_scores(cap)[b]`` with ``cap[b] == cap_b`` (same elementwise
    graph; see module docstring)."""
    g = {k: (v[b][None] if k in _GATHERED else v) for k, v in ctx.items()}
    return _ctx_scores(cap_b[None], g, w)[0]


def _smallest_arrival(demands, live, axis=None):
    """The call's smallest arrival demand (per lane along ``axis``): the
    room a node needs to host any of them.  With no arrival, the dtype's
    largest value, so that no node qualifies and nothing is swept."""
    big = jnp.iinfo(demands.dtype).max
    return jnp.min(jnp.where(live, demands, big), axis=axis)


def _room_kwargs(mkw, hcap, dmin):
    """A kernel sweep's keyword arguments: the marginal streams and the
    room threshold.  With the marginal streams the health-masked ``cap``
    stream is the room (an unhealthy node's marginal term then counts it
    busy, which no caller sees: the node scores +inf); without them the
    room is a stream of its own."""
    room = hcap.astype(jnp.float32)
    if mkw:
        return dict(mkw, cap=room, room_min=dmin)
    return dict(room=room, room_min=dmin)


def _no_node_past_room(cand_s, cand_i, n):
    """Candidates past the last node with room score +inf; give them the
    index ``n``, which names no node, as an empty shortlist does: no
    release can rescore them and no arrival can take them."""
    return jnp.where(jnp.isfinite(cand_s), cand_i, n)


def place_jobs_full_rerank(fleet: Fleet, demands: jax.Array,
                           weights: RankWeights = RankWeights(),
                           horizon_h: float = 1.0,
                           energy: Optional[EnergyModel] = None
                           ) -> PlacementResult:
    """O(J·N) oracle: full fleet rescore + masked argmin per job."""
    J = demands.shape[0]
    return place_lifecycle_full_rerank(
        fleet, demands, jnp.full((J,), -1, jnp.int32), weights, horizon_h,
        energy=energy)


def place_lifecycle_full_rerank(fleet: Fleet, demands: jax.Array,
                                nodes: jax.Array,
                                weights: RankWeights = RankWeights(),
                                horizon_h: float = 1.0, *,
                                capacity: Optional[jax.Array] = None,
                                n_events: Optional[jax.Array] = None,
                                energy: Optional[EnergyModel] = None
                                ) -> PlacementResult:
    """Lifecycle oracle over an event stream, O(arrivals · N).

    ``demands[e] > 0``: arrival — full rescore, masked argmin, land the job.
    ``demands[e] < 0``: release — credit ``-demands[e]`` chips to
    ``nodes[e]`` (a migration is release + arrival).
    ``demands[e] == 0``: no-op (padding).

    Output ``node[e]`` is the chosen node for arrivals (-1 if unplaceable),
    the credited node for releases, and -1 for no-ops.

    ``capacity`` splits the scoring snapshot from the loop's starting
    capacity: leading releases are commutative capacity edits, so the
    scanned simulator applies them as one scatter and starts the loop at
    ``capacity`` while normalizers stay frozen at the pre-release
    ``fleet.capacity``.  ``n_events`` (a traced scalar) bounds the loop to
    the first ``n_events`` entries — the caller asserts the rest are no-op
    padding, which the loop would skip anyway, so truncation is exact."""
    E = demands.shape[0]
    ctx = frozen_ctx(fleet, weights, horizon_h, energy=energy)
    cap0 = fleet.capacity if capacity is None else capacity
    healthy = fleet.healthy

    def body(e, state):
        cap, out, sweeps = state
        d, tgt = demands[e], nodes[e]

        def arrival(cap):
            scores = _ctx_scores(cap, ctx, weights)
            masked = jnp.where((cap >= d) & healthy, scores, jnp.inf)
            best = jnp.argmin(masked).astype(jnp.int32)
            ok = jnp.isfinite(masked[best])
            return best, ok, sweeps + 1

        def release(cap):
            return tgt, jnp.bool_(True), sweeps

        def noop(cap):
            return jnp.int32(0), jnp.bool_(False), sweeps

        # flat event dispatch: sign(d) + 1 -> release | noop | arrival
        chosen, ok, sweeps = jax.lax.switch(
            jnp.sign(d) + 1, (release, noop, arrival), cap)
        # one formula for both directions: arrivals subtract d > 0,
        # releases subtract d < 0 (i.e. credit chips back)
        cap = cap.at[chosen].add(jnp.where(ok, -d, 0))
        out = out.at[e].set(jnp.where(ok, chosen, -1))
        return cap, out, sweeps

    init = (cap0, jnp.full((E,), -1, jnp.int32),
            jnp.zeros((), jnp.int32))
    cap, out, sweeps = jax.lax.fori_loop(
        0, E if n_events is None else n_events, body, init)
    return PlacementResult(node=out,
                           scores=_ctx_scores(cap, ctx, weights),
                           capacity=cap, n_sweeps=sweeps)


def place_jobs_shortlist(fleet: Fleet, demands: jax.Array,
                         weights: RankWeights = RankWeights(),
                         horizon_h: float = 1.0, *,
                         shortlist: int = 32,
                         use_kernel: bool = False,
                         interpret: Optional[bool] = None,
                         energy: Optional[EnergyModel] = None
                         ) -> PlacementResult:
    """Arrivals-only wrapper over the lifecycle engine (see below)."""
    J = demands.shape[0]
    return place_lifecycle_shortlist(
        fleet, demands, jnp.full((J,), -1, jnp.int32), weights, horizon_h,
        shortlist=shortlist, use_kernel=use_kernel, interpret=interpret,
        energy=energy)


def place_lifecycle_shortlist(fleet: Fleet, demands: jax.Array,
                              nodes: jax.Array,
                              weights: RankWeights = RankWeights(),
                              horizon_h: float = 1.0, *,
                              shortlist: int = 32,
                              use_kernel: bool = False,
                              interpret: Optional[bool] = None,
                              capacity: Optional[jax.Array] = None,
                              n_events: Optional[jax.Array] = None,
                              eager_sweep: bool = False,
                              energy: Optional[EnergyModel] = None
                              ) -> PlacementResult:
    """Shortlist-greedy lifecycle placement, bit-identical to the oracle.

    Event stream semantics match ``place_lifecycle_full_rerank``:
    ``demands[e] > 0`` arrival, ``< 0`` release of ``-demands[e]`` chips on
    ``nodes[e]``, ``== 0`` no-op padding.  ``shortlist`` (static) is K, the
    epoch shortlist size; ``use_kernel`` routes the epoch sweeps through
    the fused Pallas two-sweep kernel
    (``repro.kernels.ops.maiz_ranking_topk``) — the TPU fleet-scale path.
    Custom ``energy`` models and ``weights.marginal`` are threaded into the
    kernel (the ``ec`` stream plus the en_* scalar block; see
    ``kernels.maizx_rank``).  Kernel scores agree with the jnp path to
    float32 tolerance (not bitwise; exact-parity guarantees are for the
    default jnp scoring).

    Each sweep ranks only nodes whose healthy free chips reach the
    call's smallest arrival (``dmin``, over the first ``n_events``
    events); the module docstring, point 2, says why that stays exact.
    The eager sweep follows the same rule.

    The engine starts *dirty* (no shortlist yet): leading releases are pure
    O(1) capacity edits and the first arrival performs the epoch's lazy
    initial sweep.  Releases on shortlist nodes are rescored in O(1);
    releases outside the shortlist re-dirty the epoch (their score fell
    below what the bound can certify — see module docstring).

    ``capacity``/``n_events``: see ``place_lifecycle_full_rerank`` — they
    let the scanned simulator pre-apply an epoch's (commutative) leading
    releases as one scatter while the frozen normalizers still come from
    the pre-release ``fleet.capacity`` snapshot, exactly as if the
    releases had streamed through a dirty engine, and truncate the loop at
    the compacted event count.

    ``eager_sweep`` hoists the epoch's first sweep out of the event loop:
    before any sweep an *arrival-only* stream cannot have changed capacity
    (placements require a sweep first — the engine starts dirty — and
    failed arrivals edit nothing), so ``sweeps == 0`` certifies
    ``cap == capacity`` and the pre-computed sweep of the starting capacity
    is exact.  This keeps ``lax.top_k`` out of the loop's conditionals,
    where XLA:CPU lowers it as a full sort (~50x slower) — the decisive
    win for the scanned simulator.  Only valid for streams with no release
    events (the scanned core's layout); placements, sweep counts and all
    tie-breaks are unchanged.

    The batched-ensemble simulator does NOT run this loop under ``vmap``
    (batched ``lax.cond`` executes both branches — every event would pay
    the O(N) sweep — and jax's while-loop batching select-copies the
    whole loop state per iteration); it drives the decision-identical
    hand-batched engine ``place_lifecycle_batched`` below instead."""
    N, E = fleet.n, demands.shape[0]
    K = min(max(shortlist, 1), N)
    full_cover = K >= N          # shortlist == whole fleet: bound unused
    INF = jnp.float32(jnp.inf)
    ctx = frozen_ctx(fleet, weights, horizon_h, energy=energy)
    cap0 = fleet.capacity if capacity is None else capacity
    # health is a HARD feasibility constraint (an outaged node is not a
    # candidate, period — the soft sched-weight penalty only biases);
    # static per call, so it composes with the bound argument unchanged
    healthy = fleet.healthy
    hcap = lambda cap: jnp.where(healthy, cap, 0)
    # the room rule (module docstring, point 2): candidates are the nodes
    # whose healthy free chips reach the call's smallest arrival
    live = demands > 0
    if n_events is not None:
        live = live & (jnp.arange(E) < n_events)
    dmin = _smallest_arrival(demands, live)

    # One epoch sweep = scores + the top-(K+1) candidate list in (score,
    # node index) lexicographic order: the kernel path gets it from the
    # tile-merged top-k directly; the jnp path from lax.top_k, whose
    # lower-index-first tie rule matches argmin/stable-sort (the kernel
    # merge relies on the same property).
    k_cand = min(K + 1, N)
    if use_kernel:
        from repro.kernels.ops import maiz_ranking_topk

        # custom idle/dynamic watts reach the kernel through the ``ec``
        # stream; the marginal-CFP term (when frozen_ctx materialized it)
        # through the pk/cap/ct node streams + the (1, 4) en scalar block
        em_k = energy
        if em_k is None and weights.marginal:
            em_k = DEFAULT_ENERGY.device(w_marginal=weights.marginal)
        if em_k is None:
            mkw = {}
        else:
            mkw = dict(pk=fleet.power_kw * horizon_h,
                       chips_total=ctx["ct_f"],
                       en=jnp.stack([jnp.asarray(ctx["idle_f"], jnp.float32),
                                     jnp.asarray(ctx["dyn_f"], jnp.float32),
                                     ctx["emb_h"], ctx["w_m"]]))

        @jax.named_scope("rank_sweep")
        def sweep_topk(cap):
            ec = fleet.effective_power_kw(cap, energy=em_k) * horizon_h
            kw = _room_kwargs(mkw, hcap(cap), dmin)
            return maiz_ranking_topk(
                ec, fleet.pue, fleet.ci_now, fleet.ci_forecast,
                fleet.flops_per_j, fleet.sched_term, weights.as_array(),
                k=k_cand, lohi=ctx["lohi"], interpret=interpret, **kw)
    else:
        @jax.named_scope("rank_sweep")
        def sweep_topk(cap):
            scores = _ctx_scores(cap, ctx, weights)
            ranked = jnp.where(hcap(cap) >= dmin, scores, INF)
            neg, idx = jax.lax.top_k(-ranked, k_cand)
            return scores, -neg, idx.astype(jnp.int32)

    def split_shortlist(cand_s, cand_i):
        cand_i = _no_node_past_room(cand_s, cand_i, N)
        if full_cover:
            return cand_s[:K], cand_i[:K], INF, jnp.int32(N)
        return cand_s[:K], cand_i[:K], cand_s[K], cand_i[K]

    # the epoch's first sweep, hoisted to the top level where lax.top_k
    # takes XLA:CPU's fast path (see docstring); exact while sweeps == 0
    eager = sweep_topk(cap0) if eager_sweep else None

    karange = jnp.arange(K)

    def body(e, state):
        (cap, out, sl_s, sl_i, bound_s, bound_i, cap_max, sweeps,
         dirty, wc) = state
        d, tgt = demands[e], nodes[e]

        # cond branches read the (N,) capacity but return only scalars and
        # (K,)-sized shortlist state — the lone (N,) write (the capacity
        # scatter below) covers arrivals AND releases via one signed add.
        op = (cap, sl_s, sl_i, bound_s, bound_i, cap_max, sweeps, dirty, wc)

        def release(op):
            """Credit -d chips to node tgt: O(1), never sweeps.

            In-shortlist: rescore the entry (non-shortlist scores are
            untouched, the bound stays sound).  Outside: the node's score
            fell below anything the bound can certify -> dirty."""
            cap, sl_s, sl_i, bound_s, bound_i, cap_max, sweeps, dirty, wc = op
            new_cap = cap[tgt] - d              # d < 0: adds chips
            hitmask = (sl_i == tgt)
            hit = (~dirty) & jnp.any(hitmask)
            new_s = _one_score(new_cap, tgt, ctx, weights)
            sl_s = jnp.where(hit & hitmask, new_s, sl_s)
            return (tgt, jnp.bool_(True), sl_s, sl_i, bound_s, bound_i,
                    jnp.maximum(cap_max,
                                jnp.where(healthy[tgt], new_cap, 0)),
                    sweeps, dirty | (~hit), wc)

        def noop(op):
            return (jnp.int32(0), jnp.bool_(False)) + op[1:]

        def arrival(op):
            cap, sl_s, sl_i, bound_s, bound_i, cap_max, sweeps, dirty, _ = op
            # best feasible (capacity + health) shortlist entry by
            # (score, node index)
            sm = jnp.where((cap[sl_i] >= d) & healthy[sl_i], sl_s, INF)
            m = jnp.min(sm)
            kbest = jnp.argmin(jnp.where(sm == m, sl_i, jnp.int32(N)))
            bnode = sl_i[kbest]
            feasible = jnp.isfinite(m)
            beats = (m < bound_s) | ((m == bound_s) & (bnode < bound_i))
            use_sl = (~dirty) & feasible & beats
            # truly unplaceable without a sweep: the demand exceeds every
            # free capacity (cap_max is a sound upper bound — it only grows
            # by explicit release credits), or the clean shortlist covers
            # the whole fleet and nothing fits
            dead = (d > cap_max) | ((~dirty) & (~feasible)
                                    & (~jnp.isfinite(bound_s)))

            def from_shortlist(op):
                cap, sl_s, sl_i, bound_s, bound_i, cap_max, sweeps, _, wc = op
                new_s = _one_score(cap[bnode] - d, bnode, ctx, weights)
                return (bnode, jnp.bool_(True),
                        jnp.where(karange == kbest, new_s, sl_s), sl_i,
                        bound_s, bound_i, cap_max, sweeps, jnp.bool_(False),
                        wc)

            def land_from(swept, op):
                """Place this job from a fresh sweep's (scores, top-k) and
                open a new (clean) epoch; the landed node's shortlist entry
                is patched in place."""
                scores, cand_s, cand_i = swept
                cap, _, _, _, _, _, sweeps, _, wc = op
                masked = jnp.where((cap >= d) & healthy, scores, INF)
                best = jnp.argmin(masked).astype(jnp.int32)
                ok = jnp.isfinite(masked[best])
                new_s = _one_score(cap[best] - d, best, ctx, weights)
                sl_s, sl_i, bound_s, bound_i = split_shortlist(cand_s,
                                                               cand_i)
                sl_s = jnp.where(ok & (sl_i == best), new_s, sl_s)
                # the sweep's cause, in WALK_COUNTS' order of precedence;
                # slot 0 counts the arrivals a sweep placed until the end
                cause = jnp.where(dirty, 1, jnp.where(feasible, 3, 2))
                wc = tuple(c + (cause == i) + (ok & (i == 0))
                           for i, c in enumerate(wc))
                return (best, ok, sl_s, sl_i, bound_s, bound_i,
                        jnp.max(hcap(cap)), sweeps + 1, jnp.bool_(False), wc)

            def from_sweep(op):
                """Fresh O(N) sweep: exact placement from the full masked
                argmin.  With ``eager_sweep``, the first sweep reuses the
                hoisted top-level sweep (``sweeps == 0`` certifies the
                capacity is untouched)."""
                if eager is None:
                    return land_from(sweep_topk(op[0]), op)
                return jax.lax.cond(
                    op[6] == 0,
                    functools.partial(land_from, eager),
                    lambda o: land_from(sweep_topk(o[0]), o), op)

            def unplaceable(op):
                return (jnp.int32(0), jnp.bool_(False)) + op[1:]

            return jax.lax.cond(
                use_sl, from_shortlist,
                lambda o: jax.lax.cond(dead, unplaceable, from_sweep, o),
                op)

        # flat event dispatch: sign(d) + 1 -> release | noop | arrival
        (chosen, ok, sl_s, sl_i, bound_s, bound_i, cap_max, sweeps,
         dirty, wc) = jax.lax.switch(
            jnp.sign(d) + 1, (release, noop, arrival), op)
        # arrivals subtract d > 0; releases subtract d < 0 (credit)
        cap = cap.at[chosen].add(jnp.where(ok, -d, 0))
        out = out.at[e].set(jnp.where(ok, chosen, -1))
        return (cap, out, sl_s, sl_i, bound_s, bound_i, cap_max, sweeps,
                dirty, wc)

    state = (cap0, jnp.full((E,), -1, jnp.int32),
             jnp.full((K,), INF), jnp.full((K,), N, jnp.int32),
             INF, jnp.int32(N), jnp.max(hcap(cap0)),
             jnp.zeros((), jnp.int32), jnp.bool_(True),
             (jnp.zeros((), jnp.int32),) * len(WALK_COUNTS))
    out_state = jax.lax.fori_loop(
        0, E if n_events is None else n_events, body, state)
    cap, out, sweeps = out_state[0], out_state[1], out_state[7]
    # scalar counters, touched only where a sweep runs: the per-event path
    # carries them and does no more work
    swept_placed, *causes = out_state[9]
    placed = jnp.sum((demands > 0) & (out >= 0), dtype=jnp.int32)
    return PlacementResult(node=out,
                           scores=_ctx_scores(cap, ctx, weights),
                           capacity=cap, n_sweeps=sweeps,
                           walk_counts=jnp.stack([placed - swept_placed,
                                                  *causes]))


# ---------------------------------------------------------------------------
# hand-batched lifecycle engine: an explicit lane axis for the ensemble
# ---------------------------------------------------------------------------


def _one_score_b(cap_b, b, ctx, w: RankWeights):
    """Per-lane O(1) rescore: lane l's node ``b[l]`` at free chips
    ``cap_b[l]`` — the batched twin of ``_one_score``, bit-identical per
    lane (the same barrier-pinned elementwise graph, gathered per lane)."""
    lanes = jnp.arange(b.shape[0])
    g = {k: (v[lanes, b][:, None] if k in _GATHERED else v)
         for k, v in ctx.items()}
    return _ctx_scores(cap_b[:, None], g, w)[:, 0]


def place_lifecycle_batched(fleet: Fleet, demands: jax.Array,
                            weights: RankWeights = RankWeights(),
                            horizon_h: float = 1.0, *,
                            engine: str = "shortlist", shortlist: int = 32,
                            use_kernel: bool = False,
                            interpret: Optional[bool] = None,
                            capacity: Optional[jax.Array] = None,
                            n_events: Optional[jax.Array] = None,
                            energy: Optional[EnergyModel] = None,
                            mesh: Optional[jax.sharding.Mesh] = None):
    """Arrival-only lifecycle placement over an explicit leading lane axis
    — the batched-ensemble twin of ``place_lifecycle_shortlist`` (with
    ``eager_sweep``) and ``place_lifecycle_full_rerank``.  Its sweeps
    follow the same room rule (module docstring, point 2), ``dmin`` taken
    per lane over that lane's ``n_events`` arrivals.

    ``fleet`` carries ``(L, N)`` leaves (L ensemble lanes), ``demands``
    is ``(L, E)`` arrival chips (pads 0), ``capacity`` the ``(L, N)``
    post-release starting capacity, ``n_events`` the ``(L,)`` compacted
    arrival counts.  Returns ``(node (L, E), capacity (L, N),
    n_sweeps (L,), walk_counts (L, 4), sweep_rounds ())`` —
    **decision-identical per lane** to running the sequential engine on
    that lane: same shortlist/bound predicates, same tie-breaks, same
    sweep and ``WALK_COUNTS`` counts.  ``sweep_rounds`` counts the outer
    rounds below, each one batched sweep over all L lanes whether or not
    a lane stalled; the full-rerank oracle returns None for both.

    Why not just ``vmap`` the sequential engine: batched ``lax.cond``
    executes BOTH branches, so every event would pay the O(N) sweep +
    top-k, and jax's while-loop batching select-copies the entire loop
    state every iteration.  This implementation instead runs two nested
    ``while_loop``s with SCALAR (any-reduced) conditions and explicit
    per-lane masks:

    - the **inner walk** consumes events with O(K) shortlist work per
      lane per step — a lane whose event needs a fresh sweep *stalls*
      (its pointer stops advancing);
    - the **outer round** performs ONE batched O(L·N) sweep + top-k and
      lands every stalled lane's event from it (on that lane's current
      capacity — exactly the tensor the sequential engine would have
      computed at that event), then resumes the walk.

    O(N) work therefore happens ~sweep-count times per epoch for the
    whole ensemble, and the per-event ops amortize their dispatch
    overhead across lanes — the enabling structure for
    ``simulator.simulate_fleet_ensemble``.  The shortlist top-k merge is
    the batched ``lax.top_k``; with ``use_kernel`` the round-boundary
    sweep is instead ONE Pallas launch on a (stalled-lanes × node-tiles)
    grid (``repro.kernels.ops.maiz_ranking_topk_batched``), per-lane
    identical to the sequential engine's kernel sweep.  ``mesh`` is the
    ensemble's device mesh when its buffers are sharded: the kernel sweep
    then runs per device under ``shard_map`` (the jnp path needs no
    mesh; XLA partitions it)."""
    L, N = fleet.capacity.shape
    E = demands.shape[1]
    K = min(max(shortlist, 1), N)
    k_cand = min(K + 1, N)
    full_cover = K >= N
    INF = jnp.float32(jnp.inf)
    lanes = jnp.arange(L)
    karange = jnp.arange(K)
    if energy is None:
        ctx = jax.vmap(lambda f: frozen_ctx(f, weights, horizon_h))(fleet)
    else:
        # energy carries (L,)-scalar leaves — one model per ensemble lane
        ctx = jax.vmap(
            lambda f, e: frozen_ctx(f, weights, horizon_h, energy=e)
        )(fleet, energy)
    # (L,) normalizer scalars broadcast against (L, N) score columns
    ctx = {k: (v[:, None] if v.ndim == 1 else v) for k, v in ctx.items()}
    cap0 = fleet.capacity if capacity is None else capacity
    healthy = fleet.healthy
    n_ev = jnp.full((L,), E, jnp.int32) if n_events is None else n_events
    hcap = lambda cap: jnp.where(healthy, cap, 0)
    hmax = lambda cap: jnp.max(hcap(cap), axis=1)
    arrivals = (jnp.arange(E)[None, :] < n_ev[:, None]) & (demands > 0)
    # the sequential engine's room rule, one smallest arrival per lane
    dmin = _smallest_arrival(demands, arrivals, axis=1)

    def ev_demand(ptr):
        p = jnp.minimum(ptr, E - 1)
        return p, jnp.take_along_axis(demands, p[:, None], 1)[:, 0]

    def keep_out(out, p):
        return jnp.take_along_axis(out, p[:, None], 1)[:, 0]

    if engine == "full":
        # full-rerank oracle: every arrival is one batched O(L·N) rescore
        # + masked argmin — no branch structure to restructure
        def fbody(e, st):
            cap, out, sweeps = st
            d = demands[:, e]
            live = (e < n_ev) & (d > 0)
            scores = _ctx_scores(cap, ctx, weights)
            masked = jnp.where((cap >= d[:, None]) & healthy, scores, INF)
            best = jnp.argmin(masked, axis=1).astype(jnp.int32)
            ok = live & jnp.isfinite(
                jnp.take_along_axis(masked, best[:, None], 1)[:, 0])
            cap = cap.at[lanes, best].add(jnp.where(ok, -d, 0))
            out = out.at[lanes, e].set(jnp.where(ok, best, out[:, e]))
            return cap, out, sweeps + live.astype(jnp.int32)

        cap, out, sweeps = jax.lax.fori_loop(
            0, jnp.max(n_ev), fbody,
            (cap0, jnp.full((L, E), -1, jnp.int32),
             jnp.zeros((L,), jnp.int32)))
        return out, cap, sweeps, None, None

    if use_kernel:
        from repro.kernels.ops import maiz_ranking_topk_batched

        # the same stream threading as the sequential engine, one lane
        # axis wider: ec via (vmapped) effective power, the marginal term
        # via pk/cap/ct + the per-lane (L, 4) en block from the vmapped ctx
        if energy is None:
            eff_pw = fleet.effective_power_kw
        else:
            def eff_pw(cap):
                return jax.vmap(
                    lambda f, c, e: f.effective_power_kw(c, energy=e)
                )(fleet, cap, energy)
        if "m_dyn" in ctx:
            mkw = dict(pk=fleet.power_kw * horizon_h,
                       chips_total=ctx["ct_f"],
                       en=jnp.concatenate(
                           [ctx["idle_f"], ctx["dyn_f"],
                            ctx["emb_h"], ctx["w_m"]], axis=1))
        else:
            mkw = {}

        @jax.named_scope("rank_sweep")
        def sweep_topk(cap):
            ec = eff_pw(cap) * horizon_h
            kw = _room_kwargs(mkw, hcap(cap), dmin)
            return maiz_ranking_topk_batched(
                ec, fleet.pue, fleet.ci_now, fleet.ci_forecast,
                fleet.flops_per_j, fleet.sched_term, weights.as_array(),
                k=k_cand, lohi=ctx["lohi"], interpret=interpret, mesh=mesh,
                **kw)
    else:
        @jax.named_scope("rank_sweep")
        def sweep_topk(cap):
            scores = _ctx_scores(cap, ctx, weights)
            ranked = jnp.where(hcap(cap) >= dmin[:, None], scores, INF)
            neg, idx = jax.lax.top_k(-ranked, k_cand)
            return scores, -neg, idx.astype(jnp.int32)

    def split_shortlist(cand_s, cand_i):
        cand_i = _no_node_past_room(cand_s, cand_i, N)
        if full_cover:
            return (cand_s[:, :K], cand_i[:, :K],
                    jnp.full((L,), INF), jnp.full((L,), N, jnp.int32))
        return cand_s[:, :K], cand_i[:, :K], cand_s[:, K], cand_i[:, K]

    # The inner walk never touches the (L, N) capacity array: feasibility
    # inside a round only consults SHORTLIST nodes (the resident ``slcap``
    # mirror of ``cap[sl_i]``, updated in O(1) per placement) and the
    # round-static ``cap_max`` upper bound — exactly the sequential
    # engine's invariant.  Placements are applied to ``cap`` as one
    # deferred scatter at the round boundary (disjoint single-node edits,
    # so the deferral is exact), keeping the per-event while carry at
    # O(L·K) + the output row instead of O(L·N).

    wc_index = jnp.arange(len(WALK_COUNTS), dtype=jnp.int32)[None, :]

    def inner_cond(c):
        return jnp.any((c[3] < n_ev) & ~c[4])

    def make_inner(sl_i, slh, bound_s, bound_i, cap_max, dirty):
        """Inner step closed over the round-static shortlist identity —
        only scores/capacities of shortlist entries evolve mid-round."""

        def inner_step(c):
            out, slcap, sl_s, ptr, need = c
            act = (ptr < n_ev) & ~need
            p, d = ev_demand(ptr)
            is_arr = act & (d > 0)
            sm = jnp.where((slcap >= d[:, None]) & slh, sl_s, INF)
            m = jnp.min(sm, axis=1)
            kbest = jnp.argmin(jnp.where(sm == m[:, None], sl_i, N),
                               axis=1)
            bnode = jnp.take_along_axis(sl_i, kbest[:, None], 1)[:, 0]
            feasible = jnp.isfinite(m)
            beats = (m < bound_s) | ((m == bound_s) & (bnode < bound_i))
            use_sl = (~dirty) & feasible & beats
            dead = (d > cap_max) | ((~dirty) & (~feasible)
                                    & (~jnp.isfinite(bound_s)))
            place_sl = is_arr & use_sl
            stall = is_arr & (~use_sl) & (~dead)
            cap_b = jnp.take_along_axis(slcap, kbest[:, None], 1)[:, 0] - d
            new_s = _one_score_b(cap_b, bnode, ctx, weights)
            hit = place_sl[:, None] & (karange[None, :] == kbest[:, None])
            sl_s = jnp.where(hit, new_s[:, None], sl_s)
            slcap = jnp.where(hit, slcap - d[:, None], slcap)
            out = out.at[lanes, p].set(jnp.where(place_sl, bnode,
                                                 keep_out(out, p)))
            ptr = jnp.where(act & ~stall, ptr + 1, ptr)
            return out, slcap, sl_s, ptr, need | stall

        return inner_step

    def outer_cond(st):
        return jnp.any((st[10] < n_ev) | st[12])

    def outer_body(st):
        (cap, out, slcap, sl_s, sl_i, bound_s, bound_i, cap_max, sweeps,
         dirty, ptr, ptr0, need, wc, rounds) = st
        slh = jnp.take_along_axis(healthy, sl_i, 1)
        out, slcap, sl_s, ptr, need = jax.lax.while_loop(
            inner_cond, make_inner(sl_i, slh, bound_s, bound_i, cap_max,
                                   dirty),
            (out, slcap, sl_s, ptr, need))
        # the cause of each stalled lane's sweep, counted once a round and
        # not in the walk: a stalled lane's shortlist is as it stalled
        p, d = ev_demand(ptr)
        fits = jnp.isfinite(jnp.min(jnp.where(
            (slcap >= d[:, None]) & slh, sl_s, INF), axis=1))
        cause = jnp.where(dirty, 1, jnp.where(fits, 3, 2))
        wc = wc + (need[:, None]
                   & (wc_index == cause[:, None])).astype(jnp.int32)
        # apply the walk's placements (events [ptr0, ptr) that landed) to
        # the full capacity as ONE scatter of disjoint single-node edits
        seg = jnp.arange(E, dtype=jnp.int32)[None, :]
        newly = (seg >= ptr0[:, None]) & (seg < ptr[:, None]) & (out >= 0)
        cap = cap.at[lanes[:, None], jnp.clip(out, 0, N - 1)].add(
            jnp.where(newly, -demands, 0))
        # one fresh sweep per round — the tensors ``land_from`` computes,
        # applied only on stalled lanes (at their own current capacity)
        scores, cand_s, cand_i = sweep_topk(cap)
        masked = jnp.where((cap >= d[:, None]) & healthy, scores, INF)
        best = jnp.argmin(masked, axis=1).astype(jnp.int32)
        ok = jnp.isfinite(
            jnp.take_along_axis(masked, best[:, None], 1)[:, 0])
        cap_b = jnp.take_along_axis(cap, best[:, None], 1)[:, 0] - d
        new_s = _one_score_b(cap_b, best, ctx, weights)
        sl_s2, sl_i2, bound_s2, bound_i2 = split_shortlist(cand_s, cand_i)
        sl_s2 = jnp.where(ok[:, None] & (sl_i2 == best[:, None]),
                          new_s[:, None], sl_s2)
        cm2 = hmax(cap)                  # pre-placement, as in land_from
        out = out.at[lanes, p].set(jnp.where(
            need, jnp.where(ok, best, -1), keep_out(out, p)))
        cap = cap.at[lanes, best].add(jnp.where(need & ok, -d, 0))
        slcap2 = jnp.take_along_axis(cap, sl_i2, 1)
        pick = lambda a, b: jnp.where(need, a, b)
        pick2 = lambda a, b: jnp.where(need[:, None], a, b)
        ptr = jnp.where(need, ptr + 1, ptr)
        return (cap, out, pick2(slcap2, slcap), pick2(sl_s2, sl_s),
                pick2(sl_i2, sl_i),
                pick(bound_s2, bound_s), pick(bound_i2, bound_i),
                pick(cm2, cap_max), sweeps + need.astype(jnp.int32),
                dirty & ~need, ptr, ptr,
                jnp.zeros_like(need),
                wc.at[:, 0].add((need & ok).astype(jnp.int32)), rounds + 1)

    st = (cap0, jnp.full((L, E), -1, jnp.int32),
          jnp.take_along_axis(cap0, jnp.full((L, K), N - 1, jnp.int32), 1),
          jnp.full((L, K), INF), jnp.full((L, K), N, jnp.int32),
          jnp.full((L,), INF), jnp.full((L,), N, jnp.int32),
          hmax(cap0), jnp.zeros((L,), jnp.int32),
          jnp.ones((L,), bool), jnp.zeros((L,), jnp.int32),
          jnp.zeros((L,), jnp.int32), jnp.zeros((L,), bool),
          jnp.zeros((L, len(WALK_COUNTS)), jnp.int32),
          jnp.zeros((), jnp.int32))
    st = jax.lax.while_loop(outer_cond, outer_body, st)
    # column 0 counted the arrivals a sweep placed: those the shortlist
    # placed are the rest of the placed arrivals
    placed = jnp.sum(arrivals & (st[1] >= 0), axis=1, dtype=jnp.int32)
    wc = st[13].at[:, 0].set(placed - st[13][:, 0])
    return st[1], st[0], st[8], wc, st[14]
