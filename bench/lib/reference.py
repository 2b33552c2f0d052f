"""Plain reference of MAIZX placement and of the fleet simulator.

Straightforward numpy, written from the semantics and not from the
program's code, and imports nothing of the program:

- Eq. 2: a node's carbon footprint for the next hour is its power draw
  (idle floor + dynamic share by occupied chips) x PUE x carbon intensity,
  now (CFP) and forecast (FCFP);
- Eq. 1: score = w1 n(CFP) + w2 n(FCFP) + w3 (1 - n(efficiency))
  + w4 n(schedule weight) [+ w_m n(marginal CFP)], with each n() a min-max
  normalization frozen at the state the decision starts from (a release
  or a landing job moves one node's own terms, never the normalizers);
- greedy placement: each arriving job takes the healthy node with room
  and the lowest score (lowest index on a tie); a release returns its chips;
- the simulator: hourly epochs of releases, placement of the epoch's
  arrivals, and emissions of every powered node at the true intensity.

Every function takes a numpy dtype ``dt``: float64 is the reference,
``ml_dtypes.bfloat16`` the control that stands in the program's place one
precision below what the configuration states (float32).  ``follow``
replays another implementation's answers and measures, for each
placement, how far its score lies above the best one (``gap``); a
placement that is not allowed at all counts in ``invalid``.
"""
from __future__ import annotations

import numpy as np

INF = float("inf")


# ---------------------------------------------------------------------------
# Eq. 1 with frozen normalizers
# ---------------------------------------------------------------------------


def _norm(x, dt):
    lo, hi = x.min(), x.max()
    span = hi - lo
    if not span > 1e-12:
        return lo, dt(0.0)
    return lo, dt(1.0) / span


class Scorer:
    """Scores of one decision: normalizers frozen at ``cap_ctx``."""

    def __init__(self, fl: dict, cap_ctx, sched, weights: dict, energy: dict,
                 marginal: bool, dt=np.float64):
        f = lambda x: np.asarray(x).astype(dt)
        self.dt = dt
        self.w = [dt(weights[k]) for k in ("w1", "w2", "w3", "w4")]
        self.idle, self.dyn = dt(energy["idle_frac"]), dt(energy["dyn_frac"])
        pue = f(fl["pue"])
        self.a_now = f(fl["power_kw"]) * pue * f(fl["ci_now"])
        self.a_fc = f(fl["power_kw"]) * pue * f(fl["ci_fc"])
        self.ct = f(fl["chips_total"])
        eff, sch = f(fl["flops_per_j"]), f(sched)
        lo_e, r_e = _norm(eff, dt)
        lo_s, r_s = _norm(sch, dt)
        self.static = (self.w[2] * (dt(1.0) - (eff - lo_e) * r_e)
                       + self.w[3] * ((sch - lo_s) * r_s))
        self.marginal = marginal
        if marginal:
            self.emb = dt(energy["embodied_g_per_node_h"])
            self.w_m = dt(energy["w_marginal"])
        cap0 = f(cap_ctx)
        self.lo_now, self.r_now = _norm(self.a_now * self._factor(cap0), dt)
        self.lo_fc, self.r_fc = _norm(self.a_fc * self._factor(cap0), dt)
        if marginal:
            self.lo_m, self.r_m = _norm(self._mcfp(cap0, slice(None)), dt)

    def _factor(self, capf, idx=slice(None)):
        return self.idle + self.dyn * (self.dt(1.0) - capf / self.ct[idx])

    def _mcfp(self, capf, idx):
        a, ct = self.a_now[idx], self.ct[idx]
        wake = np.where(capf == ct, a * self.idle + self.emb, self.dt(0.0))
        return (a / ct * self.dyn + wake).astype(self.dt)

    def score(self, cap, idx=slice(None)):
        capf = np.asarray(cap).astype(self.dt)
        fac = self._factor(capf, idx)
        s = (self.w[0] * ((self.a_now[idx] * fac - self.lo_now) * self.r_now)
             + self.w[1] * ((self.a_fc[idx] * fac - self.lo_fc) * self.r_fc)
             + self.static[idx])
        if self.marginal:
            s = s + self.w_m * ((self._mcfp(capf, idx) - self.lo_m)
                                * self.r_m)
        return np.asarray(s).astype(self.dt)


def sched_term(straggler, healthy):
    """Eq. 1 SCHEDULE_WEIGHT: straggler score, +1e3 for a node out of
    service (which is also never a candidate)."""
    return np.asarray(straggler, np.float64) + np.where(healthy, 0.0, 1e3)


# ---------------------------------------------------------------------------
# one decision: an event stream of releases and arrivals
# ---------------------------------------------------------------------------


class Audit:
    """Widest score gap of a placement above the best, and the count of
    placements that were not allowed (no room, node out of service, a job
    left out while a node had room, a release echoed to another node)."""

    def __init__(self):
        self.gap = 0.0
        self.invalid = 0
        self.checked = 0
        self.scored = 0

    def merge(self, other: "Audit"):
        self.gap = max(self.gap, other.gap)
        self.invalid += other.invalid
        self.checked += other.checked
        self.scored += other.scored


def place_events(sc: Scorer, cap, healthy, demands, nodes, follow=None,
                 audit: Audit = None, score=None):
    """Greedy placement of one decision's events, in order.  Returns the
    chosen node per event (-1 for a job that fits nowhere) and the final
    capacity.  With ``follow``, the answers are those of ``follow`` and
    ``audit`` records their gaps.

    ``score`` (with ``follow``; a boolean per event) names the arrivals
    whose gap is measured, each by the argmin over all nodes.  Every other
    arrival costs O(1): its node is checked for room and health, and a job
    left out is checked against every healthy node, only where one may
    still have room.  ``invalid`` counts the same either way."""
    cap = np.asarray(cap, np.int64).copy()
    healthy = np.asarray(healthy, bool)
    s = sc.score(cap)
    out = np.full(len(demands), -1, np.int64)
    demands, nodes = np.asarray(demands).tolist(), np.asarray(nodes).tolist()
    if follow is not None:
        follow = np.asarray(follow).tolist()
    if score is not None:
        score = np.asarray(score, bool).tolist()
    stale = []          # nodes whose score is out of date (unscored arrivals)
    room_hi = INF       # bound on the most room a healthy node has
    for e in range(len(demands)):
        d = demands[e]
        if d == 0:
            continue
        if d < 0:
            c = nodes[e]
            if follow is not None and follow[e] != c:
                audit.invalid += 1
            cap[c] -= d
            room_hi = max(room_hi, int(cap[c]))
            s[c] = sc.score(cap[c:c + 1], slice(c, c + 1))[0]
            out[e] = c
            continue
        if score is not None and not score[e]:
            c = follow[e]
            audit.checked += 1
            if c < 0:
                if d <= room_hi:
                    room_hi = int(np.max(cap, where=healthy, initial=-1))
                    audit.invalid += int(room_hi >= d)
            elif not (0 <= c < cap.size and healthy[c] and cap[c] >= d):
                audit.invalid += 1
                c = -1
            if c >= 0:
                cap[c] -= d
                stale.append(c)
            out[e] = c
            continue
        if stale:
            idx = np.asarray(stale)
            s[idx] = sc.score(cap[idx], idx)
            stale = []
        feas = healthy & (cap >= d)
        masked = np.where(feas, s, INF)
        b = int(np.argmin(masked))
        if not feas[b]:
            b = -1
        c = b if follow is None else follow[e]
        if follow is not None:
            audit.checked += 1
            audit.scored += 1
            if c < 0:
                audit.invalid += int(b >= 0)
            elif not (0 <= c < cap.size and feas[c]):
                audit.invalid += 1
                c = -1
            else:
                audit.gap = max(audit.gap,
                                float(s[c]) - float(masked[b]))
        if c >= 0:
            cap[c] -= d
            s[c] = sc.score(cap[c:c + 1], slice(c, c + 1))[0]
        out[e] = c
    return out, cap


# ---------------------------------------------------------------------------
# the simulator: one lane, hourly epochs
# ---------------------------------------------------------------------------

PERIODS = ((24.0, 3), (168.0, 2), (8760.0, 1))


def _design(t, periods):
    cols = [np.ones_like(t)]
    for period, nh in periods:
        for k in range(1, nh + 1):
            w = 2 * np.pi * k * t / period
            cols += [np.cos(w), np.sin(w)]
    return np.stack(cols, axis=-1)


def forecast_mean(history, horizon):
    """Mean of the next ``horizon`` hours of the harmonic-regression
    forecast: a least-squares Fourier fit (daily, weekly, annual terms,
    each only with a full cycle in the window), plus the last day's
    residual pattern decaying 0.82 a day, floored at 0."""
    T = history.shape[0]
    periods = tuple(p for p in PERIODS if T >= p[0])
    X = _design(np.arange(T, dtype=np.float64), periods)
    coef = np.linalg.lstsq(X, history, rcond=None)[0]
    resid = history - X @ coef
    h = np.arange(horizon, dtype=np.float64)
    L = min(T, 24)
    pattern = resid[-L:][np.mod(h.astype(np.int64), L)]
    fc = _design(T + h, periods) @ coef + pattern * 0.82 ** (h / 24 + 0.25)
    return float(np.maximum(fc, 0.0).mean())


def simulate_lane(lane: dict, dt=np.float64, follow: dict = None,
                  score=None):
    """One trajectory of the simulator (no deferral, migration or outage).

    ``lane`` holds the fleet arrays, ``traces`` (R, hours), ``ridx``, the
    job columns, ``epochs``, ``history_h``, ``horizon_h``, ``consolidate``,
    ``weights`` and ``energy``.  Returns the trajectory (first node and
    start epoch per job, emissions per epoch, placed/completed counts);
    with ``follow`` (another implementation's trajectory) it replays those
    answers and returns ``(audit, widest relative emission error)``;
    ``score`` (a boolean per job) then names the arrivals whose gap is
    measured (``place_events``), None all of them.  Every epoch's
    emissions are recomputed in full either way."""
    T, hist, hor = lane["epochs"], lane["history_h"], lane["horizon_h"]
    traces, ridx = np.asarray(lane["traces"], np.float64), lane["ridx"]
    healthy = np.asarray(lane["healthy"], bool)
    chips, dur, arrive = lane["chips"], lane["duration"], lane["arrive"]
    J, N = chips.shape[0], ridx.shape[0]
    en = lane["energy"]
    cap = np.asarray(lane["capacity"], np.int64).copy()
    njobs = np.zeros(N, np.int64)
    node = np.full(J, -1, np.int64)
    start = np.full(J, -1, np.int64)
    end = np.full(J, -1, np.int64)
    series = np.zeros(T)
    placed = completed = 0
    audit = Audit()
    order = np.argsort(arrive, kind="stable")
    by_t = np.split(order, np.searchsorted(arrive[order], np.arange(1, T)))
    power = np.asarray(lane["power_kw"]).astype(dt)
    pue = np.asarray(lane["pue"]).astype(dt)
    ct = np.asarray(lane["chips_total"]).astype(dt)
    idle, dyn, emb = dt(en["idle_frac"]), dt(en["dyn_frac"]), \
        dt(en["embodied_g_per_node_h"])
    for t in range(T):
        a = hist + t
        ci_now = traces[:, a]
        ci_fc = np.array([forecast_mean(traces[r, a - hist:a], hor)
                          for r in range(traces.shape[0])])
        cap_ctx = cap.copy()
        rel = np.where(end == t)[0]
        np.add.at(cap, node[rel], chips[rel])
        np.add.at(njobs, node[rel], -1)
        end[rel] = -1
        completed += rel.size
        strag = np.asarray(lane["straggler_score"], np.float64) \
            + lane["consolidate"] * (njobs == 0)
        fl = dict(lane, ci_now=ci_now[ridx], ci_fc=ci_fc[ridx])
        sc = Scorer(fl, cap_ctx, sched_term(strag, healthy), lane["weights"],
                    en, marginal=True, dt=dt)
        arr = by_t[t] if t < len(by_t) else np.empty(0, np.int64)
        fol = None
        if follow is not None:
            fol = np.where(follow["start_epoch"][arr] == t,
                           follow["first_node"][arr], -1)
            audit.invalid += int(np.sum((follow["start_epoch"][arr] != t)
                                        & (follow["start_epoch"][arr] >= 0)))
        out, cap = place_events(sc, cap, healthy, chips[arr],
                                np.full(arr.size, -1), follow=fol,
                                audit=audit,
                                score=None if score is None else score[arr])
        ok = out >= 0
        node[arr[ok]], start[arr[ok]] = out[ok], t
        end[arr[ok]] = t + dur[arr[ok]]
        np.add.at(njobs, out[ok], 1)
        placed += int(ok.sum())
        on = njobs > 0
        occ = dt(1.0) - cap.astype(dt) / ct
        node_g = (power * (idle + dyn * occ) * on) * pue \
            * traces[:, a][ridx].astype(dt) + emb * on
        series[t] = float(np.sum(node_g.astype(dt), dtype=dt))
    result = dict(first_node=node, start_epoch=start, emissions=series,
                  placed=placed, completed=completed)
    if follow is None:
        return result
    audit.invalid += int(follow["placed"] != placed)
    audit.invalid += int(follow["completed"] != completed)
    got = np.asarray(follow["emissions"], np.float64)
    rel_err = float(np.max(np.abs(got - series)
                           / np.maximum(np.abs(series), 1e-30)))
    return audit, rel_err
