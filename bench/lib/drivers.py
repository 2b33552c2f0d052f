"""The two traffic drivers: a traffic file names one of them under "driver"
and gives its parameters; a configuration file gives the deployment.

- ``decide``: the MAIZX decision service.  A closed loop with one caller:
  each call places one control interval's arrivals and releases through
  ``scheduler.place_events`` on the fleet its own earlier decisions left.
- ``sim``: what-if studies.  Each call runs a batch of simulated fleet
  trajectories (``simulate_fleet_ensemble`` or ``simulate_fleet_scan``);
  calls rotate through input sets generated in set-up.  An ensemble's
  traffic may name a device layout, ``"shard": "e" | "en"``, which the
  program lays the batch out by over every visible device.

A driver builds its inputs from the seed in ``setup`` (which also warms
every shape), runs one unit of work per ``call`` inside the harness's
spans, and after the window ``check``s what the window produced against
the plain reference (``bench/lib/reference.py``).  ``entry`` is the
program's function the timed path calls; the tests replace it to plant
faults.
"""
from __future__ import annotations

import time

import numpy as np

from lib import gen, reference


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _subseed(seed: int, *path) -> int:
    return int(np.random.default_rng([seed % (1 << 63), *path])
               .integers(1 << 31))


class Decide:
    """Decision service: one call = one control interval.

    The deployment (fleet, service jobs, the job stream) is drawn from the
    configuration's ``deployment_seed``; the run's seed orders each
    interval's arrivals.  Set-up places the service jobs and runs
    ``warm_calls`` decisions through the program, which leaves the
    fleet's state; the window carries on from there.  On each simulated
    hour the timed call first refreshes the grid signal on the device:
    the hour's intensity and the program's forecast over the next
    ``horizon_h`` hours."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.core import scheduler
        self.cfg, self.tr, self.seed = cfg, traffic, seed
        self.imin = int(traffic["interval_min"])
        self.per_h = 60 // self.imin
        self.entry = scheduler.place_events_jit
        self.records = []

    # -- set-up ----------------------------------------------------------
    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.core import forecast
        from repro.core.fleet import Fleet
        cfg, tr = self.cfg, self.tr
        self.dseed = int(cfg["deployment_seed"])
        fl = gen.cell_fleet(cfg, self.dseed)
        self.fl = fl
        self.hour0 = int(tr["hour"])
        self.cap = fl["chips_total"].astype(np.int64)
        self.buckets = {}
        self._dev = {k: jnp.asarray(fl[k]) for k in
                     ("pue", "power_kw", "healthy", "straggler_score",
                      "flops_per_j", "chips_total")}
        self._traces = jnp.asarray(fl["traces"], jnp.float32)
        self._ridx = jnp.asarray(fl["ridx"])
        hist, hor = int(cfg["history_h"]), int(cfg["horizon_h"])

        def signal(traces, ridx, hour):
            now = jax.lax.dynamic_slice_in_dim(traces, hour, 1, axis=1)[:, 0]
            win = jax.lax.dynamic_slice_in_dim(traces, hour - hist, hist,
                                               axis=1)
            fc = forecast.forecast_regions(win, hor, 0)[0]
            return now[ridx], jnp.mean(fc, axis=-1)[ridx]

        self._signal = jax.jit(signal)
        self.sig_hour = None
        self.Fleet = Fleet
        self.kw = dict(engine=tr["engine"], shortlist=int(tr["shortlist"]),
                       use_kernel=bool(tr["use_kernel"]))
        self.pad = int(tr["event_pad"])
        self.k = 0
        # the service jobs, submitted before the first interval
        svc = gen.service(cfg, self.dseed)
        for i in range(0, svc.size, self.pad):
            dem = svc[i:i + self.pad]
            self._decide(0, dem, np.full(dem.size, -1, np.int64))
        # the service runs on its own until its placements set the state
        for _ in range(int(tr["warm_calls"])):
            self.call()
        self.records = []
        self.cap0 = self.cap.copy()

    def _events(self, k):
        """Releases due in interval ``k``, then its arrivals (in an order
        drawn from the run's seed)."""
        rel = self.buckets.pop(k, [])
        _, chips, dur = gen.stream(self.cfg, self.dseed, k, k + 1, self.imin,
                                   self.hour0)
        order = np.random.default_rng([self.seed % (1 << 63), 3, k]) \
            .permutation(chips.size)
        r = np.asarray(rel, np.int64).reshape(-1, 2)
        dem = np.concatenate([-r[:, 1], chips[order]])
        nod = np.concatenate([r[:, 0], np.full(chips.size, -1, np.int64)])
        return dem, nod, dur[order]

    def _decide(self, k, dem, nod):
        """One call of the program: the grid signal on the hour's first
        call, then the padded event stream.  Returns the chosen nodes and
        the sweeps it made, once they are on the host."""
        import jax.numpy as jnp
        n = dem.size
        if n > self.pad:
            raise RuntimeError(f"interval {k}: {n} events > pad {self.pad}")
        hour = self.hour0 + k // self.per_h
        if hour != self.sig_hour:
            with _span("forecast"):
                now, fc = self._signal(self._traces, self._ridx,
                                       np.int32(hour))
                self._dev.update(ci_now=now, ci_forecast=fc)
                self.sig_hour = hour
        with _span("build_events"):
            d = np.zeros(self.pad, np.int32)
            v = np.full(self.pad, -1, np.int32)
            d[:n], v[:n] = dem, nod
            fleet = self.Fleet(capacity=jnp.asarray(self.cap.astype(np.int32)),
                               **self._dev)
            ev = dict(demands=jnp.asarray(d), nodes=jnp.asarray(v),
                      n_events=jnp.asarray(np.int32(n)))
        with _span("decide"):
            p = self.entry(fleet, **ev, **self.kw)
            p.node.block_until_ready()
        with _span("readback"):
            out = np.asarray(p.node)[:n].astype(np.int64)
            sweeps = int(p.n_sweeps)
        # the caller's own book: releases return chips, placements take them
        ok = out >= 0
        np.add.at(self.cap, out[ok], -dem[ok])
        return out, sweeps

    # -- the window --------------------------------------------------------
    def call(self):
        k = self.k
        dem, nod, dur = self._events(k)     # the interval's requests arrive
        t0 = time.perf_counter()
        out, sweeps = self._decide(k, dem, nod)
        t1 = time.perf_counter()
        self.records.append(dict(k=k, t0=t0, t1=t1, dem=dem, nod=nod,
                                 out=out, sweeps=sweeps,
                                 hour=self.hour0 + k // self.per_h))
        placed = (out >= 0) & (dem > 0)
        for n, c, e in zip(out[placed].tolist(), dem[placed].tolist(),
                           (k + dur[placed[dem > 0]]).tolist()):
            self.buckets.setdefault(e, []).append((n, c))
        self.k += 1
        return t1 - t0

    def counters(self):
        return dict(calls=len(self.records),
                    sweeps=sum(r["sweeps"] for r in self.records))

    def sweep_shape(self):
        return dict(n_nodes=int(self.cap.size), lanes=1, marginal=False,
                    room=True)

    def mesh_shape(self):
        """The decision service runs on one device."""
        return None

    def e2e(self, records, window_t0):
        ms = np.array([(r["t1"] - r["t0"]) * 1e3 for r in records])
        return {"decision_ms_p50": float(np.percentile(ms, 50)),
                "decision_ms_p95": float(np.percentile(ms, 95))}

    # -- correctness -------------------------------------------------------
    def _ref_signal(self, hour):
        """The hour's intensity and the reference forecast's mean, per
        node, in float64."""
        tr = np.asarray(self.fl["traces"], np.float64)
        hist, hor = int(self.cfg["history_h"]), int(self.cfg["horizon_h"])
        fc = np.array([reference.forecast_mean(tr[r, hour - hist:hour], hor)
                       for r in range(tr.shape[0])])
        ridx = self.fl["ridx"]
        return tr[:, hour][ridx], fc[ridx]

    def check(self, rng):
        """Every decision of the window: every placement allowed (room,
        node in service, nobody left out while a node had room, releases
        echoed).  A sample drawn from the seed, the decision with the most
        arrivals in it: each placement's score gap to the reference's
        best, under the reference's own grid signal and forecast."""
        cfg, fl = self.cfg, self.fl
        healthy = np.asarray(fl["healthy"], bool)
        recs = self.records
        n_s = min(int(self.tr["check"]["sample"]), len(recs))
        longest = int(np.argmax([int((r["dem"] > 0).sum()) for r in recs]))
        pick = set(rng.choice(len(recs), n_s, replace=False).tolist())
        pick.add(longest)
        audit = reference.Audit()
        cap = self.cap0.copy()
        sched = reference.sched_term(fl["straggler_score"], healthy)
        signal = {}
        for i, r in enumerate(recs):
            if i in pick:
                if r["hour"] not in signal:
                    signal[r["hour"]] = self._ref_signal(r["hour"])
                now, fc = signal[r["hour"]]
                sc = reference.Scorer(dict(fl, ci_now=now, ci_fc=fc), cap,
                                      sched, cfg["weights"], cfg["energy"],
                                      marginal=False)
                a = reference.Audit()
                reference.place_events(sc, cap, healthy, r["dem"], r["nod"],
                                       follow=r["out"], audit=a)
                audit.merge(a)
            else:
                audit.invalid += _cheap_events(cap, healthy, r)
            ok = r["out"] >= 0
            np.add.at(cap, r["out"][ok], -r["dem"][ok])
        info = dict(decisions=len(recs), gap_checked=len(pick),
                    placements_checked=audit.checked)
        return {"place_gap": audit.gap, "invalid": audit.invalid}, info


def _cheap_events(cap, healthy, r):
    """Count not-allowed answers of one decision without scoring."""
    cap = cap.copy()
    bad = 0
    for d, nd, c in zip(r["dem"].tolist(), r["nod"].tolist(),
                        r["out"].tolist()):
        if d < 0:
            bad += int(c != nd)
            cap[nd] -= d
        elif d > 0:
            if c < 0:
                bad += int(np.any(healthy & (cap >= d)))
                continue
            if not (0 <= c < cap.size and healthy[c] and cap[c] >= d):
                bad += 1
                continue
            cap[c] -= d
    return bad


class Sim:
    """What-if studies: one call = one batch of trajectories."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.core import simulator
        self.cfg, self.tr, self.seed = cfg, traffic, seed
        self.sim = simulator
        self.entry = (simulator.simulate_fleet_ensemble
                      if traffic["entry"] == "ensemble"
                      else simulator.simulate_fleet_scan)
        self.shard = traffic.get("shard", False)
        if self.shard not in (False, "e", "en"):
            raise ValueError(f"shard must be 'e' or 'en', got {self.shard!r}")
        if self.shard and traffic["entry"] != "ensemble":
            raise ValueError("shard lays out an ensemble; entry "
                             f"{traffic['entry']!r} runs on one device")
        self.n_nodes = int(cfg["nodes_per_cell"]) * len(cfg["regions"])
        self.records = []

    def setup(self):
        self.sets = [self._input_set(i)
                     for i in range(int(self.tr["input_sets"]))]
        for inputs in self.sets:    # seeds may land in other buffer sizes
            self._run(inputs)

    def _lane_cfg(self, tseed, lane):
        from repro.core.energy import EnergyModel
        from repro.core.ranking import RankWeights
        cfg, tr = self.cfg, self.tr
        w = cfg["weights"]
        return self.sim.SimConfig(
            epochs=int(cfg["epochs"]), seed=tseed,
            weights=RankWeights(w1=w["w1"], w2=w["w2"], w3=w["w3"],
                                w4=w["w4"], marginal=lane["marginal"]),
            engine=tr["engine"], shortlist=int(tr["shortlist"]),
            use_kernel=bool(tr["use_kernel"]),
            horizon_h=int(cfg["horizon_h"]), history_h=int(cfg["history_h"]),
            energy=EnergyModel(idle_frac=cfg["energy"]["idle_frac"],
                               embodied_g_per_node_h=lane["embodied"]),
            consolidate=float(cfg["consolidate"]))

    def _input_set(self, i):
        import jax.numpy as jnp
        from repro.core.fleet import Fleet
        cfg, tr = self.cfg, self.tr
        T = int(cfg["epochs"])
        hist, hor = int(cfg["history_h"]), int(cfg["horizon_h"])
        n = self.n_nodes
        region = gen.REGION_ORDER.index(cfg["regions"][0]) \
            if len(cfg["regions"]) == 1 else None
        runs, lanes = [], []
        for r in range(int(tr["trajectories"])):
            ts = _subseed(self.seed, 10, i, r)
            fl = gen.lifecycle_fleet(n, ts, hist + T + hor + 1, hist,
                                     int(cfg["chips_per_node"]), region)
            jb = gen.schedule(cfg, ts, T)
            fleet = Fleet(**{k: jnp.asarray(v) for k, v in fl.items()
                             if k not in ("traces", "ridx")})
            sched = self.sim.JobSchedule(**jb)
            for lane in tr["lanes"]:
                runs.append((fleet, fl["traces"], fl["ridx"],
                             self._lane_cfg(ts, lane), sched))
                lanes.append(dict(
                    fl, **jb, epochs=T, history_h=hist, horizon_h=hor,
                    consolidate=float(cfg["consolidate"]),
                    weights=cfg["weights"],
                    energy=dict(cfg["energy"],
                                embodied_g_per_node_h=lane["embodied"],
                                w_marginal=lane["marginal"])))
        return runs, lanes

    def _run(self, inputs):
        runs = inputs[0]
        if self.tr["entry"] == "ensemble":
            return self.entry(runs, shard=self.shard)
        return [self.entry(*r[:4], jobs=r[4], pad_plan=True) for r in runs]

    def call(self):
        i = len(self.records) % len(self.sets)
        t0 = time.perf_counter()
        failed = 0
        with _span("plan_and_run"):
            try:
                res = self._run(self.sets[i])
            except RuntimeError:        # the scanned core's overflow error
                res, failed = None, 1
        t1 = time.perf_counter()
        keep = None if res is None else [
            dict(first_node=r.first_node, start_epoch=r.start_epoch,
                 emissions=r.emissions_series, placed=r.arrivals_placed,
                 completed=r.jobs_completed, sweeps=r.rank_sweeps)
            for r in res]
        self.records.append(dict(set=i, t0=t0, t1=t1, res=keep,
                                 failed=failed,
                                 lanes=len(self.sets[i][0])))
        return t1 - t0

    def _lanes(self):
        return len(self.sets[0][0]) if self.tr["entry"] == "ensemble" else 1

    def mesh_shape(self):
        """``(e, n)``: the lanes' and the nodes' share of the devices the
        program lays a call out on, read from the sharding of the inputs
        its plan build gives the first input set, or None where the
        traffic names no layout."""
        if not self.shard:
            return None
        if not hasattr(self, "_mesh"):
            buckets = self.sim._ensemble_buckets(self.sets[0][0], True,
                                                 self.shard)
            stacked = next(buckets)[3]
            buckets.close()
            mesh = getattr(stacked["capacity"].sharding, "mesh", None)
            shape = {} if mesh is None else mesh.shape
            self._mesh = int(shape.get("e", 1)), int(shape.get("n", 1))
            del stacked
        return self._mesh

    def sweep_shape(self):
        """What one launch of the sweep reads on one device: its block of
        the lanes and of the nodes."""
        e, n = self.mesh_shape() or (1, 1)
        return dict(n_nodes=self.n_nodes // n, lanes=self._lanes() // e,
                    marginal=True)

    def lane_epochs(self, records):
        return sum(r["lanes"] * int(self.cfg["epochs"])
                   for r in records if not r["failed"])

    def counters(self):
        ok = [r for r in self.records if not r["failed"]]
        return dict(calls=len(self.records),
                    lane_epochs=self.lane_epochs(ok),
                    sweeps=sum(x["sweeps"] for r in ok for x in r["res"]))

    def e2e(self, records, window_t0):
        done = [r for r in records if not r["failed"]]
        if not done:
            return {}
        span = max(r["t1"] for r in done) - window_t0
        return {"sim_lane_epochs_per_s": self.lane_epochs(done) / span}

    def check(self, rng):
        """Every lane of every call: jobs start when they arrive (no
        deferral is configured), land on a node in service, never overfill
        a node, are left out only when no node had room, and the counters
        agree.  A sample of lanes drawn from the seed: the reference
        replays each placement and recomputes the emissions of every
        epoch.  It scores each placement's gap to its best, or, where the
        traffic gives ``check.events``, that many arrivals of the lane
        drawn from the seed and the last arrival of its busiest epoch; it
        checks every other placement for room and health."""
        events = self.tr["check"].get("events")
        audit = reference.Audit()
        pairs = []
        for ci, r in enumerate(self.records):
            if r["failed"]:
                continue
            lanes = self.sets[r["set"]][1]
            for li, res in enumerate(r["res"]):
                audit.invalid += _cheap_lane(lanes[li], res)
                pairs.append((ci, li))
        n_s = min(int(self.tr["check"]["lanes"]), len(pairs))
        emis = 0.0
        for p in rng.choice(len(pairs), n_s, replace=False).tolist():
            ci, li = pairs[p]
            r = self.records[ci]
            lane = self.sets[r["set"]][1][li]
            score = None if events is None else _gap_sample(
                lane, int(events), rng)
            a, e = reference.simulate_lane(lane, follow=r["res"][li],
                                           score=score)
            audit.merge(a)
            emis = max(emis, e)
        info = dict(lanes=len(pairs), lanes_replayed=n_s,
                    placements_checked=audit.checked)
        if events is not None:
            info["gaps_scored"] = audit.scored
        return {"place_gap": audit.gap, "emis_rel": emis,
                "invalid": audit.invalid}, info


def _gap_sample(lane, n, rng):
    """The arrivals of one lane whose gap the check scores: ``n`` drawn
    from ``rng``, and the last arrival of the epoch with the most of
    them, where the fleet is fullest.  A boolean per job."""
    arrive = np.asarray(lane["arrive"])
    due = np.flatnonzero(arrive < lane["epochs"])
    pick = np.zeros(arrive.size, bool)
    if not due.size:
        return pick
    pick[rng.choice(due, min(n, due.size), replace=False)] = True
    busiest = np.argmax(np.bincount(arrive[due]))
    pick[due[arrive[due] == busiest][-1]] = True
    return pick


def _cheap_lane(lane, res):
    """Count not-allowed outcomes of one trajectory without scoring."""
    T = lane["epochs"]
    arrive, chips, dur = lane["arrive"], lane["chips"], lane["duration"]
    node, start = res["first_node"], res["start_epoch"]
    N = lane["ridx"].shape[0]
    if node.shape != arrive.shape or start.shape != arrive.shape:
        return int(arrive.shape[0])
    healthy = np.asarray(lane["healthy"], bool)
    ok = start >= 0
    bad = int(np.sum(ok & (start != arrive)))
    bad += int(np.sum(ok & ((node < 0) | (node >= N))))
    ok &= (node >= 0) & (node < N)
    bad += int(np.sum(ok & ~healthy[np.clip(node, 0, N - 1)]))
    occ = np.zeros((N, T + 1), np.int64)
    stop = np.minimum(start + dur, T)
    np.add.at(occ, (node[ok], start[ok]), chips[ok])
    np.add.at(occ, (node[ok], stop[ok]), -chips[ok])
    occ = np.cumsum(occ, axis=1)[:, :T]
    free = np.asarray(lane["capacity"], np.int64)[:, None] - occ
    bad += int(np.sum(free < 0))
    drop = np.where(~ok)[0]
    if drop.size:
        room = np.where(healthy[:, None], free, -1).max(axis=0)
        bad += int(np.sum(room[arrive[drop]] >= chips[drop]))
    bad += int(res["placed"] != int(ok.sum()))
    bad += int(res["completed"] != int(np.sum(ok & (start + dur < T))))
    return bad


DRIVERS = {"decide": Decide, "sim": Sim}
