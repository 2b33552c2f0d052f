"""The control: the plain reference put in the program's place, computed
in bfloat16, the precision below the float32 the configurations state.

``install(driver)`` swaps the driver's ``entry`` for a function with the
program's interface that answers from ``reference`` at ``ml_dtypes``
bfloat16.  The harness then runs and checks it exactly as it runs and
checks the program; a limit is sound only where the control fails it.
"""
from __future__ import annotations

import types

import ml_dtypes
import numpy as np

from lib import reference

BF16 = ml_dtypes.bfloat16


class _Done:
    """Host result with the device result's ``block_until_ready``."""

    def __init__(self, arr):
        self._a = arr

    def block_until_ready(self):
        return self

    def __array__(self, dtype=None, copy=None):
        return self._a if dtype is None else self._a.astype(dtype)

    def __getitem__(self, i):
        return self._a[i]


def _np(x):
    return np.asarray(x)


def decide_entry(cfg):
    def entry(fleet, demands, nodes, n_events, **_):
        n = int(n_events)
        fl = dict(pue=_np(fleet.pue), power_kw=_np(fleet.power_kw),
                  ci_now=_np(fleet.ci_now), ci_fc=_np(fleet.ci_forecast),
                  flops_per_j=_np(fleet.flops_per_j),
                  chips_total=_np(fleet.chips_total))
        healthy = _np(fleet.healthy).astype(bool)
        cap = _np(fleet.capacity)
        sched = reference.sched_term(_np(fleet.straggler_score), healthy)
        sc = reference.Scorer(fl, cap, sched, cfg["weights"], cfg["energy"],
                              marginal=False, dt=BF16)
        out, _ = reference.place_events(sc, cap, healthy,
                                        _np(demands)[:n], _np(nodes)[:n])
        node = np.full(_np(demands).shape[0], -1, np.int32)
        node[:n] = out
        return types.SimpleNamespace(node=_Done(node), n_sweeps=0)
    return entry


def lane_from_run(run) -> dict:
    """The reference's lane dict from a simulator argument tuple."""
    fleet, traces, ridx, cfg, jobs = run
    em = cfg.energy
    return dict(
        pue=_np(fleet.pue), power_kw=_np(fleet.power_kw),
        chips_total=_np(fleet.chips_total), healthy=_np(fleet.healthy),
        capacity=_np(fleet.capacity), flops_per_j=_np(fleet.flops_per_j),
        straggler_score=_np(fleet.straggler_score),
        traces=np.asarray(traces), ridx=np.asarray(ridx),
        arrive=jobs.arrive, chips=jobs.chips, duration=jobs.duration,
        epochs=cfg.epochs, history_h=cfg.history_h, horizon_h=cfg.horizon_h,
        consolidate=cfg.consolidate,
        weights=dict(w1=cfg.weights.w1, w2=cfg.weights.w2,
                     w3=cfg.weights.w3, w4=cfg.weights.w4),
        energy=dict(idle_frac=em.idle_frac, dyn_frac=em.dyn_frac,
                    embodied_g_per_node_h=em.embodied_g_per_node_h,
                    w_marginal=cfg.weights.marginal))


def _sim_result(r: dict):
    return types.SimpleNamespace(
        first_node=r["first_node"], start_epoch=r["start_epoch"],
        emissions_series=r["emissions"], arrivals_placed=r["placed"],
        jobs_completed=r["completed"], rank_sweeps=0)


def sim_entry(ensemble: bool):
    def one(run):
        return _sim_result(reference.simulate_lane(lane_from_run(run),
                                                   dt=BF16))
    if ensemble:
        return lambda runs, **_: [one(r) for r in runs]

    def scan(fleet, traces, ridx, cfg, jobs=None, **_):
        return one((fleet, traces, ridx, cfg, jobs))
    return scan


def install(driver):
    if driver.tr["driver"] == "decide":
        driver.entry = decide_entry(driver.cfg)
    else:
        driver.entry = sim_entry(driver.tr["entry"] == "ensemble")
