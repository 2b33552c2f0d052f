"""``correct`` comes out false when the timed path is broken underneath,
for each fault a cell can have, and for the control (the plain reference
in bfloat16 in the program's place).  One-chip cells exchange nothing
between chips, so that fault has no place here."""
import dataclasses
import types

import numpy as np
import pytest

from bench_tiny import CELLS, run_tiny
from lib import control


def _decide_fault(kind):
    def hook(drv):
        real = drv.entry

        def entry(fleet, demands, nodes, n_events, **kw):
            n = int(n_events)
            if kind == "half":              # half of the batch left out
                n_events = np.int32(n // 2)
            p = real(fleet, demands=demands, nodes=nodes, n_events=n_events,
                     **kw)
            node = np.asarray(p.node).copy()
            if kind == "unchanged":         # the step changes nothing
                node[:] = -1
            elif kind == "altered":         # one answer altered
                arr = np.where((np.asarray(demands)[:n] > 0)
                               & (node[:n] >= 0))[0]
                if arr.size:
                    e = arr[0]
                    node[e] = (node[e] + 1) % node.size
            return types.SimpleNamespace(node=control._Done(node),
                                         n_sweeps=p.n_sweeps)
        drv.entry = entry
    return hook


def _blank(r):
    J = r.first_node.shape[0]
    return dataclasses.replace(
        r, first_node=np.full(J, -1), start_epoch=np.full(J, -1),
        node_log=np.full(J, -1), arrivals_placed=0, jobs_completed=0,
        emissions_series=np.zeros_like(r.emissions_series))


def _sim_fault(kind):
    def hook(drv):
        real = drv.entry
        ensemble = drv.tr["entry"] == "ensemble"

        def fix(results):
            if kind == "unchanged":
                return [_blank(r) for r in results]
            if kind == "half":              # half of the batch left out
                h = max(len(results) // 2, 1)
                if len(results) == 1:       # one lane: half of its jobs
                    r = results[0]
                    fn = r.first_node.copy()
                    fn[1::2] = -1
                    return [dataclasses.replace(r, first_node=fn)]
                return results[:h] + [_blank(r) for r in results[h:]]
            r = results[0]                  # one answer altered
            fn = r.first_node.copy()
            j = int(np.argmax(fn >= 0))
            fn[j] = (fn[j] + 1) % 1024
            return [dataclasses.replace(r, first_node=fn)] + results[1:]

        if ensemble:
            drv.entry = lambda runs, **kw: fix(real(runs, **kw))
        else:
            drv.entry = lambda *a, **kw: fix([real(*a, **kw)])[0]
    return hook


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_fault_under_the_timed_path_is_not_correct(workload, kind):
    make = _decide_fault if "decide" in workload else _sim_fault
    res, compared = run_tiny(workload, calls=3, hook=make(kind))
    assert res["correct"] is False, compared


@pytest.mark.parametrize("workload", CELLS)
def test_control_in_bfloat16_is_not_correct(workload):
    res, compared = run_tiny(workload, calls=3, hook=control.install,
                             seed=11)
    assert res["correct"] is False, compared
    gaps = dict((k, v) for k, v, _ in compared)
    assert gaps["place_gap"] > 0
