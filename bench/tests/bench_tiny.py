"""Tiny versions of the benchmark's cells for the CPU tests: the cells'
own configuration and traffic files, with the fleet and the job stream cut
to what interpret-mode Pallas and XLA:CPU run in seconds."""
from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402

CELLS = ("borg_3dc.decide", "borg_1dc.sweep12", "borg_1dc.scan")


def tiny_cell(workload: str) -> dict:
    cell = run.load_cell(ROOT, workload)
    cfg, tr = cell["cfg"], cell["traffic"]
    cfg["service"] = dict(cfg["service"], share_of_chips=0.1)
    if tr["driver"] == "decide":
        cfg.update(regions=cfg["regions"][:2], nodes_per_cell=1024,
                   arrivals_per_hour_per_cell=60, trace_hours=1200)
        tr.update(warm_calls=5)
    else:
        cfg.update(nodes_per_cell=1024, arrivals_per_hour_per_cell=40,
                   epochs=6)
        tr.update(input_sets=2)
        if tr["entry"] == "ensemble":
            tr.update(lanes=tr["lanes"][:2], trajectories=1)
    return cell


def run_tiny(workload: str, seed: int = 2 ** 31 + 7, calls: int = 4,
             hook=None, traced: bool = False):
    import time
    return run.run_cell(tiny_cell(workload), seed, 1e9, traced,
                        t_proc=time.perf_counter(), driver_hook=hook,
                        max_calls=calls)
