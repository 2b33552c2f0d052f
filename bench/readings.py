"""Readings of the numbers a cell compares, over many seeds in one process,
for the program or for the control (the reference in bfloat16 in the
program's place).  The limits in ``bench/traffic/*.json`` are set from
these: above the program's largest reading, below the control's smallest.

    python3 bench/readings.py --workload <name> --calls <n> --seeds 1 2 3 [--control]

Each seed runs set-up, a window of ``--calls`` calls and the cell's check;
one JSON line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--calls", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    cell = run.load_cell(run.ROOT, args.workload)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(run.CACHE, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from lib import control
    hook = control.install if args.control else None
    for seed in args.seeds:
        t0 = time.perf_counter()
        res, compared = run.run_cell(cell, seed, 1e9, False, t_proc=t0,
                                     driver_hook=hook, max_calls=args.calls)
        print(json.dumps(dict(
            workload=args.workload, seed=seed, control=args.control,
            correct=res["correct"], attempted=res["attempted"],
            device=res["device"]["kind"],
            seconds=time.perf_counter() - t0,
            **{k: v for k, v, _ in compared})), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
