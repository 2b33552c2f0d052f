"""Benchmark harness: runs one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything cell-specific is data, found by name: the workload entry of
``BENCHMARK.json`` names a configuration (its file under
``bench/configs``) and a traffic mix (``bench/traffic/<mix>.json``, whose
``driver`` picks one of ``lib.drivers.DRIVERS``); each per-layer metric is
a reader ``bench/metrics/<metric>.py``.  One run: set-up (imports, device,
inputs from the seed, warm-up of every shape; ``setup_s``), a measured
window of ``--seconds`` (``--trace 1`` also records a profiler trace of its
first calls), then the check of what the window produced against the
plain reference.  The last line of standard output is one JSON object;
the last lines of standard error give each number compared beside its
limit.  Exits non-zero, with no result, where JAX finds no TPU, or
fewer chips than the cell asks for; where the traffic lays the study out
over the devices (``shard``), also where JAX finds more.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(ROOT, ".bench_cache")
sys.path.insert(0, BENCH)


def say(*a):
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------


def load_cell(root: str, workload: str) -> dict:
    """Workload entry, configuration, traffic and the metrics it reports."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    wl = {w["name"]: w for w in spec["workloads"]}[workload]
    conf = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           wl["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if workload in m.get("workloads", [workload])
             and m["moves"] in names]
    return dict(workload=wl, cfg=cfg, traffic=traffic, e2e=e2e,
                layer=layer, root=root)


def load_reader(root: str, name: str):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def load_peak(root: str, kind: str) -> dict:
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


class Ctx:
    """What a per-layer reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


class CompileCounter:
    """Programs compiled, or loaded from the cache, between its creation
    and ``close``."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._dur)


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except (AttributeError, KeyError, TypeError, RuntimeError):
            pass            # a backend without memory statistics
    return max(peaks) if peaks else 0


def run_cell(cell: dict, seed: int, seconds: float, traced: bool,
             t_proc: float = T_PROC, driver_hook=None, max_calls=None):
    """Set up, measure, read and check one cell.  Returns the result line
    (a dict) and the numbers compared as ``(name, value, limit)``.
    ``driver_hook(driver)`` runs after set-up (the control and the fault
    tests swap the timed path there); ``max_calls`` ends the window after
    that many calls."""
    import jax
    import numpy as np
    from lib import drivers, trace

    tr = cell["traffic"]
    drv = drivers.DRIVERS[tr["driver"]](cell["cfg"], tr, seed)
    drv.setup()
    if driver_hook is not None:
        driver_hook(drv)
    devs = jax.devices()[:int(cell["workload"]["chips"])]
    logdir = os.path.join(CACHE, "trace", cell["workload"]["name"])
    if traced:
        shutil.rmtree(logdir, ignore_errors=True)
    n_traced = int(tr["trace_calls"]) if traced else 0

    # ---- the measured window -------------------------------------------
    counter = CompileCounter()
    w0 = time.perf_counter()
    setup_s = w0 - t_proc
    t_end = w0 + seconds
    failed = 0
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # harness spans and device ops only
        jax.profiler.start_trace(logdir, profiler_options=opts)
    tracing = traced
    while time.perf_counter() < t_end and (
            max_calls is None or len(drv.records) + failed < max_calls):
        try:
            drv.call()
        except Exception as e:          # a call that raises has failed
            failed += 1
            say(f"call {len(drv.records)} raised {type(e).__name__}: {e}")
            traceback.print_exc()
            if failed > 3:
                break
        if tracing and len(drv.records) >= n_traced:
            jax.profiler.stop_trace()
            tracing = False
    if tracing:
        jax.profiler.stop_trace()
    counter.close()
    records = list(drv.records)
    say(f"compiles_in_window={counter.n} calls={len(records)} "
        f"window_s={time.perf_counter() - w0:.3f}")
    mem = memory_peak(devs)
    dev = devs[0]

    # ---- metrics -------------------------------------------------------
    metrics, device = {}, {"platform": dev.platform, "kind": dev.device_kind,
                           "count": len(devs), "memory_peak_bytes": mem}
    mesh = drv.mesh_shape()
    if mesh is not None:
        device["mesh"] = list(mesh)
    out = {}
    if not traced:
        vals = drv.e2e(records, w0)
        vals["setup_s"] = setup_s
        for m in cell["e2e"]:
            if m["name"] in vals:
                metrics[m["name"]] = {"value": vals[m["name"]],
                                      "unit": m["unit"]}
    else:
        raw = trace.load(trace.find(logdir))
        red = trace.reduce(raw)
        ctx = Ctx(trace=red, trace_raw=raw, counters=drv.counters(),
                  records=records, driver=drv,
                  sweep_shape=drv.sweep_shape(),
                  peak=load_peak(cell["root"], dev.device_kind)
                  if dev.platform == "tpu" else None)
        for m in cell["layer"]:
            v = load_reader(cell["root"], m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=red["busy_ns"] * 1e-9,
                      window_s=red["window_ns"] * 1e-9)
        ops = sorted(red["per_op_ns"].items(), key=lambda kv: -kv[1])[:10]
        out["breakdown"] = {
            "device_ops": [[n, v * 1e-9] for n, v in ops],
            "idle_gaps": [[n, v * 1e-9] for n, v in red["gaps"][:10]]}

    # ---- correctness, after the window and with the program's state freed
    rng = np.random.default_rng([seed % (1 << 63), 99])
    t_chk = time.perf_counter()
    nums, info = drv.check(rng)
    limits = tr["limits"]
    compared = [(k, float(v), float(limits[k])) for k, v in nums.items()]
    correct = failed == 0 and all(v <= lim for _, v, lim in compared)
    say(f"check_s={time.perf_counter() - t_chk:.3f} "
        + " ".join(f"{k}={v}" for k, v in info.items()))
    attempted = len(records) + failed
    failed += sum(int(r.get("failed", 0)) for r in records)
    result = dict(correct=bool(correct), attempted=attempted, failed=failed,
                  metrics=metrics, device=device, **out)
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, v, lim in compared}
    return result, compared


def refusal(cell: dict, devs) -> str:
    """Why the cell cannot run on the devices JAX found, or ''."""
    want = int(cell["workload"]["chips"])
    if devs[0].platform != "tpu" or len(devs) < want:
        return (f"needs {want} TPU chip(s); JAX found {len(devs)} "
                f"{devs[0].platform} device(s)")
    if cell["traffic"].get("shard") and len(devs) != want:
        return (f"traffic {cell['workload']['traffic']!r} lays its study "
                f"out over every visible device: needs exactly {want} TPU "
                f"chip(s); JAX found {len(devs)}")
    return ""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(ROOT, args.workload)
    except (OSError, KeyError, ValueError) as e:
        say(f"bench: cannot load workload {args.workload!r}: {e!r}")
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        say(f"bench: the system under test is missing ({src}/repro)")
        return 2
    sys.path.insert(0, src)
    # the compile cache lives inside the checkout, whatever the
    # environment says, at a fixed path (the path is part of its key)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(CACHE, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devs = jax.devices()
    except RuntimeError as e:
        say(f"bench: JAX found no accelerator: {e}")
        return 3
    why = refusal(cell, devs)
    if why:
        say(f"bench: {why}")
        return 3
    result, compared = run_cell(cell, args.seed, args.seconds,
                                bool(args.trace))
    for k, v, lim in compared:
        say(f"compared {k} = {v!r} limit {lim!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
