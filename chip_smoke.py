"""Smoke run of the main path on a TPU: placement, the fleet simulator and
the Pallas ranking sweep, through the library's own entry points.

    python3 chip_smoke.py              # one chip: every single-chip phase
    python3 chip_smoke.py --chips 4    # four chips: the sharded ensemble only

Phases (one chip):

- placement at region scale: 256 jobs of 64 chips on a 1,048,576-node fleet
  through ``scheduler.place_jobs``, with the compiled kernel sweep, the jnp
  sweep and the full re-rank oracle; node assignments must agree;
- simulator: the host loop against the scanned core at N=4096 / T=168,
  the kernel sweep inside the scan against the host loop, and one T=8760
  year through the scan (cold and warm seconds);
- ensemble: 12 kernel lanes at N=4096 / T=168, each against its own scan;
- golden digests of the policy suite's BASE / MIXED streams (printed
  beside the committed ones; host-vs-scan parity must hold).

With ``--chips 4`` it runs only the sharded ensemble: ``shard=True`` with
4 lanes at N=4096 and ``shard="en"`` with 2 lanes at N=65536, each lane
against the same ensemble unsharded.

Every check that fails ends the run with a nonzero exit.  The script
refuses to start where JAX finds no TPU, and every kernel call it makes
is compiled (``interpret=False``).  Times it prints are smoke timings of
one run, not benchmark numbers.  The last line of its output is one JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import hashlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# tests/test_policy.py's golden trajectories and their committed digests
GOLDEN = {"BASE": "0141b64da0651227", "MIXED": "0e6437d00c3ba558"}
LANE_COUNTERS = ("rank_sweeps", "arrivals_placed", "jobs_completed",
                 "jobs_dropped", "jobs_deferred", "migrations", "evictions",
                 "deadline_misses", "defer_delay_h")


def say(*parts) -> None:
    print(*parts, flush=True)


def check(ok: bool, what: str) -> None:
    say(f"  check {what}: {'ok' if ok else 'FAILED'}")
    if not ok:
        raise SystemExit(f"chip_smoke: {what} failed")


def sim_config(**kw):
    """The ``sim_scale`` bench configuration (benchmarks/run.py)."""
    from repro.core.simulator import SimConfig
    base = dict(epochs=168, seed=1, arrival_rate=12.0, mean_duration_h=12.0,
                migration_budget=2, deferrable_frac=0.1, shortlist=64)
    return SimConfig(**{**base, **kw})


def run_spec(cfg, n, chips=256):
    from repro.core.simulator import generate_jobs, synthetic_lifecycle_fleet
    fleet, traces, ridx = synthetic_lifecycle_fleet(n, cfg,
                                                    chips_per_node=chips)
    return fleet, traces, ridx, cfg, generate_jobs(cfg)


def digest(r) -> str:
    import numpy as np
    return hashlib.sha256(np.concatenate(
        [r.node_log, r.first_node]).tobytes()).hexdigest()[:16]


def same_placements(a, b) -> bool:
    import numpy as np
    return bool(np.array_equal(a.node_log, b.node_log)
                and np.array_equal(a.first_node, b.first_node)
                and np.array_equal(a.start_epoch, b.start_epoch))


def lanes_match(ref, got) -> bool:
    """Per-lane ensemble contract: placements and every counter exact,
    emissions within the scanned core's f32 tolerance."""
    ok = len(ref) == len(got)
    bitwise = True
    for a, b in zip(ref, got):
        ok &= same_placements(a, b)
        ok &= all(getattr(a, f) == getattr(b, f) for f in LANE_COUNTERS)
        ok &= abs(a.emissions_g - b.emissions_g) \
            <= 1e-4 * abs(a.emissions_g)
        bitwise &= a.emissions_g == b.emissions_g
    say(f"  lanes={len(got)} emissions bitwise equal={bitwise}")
    return bool(ok)


def timed(fn, *a, **kw):
    t0 = time.perf_counter()
    out = fn(*a, **kw)
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_placement(n, jobs, chips, shortlist, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.fleet import synthetic_fleet
    from repro.core.scheduler import place_jobs
    say(f"[placement] N={n} J={jobs} x {chips} chips, shortlist={shortlist}")
    fleet = synthetic_fleet(n, seed=1)
    demands = jnp.full((jobs,), chips, jnp.int32)
    ways = {
        "kernel": dict(engine="shortlist", shortlist=shortlist,
                       use_kernel=True, interpret=interpret),
        "jnp": dict(engine="shortlist", shortlist=shortlist,
                    use_kernel=False),
        "full": dict(engine="full"),
    }
    nodes = {}
    for name, kw in ways.items():
        lowered = jax.jit(functools.partial(place_jobs, **kw)).lower(
            fleet, demands)
        compiled, c_s = timed(lowered.compile)
        if name == "kernel" and not interpret:
            check("tpu_custom_call" in compiled.as_text(),
                  "tpu_custom_call in the kernel placement program")
        r, s = timed(lambda: jax.block_until_ready(compiled(fleet, demands)))
        nodes[name] = np.asarray(r.node)
        say(f"  {name}: rank_sweeps={int(r.n_sweeps)} "
            f"placed={int((nodes[name] >= 0).sum())} compile_s={c_s:.3f} "
            f"run_s={s:.4f}")
    check(np.array_equal(nodes["kernel"], nodes["full"])
          and np.array_equal(nodes["jnp"], nodes["full"]),
          "placement parity kernel == jnp shortlist == full re-rank")


def phase_simulator(n, epochs, year_epochs, interpret):
    from repro.core.simulator import (scan_vs_host_parity, simulate_fleet,
                                      simulate_fleet_scan)
    cfg = sim_config(epochs=epochs)
    fleet, traces, ridx, _, jobs = run_spec(cfg, n)
    say(f"[simulator] N={n} T={epochs} jobs={jobs.n}")
    host, h_s = timed(simulate_fleet, fleet, traces, ridx, cfg, jobs=jobs)
    scan, s_s = timed(simulate_fleet_scan, fleet, traces, ridx, cfg,
                      jobs=jobs)
    ok, rel = scan_vs_host_parity(host, scan)
    say(f"  host: sweeps={host.rank_sweeps} placed={host.arrivals_placed} "
        f"migrations={host.migrations} "
        f"emissions_g={float(host.emissions_g)!r} host_s={h_s:.3f} "
        f"scan_first_call_s={s_s:.3f} rel_err={rel!r}")
    check(ok, "host-vs-scan parity (jnp sweep)")

    kcfg = dataclasses.replace(cfg, use_kernel=True, shortlist=32,
                               interpret=interpret)
    kscan, k_s = timed(simulate_fleet_scan, fleet, traces, ridx, kcfg,
                       jobs=jobs)
    say(f"  kernel scan: sweeps={kscan.rank_sweeps} "
        f"placed={kscan.arrivals_placed} first_call_s={k_s:.3f}")
    check(same_placements(host, kscan),
          "kernel-vs-jnp placements (kernel scan vs jnp host loop)")

    ycfg = sim_config(epochs=year_epochs)
    yfleet, ytraces, yridx, _, yjobs = run_spec(ycfg, n)
    y1, cold = timed(simulate_fleet_scan, yfleet, ytraces, yridx, ycfg,
                     jobs=yjobs)
    y2, warm = timed(simulate_fleet_scan, yfleet, ytraces, yridx, ycfg,
                     jobs=yjobs)
    say(f"  year scan T={year_epochs} jobs={yjobs.n}: smoke timings "
        f"cold_s={cold:.3f} warm_s={warm:.3f} (not benchmark numbers)")
    check(same_placements(y1, y2) and y1.emissions_g == y2.emissions_g
          and y1.arrivals_placed > 0 and y1.emissions_g > 0,
          "year scan repeatable and non-empty")


def phase_ensemble(n, epochs, lanes, interpret):
    from repro.core.simulator import (simulate_fleet_ensemble,
                                      simulate_fleet_scan)
    cfg = sim_config(epochs=epochs, use_kernel=True, shortlist=32,
                     interpret=interpret)
    runs = [run_spec(dataclasses.replace(cfg, seed=s), n)
            for s in range(1, lanes + 1)]
    say(f"[ensemble] N={n} T={epochs} lanes={lanes} use_kernel=True")
    seq, s_s = timed(lambda: [simulate_fleet_scan(*r[:4], jobs=r[4],
                                                  pad_plan=True)
                              for r in runs])
    ens, e_s = timed(simulate_fleet_ensemble, runs)
    say(f"  scans_s={s_s:.3f} ensemble_first_call_s={e_s:.3f} "
        f"sweeps={[r.rank_sweeps for r in ens]}")
    check(lanes_match(seq, ens), "ensemble lanes == their scans")


def phase_digests():
    from repro.core.simulator import (SimConfig, scan_vs_host_parity,
                                      simulate_fleet, simulate_fleet_scan)
    cfgs = {
        "BASE": SimConfig(epochs=24, seed=3, arrival_rate=6.0,
                          mean_duration_h=6.0, shortlist=16, history_h=48,
                          horizon_h=8),
        "MIXED": SimConfig(epochs=36, seed=11, arrival_rate=8.0,
                           mean_duration_h=10.0, shortlist=32, history_h=48,
                           horizon_h=12, migration_budget=2,
                           deferrable_frac=0.3, outage=(0, 12, 6),
                           flash_crowd=(20, 3, 2.5)),
    }
    say("[digests] golden policy streams, N=96")
    for name, cfg in cfgs.items():
        fleet, traces, ridx, _, jobs = run_spec(cfg, 96, chips=64)
        host = simulate_fleet(fleet, traces, ridx, cfg, jobs=jobs)
        scan = simulate_fleet_scan(fleet, traces, ridx, cfg, jobs=jobs)
        say(f"  {name}: host={digest(host)} scan={digest(scan)} "
            f"committed={GOLDEN[name]} "
            f"match={digest(host) == GOLDEN[name]}")
        check(scan_vs_host_parity(host, scan)[0],
              f"host-vs-scan parity on {name}")


def phase_sharded(n_e, n_en, epochs, interpret):
    import jax
    from repro.core.simulator import simulate_fleet_ensemble
    from repro.distributed.sharding import ensemble_mesh
    check(jax.device_count() >= 4, f"4 devices (got {jax.device_count()})")
    cfg = sim_config(epochs=epochs, use_kernel=True, shortlist=32,
                     interpret=interpret)
    for shard, lanes, n in ((True, 4, n_e), ("en", 2, n_en)):
        mesh = ensemble_mesh(lanes, n)
        want = (4, 1) if shard is True else (2, 2)
        say(f"[sharded] shard={shard!r} N={n} T={epochs} lanes={lanes} "
            f"mesh(e, n)={mesh.devices.shape}")
        check(mesh.devices.shape == want, f"mesh {want} for shard={shard!r}")
        runs = [run_spec(dataclasses.replace(cfg, seed=s), n)
                for s in range(1, lanes + 1)]
        plain, p_s = timed(simulate_fleet_ensemble, runs)
        split, s_s = timed(simulate_fleet_ensemble, runs, shard=shard)
        say(f"  unsharded_first_call_s={p_s:.3f} "
            f"sharded_first_call_s={s_s:.3f} "
            f"sweeps={[r.rank_sweeps for r in split]}")
        check(lanes_match(plain, split),
              f"shard={shard!r} lanes == unsharded lanes")


# ---------------------------------------------------------------------------


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"chip_smoke: no repro package under {SRC}")
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache

    import jax
    dev = jax.devices()[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={jax.device_count()} jax={jax.__version__}")
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {dev.platform}")
    say(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(4096, 65536, 168, interpret=False)
    else:
        phase_placement(1_048_576, 256, 64, 32, interpret=False)
        phase_simulator(4096, 168, 8760, interpret=False)
        phase_ensemble(4096, 168, 12, interpret=False)
        phase_digests()
    say(f"total_s={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}), flush=True)


if __name__ == "__main__":
    main()
