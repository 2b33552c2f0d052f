"""Seeded input generators of the benchmark: carbon-intensity traces, fleet
arrays and job streams.

The traces and the simulator's fleet are the benchmark's own copies of
``repro.core.telemetry.hourly_ci`` and
``repro.core.simulator.synthetic_lifecycle_fleet``, so that a change to the
program's generators cannot change what the benchmark measures; they
started equal, draw for draw (``bench/tests/test_bench_gen.py`` pins
that).  The job stream is the configuration's (``jobs`` and ``service``
in ``bench/configs``), one generator for every cell.  Everything here is
numpy and imports nothing of the program.
"""
from __future__ import annotations

import zlib

import numpy as np

# 2022-calibrated region profiles (electricityMaps annual means), copied from
# the program's telemetry table: ci_mean gCO2/kWh, relative daily and
# seasonal amplitudes, renewable-dip rate/depth/length, AR(1) noise, PUE.
REGIONS = {
    "ES": dict(ci_mean=256.0, daily_amp=0.28, seasonal_amp=0.10,
               dip_rate=0.45, dip_depth=0.8171, dip_len=10, noise=0.05,
               pue=1.12),
    "NL": dict(ci_mean=386.0, daily_amp=0.12, seasonal_amp=0.08,
               dip_rate=0.08, dip_depth=0.35, dip_len=6, noise=0.05,
               pue=1.50),
    "DE": dict(ci_mean=385.0, daily_amp=0.15, seasonal_amp=0.12,
               dip_rate=0.12, dip_depth=0.40, dip_len=7, noise=0.05,
               pue=1.58),
}
REGION_ORDER = ("ES", "NL", "DE")
CHIP_KW = 0.25          # chip-only nameplate kW per chip


def _dip_mask(rng, hours, rate, mean_len):
    if rate <= 0:
        return np.zeros(hours)
    p_on = rate / mean_len / max(1 - rate, 1e-6)
    p_off = 1.0 / mean_len
    state, out = 0.0, np.zeros(hours)
    u = rng.random(hours)
    for t in range(hours):
        if state == 0.0 and u[t] < p_on:
            state = 1.0
        elif state == 1.0 and u[t] < p_off:
            state = 0.0
        out[t] = state
    k = np.array([0.25, 0.5, 1.0, 0.5, 0.25])
    return np.convolve(out, k / k.max(), mode="full")[2:2 + hours].clip(0, 1)


def hourly_ci(region: str, hours: int, seed: int) -> np.ndarray:
    """Synthetic hourly carbon intensity (gCO2/kWh) of one region."""
    p = REGIONS[region]
    rng = np.random.default_rng(
        zlib.crc32(f"{region}:{seed}".encode()) & 0xFFFFFFFF)
    t = np.arange(hours)
    day = p["daily_amp"] * np.cos(2 * np.pi * (t % 24 - 19) / 24)
    season = p["seasonal_amp"] * np.cos(2 * np.pi * (t / 24 - 15) / 365)
    ar = np.zeros(hours)
    innov = rng.normal(0, p["noise"], hours)
    for i in range(1, hours):
        ar[i] = 0.95 * ar[i - 1] + innov[i]
    dip = 1.0 - p["dip_depth"] * _dip_mask(rng, hours, p["dip_rate"],
                                           p["dip_len"])
    return np.maximum(p["ci_mean"] * (1.0 + day + season + ar) * dip, 12.0)


def region_traces(hours: int, seed: int) -> np.ndarray:
    """(3, hours) traces of ES, NL, DE; region i is seeded ``seed + i``."""
    return np.stack([hourly_ci(r, hours, seed + i)
                     for i, r in enumerate(REGION_ORDER)])


def lifecycle_fleet(n: int, seed: int, hours: int, history_h: int,
                    chips_per_node: int = 256, region=None) -> dict:
    """Empty fleet for the simulator: numpy arrays in the dtypes the
    program takes, the (3, hours) traces and the node->region map.  The
    draws are those of ``synthetic_lifecycle_fleet``."""
    rng = np.random.default_rng(seed)
    ridx = rng.integers(0, len(REGION_ORDER), n) if region is None \
        else np.full(n, int(region))
    traces = region_traces(hours, seed)
    pue = np.array([REGIONS[r]["pue"] for r in REGION_ORDER])[ridx]
    power = chips_per_node * CHIP_KW * (1 + 0.1 * rng.random(n))
    straggler = np.abs(rng.normal(0, 0.05, n))
    flops = 788e9 * (1 + 0.05 * rng.standard_normal(n))
    return dict(
        ci_now=traces[ridx, history_h].astype(np.float32),
        ci_forecast=traces[ridx, history_h].astype(np.float32),
        pue=pue.astype(np.float32),
        power_kw=power.astype(np.float32),
        capacity=np.full(n, chips_per_node, np.int32),
        healthy=np.ones(n, bool),
        straggler_score=straggler.astype(np.float32),
        flops_per_j=flops.astype(np.float32),
        chips_total=np.full(n, chips_per_node, np.int32),
        traces=traces, ridx=ridx)




# ---------------------------------------------------------------------------
# a Borg cell's jobs: one stream of arriving jobs, and the long-running
# service jobs that hold the cell's chips when a run starts
# ---------------------------------------------------------------------------


def diurnal_rate(hours, rate: float, amp: float):
    """Arrivals per hour with a business-hours factor peaking at 14:00."""
    return float(rate) * (1.0 + amp * np.cos(2 * np.pi
                                             * (np.asarray(hours) % 24 - 14)
                                             / 24))


def pareto_minutes(rng, n: int, lo: float, hi: float, alpha: float):
    """Bounded Pareto on [lo, hi] (inverse CDF)."""
    u = rng.random(n)
    return lo / (1.0 - u * (1.0 - (lo / hi) ** alpha)) ** (1.0 / alpha)


def stream(cfg: dict, seed: int, k0: int, k1: int, interval_min: int,
           hour0: float = 0.0):
    """Jobs arriving in control intervals ``k0 .. k1-1`` of
    ``interval_min`` minutes, interval 0 starting at ``hour0``: Poisson
    arrivals at the cell's job rate with the business-hours factor, chips
    from the size ladder, bounded-Pareto durations rounded up to whole
    intervals.  Each interval draws from its own stream, so the jobs of an
    interval do not depend on the span asked for.  Returns the arrays
    ``(interval, chips, duration in intervals)``."""
    jc = cfg["jobs"]
    rate = cfg["arrivals_per_hour_per_cell"] * len(cfg["regions"])
    ladder = np.asarray(jc["chips"], np.int64)
    p = np.asarray(jc["chips_weights"], np.float64)
    p = p / p.sum()
    ks, cs, ds = [], [], []
    for k in range(k0, k1):
        rng = np.random.default_rng([seed % (1 << 63), 3, k])
        lam = diurnal_rate(hour0 + k * interval_min / 60.0, rate,
                           jc["diurnal_amp"]) * interval_min / 60.0
        n = int(rng.poisson(lam))
        cs.append(ladder[rng.choice(ladder.size, n, p=p)])
        mins = pareto_minutes(rng, n, jc["duration_min_minutes"],
                              jc["duration_max_h"] * 60.0,
                              jc["duration_alpha"])
        ds.append(np.maximum(np.ceil(mins / interval_min), 1)
                  .astype(np.int64))
        ks.append(np.full(n, k, np.int64))
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros(0, np.int64)
    return cat(ks), cat(cs), cat(ds)


def service(cfg: dict, seed: int) -> np.ndarray:
    """Chips of the long-running service jobs that hold
    ``cfg["service"]["share_of_chips"]`` of the fleet's chips, in the
    order they were submitted."""
    sv = cfg["service"]
    total = (sv["share_of_chips"] * cfg["nodes_per_cell"]
             * len(cfg["regions"]) * cfg["chips_per_node"])
    sizes = np.asarray(sv["chips"], np.int64)
    rng = np.random.default_rng([seed % (1 << 63), 4])
    c = sizes[rng.integers(0, sizes.size, int(total / sizes.min()) + 1)]
    return c[:int(np.searchsorted(np.cumsum(c), total, side="right"))]


def schedule(cfg: dict, seed: int, epochs: int) -> dict:
    """The simulator's job table for one trajectory of hourly epochs: the
    service jobs, all arriving at epoch 0 and outliving the study, then
    the stream."""
    svc = service(cfg, seed)
    k, c, d = stream(cfg, seed, 0, epochs, 60)
    arrive = np.concatenate([np.zeros(svc.size, np.int64), k])
    chips = np.concatenate([svc, c])
    dur = np.concatenate([np.full(svc.size, int(cfg["service"]["duration_h"]),
                                  np.int64), d])
    return dict(arrive=arrive, chips=chips, duration=dur,
                load=chips.astype(np.float64),
                deferrable=np.zeros(arrive.size, bool))


# ---------------------------------------------------------------------------
# the decision service's fleet: Borg cells, one per region
# ---------------------------------------------------------------------------


def cell_fleet(cfg: dict, seed: int) -> dict:
    """Fleet arrays of ``cfg["regions"]`` cells of ``cfg["nodes_per_cell"]``
    nodes each (node i of cell r lies in region r) and the deployment's
    year of hourly traces (seeded by ``cfg["trace_seed"]``, not by the
    run)."""
    regions = cfg["regions"]
    n = cfg["nodes_per_cell"] * len(regions)
    rng = np.random.default_rng([seed, 1])
    col = [REGION_ORDER.index(r) for r in regions]
    ridx = np.repeat(np.arange(len(regions)), cfg["nodes_per_cell"])
    hours = int(cfg["trace_hours"])
    traces = np.stack([hourly_ci(r, hours, int(cfg["trace_seed"]) + c)
                       for r, c in zip(regions, col)])
    cpn = int(cfg["chips_per_node"])
    return dict(
        pue=np.array([REGIONS[r]["pue"] for r in regions],
                     np.float32)[ridx],
        power_kw=(cpn * CHIP_KW * (1 + 0.1 * rng.random(n))
                  ).astype(np.float32),
        healthy=np.ones(n, bool),
        straggler_score=np.abs(rng.normal(0, 0.05, n)).astype(np.float32),
        flops_per_j=(788e9 * (1 + 0.05 * rng.standard_normal(n))
                     ).astype(np.float32),
        chips_total=np.full(n, cpn, np.int32),
        traces=traces, ridx=ridx)
