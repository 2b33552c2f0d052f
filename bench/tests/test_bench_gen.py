"""The benchmark's own generators (bench/lib/gen.py): the copies of the
program's start equal to it draw for draw, the job stream is the same
whatever span is asked for, and the rank sweep's byte count and the table
of peaks hold what they say."""
import numpy as np
import pytest

from bench_tiny import ROOT  # noqa: F401  (puts bench/ and src/ on the path)
from lib import gen
from lib.kernel_cost import sweep_bytes


def _cfg():
    import json
    with open(f"{ROOT}/bench/configs/borg_1dc.json") as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [0, 1, 3, 11, 2 ** 31 + 5])
def test_stream_draws_each_interval_alone(seed):
    cfg = _cfg()
    k, c, d = gen.stream(cfg, seed, 0, 48, 60)
    k2, c2, d2 = (np.concatenate(x) for x in zip(
        gen.stream(cfg, seed, 0, 20, 60), gen.stream(cfg, seed, 20, 48, 60)))
    for x, y in ((k, k2), (c, c2), (d, d2)):
        np.testing.assert_array_equal(x, y)
    # about 965 jobs an hour, sizes on the ladder, at least one interval
    assert 0.9 * 965 * 48 < k.size < 1.1 * 965 * 48
    assert set(np.unique(c)) <= set(cfg["jobs"]["chips"])
    assert d.min() >= 1 and d.max() <= cfg["jobs"]["duration_max_h"]
    # the business-hours factor: more arrivals at 14:00 than at 02:00
    per_h = np.bincount(k % 24, minlength=24)
    assert per_h[14] > per_h[2]
    np.testing.assert_array_equal(gen.stream(cfg, seed, 0, 48, 60)[2], d)


@pytest.mark.parametrize("seed,region", [(1, None), (7, 0), (12, 2)])
def test_fleet_matches_synthetic_lifecycle_fleet(seed, region):
    from repro.core.simulator import SimConfig, synthetic_lifecycle_fleet
    cfg = SimConfig(epochs=24, seed=seed, history_h=48, horizon_h=8)
    fleet, traces, ridx = synthetic_lifecycle_fleet(3000, cfg, region=region)
    got = gen.lifecycle_fleet(3000, seed, 48 + 24 + 8 + 1, 48, region=region)
    np.testing.assert_array_equal(got["traces"], traces)
    np.testing.assert_array_equal(got["ridx"], ridx)
    for k in ("ci_now", "ci_forecast", "pue", "power_kw", "capacity",
              "healthy", "straggler_score", "flops_per_j", "chips_total"):
        np.testing.assert_array_equal(got[k], np.asarray(getattr(fleet, k)))


def test_hourly_ci_matches_telemetry():
    from repro.core import telemetry
    for i, r in enumerate(gen.REGION_ORDER):
        np.testing.assert_array_equal(
            gen.hourly_ci(r, 500, 40 + i),
            telemetry.hourly_ci(telemetry.REGIONS[r], 500, 40 + i))
        assert gen.REGIONS[r]["pue"] == telemetry.REGIONS[r].pue


def test_sweep_bytes_by_hand():
    # 36,864 nodes, 6 float32 streams read + 1 written: 36,864 * 4 * 7
    assert sweep_bytes(36864) == 1_032_192
    # 12 lanes of 12,288 nodes with the 3 marginal-term streams: 9 + 1
    assert sweep_bytes(12288, lanes=12, marginal=True) == 12 * 12288 * 40


def test_peaks_table_names_v5e_and_refuses_unknown_kinds():
    import run
    peak = run.load_peak(ROOT, "TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peak["bf16_flops_per_s"] == 197e12
    assert "Google Cloud" in peak["source"]
    with pytest.raises(KeyError):
        run.load_peak(ROOT, "TPU v9 imaginary")


def test_service_jobs_hold_their_share_and_open_the_schedule():
    cfg = _cfg()
    svc = gen.service(cfg, 5)
    total = 12288 * 256
    assert 0.5 * total - 256 < svc.sum() <= 0.5 * total
    assert set(np.unique(svc)) <= {128, 256}
    jb = gen.schedule(cfg, 5, 6)
    n = svc.size
    np.testing.assert_array_equal(jb["chips"][:n], svc)
    assert (jb["arrive"][:n] == 0).all() and (jb["duration"][:n] == 696).all()
    assert (np.diff(jb["arrive"][n:]) >= 0).all() and jb["arrive"].max() < 6
