"""The harness finds every cell's pieces by name, runs each traffic mix end
to end at a tiny size on the CPU (Pallas in interpret mode), and refuses
to measure where there is no TPU or no program."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import bench_tiny
from bench_tiny import CELLS, ROOT, run_tiny
import run


def test_every_cell_finds_its_files_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert spec["paths"] == ["bench"]
    for wl in spec["workloads"]:
        cell = run.load_cell(ROOT, wl["name"])
        assert cell["traffic"]["driver"] in ("decide", "sim")
        names = {m["name"] for m in cell["e2e"]}
        assert "setup_s" in names and len(names) >= 2
        assert cell["layer"]
        for m in cell["layer"]:
            assert callable(run.load_reader(ROOT, m["name"]))
            assert m["moves"] in names


def test_new_config_traffic_and_metric_are_picked_up_without_edit(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(ROOT, "bench/configs/borg_3dc.json")))
    cfg["regions"] = ["NL"]
    (root / "bench/configs/borg_nl.json").write_text(json.dumps(cfg))
    tr = json.load(open(os.path.join(ROOT, "bench/traffic/decide.json")))
    tr["interval_min"] = 15
    (root / "bench/traffic/decide15.json").write_text(json.dumps(tr))
    (root / "bench/metrics/events_per_decision.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec["configs"].append(dict(spec["configs"][0], name="borg_nl",
                                file="bench/configs/borg_nl.json"))
    spec["workloads"].append(dict(spec["workloads"][0], name="borg_nl.d15",
                                  config="borg_nl", traffic="decide15"))
    for m in spec["end_to_end"]:
        if "borg_3dc.decide" in m.get("workloads", []):
            m["workloads"].append("borg_nl.d15")
    spec["per_layer"].append(dict(spec["per_layer"][0],
                                  name="events_per_decision",
                                  workloads=["borg_nl.d15"]))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = run.load_cell(str(root), "borg_nl.d15")
    assert cell["cfg"]["regions"] == ["NL"]
    assert cell["traffic"]["interval_min"] == 15
    assert "events_per_decision" in [m["name"] for m in cell["layer"]]
    assert run.load_reader(str(root), "events_per_decision")(None) == 42.0
    # the existing cells are untouched by the addition
    assert run.load_cell(str(root), "borg_3dc.decide")["cfg"]["regions"] \
        == ["ES", "NL", "DE"]


@pytest.mark.parametrize("workload", CELLS)
def test_each_cell_runs_end_to_end_and_checks_correct(workload):
    res, compared = run_tiny(workload, calls=3)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 3
    names = set(res["metrics"])
    assert "setup_s" in names and len(names) >= 2
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "compared"
    for k, v, lim in compared:
        assert 0 <= v <= lim


def test_traced_run_reports_per_layer_metrics_and_breakdown():
    res, _ = run_tiny("borg_1dc.scan", calls=2, traced=True)
    assert "sweeps_per_lane_epoch" in res["metrics"]
    assert "setup_s" not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["device"]["window_s"] > 0


def _bench(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=240)


ARGS = ["--workload", "borg_3dc.decide", "--seed", "2147483700",
        "--seconds", "1", "--trace", "0"]


def test_run_refuses_a_machine_without_tpu():
    p = _bench(ARGS, ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(ARGS, str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_tiny_cells_keep_the_cells_own_files():
    cell = bench_tiny.tiny_cell("borg_3dc.decide")
    assert cell["traffic"]["use_kernel"] is True
    assert cell["cfg"]["precision"] == "float32"


def test_decide_window_carries_on_from_the_state_set_up_leaves():
    cell = bench_tiny.tiny_cell("borg_3dc.decide")
    keep = {}
    res, _ = run.run_cell(cell, 2 ** 31 + 9, 1e9, False, t_proc=0.0,
                          driver_hook=lambda d: keep.update(drv=d),
                          max_calls=4)
    assert res["correct"] is True
    drv = keep["drv"]
    warm = cell["traffic"]["warm_calls"]
    assert [r["k"] for r in drv.records] == list(range(warm, warm + 4))
    # the service jobs were placed by the program before the window
    from lib import gen
    svc = gen.service(cell["cfg"], drv.dseed).sum()
    held = (drv.fl["chips_total"].astype(np.int64) - drv.cap0).sum()
    assert svc <= held <= svc + 256 * warm * 60


def test_every_seed_serves_the_same_arrivals_in_its_own_order():
    from lib import drivers
    cell = bench_tiny.tiny_cell("borg_3dc.decide")
    ev = []
    for seed in (5, 2 ** 32 + 5, 5):
        d = drivers.Decide(cell["cfg"], cell["traffic"], seed)
        d.dseed, d.hour0, d.buckets = 2011, 416, {17: [(3, 64)]}
        ev.append(d._events(17))
    (a, na, da), (b, nb, db), (c, _, _) = ev
    assert a[0] == -64 and na[0] == 3 and (na[1:] == -1).all()
    assert sorted(zip(a[1:], da)) == sorted(zip(b[1:], db))
    assert a.size > 3 and not np.array_equal(a, b)
    np.testing.assert_array_equal(a, c)
