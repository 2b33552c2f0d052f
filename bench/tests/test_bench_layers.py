"""What the program names, read back (bench/lib/layers.py): the program's
spans, the named-scope table joined to a trace, the walk counters of the
traced calls, and the six readers on them.  And the rule beside it: no
metric the benchmark had moves, on the recorded chip trace or on a trace
that also holds the program's spans."""
import dataclasses
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench_tiny import run_tiny
from lib import layers, trace
import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "decide_n2048.xplane.pb")
OLD = ("device_idle.decide", "device_idle.sim", "rank_sweep_roofline.decide",
       "rank_sweep_roofline.sim", "sweeps_per_decision",
       "sweeps_per_lane_epoch", "host_lead_ms.sim")
NEW = ("shortlist_hit_pct.decide", "sweep_no_room_pct.decide",
       "sweep_no_room_pct.sim", "sweep_lane_use_pct.sim",
       "plan_build_ms.sim", "walk_device_pct.sim")


def _old_values(raw):
    ctx = run.Ctx(trace=trace.reduce(raw), trace_raw=raw,
                  # the trace predates the room stream: its own shape
                  sweep_shape=dict(n_nodes=2048, lanes=1, marginal=False,
                                   room=False),
                  peak=run.load_peak(run.ROOT, "TPU v5 lite"),
                  counters=dict(calls=2, sweeps=7, lane_epochs=48))
    return {n: run.load_reader(run.ROOT, n)(ctx) for n in OLD}


# the values every reader the benchmark had gives on the recorded trace
PINNED = {"device_idle.decide": 73.43053753489014,
          "device_idle.sim": 73.43053753489014,
          "rank_sweep_roofline.decide": 0.3086901244030245,
          "rank_sweep_roofline.sim": 0.3086901244030245,
          "sweeps_per_decision": 3.5,
          "sweeps_per_lane_epoch": 0.14583333333333334,
          "host_lead_ms.sim": None}


def test_existing_readers_pinned_on_the_recorded_chip_trace():
    raw = trace.load(DATA)
    assert _old_values(raw) == pytest.approx(PINNED)
    ext = layers.load(DATA)
    assert {k: ext[k] for k in raw} == raw       # trace.load's keys, as-is
    assert _old_values(ext) == pytest.approx(PINNED)
    assert [m[0] for m in ext["modules"]["/device:TPU:0"]] == [
        "jit_place_events(1631911442633874029)"] * 2


def _synthetic():
    ops = [("%fusion.1", 10, 20), ("%while.2", 30, 90), ("%kernel.3", 40, 60),
           ("%fusion.4", 130, 150)]
    return {"ops": {"/device:TPU:0": ops},
            "spans": [("plan_and_run", 0, 100), ("plan_and_run", 110, 200)]}


def test_existing_readers_pinned_on_a_trace_with_program_spans():
    raw = _synthetic()
    before = _old_values(raw)
    assert before["host_lead_ms.sim"] == pytest.approx(25 * 1e-6)
    program = [("plan_build", 0, 25), ("dispatch", 25, 28),
               ("device_wait", 28, 92), ("readback", 92, 95),
               ("result_assembly", 95, 99), ("plan_build", 110, 128),
               ("dispatch", 128, 129), ("device_wait", 129, 152),
               ("readback", 152, 198)]
    ext = dict(raw, extra_spans=program,
               modules={"/device:TPU:0": [("jit_f(1)", 10, 150)]})
    assert _old_values(ext) == before
    red = trace.reduce(ext)
    assert red["window"] == (0, 200)             # the harness window
    assert {g[0] for g in red["gaps"]} == {"plan_and_run"}
    gaps = layers.labelled_gaps(ext, red["window"])
    assert sum(g[1] for g in gaps) == sum(g[1] for g in red["gaps"])
    # idle [150, 200], [90, 130], [0, 10], [20, 30], each labelled at its
    # middle with the innermost span open there
    assert gaps == [("readback", 50), ("plan_build", 40), ("plan_build", 10),
                    ("dispatch", 10)]
    assert layers.spans_per_call(ext, "plan_build") == [25, 18]


def test_forecast_span_labels_gaps_without_moving_the_window():
    raw = {"ops": {"/device:TPU:0": [("%a", 30, 40)]},
           "spans": [("decide", 20, 50)]}
    ext = dict(raw, extra_spans=[("forecast", 0, 15)], modules={})
    red = trace.reduce(raw)
    assert red["window"] == (20, 50)
    assert layers.labelled_gaps(ext, red["window"]) == red["gaps"]
    # where a window holds it, the forecast refresh names its gap
    assert layers.labelled_gaps(ext, (0, 50)) == [("forecast", 30),
                                                  ("decide", 10)]


def test_recorded_cpu_trace_holds_the_program_spans(tmp_path):
    """A real trace of the simulator inside the harness's span: the
    harness's reduction sees only its own span, ``layers`` the program's."""
    from repro.core.simulator import (SimConfig, generate_jobs,
                                      simulate_fleet_scan,
                                      synthetic_lifecycle_fleet)
    cfg = SimConfig(epochs=6, seed=2, arrival_rate=4.0, mean_duration_h=3.0,
                    shortlist=8, history_h=48, horizon_h=8)
    fleet, traces, ridx = synthetic_lifecycle_fleet(32, cfg,
                                                    chips_per_node=64)
    jobs = generate_jobs(cfg)
    simulate_fleet_scan(fleet, traces, ridx, cfg, jobs)
    logdir = tmp_path / "cell"
    jax.profiler.start_trace(str(logdir))
    with jax.profiler.TraceAnnotation("plan_and_run"):
        simulate_fleet_scan(fleet, traces, ridx, cfg, jobs)
    jax.profiler.stop_trace()
    raw = trace.load(trace.find(str(logdir)))
    # the program's readback shares the harness span's name: trace.SPANS
    # takes it (nested in the call, it moves no window)
    assert [s[0] for s in raw["spans"]] == ["plan_and_run", "readback"]
    ext = layers.find(raw, root=str(tmp_path))
    assert ext is not None and ext["spans"] == raw["spans"]
    both = sorted(ext["spans"] + ext["extra_spans"], key=lambda s: s[1])
    assert [s[0] for s in both] == ["plan_and_run", *layers.PROGRAM_SPANS]
    assert trace.reduce(raw)["window"] == raw["spans"][0][1:]
    assert layers.find({"spans": [("decide", 1, 2)]},
                       root=str(tmp_path)) is None


def _scoped(x):
    with jax.named_scope("placement_walk"):
        y = jnp.cumsum(x * 3.0)
        with jax.named_scope("rank_sweep"):
            y = jax.lax.top_k(y, 4)[0].sum() + y
    with jax.named_scope("epoch_post"):
        return jnp.sin(y) * 2.0


def test_scope_table_and_trace_join_on_a_cpu_compiled_program():
    text = jax.jit(_scoped).lower(jnp.ones(64)).compile().as_text()
    name, table = layers.scope_table(text)
    assert name == "jit__scoped"
    assert {"placement_walk", "rank_sweep", "epoch_post"} <= set(
        table.values())
    pick = {s: next(i for i, v in table.items() if v == s)
            for s in ("placement_walk", "rank_sweep", "epoch_post")}
    bare = next(i for i, v in table.items() if v is None)
    dev = "/device:TPU:0"
    ext = {"ops": {dev: [(pick["placement_walk"], 0, 10),
                         (pick["rank_sweep"], 10, 15),
                         (pick["epoch_post"], 15, 22),
                         (bare, 22, 23),
                         ("%not_in_table.9", 23, 25),
                         (pick["epoch_post"], 40, 44)]},
           "modules": {dev: [("jit__scoped(77)", 0, 30),
                             ("jit_other(5)", 39, 50)]}}
    got = layers.scope_times(ext, (0, 100), [(name, table)])
    assert got["placement_walk"] == 10 and got["rank_sweep"] == 5
    assert got["epoch_post"] == 7 and got[layers.UNSCOPED] == 1
    assert got[layers.NO_TABLE] == 2 + 4
    assert got["by_module"] == {"jit__scoped": 2, "jit_other": 4}
    # a window clips, and nested operations count their self time only
    ext["ops"][dev].append(("%outer_loop", 0, 30))
    tbl = dict(table, **{"%outer_loop": "placement_walk"})
    got = layers.scope_times(ext, (0, 30), [(name, tbl)])
    assert got["placement_walk"] == 10 + 5      # 30 less its children
    assert layers.innermost_scope("jit(f)/vmap(jit(fit_forecast))/"
                                  "forecast/sub") == "forecast"
    # vmap names the scope it maps over after itself
    assert layers.innermost_scope("jit(g)/while/body/closed_call/"
                                  "vmap(epoch_pre)/jit(floor_divide)") \
        == "epoch_pre"
    assert layers.innermost_scope("jit(g)/vmap(vmap(jit(f)))/mul") is None


HLO = """HloModule jit_g, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_a (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p, %p), metadata={op_type="mul" \
op_name="jit(g)/while/body/vmap(epoch_pre)/mul" stack_frame_id=2}
}

ENTRY %main.2 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  ROOT %fusion.9 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_a
}
"""


def test_scope_table_gives_a_bare_fusion_its_callees_scope():
    """The compiler makes some fusions without metadata: they take the
    scope of the instructions they call."""
    assert layers.scope_table(HLO) == ("jit_g", {
        "%p": None, "%mul.1": "epoch_pre", "%x": None,
        "%fusion.9": "epoch_pre"})


def _ctx(counts, lane_sweeps=None, lane_rounds=None, scopes=None, busy=0,
         ext=None):
    return run.Ctx(
        walk=None if counts is None else dict(
            counts=np.asarray(counts, np.int64), lane_sweeps=lane_sweeps,
            lane_rounds=lane_rounds),
        layer_report=None if ext is None and scopes is None else dict(
            ext=ext, scopes=scopes, busy_ns=busy, gaps=[]))


def _read(name, ctx):
    return run.load_reader(run.ROOT, name)(ctx)


def test_new_readers_on_synthetic_counters_and_scopes():
    ctx = _ctx([5, 60, 30, 5], lane_sweeps=95, lane_rounds=190)
    assert _read("shortlist_hit_pct.decide", ctx) == 5.0
    assert _read("sweep_no_room_pct.decide", ctx) == pytest.approx(
        100 * 30 / 95)
    assert _read("sweep_no_room_pct.sim", ctx) == pytest.approx(100 * 30 / 95)
    assert _read("sweep_lane_use_pct.sim", ctx) == 50.0
    # two calls: one plan_build span of 2 ms, then two of 1 ms each
    ext = {"spans": [("plan_and_run", 0, 4e6), ("plan_and_run", 5e6, 9e6)],
           "extra_spans": [("plan_build", 0, 2e6), ("dispatch", 2e6, 3e6),
                           ("plan_build", 5e6, 6e6),
                           ("plan_build", 6e6, 7e6)]}
    ctx = _ctx(None, ext=ext, scopes={"placement_walk": 30, "rank_sweep": 50,
                                      layers.NO_TABLE: 1, "by_module": {}},
               busy=120)
    assert _read("plan_build_ms.sim", ctx) == pytest.approx(2.0)
    assert _read("walk_device_pct.sim", ctx) == 25.0


def test_new_readers_find_nothing_and_raise_nothing():
    """An older program, without the counters, spans or scope texts:
    every new reader leaves its metric out."""
    empty = _ctx(None, ext={"spans": [("plan_and_run", 0, 9)],
                            "extra_spans": []}, scopes=None, busy=5)
    assert all(_read(n, empty) is None for n in NEW)
    none = _ctx([0, 0, 0, 0], lane_rounds=None)
    assert all(_read(n, none) is None for n in NEW)


def _strip_walk(drv):
    """Make the driver's program look like one without walk counters or
    scope texts."""
    real = drv.entry
    if drv.tr["driver"] == "decide":
        def entry(*a, **kw):
            p = real(*a, **kw)
            return types.SimpleNamespace(node=p.node, n_sweeps=p.n_sweeps)
    else:
        def entry(*a, **kw):
            res = real(*a, **kw)
            strip = (lambda r: dataclasses.replace(r, walk_counts=None,
                                                   sweep_rounds=None))
            return [strip(r) for r in res] if isinstance(res, list) \
                else strip(res)
        drv.sim = types.SimpleNamespace()
    drv.entry = entry


@pytest.mark.parametrize("workload", bench_tiny.CELLS)
def test_traced_tiny_cells_report_the_new_metrics(workload):
    cell = bench_tiny.tiny_cell(workload)
    mine = {m["name"] for m in cell["layer"]} & set(NEW)
    res, _ = run_tiny(workload, calls=2, traced=True)
    assert res["correct"] is True
    got = res["metrics"]
    # on the CPU no device op is traced: the device-time share finds
    # nothing; the counters and host spans are all there
    for n in mine - {"walk_device_pct.sim"}:
        assert n in got, n
        assert 0 <= got[n]["value"] <= (100 if got[n]["unit"] == "%"
                                        else 1e6)
    assert "walk_device_pct.sim" not in got
    bare, _ = run_tiny(workload, calls=2, traced=True, hook=_strip_walk)
    assert bare["correct"] is True
    assert not mine & set(bare["metrics"]) - {"plan_build_ms.sim"}
