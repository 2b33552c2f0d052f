"""A study laid out over a device mesh (the traffic key ``shard``), the
sampled gap check (``check.events``) and the rank sweep's roofline read
per chip: a sharded tiny cell on four virtual CPU devices, the sampled
check against the full replay, faults outside the sample, and the cells
without the new keys giving the compared numbers they gave before."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest

import bench_tiny
from bench_tiny import BENCH, CELLS, ROOT, run_tiny
from lib import control, drivers, gen, layers, readers, reference, trace
from lib.kernel_cost import sweep_bytes
import run

TESTS = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# a sharded study through the harness
# ---------------------------------------------------------------------------


def test_sharded_study_runs_end_to_end_on_four_cpu_devices():
    code = textwrap.dedent(f"""
        import json, sys, time
        sys.path[:0] = [{BENCH!r}, {TESTS!r}, {os.path.join(ROOT, "src")!r}]
        import bench_tiny, run
        cell = bench_tiny.tiny_cell("borg_1dc.sweep12")
        cell["workload"] = dict(cell["workload"], chips=4,
                                name="borg_1dc.sharded_tiny")
        tr = cell["traffic"]
        tr.update(shard="en", check=dict(tr["check"], events=64))
        keep = {{}}
        for traced in (False, True):
            res, _ = run.run_cell(cell, 2 ** 31 + 17, 1e9, traced,
                                  t_proc=time.perf_counter(), max_calls=2,
                                  driver_hook=lambda d: keep.update(d=d))
            print(json.dumps(dict(res, shape=keep["d"].sweep_shape())))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    plain, traced = [json.loads(x) for x in p.stdout.strip().splitlines()]
    for res in (plain, traced):
        assert res["correct"] is True and res["failed"] == 0
        assert res["device"]["count"] == 4
        assert res["device"]["mesh"] == [2, 2]
        # one launch on one device: one of the two lanes, half the nodes
        assert res["shape"] == dict(n_nodes=512, lanes=1, marginal=True)
        assert res["compared"]["invalid"]["value"] == 0
    assert "sim_lane_epochs_per_s" in plain["metrics"]
    # the walk counters come from reruns of the sharded program
    assert "sweep_no_room_pct.sim" in traced["metrics"]
    assert "gaps_scored=" in p.stderr


def test_shard_needs_an_ensemble_and_a_known_layout():
    cell = bench_tiny.tiny_cell("borg_1dc.scan")
    with pytest.raises(ValueError, match="ensemble"):
        drivers.Sim(cell["cfg"], dict(cell["traffic"], shard="en"), 1)
    cell = bench_tiny.tiny_cell("borg_1dc.sweep12")
    with pytest.raises(ValueError, match="shard"):
        drivers.Sim(cell["cfg"], dict(cell["traffic"], shard="n"), 1)


# ---------------------------------------------------------------------------
# the sampled check
# ---------------------------------------------------------------------------


def _checks(hook, events, seed=2 ** 31 + 23):
    """The full check and the sampled one of the same window."""
    cell = bench_tiny.tiny_cell("borg_1dc.sweep12")
    keep = {}

    def grab(d):
        keep["d"] = d
        if hook is not None:
            hook(d)
    run_tiny("borg_1dc.sweep12", seed=seed, calls=2, hook=grab)
    drv = keep["d"]
    assert drv.tr["check"] == cell["traffic"]["check"]
    out = []
    for ev in (None, events):
        drv.tr["check"] = dict(cell["traffic"]["check"])
        if ev is not None:
            drv.tr["check"]["events"] = ev
        out.append(drv.check(np.random.default_rng([seed, 99])))
    return out


def _altered(drv):
    real = drv.entry

    def entry(runs, **kw):
        res = real(runs, **kw)
        fn = res[0].first_node.copy()
        placed = np.flatnonzero(fn >= 0)
        fn[placed[len(placed) // 2]] = -1          # left out beside room
        fn[placed[-1]] = fn[placed[0]]             # moved onto another node
        return [dataclasses.replace(res[0], first_node=fn)] + res[1:]
    drv.entry = entry


@pytest.mark.parametrize("hook", [None, control.install, _altered],
                         ids=["program", "control", "altered"])
def test_sampled_check_with_every_arrival_equals_the_full_replay(hook):
    (full, _), (samp, info) = _checks(hook, events=10 ** 6)
    assert samp == full
    assert info["gaps_scored"] == info["placements_checked"]
    if hook is not None:
        assert full["place_gap"] > 0 or full["invalid"] > 0


def test_sampled_check_counts_invalid_as_the_full_replay_does():
    (full, _), (samp, info) = _checks(_altered, events=3)
    assert full["invalid"] > 0
    assert samp["invalid"] == full["invalid"]
    assert samp["emis_rel"] == full["emis_rel"]
    assert samp["place_gap"] <= full["place_gap"]
    # three drawn arrivals and the busiest epoch's last, in each of 2 lanes
    assert 2 <= info["gaps_scored"] <= 8 < info["placements_checked"]


def _lane(n=4, chips=16, epochs=2):
    """A hand-sized lane: ``n`` empty nodes of ``chips`` chips, and jobs
    that fill them at epoch 0 and leave one job with no room."""
    hist, hor = 24, 4
    fl = gen.lifecycle_fleet(n, 7, hist + epochs + hor + 1, hist, chips, 0)
    arrive = np.array([0] * (n + 1) + [1], np.int64)
    job_chips = np.array([chips] * n + [8, 8], np.int64)
    return dict(fl, arrive=arrive, chips=job_chips,
                duration=np.full(arrive.size, 10, np.int64), epochs=epochs,
                history_h=hist, horizon_h=hor, consolidate=1.0,
                weights=dict(w1=0.35, w2=0.25, w3=0.25, w4=0.15),
                energy=dict(idle_frac=0.35, dyn_frac=0.65,
                            embodied_g_per_node_h=0.0, w_marginal=0.0))


def _replay(lane, follow, score):
    res = dict(follow)
    res["placed"] = int((follow["start_epoch"] >= 0).sum())
    res["completed"] = 0
    return reference.simulate_lane(lane, follow=res, score=score)[0]


def test_faults_outside_the_sample_are_still_invalid():
    lane = _lane()
    ref = reference.simulate_lane(lane)
    # the fleet is full at epoch 0: both 8-chip jobs are left out
    assert (ref["first_node"][-2:] == -1).all()
    only_first = np.zeros(lane["arrive"].size, bool)
    only_first[0] = True
    clean = _replay(lane, ref, only_first)
    assert clean.invalid == 0 and clean.scored == 1
    # an 8-chip job placed on a full node, outside the sample
    over = dict(ref, first_node=ref["first_node"].copy(),
                start_epoch=ref["start_epoch"].copy())
    over["first_node"][4], over["start_epoch"][4] = 2, 0
    # a 16-chip job left out at epoch 0 beside the node it would fill;
    # the 8-chip job after it, left out too, then had room as well
    out = dict(ref, first_node=ref["first_node"].copy(),
               start_epoch=ref["start_epoch"].copy())
    out["first_node"][1] = out["start_epoch"][1] = -1
    for planted in (over, out):
        for score in (only_first, None):
            a = _replay(lane, planted, score)
            assert a.invalid >= 1, score
        assert _replay(lane, planted, only_first).invalid \
            == _replay(lane, planted, None).invalid


def test_gap_sample_draws_from_the_seed_and_takes_the_busiest_epoch():
    lane = dict(arrive=np.array([0, 0, 1, 1, 1, 2, 5]), epochs=3)
    a = drivers._gap_sample(lane, 2, np.random.default_rng(1))
    b = drivers._gap_sample(lane, 2, np.random.default_rng(1))
    np.testing.assert_array_equal(a, b)
    assert a[4] and not a[6]            # epoch 1's last; 5 is past the day
    assert 2 <= a.sum() <= 3
    assert drivers._gap_sample(lane, 99, np.random.default_rng(2))[:6].all()


# ---------------------------------------------------------------------------
# the rank sweep's roofline, per chip
# ---------------------------------------------------------------------------


def _sim_shape(mesh):
    cell = bench_tiny.tiny_cell("borg_1dc.sweep12")
    sim = drivers.Sim(cell["cfg"], cell["traffic"], 1)
    sim.sets = [([None] * 12, None)]
    sim.mesh_shape = lambda: mesh
    return sim.sweep_shape()


def _roofline(planes, per_launch_ns, shape, launches=5):
    ops = {f"/device:TPU:{p}": [
        ("%maiz_topk_pallas_b.1", 100 + i * 1000,
         100 + i * 1000 + per_launch_ns[p]) for i in range(launches)]
        for p in range(planes)}
    red = trace.reduce({"ops": ops,
                        "spans": [("plan_and_run", 0, 10 ** 6)]})
    ctx = run.Ctx(trace=red, sweep_shape=shape,
                  peak=run.load_peak(ROOT, "TPU v5 lite"))
    return readers.sweep_roofline_pct(ctx)


def test_four_plane_trace_reads_the_roofline_of_one_plane():
    one = _sim_shape(None)
    four = _sim_shape((2, 2))
    assert one == dict(n_nodes=1024, lanes=12, marginal=True)
    assert four == dict(n_nodes=512, lanes=6, marginal=True)
    # the same launches: each chip sweeps a quarter at the same speed
    base = _roofline(1, [400], one)
    assert base == pytest.approx(
        100 * sweep_bytes(**one) / 819e9 / 400e-9)
    assert _roofline(4, [100] * 4, four) == pytest.approx(base)
    # chips of unequal speed: their mean share, weighted by kernel time
    mixed = _roofline(4, [50, 100, 100, 150], four)
    assert mixed == pytest.approx(base)
    assert _roofline(4, [200] * 4, four) == pytest.approx(base / 2)
    for name in ("rank_sweep_roofline.sim", "rank_sweep_roofline.decide"):
        assert run.load_reader(ROOT, name) is not None


SHARDED_HLO = """HloModule jit_walk, entry_computation_layout={(f32[4]{0})->\
f32[4]{0}}, num_partitions=4

%add.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.2 = f32[] add(%a, %b)
}

%exchange.16 (q: f32[4]) -> f32[4] {
  %q = f32[4]{0} parameter(0)
  ROOT %all-reduce.17 = f32[4]{0} all-reduce(%q), to_apply=%add.1
}

%body.3 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %mul.4 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(walk)/while/\
body/placement_walk/mul"}
  %neg.5 = f32[4]{0} negate(%mul.4), metadata={op_name="jit(walk)/while/\
body/placement_walk/neg"}
  %dynamic-update-slice.12 = f32[4]{0} dynamic-update-slice(%mul.4, %p, %p)
  %all-reduce.6 = f32[4]{0} all-reduce(%dynamic-update-slice.12), \
channel_id=1, replica_groups=[2,2]<=[4], to_apply=%add.1
  %all-gather-start.7 = (f32[4]{0}, f32[8]{0}) all-gather-start(%neg.5), \
channel_id=2, dimensions={0}
  %all-reduce.13 = (f32[], f32[], f32[], f32[], f32[], /*index=5*/f32[]) \
all-reduce(%p, %p, %p, %p, %p, %p), channel_id=3, to_apply=%add.1, \
metadata={op_name="jit(walk)/while/body/placement_walk/reduce_min"}
  %slice.14 = f32[2]{0} slice(%all-reduce.6), slice={[0:2]}
  %async-start.15 = ((f32[4]{0}), f32[4]{0}) async-start(%p), \
calls=%exchange.16
  ROOT %all-gather-done.8 = f32[8]{0} all-gather-done(%all-gather-start.7)
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %copy.10 = f32[4]{0} copy(%x)
  ROOT %while.11 = f32[4]{0} while(%copy.10), condition=%body.3, body=%body.3
}
"""


PARTITIONER_OPS = ("%dynamic-update-slice.12", "%slice.14")
COLLECTIVES = ("%all-reduce.6", "%all-gather-start.7", "%all-gather-done.8",
               "%all-reduce.13", "%all-reduce.17", "%async-start.15")


def test_partitioner_ops_join_their_computations_scope():
    name, table = layers.scope_table(SHARDED_HLO)
    assert name == "jit_walk"
    for op in PARTITIONER_OPS:
        assert table[op] == "placement_walk", op
    # the exchange between chips has a bucket of its own, with or
    # without metadata, and a tuple shape does not hide its opcode
    for op in COLLECTIVES:
        assert table[op] == layers.COLLECTIVE, op
    # a computation with no scoped instruction gives none
    assert table["%copy.10"] is None and table["%add.2"] is None
    assert table["%while.11"] == "placement_walk"      # by its callees
    # the same program on one device keeps the rules it had
    one = SHARDED_HLO.replace(", num_partitions=4", "")
    _, table = layers.scope_table(one)
    assert all(table[op] is None for op in PARTITIONER_OPS)
    assert all(table[op] == layers.COLLECTIVE for op in COLLECTIVES)
    assert table["%while.11"] == "placement_walk"


def test_scope_times_are_averaged_over_device_planes():
    _, table = layers.scope_table(SHARDED_HLO)
    mods = [("jit_walk(3)", 0, 100)]
    one = {"ops": {"/device:TPU:0": [("%mul.4", 0, 40), ("%copy.10", 40, 50),
                                     ("%all-reduce.6", 50, 70)]},
           "modules": {"/device:TPU:0": mods}}
    four = {"ops": {f"/device:TPU:{i}": [("%mul.4", 0, 40 + i),
                                         ("%copy.10", 40 + i, 50),
                                         ("%all-reduce.6", 50, 70)]
                    for i in range(4)},
            "modules": {f"/device:TPU:{i}": mods for i in range(4)}}
    a = layers.scope_times(one, (0, 100), [("jit_walk", table)])
    b = layers.scope_times(four, (0, 100), [("jit_walk", table)])
    assert a["placement_walk"] == 40 and a[layers.UNSCOPED] == 10
    assert a[layers.COLLECTIVE] == 20
    assert b["placement_walk"] == pytest.approx(41.5)
    assert b[layers.UNSCOPED] == pytest.approx(8.5)
    assert b[layers.COLLECTIVE] == pytest.approx(20)
    assert b["unscoped_ops"] == {"%copy.10": pytest.approx(8.5)}
    assert trace.reduce(dict(four, spans=[("plan_and_run", 0, 100)]))[
        "busy_ns"] == 70


def test_device_ops_are_averaged_over_device_planes():
    def plane(skew):
        return [("%while.11", 0, 60 + skew), ("%mul.4", 10, 40),
                ("%all-reduce.6", 60 + skew, 80)]
    spans = [("plan_and_run", 0, 100)]
    one = trace.reduce({"ops": {"/device:TPU:0": plane(0)}, "spans": spans})
    four = trace.reduce({"ops": {f"/device:TPU:{i}": plane(i - 1)
                                 for i in range(4)}, "spans": spans})
    assert one["per_op_ns"] == {"%while.11": 30, "%mul.4": 30,
                                "%all-reduce.6": 20}
    # the breakdown's device operations: each chip's mean, as busy time
    assert four["per_op_ns"]["%while.11"] == pytest.approx(30.5)
    assert four["per_op_ns"]["%mul.4"] == pytest.approx(30)
    assert four["per_op_ns"]["%all-reduce.6"] == pytest.approx(19.5)
    assert four["op_count"] == one["op_count"]
    assert sum(four["per_op_ns"].values()) == pytest.approx(four["busy_ns"])


def test_a_program_on_one_device_is_not_partitioned():
    import jax
    import jax.numpy as jnp
    text = jax.jit(lambda x: jnp.cumsum(x).max() + x).lower(
        jnp.ones(64)).compile().as_text()
    assert layers._PARTITIONS.search(text) is None
    assert layers._PARTITIONS.search(SHARDED_HLO).group(1) == "4"


def test_sweep_bytes_count_the_room_stream_only_without_marginal():
    assert sweep_bytes(2048, room=True) == sweep_bytes(2048) * 8 // 7
    assert sweep_bytes(2048, 3, marginal=True, room=True) \
        == sweep_bytes(2048, 3, marginal=True)
    cell = bench_tiny.tiny_cell("borg_3dc.decide")
    d = drivers.Decide(cell["cfg"], cell["traffic"], 1)
    d.cap = np.zeros(2048)
    assert d.sweep_shape() == dict(n_nodes=2048, lanes=1, marginal=False,
                                   room=True)
    assert d.mesh_shape() is None


def test_mesh_is_read_from_the_programs_inputs():
    cell = bench_tiny.tiny_cell("borg_1dc.sweep12")
    plain = drivers.Sim(cell["cfg"], cell["traffic"], 5)
    plain.sets = [plain._input_set(0)]
    assert plain.mesh_shape() is None
    laid = drivers.Sim(cell["cfg"], dict(cell["traffic"], shard="en"), 5)
    laid.sets = plain.sets
    # one device: the program lays nothing out
    assert laid.mesh_shape() == (1, 1)
    assert laid.sweep_shape() == plain.sweep_shape()


# ---------------------------------------------------------------------------
# the cells without the new keys
# ---------------------------------------------------------------------------

# the compared numbers of the tiny cells, program and control, as the
# harness gave them before the new keys
BEFORE = {
    "borg_3dc.decide": ([("place_gap", 0.0), ("invalid", 0.0)],
                        [("place_gap", 0.0033983442457086732),
                         ("invalid", 0.0)]),
    "borg_1dc.sweep12": ([("place_gap", 0.0),
                          ("emis_rel", 1.6959669063350928e-07),
                          ("invalid", 0.0)],
                         [("place_gap", 0.06308337951716103),
                          ("emis_rel", 0.03544550849629211),
                          ("invalid", 0.0)]),
    "borg_1dc.scan": ([("place_gap", 0.0),
                       ("emis_rel", 1.6959669063350928e-07),
                       ("invalid", 0.0)],
                      [("place_gap", 0.06308337951716103),
                       ("emis_rel", 0.051463325543791405),
                       ("invalid", 0.0)]),
}


@pytest.mark.parametrize("workload", CELLS)
def test_cells_without_the_new_keys_compare_as_before(workload):
    prog, ctrl = BEFORE[workload]
    res, compared = run_tiny(workload, calls=3)
    assert [(k, v) for k, v, _ in compared] == prog
    assert "mesh" not in res["device"]          # no layout named
    _, compared = run_tiny(workload, calls=3, hook=control.install, seed=11)
    assert [(k, v) for k, v, _ in compared] == ctrl


def test_a_layout_runs_only_on_exactly_its_chips():
    tpu = types.SimpleNamespace(platform="tpu")
    cell = bench_tiny.tiny_cell("borg_1dc.sweep12")
    cell["workload"] = dict(cell["workload"], chips=4)
    assert run.refusal(cell, [tpu] * 4) == ""
    assert run.refusal(cell, [tpu] * 8) == ""    # one chip of eight: no
    cell["traffic"]["shard"] = "en"               # layout, the first four
    assert run.refusal(cell, [tpu] * 4) == ""
    assert "exactly 4" in run.refusal(cell, [tpu] * 8)
    assert "needs 4 TPU" in run.refusal(cell, [tpu] * 2)
    cpu = types.SimpleNamespace(platform="cpu")
    assert "TPU" in run.refusal(cell, [cpu] * 4)
