"""The trace reduction (bench/lib/trace.py), on hand-made intervals and on
a small trace recorded on a v5e chip: two place_events decisions at
N=2,048 inside the harness's ``decide`` span."""
import os

import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
from lib import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "decide_n2048.xplane.pb")


def test_self_times_subtract_nested_children():
    evs = [("while", 0, 100), ("body", 10, 40), ("kernel", 15, 35),
           ("body", 50, 90)]
    st = trace.self_times(evs)
    assert st == {"while": 30, "body": 50, "kernel": 20}
    assert sum(st.values()) == 100


def test_reduce_busy_union_and_labelled_gaps():
    tr = {"ops": {"/device:TPU:0": [("a", 10, 20), ("b", 15, 30),
                                    ("c", 60, 70)]},
          "spans": [("decide", 0, 50), ("readback", 55, 80)]}
    red = trace.reduce(tr)
    assert red["window_ns"] == 80
    assert red["busy_ns"] == 30            # [10, 30] and [60, 70]
    # idle [0, 10] and [30, 60] fall in the decide span, [70, 80] in readback
    assert red["gaps"] == [("decide", 30), ("decide", 10), ("readback", 10)]
    assert trace.label_at(tr["spans"], 52) == "between_calls"
    assert trace.longest_op_in(tr, 0, 50) == 15
    assert trace.longest_op_in(tr, 31, 50) is None


def test_reduce_recorded_chip_trace():
    raw = trace.load(DATA)
    assert list(raw["ops"]) == ["/device:TPU:0"]
    assert [s[0] for s in raw["spans"]] == ["decide", "decide"]
    red = trace.reduce(raw)
    assert 0 < red["busy_ns"] < red["window_ns"]
    # self times partition the busy union of properly nested ops
    assert sum(red["per_op_ns"].values()) == pytest.approx(red["busy_ns"])
    kernels = [n for n in red["op_count"] if "maiz_topk_pallas" in n]
    assert kernels and all(red["op_count"][n] >= 1 for n in kernels)
    assert all(label == "decide" for label, _ in red["gaps"][:3])
    assert red["gaps"][0][1] >= red["gaps"][-1][1]


def test_sweep_roofline_reader_on_recorded_trace():
    import run
    from lib import readers
    raw = trace.load(DATA)
    ctx = run.Ctx(trace=trace.reduce(raw), trace_raw=raw,
                  sweep_shape=dict(n_nodes=2048, lanes=1, marginal=False),
                  peak=run.load_peak(run.ROOT, "TPU v5 lite"))
    pct = readers.sweep_roofline_pct(ctx)
    assert 0 < pct < 100
    assert 0 < readers.idle_pct(ctx) < 100
    assert readers.host_lead_ms(ctx, span="decide") > 0
