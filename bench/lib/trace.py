"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: the device operations as intervals, their busy union over a window,
self time per operation name, and the idle gaps between operations, each
labelled with the harness span that was open on the host at the time.

``jax.profiler.ProfileData`` reads the file with nothing but JAX.  Device
planes are named ``/device:TPU:<i>``; their operations sit on the line
``XLA Ops``.  The harness's spans (``jax.profiler.TraceAnnotation``) are
events on the host plane ``/host:CPU``; both share the trace's clock.
"""
from __future__ import annotations

import collections
import glob
import os

SPANS = ("build_events", "decide", "readback", "plan_and_run")
OPS_LINE = "XLA Ops"


def find(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str) -> dict:
    """``{"ops": {device: [(op, start_ns, end_ns), ...]},
    "spans": [(name, start_ns, end_ns), ...]}``; an op is named by its HLO
    instruction (``%maiz_topk_pallas.1``), not its whole text."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            evs = [(e.name.split(" = ")[0], e.start_ns,
                    e.start_ns + e.duration_ns)
                   for ln in lines for e in ln.events]
            if evs:
                ops[plane.name] = evs
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in SPANS:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    spans.sort(key=lambda s: s[1])
    return {"ops": ops, "spans": spans}


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def label_at(spans, t: float) -> str:
    """Innermost (latest-starting) harness span open at time ``t``."""
    best = None
    for name, s, e in spans:
        if s > t:
            break
        if e >= t:
            best = name
    return best or "between_calls"


def self_times(evs):
    """Per-name self time (duration less nested children) of the events
    of one line; device operations nest (a loop holds its body)."""
    out = collections.Counter()
    stack = []                  # [end, name, child time, duration]
    for name, s, e in sorted(evs, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            top = stack.pop()
            out[top[1]] += top[3] - top[2]
        if stack:
            stack[-1][2] += e - s
        stack.append([e, name, 0.0, e - s])
    for top in stack:
        out[top[1]] += top[3] - top[2]
    return out


def reduce(tr: dict, window=None) -> dict:
    """Busy union, per-op self time and labelled idle gaps inside
    ``window`` (default: from the first harness span's start to the last
    one's end).  Times in ns.  Busy time, per-op self time and op counts
    are averaged over the device planes (a mesh's chips each run their
    share of every launch); the idle gaps are each plane's own."""
    spans = tr["spans"]
    if window is None:
        if not spans:
            raise ValueError("trace holds no harness span")
        window = (spans[0][1], max(s[2] for s in spans))
    w0, w1 = window
    per_op = collections.Counter()
    count = collections.Counter()
    busy, gaps = [], []
    for evs in tr["ops"].values():
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in evs
                  if e > w0 and s < w1]
        per_op.update(self_times(inside))
        count.update(n for n, _, _ in inside)
        merged = _merge([(s, e) for _, s, e in inside])
        busy.append(sum(e - s for s, e in merged))
        t = w0
        for s, e in merged + [[w1, w1]]:
            if s > t:
                gaps.append((label_at(spans, (t + s) / 2), s - t))
            t = max(t, e)
    n_dev = max(len(tr["ops"]), 1)
    return {"window_ns": w1 - w0, "busy_ns": sum(busy) / n_dev,
            "per_op_ns": collections.Counter(
                {k: v / n_dev for k, v in per_op.items()}),
            "op_count": collections.Counter(
                {k: v / n_dev for k, v in count.items()}),
            "gaps": sorted(gaps, key=lambda g: -g[1]), "window": window}


def longest_op_in(tr: dict, t0: float, t1: float):
    """Start of the longest device operation that starts in [t0, t1]."""
    best = None
    for evs in tr["ops"].values():
        for _, s, e in evs:
            if t0 <= s <= t1 and (best is None or e - s > best[1] - best[0]):
                best = (s, e)
    return None if best is None else best[0]
