"""What the program itself names, read back for the per-layer metrics:

- its host spans (``jax.profiler.TraceAnnotation`` in the simulator's
  compiled drivers: ``PROGRAM_SPANS``), on the host plane beside the
  harness's own spans;
- its device layers (``jax.named_scope``: ``SCOPES``), which live only in
  the ``op_name`` metadata of the optimized HLO.  The scope table maps
  each instruction of a program's HLO text to its innermost scope; the
  trace's operations join it through their ``XLA Modules`` event (module
  name) and instruction name;
- its walk counters (``Placement.walk_counts``, ``SimResult.walk_counts``
  and ``sweep_rounds``), summed over the traced calls by running those
  calls again after the window, on the state the driver kept, and only
  where the rerun gives back the same placements and sweep counts.

A reader gets these through ``walk(ctx)`` and ``report(ctx)``, which keep
what they found on ``ctx`` for the next reader.  Where the program lacks a
span, a scope or a counter (an older program), they find nothing: the
value is None and nothing raises.  ``report`` prints the layer breakdown
of the traced window to standard error; the harness has no hook after the
window but the readers, so each reader below asks for it first and the
first one of a run prints it.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import sys
import traceback

import numpy as np

from lib import trace

PROGRAM_SPANS = ("plan_build", "dispatch", "device_wait", "readback",
                 "result_assembly")
# the harness span that trace.SPANS leaves out: it labels gaps, it does
# not widen the window
HARNESS_EXTRA = ("forecast",)
SCOPES = ("forecast", "epoch_pre", "epoch_post", "placement_walk",
          "rank_sweep", "router")
MODULES_LINE = "XLA Modules"
UNSCOPED, NO_TABLE = "(no scope)", "(no table)"
# the exchange between chips: its own bucket, whatever scope it sits in
COLLECTIVE = "(collective)"
COLLECTIVE_OPS = ("all-reduce", "all-gather", "all-to-all",
                  "collective-permute", "collective-broadcast",
                  "reduce-scatter")
TRACE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".bench_cache", "trace")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_WRAPPED = re.compile(r"\w+\((.*)\)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+) .*\{\s*$")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=(%[\w.\-]+)")
_PARTITIONS = re.compile(r"^HloModule [^\n]*\bnum_partitions=(\d+)", re.M)
# an instruction's opcode: the first word after its shape that opens "("
_OPCODE = re.compile(r" = .*? ([a-z][\w\-]*)\(")


def say(*a):
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the trace, with what trace.load leaves out
# ---------------------------------------------------------------------------


def load(path: str) -> dict:
    """``trace.load(path)`` (its keys and values unchanged) plus
    ``"extra_spans"``: the program's spans and the harness's ``forecast``
    span that ``trace.SPANS`` leaves out (it already takes ``readback``,
    a name the harness and the program share), ``[(name, start_ns,
    end_ns)]``; and ``"modules"``: the
    ``XLA Modules`` events per device, ``{device: [(module, start_ns,
    end_ns)]}``, a module named as the trace names it
    (``jit_place_events(<program id>)``)."""
    from jax.profiler import ProfileData
    raw = trace.load(path)
    names = [n for n in PROGRAM_SPANS + HARNESS_EXTRA
             if n not in trace.SPANS]
    extra, modules = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for ln in plane.lines if ln.name == MODULES_LINE
                   for e in ln.events]
            if evs:
                modules[plane.name] = sorted(evs, key=lambda m: m[1])
        elif plane.name == "/host:CPU":
            extra += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for ln in plane.lines for e in ln.events
                      if e.name in names]
    extra.sort(key=lambda s: s[1])
    return dict(raw, extra_spans=extra, modules=modules)


def find(raw: dict, root: str = TRACE_ROOT):
    """The ``load`` of the trace file whose harness spans are ``raw``'s
    (the run's own trace, among every cell's under ``root``), or None."""
    if not raw.get("spans"):
        return None
    paths = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime,
                   reverse=True)
    for p in paths:
        got = load(p)
        if got["spans"] == raw["spans"]:
            return got
    return None


def labelled_gaps(ext: dict, window) -> list:
    """Idle gaps of ``window``, each labelled with the innermost span open
    on the host: harness, ``forecast`` or program.  Largest first."""
    spans = sorted(ext["spans"] + ext["extra_spans"], key=lambda s: s[1])
    return trace.reduce(dict(ops=ext["ops"], spans=spans),
                        window=window)["gaps"]


def spans_per_call(ext: dict, name: str, call: str = "plan_and_run"):
    """Total time (ns) of the spans ``name`` inside each harness span
    ``call``, one entry per call."""
    spans = ext["spans"] + ext["extra_spans"]
    return [sum(b - a for n, a, b in spans
                if n == name and a >= s and b <= e)
            for c, s, e in ext["spans"] if c == call]


# ---------------------------------------------------------------------------
# named scopes: the table from a program's HLO text, joined to the trace
# ---------------------------------------------------------------------------


def innermost_scope(op_name: str):
    """The last of ``SCOPES`` on an ``op_name`` path.  A transform wraps
    the scope it was applied to (``vmap(epoch_pre)``): unwrap it."""
    for part in reversed(op_name.split("/")):
        while (m := _WRAPPED.fullmatch(part)):
            part = m.group(1)
        if part in SCOPES:
            return part
    return None


def scope_table(text: str):
    """``(module name, {instruction: innermost scope or None})`` of one
    optimized HLO module's text.  A collective (``COLLECTIVE_OPS``, their
    ``-start`` and ``-done``) maps to ``COLLECTIVE``, whatever its
    metadata.  Any other instruction the compiler made without metadata
    (a layout fusion, say) takes the scope most of the instructions of
    the computations it calls carry, or ``COLLECTIVE`` where those carry
    none and hold a collective (an async wrapper).  In a program laid out
    over several devices (``num_partitions`` above 1) the partitioner
    adds more such instructions (the buffers a collective fills, each
    shard's slices): one that calls nothing scoped takes the scope most
    instructions of its own computation carry."""
    m = _MODULE.search(text)
    p = _PARTITIONS.search(text)
    partitioned = p is not None and int(p.group(1)) > 1
    table, calls, comp_of, exchanges = {}, {}, {}, set()
    comp, in_comp = None, collections.defaultdict(collections.Counter)
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        i = _INSTR.match(line)
        if not i:
            continue
        op = _OPCODE.search(line)
        o = _OP_NAME.search(line)
        if op and op.group(1).removesuffix("-start").removesuffix(
                "-done") in COLLECTIVE_OPS:
            table[i.group(1)] = COLLECTIVE
            exchanges.add(comp)
        elif o:
            table[i.group(1)] = scope = innermost_scope(o.group(1))
            if scope:
                in_comp[comp][scope] += 1
        else:
            table[i.group(1)] = None
            calls[i.group(1)] = _CALLS.findall(line)
            comp_of[i.group(1)] = comp
    for name, called in calls.items():
        votes = sum((in_comp[c] for c in called), collections.Counter())
        if votes:
            table[name] = votes.most_common(1)[0][0]
        elif any(c in exchanges for c in called):    # an async wrapper
            table[name] = COLLECTIVE
        elif partitioned and in_comp[comp_of[name]]:
            table[name] = in_comp[comp_of[name]].most_common(1)[0][0]
    return (m.group(1) if m else None), table


def scope_times(ext: dict, window, tables) -> dict:
    """Device self time (ns) in ``window`` by innermost named scope,
    averaged over the device planes (as ``trace.reduce``'s busy time).

    Each operation belongs to the ``XLA Modules`` event it starts in; of
    the ``tables`` for that module's name, the one that holds the most of
    the event's operations maps it.  Keys: each scope, ``COLLECTIVE``
    (the exchange between chips), ``UNSCOPED`` (an instruction of a table
    with no scope) and ``NO_TABLE`` (no table for
    the module or the instruction); ``"by_module"`` splits the last by
    module name, ``"unscoped_ops"`` the one before by instruction."""
    w0, w1 = window
    by_name = collections.defaultdict(list)
    for name, table in tables:
        by_name[name].append(table)
    out = collections.Counter()
    no_table, unscoped = collections.Counter(), collections.Counter()
    for dev, evs in ext["ops"].items():
        mods = ext["modules"].get(dev, [])
        starts = [m[1] for m in mods]
        keyed = []
        for op, s, e in evs:
            if e <= w0 or s >= w1:
                continue
            k = bisect.bisect_right(starts, s) - 1
            mod = mods[k][0] if k >= 0 and s < mods[k][2] else None
            keyed.append(((mod, op), max(s, w0), min(e, w1)))
        ops_of = collections.defaultdict(set)
        for (mod, op), _, _ in keyed:
            ops_of[mod].add(op)
        pick = {}
        for mod, ops in ops_of.items():
            cands = by_name.get(mod.split("(")[0] if mod else None, [])
            pick[mod] = max(cands, key=lambda t: len(ops & t.keys()),
                            default=None)
        for (mod, op), ns in trace.self_times(keyed).items():
            table = pick[mod]
            if table is None or op not in table:
                out[NO_TABLE] += ns
                no_table[(mod or "?").split("(")[0]] += ns
            else:
                out[table[op] or UNSCOPED] += ns
                if table[op] is None:
                    unscoped[op] += ns
    n_dev = max(len(ext["ops"]), 1)
    res = {k: v / n_dev for k, v in out.items()}
    res["by_module"] = {k: v / n_dev for k, v in no_table.items()}
    res["unscoped_ops"] = {k: v / n_dev for k, v in unscoped.items()}
    return res


# ---------------------------------------------------------------------------
# the programs a cell ran
# ---------------------------------------------------------------------------


def _traced_records(ctx) -> list:
    n = int(ctx.driver.tr["trace_calls"])
    return [r for r in ctx.records[:n] if not r.get("failed")]


def _decide_inputs(drv, cap, r, sig):
    import jax.numpy as jnp
    n = r["dem"].size
    d = np.zeros(drv.pad, np.int32)
    v = np.full(drv.pad, -1, np.int32)
    d[:n], v[:n] = r["dem"], r["nod"]
    now, fc = sig
    fleet = drv.Fleet(capacity=jnp.asarray(cap.astype(np.int32)),
                      **dict(drv._dev, ci_now=now, ci_forecast=fc))
    return fleet, dict(demands=jnp.asarray(d), nodes=jnp.asarray(v),
                       n_events=jnp.asarray(np.int32(n)))


def _signal(drv, hour):
    return drv._signal(drv._traces, drv._ridx, np.int32(hour))


def _program_texts(ctx) -> list:
    """Optimized HLO text of the programs of the traced calls."""
    drv = ctx.driver
    recs = _traced_records(ctx)
    if not recs:
        return []
    if drv.tr["driver"] == "decide":
        if not hasattr(drv.entry, "lower"):     # not the jitted program
            return []
        r = recs[0]
        sig = _signal(drv, r["hour"])
        fleet, ev = _decide_inputs(drv, drv.cap0, r, sig)
        return [drv.entry.lower(fleet, **ev, **drv.kw).compile().as_text(),
                drv._signal.lower(drv._traces, drv._ridx,
                                  np.int32(r["hour"])).compile().as_text()]
    texts = getattr(drv.sim, "program_texts", None)
    if texts is None:               # a program that cannot say
        return []
    ensemble = drv.tr["entry"] == "ensemble"
    out = []
    for i in sorted({r["set"] for r in recs}):
        for t in texts(drv.sets[i][0], ensemble=ensemble, pad_plan=True,
                       shard=drv.shard):
            if t not in out:
                out.append(t)
    return out


# ---------------------------------------------------------------------------
# the walk counters of the traced calls
# ---------------------------------------------------------------------------


def _decide_walk(drv, recs):
    cap = drv.cap0.copy()
    counts = np.zeros(4, np.int64)
    sig = {}
    for r in recs:
        if r["hour"] not in sig:
            sig[r["hour"]] = _signal(drv, r["hour"])
        fleet, ev = _decide_inputs(drv, cap, r, sig[r["hour"]])
        p = drv.entry(fleet, **ev, **drv.kw)
        wc = getattr(p, "walk_counts", None)
        if wc is None:
            return None
        n = r["dem"].size
        out = np.asarray(p.node)[:n].astype(np.int64)
        if not (np.array_equal(out, r["out"])
                and int(p.n_sweeps) == r["sweeps"]):
            say(f"layers: decision {r['k']} placed otherwise when run "
                f"again; no walk counts")
            return None
        counts += np.asarray(wc, np.int64)
        ok = out >= 0
        np.add.at(cap, out[ok], -r["dem"][ok])
    return dict(counts=counts, lane_sweeps=None, lane_rounds=None)


def _sim_walk(drv, recs):
    if not hasattr(getattr(drv.sim, "SimResult", None), "walk_counts"):
        return None                 # an older program: no rerun for nothing
    counts = np.zeros(4, np.int64)
    sweeps, rounds = 0, 0
    for r in recs:
        for x, kept in zip(drv._run(drv.sets[r["set"]]), r["res"]):
            wc = getattr(x, "walk_counts", None)
            if wc is None:
                return None
            if not (x.rank_sweeps == kept["sweeps"] and np.array_equal(
                    x.first_node, kept["first_node"])):
                say("layers: a lane placed otherwise when run again; no "
                    "walk counts")
                return None
            counts += np.asarray(wc, np.int64)
            sweeps += x.rank_sweeps
            sr = getattr(x, "sweep_rounds", None)
            rounds = None if sr is None or rounds is None else rounds + sr
    return dict(counts=counts, lane_sweeps=sweeps, lane_rounds=rounds)


def walk(ctx):
    """Walk counters of the traced calls: ``counts`` (``WALK_COUNTS`` of
    the program, summed over calls and lanes), and the simulator's
    ``lane_sweeps`` and ``lane_rounds`` (each lane's sweeps, and its
    bucket's batched sweep rounds, summed over lanes; rounds None outside
    the batched ensemble).  None where the program has no such
    counters."""
    if not hasattr(ctx, "walk"):
        ctx.walk = _boundary("walk counters", _walk, ctx)
    return ctx.walk


def _walk(ctx):
    recs = _traced_records(ctx)
    if not recs:
        return None
    if ctx.driver.tr["driver"] == "decide":
        return _decide_walk(ctx.driver, recs)
    return _sim_walk(ctx.driver, recs)


# ---------------------------------------------------------------------------
# the layer report of a traced run
# ---------------------------------------------------------------------------


def report(ctx):
    """The traced window by layer: ``ext`` (the ``load`` of the run's
    trace), ``scopes`` (``scope_times``; None without tables), ``busy_ns``
    and ``gaps`` (``labelled_gaps``).  Printed to standard error once; None
    where the run's trace cannot be found."""
    if not hasattr(ctx, "layer_report"):
        ctx.layer_report = _boundary("layer report", _report, ctx)
    return ctx.layer_report


def _report(ctx):
    if ctx.trace is None or ctx.trace_raw is None:
        return None
    ext = find(ctx.trace_raw)
    if ext is None:
        say("layers: the run's trace file was not found")
        return None
    window = ctx.trace["window"]
    tables = [scope_table(t) for t in _program_texts(ctx)]
    scopes = scope_times(ext, window, tables) if tables else None
    rep = dict(ext=ext, scopes=scopes, busy_ns=ctx.trace["busy_ns"],
               gaps=labelled_gaps(ext, window))
    _print(rep, [name for name, _ in tables])
    return rep


def _print(rep, modules):
    busy = rep["busy_ns"]
    say("layers: idle gaps by innermost span (ms): " + ", ".join(
        f"{n} {v * 1e-6:.3f}" for n, v in rep["gaps"][:12]))
    if rep["scopes"] is None:
        say("layers: no scope table (the program gives no HLO text)")
        return
    sc = rep["scopes"]
    share = {k: v for k, v in sc.items()
             if k not in ("by_module", "unscoped_ops")}
    say(f"layers: scope tables of {modules}; device self time by scope "
        "(% of busy): " + ", ".join(
            f"{k} {100 * v / busy:.2f}" for k, v in
            sorted(share.items(), key=lambda kv: -kv[1]) if busy > 0))
    say(f"layers: no table covered {100 * sc.get(NO_TABLE, 0) / busy:.2f}%"
        " of busy time" + (": " + ", ".join(
            f"{m} {100 * v / busy:.2f}%" for m, v in
            sorted(sc["by_module"].items(), key=lambda kv: -kv[1]))
            if sc["by_module"] else "") if busy > 0 else "")
    say("layers: largest unscoped ops (ms): " + ", ".join(
        f"{op} {v * 1e-6:.3f}" for op, v in
        sorted(sc["unscoped_ops"].items(), key=lambda kv: -kv[1])[:8]))


def _boundary(what, fn, ctx):
    """A reader's helper fails alone: the run and the other readers go
    on, the metric is left out, and the traceback goes to stderr."""
    try:
        return fn(ctx)
    except Exception:   # noqa: BLE001 — any fault here must not end the run
        say(f"layers: {what} failed; the metrics that read it are left out")
        traceback.print_exc()
        return None


# ---------------------------------------------------------------------------
# the readers' arithmetic
# ---------------------------------------------------------------------------


def _reader(fn):
    def read(ctx):
        report(ctx)
        return fn(ctx)
    read.__doc__ = fn.__doc__
    return read


@_reader
def hit_pct(ctx):
    """100 x arrivals placed from the shortlist / (those + sweeps)."""
    w = walk(ctx)
    done = 0 if w is None else int(w["counts"].sum())
    return 100.0 * int(w["counts"][0]) / done if done else None


@_reader
def no_room_pct(ctx):
    """100 x sweeps because no shortlist node had room / sweeps."""
    w = walk(ctx)
    sweeps = 0 if w is None else int(w["counts"][1:].sum())
    return 100.0 * int(w["counts"][2]) / sweeps if sweeps else None


@_reader
def lane_use_pct(ctx):
    """100 x lane sweeps / lane slots of the batched sweep launches."""
    w = walk(ctx)
    if w is None or not w["lane_rounds"]:
        return None
    return 100.0 * w["lane_sweeps"] / w["lane_rounds"]


@_reader
def plan_build_ms(ctx):
    """``plan_build`` span time per traced call, averaged over calls."""
    rep = report(ctx)
    per_call = [] if rep is None else spans_per_call(rep["ext"],
                                                     "plan_build")
    if not any(per_call):
        return None
    return sum(per_call) * 1e-6 / len(per_call)


@_reader
def walk_device_pct(ctx):
    """Device self time whose innermost scope is ``placement_walk`` (the
    walk outside ``rank_sweep``), over device busy time."""
    rep = report(ctx)
    if rep is None or rep["scopes"] is None or rep["busy_ns"] <= 0:
        return None
    return 100.0 * rep["scopes"].get("placement_walk", 0) / rep["busy_ns"]
