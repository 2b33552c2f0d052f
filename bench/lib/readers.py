"""Shared arithmetic of the per-layer metric readers (``bench/metrics``).
A reader returns None where it finds nothing to read."""
from __future__ import annotations

import re

from lib import trace
from lib.kernel_cost import sweep_bytes

SWEEP_KERNEL = re.compile(r"maiz_(topk|lohi)_pallas")


def idle_pct(ctx):
    red = ctx.trace
    if red is None or red["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_ns"] / red["window_ns"])


def sweep_roofline_pct(ctx):
    """Least HBM time of the sweeps that ran over their device time.

    Launches and their time are averaged over the trace's device planes
    (as ``busy_ns`` is), and ``ctx.sweep_shape`` is what one launch reads
    on one device, so on a mesh this is the chips' mean share, each
    weighted by its kernel time."""
    red = ctx.trace
    if red is None or ctx.peak is None:
        return None
    names = [n for n in red["per_op_ns"] if SWEEP_KERNEL.search(n)]
    kernel_ns = sum(red["per_op_ns"][n] for n in names)
    count = sum(red["op_count"][n] for n in names)
    if not count or kernel_ns <= 0:
        return None
    shape = ctx.sweep_shape
    least_s = count * sweep_bytes(**shape) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (kernel_ns * 1e-9)


def host_lead_ms(ctx, span="plan_and_run"):
    """Mean time from a call's span start to the start of the longest
    device operation inside it: the call's main program, which waits for
    the host to build its plan and inputs and to dispatch it."""
    if ctx.trace_raw is None:
        return None
    leads = []
    for name, s, e in ctx.trace_raw["spans"]:
        if name != span:
            continue
        main = trace.longest_op_in(ctx.trace_raw, s, e)
        if main is not None:
            leads.append((main - s) * 1e-6)
    return sum(leads) / len(leads) if leads else None
