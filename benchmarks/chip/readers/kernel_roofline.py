"""A kernel's share of its roofline: the least time the chip could take for
what the kernel was asked, over the time it took.

What it took comes from the traced slice: the own device time of the
operations matching `pattern`. What it was asked comes from /worker/stats
counters through kernel_costs/<cost>.py, which turns them into operations
and bytes; the least time is the larger of operations over the peak and
bytes over the memory bandwidth (devices.json, by the device_kind the worker
reports). The counters are read over the 1 Hz snapshots that BRACKET the
slice (run.py starts it at (window - 3 s) / 2; the device plane says how long
it was): the last snapshot before its start and the first after its end, a
second or so wider than the slice, so each side is divided by its own span.
Under Poisson arrivals a slice is no sample of the whole window: over the
whole window the share could land anywhere, a slice in an arrival gap far
over 100%, with no kernel changed.

args: {"pattern": ..., "cost": "grouped_expert_matmul", ...the cost file's
own sizes}. Where the counters do not move inside the bracket they are read
over the whole window (first to last snapshot), as before PR 27's repair.
No counter growth at all (a program that lacks the counters), no matching
operation, or no trace gives no value."""

import importlib.util
import os

from lib.spec import CHIP_DIR, load_device
from lib.trace_reduce import time_matching


def _cost_module(name: str):
    path = os.path.join(CHIP_DIR, "kernel_costs", name + ".py")
    spec = importlib.util.spec_from_file_location(f"kernel_cost_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _at(stats: dict, path: str) -> float:
    node = stats
    for key in path.split("."):
        if not isinstance(node, dict) or key not in node:
            return 0.0
        node = node[key]
    return float(node)


# run.py's capture_trace: the slice starts at (window - TRACE_S) / 2 on the
# clock the snapshots are stamped with; the profiler takes a moment to start
TRACE_S = 3.0
START_SLACK_S = 0.5


def bracket(snapshots: list, window_s: float, slice_s: float):
    """((t0, stats0), (t1, stats1)): the snapshots nearest outside the
    traced slice; the window's first and last where none lies outside."""
    start = max(0.0, (window_s - TRACE_S) / 2.0)
    end = start + START_SLACK_S + slice_s
    before = [s for s in snapshots if s[0] <= start]
    after = [s for s in snapshots if s[0] >= end]
    return (before[-1] if before else snapshots[0],
            after[0] if after else snapshots[-1])


def read(ctx, args):
    if ctx.trace is None or len(ctx.snapshots) < 2:
        return None
    kernel_s = time_matching(ctx.trace, args["pattern"])
    if kernel_s <= 0:
        return None
    cost = _cost_module(args["cost"])
    # around the slice; the whole window where the counters did not move
    # there (the harness cannot leave an owed metric out: PERF.md section 7)
    for (t0, first), (t1, last) in (
            bracket(ctx.snapshots, ctx.window_s, ctx.trace["window_s"]),
            (ctx.snapshots[0], ctx.snapshots[-1])):
        asked = cost.from_counters(
            lambda path: _at(last, path) - _at(first, path), args)
        if asked["ops"] > 0 and t1 > t0:
            break
    else:
        return None
    device = args.get("device") or load_device(last["device_kind"])
    least_s = max(asked["ops"] / device[asked["peak"]],
                  asked["bytes"] / device["hbm_bytes_per_s"])
    return 100.0 * (least_s / (t1 - t0)) / (kernel_s / ctx.trace["window_s"])
