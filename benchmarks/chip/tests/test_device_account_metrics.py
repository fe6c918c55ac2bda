"""Tests of the five per-layer metrics that read the stepline's device
account (PR 54: timeline.device, trace_capture.samples[].device) and of
readers/capture_busy.py. The five are NOT listed in BENCHMARK.json (its 128
per-layer places are taken): they are loaded here as run.py would load them
once listed, through lib/spec's own functions. Run by hand like the others
(no JAX, no process):

    python -m pytest benchmarks/chip/tests -q
"""

import glob
import json
import os
import sys
import types

import pytest

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP_DIR)

from lib import spec  # noqa: E402

WINDOW = ("tpot_device_idle_ms", "device_idle_window_pct",
          "device_idle_dispatch_pct", "device_idle_no_work_pct")
AGREEMENT = "device_busy_agreement_pct"
LAYERS = {"tpot_device_idle_ms": "engine step",
          "device_idle_window_pct": "device",
          "device_idle_dispatch_pct": "scheduler",
          "device_idle_no_work_pct": "scheduler", AGREEMENT: "device"}


def _read(name, ctx):
    mfile = spec._named_file("layer_metrics", name)
    return spec.load_reader(mfile["reader"]).read(ctx, dict(mfile["args"]))


def _ctx(first, last, trace=None):
    return types.SimpleNamespace(requests=[], window_s=48.0, trace=trace,
                                 snapshots=[(0.0, first), (48.0, last)],
                                 fail_s=120.0)


def _snapshot(loop_wall_s, gaps, idle_by, row_idle_s, busy_s):
    return {"timeline": {
        "loop_wall_s": loop_wall_s, "token_time": {"gaps": gaps},
        "device": {"busy_s": busy_s, "idle_s": sum(idle_by.values()),
                   "idle_by": idle_by, "row_idle_s": row_idle_s}}}


@pytest.mark.parametrize("name", WINDOW + (AGREEMENT,))
def test_each_file_is_what_a_listed_metric_is(name):
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mfile = spec._named_file("layer_metrics", name)
    assert set(mfile) == {"about", "layer", "unit", "better", "source",
                          "moves", "reader", "args"}
    assert mfile["layer"] == LAYERS[name]
    # a layer the benchmark already names, letter for letter
    assert mfile["layer"] in {m["layer"] for m in bench["per_layer"]}
    assert mfile["source"] == "program_span"
    assert mfile["moves"] == "tpot_mean_ms"
    assert mfile["unit"] == ("ms" if name.endswith("_ms") else "%")
    assert mfile["better"] == ("higher" if name == AGREEMENT else "lower")
    assert spec.NAME_RE.match(name) and spec.UNIT_RE.match(mfile["unit"])
    assert callable(spec.load_reader(mfile["reader"]).read)
    # ready and unlisted: the 128 places are taken
    assert name not in {m["name"] for m in bench["per_layer"]}
    assert len(bench["per_layer"]) == 128


def test_the_four_read_the_windows_growth():
    # the lead-in left 10 s of the thread's time, 1 s of it idle; over the
    # window's 50 s the chip idled 12.5 s: 2 s while the host dispatched,
    # 9 s for want of a request (8 waiting, 1 between steps), 1.5 s else;
    # live sequences sat out 6 s of it over 4,000 tokens
    first = _snapshot(10.0, 100, {"dispatch": 0.4, "no_work": 0.5,
                                  "between_steps": 0.0, "detok": 0.1},
                      0.2, 9.0)
    last = _snapshot(60.0, 4100, {"dispatch": 2.4, "no_work": 8.5,
                                  "between_steps": 1.0, "detok": 1.6},
                     6.2, 46.5)
    got = {n: _read(n, _ctx(first, last)) for n in WINDOW}
    assert got == pytest.approx({
        "tpot_device_idle_ms": 1.5, "device_idle_window_pct": 25.0,
        "device_idle_dispatch_pct": 4.0, "device_idle_no_work_pct": 18.0})


def _parent_pairs():
    return sorted(glob.glob(os.path.join(CHIP_DIR, "records",
                                         "pr53-*.stats.json")))


@pytest.mark.parametrize("path", _parent_pairs(), ids=os.path.basename)
def test_all_five_read_zero_on_a_recorded_parent(path):
    """A worker before PR 54 has no timeline.device and no device in its
    capture's samples: run.py cannot leave an owed metric out, so each
    reads 0 there and none raises."""
    with open(path) as f:
        pair = json.load(f)
    assert "device" not in pair["last"]["timeline"]
    ctx = _ctx(pair["first"], pair["last"],
               trace={"window_s": 2.5, "busy_s": 2.0})
    assert {n: _read(n, ctx) for n in WINDOW + (AGREEMENT,)} == {
        n: 0.0 for n in WINDOW + (AGREEMENT,)}


def test_there_are_recorded_parents_to_read():
    assert len(_parent_pairs()) >= 4


def _capture(device_at, every=0.25, upto=3.0):
    """/worker/stats `trace_capture` with the account sampled as
    device_at(t) gives it."""
    n = int(upto / every)
    return {"period_s": every, "samples": [
        {"t_s": i * every, "metrics": {}, "device": device_at(i * every)}
        for i in range(n + 1)]}


def test_capture_busy_holds_the_account_against_the_trace():
    reader = spec.load_reader("capture_busy")
    # busy from 1.1 s of the capture on, at 0.8 of every second; counted
    # from the dispatches' enters a tenth more
    def account(t):
        on = max(0.0, t - 1.1)
        return {"busy_s": 5.0 + 0.8 * on, "busy_enter_s": 5.5 + 0.88 * on,
                "idle_s": 2.0 + t - 0.8 * on}

    last = {"timeline": {}, "trace_capture": _capture(account)}
    # the reduced trace: a slice of 2.5 s (the capture less 0.25 s at
    # each end: seconds 0.25 to 2.75 of it) with 1.3 s of operations
    trace = {"window_s": 2.5, "busy_s": 1.3}
    ctx = _ctx({"timeline": {}}, last, trace)
    # between the samples at 1.0 and 1.25 the account is read linearly:
    # 0.12 at 1.25, so 0.8 * 1.5 + 0.12 = 1.32 by the samples
    assert reader.read(ctx, {}) == pytest.approx(100 * 1.32 / 1.3)
    assert reader.read(ctx, {"counter": "busy_enter_s"}) == pytest.approx(
        100 * 1.1 * 1.32 / 1.3)
    assert _read(AGREEMENT, ctx) == pytest.approx(100 * 1.32 / 1.3)
    # nothing to read: no trace, no capture, samples without the account,
    # a slice in which no operation ran
    ctx.trace = None
    assert reader.read(ctx, {}) is None and _read(AGREEMENT, ctx) == 0.0
    ctx.trace = {"window_s": 2.5, "busy_s": 0.0}
    assert reader.read(ctx, {}) is None
    ctx.trace = trace
    for sample in last["trace_capture"]["samples"]:
        del sample["device"]
    assert reader.read(ctx, {}) is None
    del last["trace_capture"]
    assert reader.read(ctx, {}) is None
    ctx.snapshots = []
    assert reader.read(ctx, {}) is None


def test_capture_busy_over_a_trace_reduced_by_the_harness():
    """A hand-made raw trace through lib/trace_reduce.reduce, as run.py
    hands it to a reader, against an account sampled from the same
    operations: the capture's two marks, then 120 ms of operations every
    150 ms from 1.1 s on."""
    from lib.trace_reduce import reduce

    ms = 10**6
    ops = [(1100 * ms + i * 150 * ms, 120 * ms) for i in range(12)]
    raw = {"names": ["mark", "step"], "planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": (
            [[0, ms // 100, 0]] + [[s0, d, 1] for s0, d in ops]
            + [[3000 * ms, ms // 100, 0]])}]}]}
    device = spec.load_device("TPU v5 lite")
    reduced = reduce(raw, device["trace_plane"], device["trace_ops_line"],
                     margin_s=0.25)
    assert reduced["window_s"] == pytest.approx(2.5, abs=1e-4)

    def busy_at(t):
        ns = t * 1e9
        return sum(min(max(ns - s0, 0), d) for s0, d in ops) / 1e9

    last = {"timeline": {}, "trace_capture": _capture(
        lambda t: {"busy_s": 40.0 + busy_at(t), "idle_s": 0.0}, upto=3.0)}
    ctx = _ctx({"timeline": {}}, last, reduced)
    # the slice's bounds fall on samples (0.25 s, 2.75 s): exact
    assert spec.load_reader("capture_busy").read(ctx, {}) == pytest.approx(
        100.0, abs=0.01)
