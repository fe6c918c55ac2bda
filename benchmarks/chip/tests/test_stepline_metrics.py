"""Tests of what PR 25 added beside the benchmark: the reader of the first
token's time outside the worker, lib/gaps.py, and the seven per-layer
metrics that read the stepline's loop account and the first token's stages.
Run by hand like the others (they import no JAX and start no process):

    python -m pytest benchmarks/chip/tests -q

The seven metrics are NOT in BENCHMARK.json: run.py owes every listed metric
on both sides of a check, and the parent's program has no such counter
(PERF.md section 7). records/proposed-per_layer-pr25.json holds their
entries; `_bench_with_proposed` is BENCHMARK.json with them appended.
"""

import json
import os
import sys
import types

import pytest

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP_DIR)

from lib import gaps, spec, stats, trace_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
TPU = ("^/device:TPU:\\d+$", "^XLA Ops$")
PROPOSED = os.path.join(CHIP_DIR, "records", "proposed-per_layer-pr25.json")
SEVEN = {"drained_dispatch_pct.chat", "drained_host_pct.chat",
         "drained_no_work_pct.chat", "queue_wait_mean_ms.chat",
         "prefill_mean_ms.chat", "worker_http_mean_ms.chat",
         "frontend_hop_mean_ms.chat"}


def _bench_with_proposed(tmp_path) -> str:
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(PROPOSED) as f:
        bench["per_layer"] += json.load(f)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


# ------------------------------------------------- the seven new metrics --


def _snapshot(loop_wall, drained, first_token, steps=0, wall=0.0):
    return {"timeline": {"loop_wall_s": loop_wall, "wall_s": wall,
                         "steps": steps,
                         "drained": {"by": drained,
                                     "total_s": sum(drained.values())}},
            "metrics": {"first_token": first_token}}


def _requests(firsts, sent_before=0.5):
    out = []
    for i, first in enumerate(firsts):
        r = stats.Request(i, "window", first - sent_before, 10, 10)
        r.sent, r.first, r.last, r.status = first - sent_before, first, \
            first + 1.0, 200
        out.append(r)
    return out


def test_cells_owe_the_seven_new_metrics_once_listed(tmp_path):
    path = _bench_with_proposed(tmp_path)
    with open(path) as f:
        bench = json.load(f)
    assert {m["name"] for m in bench["per_layer"][-7:]} == SEVEN
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"][:-7]}
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], path)
        owed = cell.owed(True)
        assert SEVEN <= set(owed)
        for m in cell.per_layer:
            if m.name in SEVEN:
                entry = by_name[m.name]
                assert entry["better"] == "lower"
                assert entry["source"] == "program_span"
                assert entry["layer"] in layers  # a layer PERF.md names
                assert w["name"] in entry["workloads"]
    # and as BENCHMARK.json stands, no cell owes them: a run of the parent
    # under this PR's benchmark files must still print its line
    assert not SEVEN & set(spec.load_cell(
        bench["workloads"][0]["name"]).owed(True))


def test_the_seven_read_deltas_and_give_nothing_where_the_program_has_none(
        tmp_path):
    cell = spec.load_cell("qwen7b-chat-r80", _bench_with_proposed(tmp_path))
    first = _snapshot(
        10.0, {"dispatch": 0.5, "admit": 0.1, "page_alloc": 0.0,
               "detok": 0.2, "bank": 0.0, "untracked": 0.1,
               "between_steps": 0.1, "no_work": 2.0},
        {"count": 10, "submit_s": 0.01, "queue_s": 1.0, "prefill_s": 3.0,
         "emit_s": 0.01, "ttft_s": 4.02})
    last = _snapshot(
        58.0, {"dispatch": 2.9, "admit": 0.58, "page_alloc": 0.24,
               "detok": 0.68, "bank": 0.0, "untracked": 0.34,
               "between_steps": 0.58, "no_work": 6.8},
        {"count": 110, "submit_s": 0.11, "queue_s": 11.0, "prefill_s": 43.0,
         "emit_s": 0.21, "ttft_s": 54.32})
    # 100 first tokens inside the snapshots, each 520 ms after it was
    # sent, one before and one after them
    requests = _requests([0.1 + 0.4799 * i for i in range(100)]
                         + [-1.0, 48.5], sent_before=0.52)
    ctx = types.SimpleNamespace(
        requests=requests, window_s=48.0, snapshots=[(0.0, first),
                                                     (48.0, last)],
        trace=None, fail_s=120.0)
    got = {m.name: m.reader.read(ctx, m.args) for m in cell.per_layer
           if m.name in SEVEN}
    assert got == pytest.approx({
        "drained_dispatch_pct.chat": 100 * 2.4 / 48.0,
        "drained_host_pct.chat": 100 * 1.92 / 48.0,
        "drained_no_work_pct.chat": 100 * 4.8 / 48.0,
        "queue_wait_mean_ms.chat": 100.0,
        "prefill_mean_ms.chat": 400.0,
        "worker_http_mean_ms.chat": 3.0,
        # the client saw 520 ms from `sent`, the worker 503 of them
        "frontend_hop_mean_ms.chat": 520.0 - 503.0})
    assert (got["drained_dispatch_pct.chat"] + got["drained_host_pct.chat"]
            + got["drained_no_work_pct.chat"]) <= 100.0
    # the parent's program: /worker/stats has no such counter. Every one
    # of the seven gives no value and none raises
    old = {"timeline": {"wall_s": 1.0, "steps": 3}, "metrics": {}}
    ctx.snapshots = [(0.0, old), (48.0, old)]
    assert {m.reader.read(ctx, m.args) for m in cell.per_layer
            if m.name in SEVEN} == {None}


@pytest.mark.parametrize("case", ["one_snapshot", "no_first_token",
                                  "count_stood_still", "failed_requests"])
def test_ttft_outside_worker_gives_nothing_rather_than_a_guess(case):
    reader = spec.load_reader("ttft_outside_worker")
    args = {"sum": "metrics.first_token.ttft_s",
            "count": "metrics.first_token.count"}
    a = {"metrics": {"first_token": {"ttft_s": 1.0, "count": 2}}}
    b = {"metrics": {"first_token": {"ttft_s": 3.0, "count": 6}}}
    ctx = types.SimpleNamespace(requests=_requests([1.0, 2.0, 3.0, 4.0]),
                                snapshots=[(0.0, a), (10.0, b)])
    assert reader.read(ctx, args) == pytest.approx(0.0)  # 500 ms each side
    if case == "one_snapshot":
        ctx.snapshots = ctx.snapshots[:1]
    elif case == "no_first_token":
        ctx.requests = _requests([11.0, 12.0])
    elif case == "count_stood_still":
        ctx.snapshots = [(0.0, a), (10.0, a)]
    else:
        for r in ctx.requests:
            r.status = 503
    assert reader.read(ctx, args) is None


# ----------------------------------------------------------- lib/gaps.py --


def _wide(ops, stepline, names=("op", "stepline/dispatch",
                                "stepline/no_work", "stepline/device_wait",
                                "stepline/step", "$python")):
    ms = 10**6
    return {"names": list(names), "planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "engine-scheduler", "events": [
                [int(s * ms), int(d * ms), i] for s, d, i in stepline]},
            {"name": "other", "events": [[0, 100 * ms, 5]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                [int(s * ms), int(d * ms), 0] for s, d in ops]},
            {"name": "XLA Modules", "events": [[0, 100 * ms, 0]]}]}]}


def test_gaps_are_put_down_to_the_phase_that_covers_most_of_them():
    # ops (ms): busy 0-10, 10-20 (touching), 26-40, 70-100; slice 0..100
    ops = [(0, 10), (10, 10), (26, 14), (70, 30)]
    # the step's annotation spans everything and must not count; the gap
    # 20-26 is 4 ms of dispatch + 1 of device_wait + 1 nobody annotated;
    # the gap 40-70 is 28 ms of no_work and 2 ms of dispatch
    stepline = [(0, 100, 4), (20, 4, 1), (25, 10, 3), (40, 28, 2),
                (68, 2, 1)]
    r = gaps.idle_gaps(_wide(ops, stepline), *TPU, margin_s=0.0)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["gaps"] == 2 and r["idle_s"] == pytest.approx(0.036)
    long, short = r["longest"]
    assert (long["phase"], long["gap_ms"], long["at_ms"]) == (
        "no_work", pytest.approx(30.0), pytest.approx(40.0))
    assert long["by_ms"] == pytest.approx({"no_work": 28.0, "dispatch": 2.0})
    assert short["phase"] == "dispatch"
    assert short["by_ms"] == pytest.approx(
        {"dispatch": 4.0, "device_wait": 1.0, gaps.UNCOVERED: 1.0})
    assert r["idle_by_phase_s"] == pytest.approx(
        {"no_work": 0.028, "dispatch": 0.006, "device_wait": 0.001,
         gaps.UNCOVERED: 0.001})
    # idle + busy = the slice, with the reducer's own busy time
    busy = trace_reduce.reduce(_wide(ops, stepline), *TPU,
                               margin_s=0.0)["busy_s"]
    assert busy + r["idle_s"] == pytest.approx(r["window_s"])
    # the margins move the slice in, and a gap is clipped to it
    r = gaps.idle_gaps(_wide(ops, stepline), *TPU, margin_s=0.045, top=1)
    assert r["window_s"] == pytest.approx(0.01)
    assert [(g["at_ms"], g["gap_ms"]) for g in r["longest"]] == [
        (pytest.approx(0.0), pytest.approx(10.0))]
    with pytest.raises(trace_reduce.TraceError):
        gaps.idle_gaps(_wide([], stepline), *TPU)


def test_a_trace_without_annotations_still_gives_its_gaps():
    """The parent's program makes no annotation: every gap is uncovered,
    and nothing raises."""
    r = gaps.idle_gaps(_wide([(0, 10), (30, 10)], []), *TPU, margin_s=0.0)
    assert r["stepline_events"] == 0
    assert [g["phase"] for g in r["longest"]] == [gaps.UNCOVERED]


def test_recorded_gaps_lie_under_the_phases_the_stepline_annotated():
    """small_gaps_trace.json: 0.23 s of a kept trace of qwen7b-chat-r80
    on the chip (PR 25; cut by `lib/gaps.py --cut`: the operations line
    of the device plane and the `stepline/*` events of the host plane).
    Two mixed steps: the device idles through the loop's fan-out and then
    through the next step's dispatch, and the annotations, written by the
    host on the profiler's clock, cover those gaps to the microsecond."""
    with open(os.path.join(HERE, "small_gaps_trace.json")) as f:
        small = json.load(f)
    host = [p for p in small["planes"] if p["name"] == "/host:CPU"]
    assert [ln["name"] for ln in host[0]["lines"]] == ["python3"]
    r = gaps.idle_gaps(small, *TPU, margin_s=0.0, top=2)
    assert r["stepline_events"] == 16 and r["gaps"] == 77
    busy = trace_reduce.reduce(small, *TPU, margin_s=0.0)["busy_s"]
    assert busy + r["idle_s"] == pytest.approx(r["window_s"], rel=1e-9)
    first, second = r["longest"]
    assert (first["phase"], second["phase"]) == ("dispatch", "between_steps")
    assert first["gap_ms"] == pytest.approx(21.697719)
    assert first["by_ms"] == pytest.approx(
        {"dispatch": 17.269436, "untracked": 4.426213,
         gaps.UNCOVERED: 0.00207})
    assert second["gap_ms"] == pytest.approx(17.510808)
    assert second["phase_ms"] == pytest.approx(13.05494)
    # one clock: what no annotation covers is half a percent of the idle
    # time, and the two long gaps are nine tenths of it
    assert r["idle_by_phase_s"][gaps.UNCOVERED] < 0.006 * r["idle_s"]
    assert (first["gap_ms"] + second["gap_ms"]) / 1e3 > 0.9 * r["idle_s"]
    assert sum(r["idle_by_phase_s"].values()) == pytest.approx(r["idle_s"])
