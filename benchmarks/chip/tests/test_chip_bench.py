"""Tests of the benchmark's own code. Run by hand, not part of tier-1:

    python -m pytest benchmarks/chip/tests -q

They import no JAX and start no process.
"""

import json
import math
import os
import statistics
import sys

import pytest

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP_DIR)

from check_line import problems_in  # noqa: E402
from lib import spec, stats, traffic, trace_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
TPU = ("^/device:TPU:\\d+$", "^XLA Ops$")


# ----------------------------------------------------- the trace reducer --


@pytest.fixture(scope="module")
def small_trace():
    """The first events of every line of the device plane of a real trace
    (tests/cut_fixture.py says how it was cut)."""
    with open(os.path.join(HERE, "small_trace.json")) as f:
        return json.load(f)


def test_recorded_trace_reduces_inside_its_window(small_trace):
    r = trace_reduce.reduce(small_trace, *TPU, margin_s=0.0002)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert r["chips"] == 1
    # self times partition the busy time of a single line
    assert sum(r["op_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert len(r["top_ops"]) <= 10
    assert r["top_ops"] == sorted(r["top_ops"], key=lambda kv: -kv[1])


def test_lines_of_one_plane_overlap_and_only_one_is_read(small_trace):
    """Modules, steps and operations lines cover the same time: summing
    them overshoots the window, which is why one line is read."""
    plane = small_trace["planes"][0]
    assert len([ln for ln in plane["lines"] if ln["events"]]) >= 2
    one = trace_reduce.reduce(small_trace, *TPU, margin_s=0.0002)
    every = trace_reduce.reduce(small_trace, TPU[0], "", margin_s=0.0002)
    summed = sum(ev[1] for ln in plane["lines"] for ev in ln["events"]) / 1e9
    assert every["busy_s"] <= every["window_s"]      # a union never overshoots
    assert summed > one["busy_s"]


def _trace(events, extra_lines=()):
    return {"names": ["while", "attn", "matmul"], "planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [[0, 10**10, 0]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": events}, *extra_lines]}]}


def test_events_straddling_the_slice_are_clipped():
    # span 0..4 s, margin 0.5 -> slice 0.5..3.5 s; one op over each edge
    s = 10**9
    tr = _trace([[0, 1 * s, 1], [2 * s, int(0.5 * s), 2], [3 * s, 1 * s, 1]])
    r = trace_reduce.reduce(tr, *TPU, margin_s=0.5)
    assert r["window_s"] == pytest.approx(3.0)
    assert r["busy_s"] == pytest.approx(0.5 + 0.5 + 0.5)
    assert r["op_s"]["attn"] == pytest.approx(1.0)
    assert trace_reduce.time_matching(r, "^attn$") == pytest.approx(1.0)
    assert trace_reduce.time_matching(r, "nothing") == 0.0


def test_a_parent_operation_keeps_only_its_own_time():
    s = 10**9
    tr = _trace([[0, 4 * s, 0], [1 * s, 1 * s, 1], [2 * s, 1 * s, 2]],
                extra_lines=[{"name": "XLA Modules",
                              "events": [[0, 4 * s, 0]]}])
    r = trace_reduce.reduce(tr, *TPU, margin_s=0.5)
    assert r["busy_s"] == pytest.approx(3.0) == r["window_s"]
    assert r["op_s"] == pytest.approx({"while": 1.0, "attn": 1.0,
                                       "matmul": 1.0})


def test_an_empty_device_plane_is_an_error():
    with pytest.raises(trace_reduce.TraceError, match="empty"):
        trace_reduce.reduce(_trace([]), *TPU)
    with pytest.raises(trace_reduce.TraceError, match="no plane"):
        trace_reduce.reduce({"names": [], "planes": [
            {"name": "/host:CPU", "lines": []}]}, *TPU)
    with pytest.raises(trace_reduce.TraceError, match="too short"):
        trace_reduce.reduce(_trace([[0, 10**8, 1]]), *TPU, margin_s=0.25)


def test_two_chips_are_averaged():
    s = 10**9
    tr = _trace([[0, 4 * s, 1]])
    tr["planes"].append({"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [[0, 1 * s, 1], [3 * s, 1 * s, 1]]}]})
    r = trace_reduce.reduce(tr, *TPU, margin_s=0.5)
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((3.0 + 1.0) / 2)


# ------------------------------------------- percentiles and due times --


def test_percentile_is_nearest_rank_and_refuses_nothing():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 51      # round(0.5 * 99) = 50 -> 51
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_quartile_spread_is_the_contracts():
    xs = [100, 101, 102, 103, 104, 110]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.quartile_spread(xs) == (q3 - q1) / statistics.median(xs)


def _req(due, sent, first, last, tokens, status=200, phase="window"):
    r = stats.Request(0, phase, due, 100, tokens)
    r.sent, r.first, r.last, r.done = sent, first, last, last
    r.status, r.completion_tokens, r.prompt_tokens = status, tokens, 100
    if first is not None and tokens > 1:
        r.frame_times = [first + i * (last - first) / (tokens - 1)
                         for i in range(tokens)]
    return r


def test_ttft_counts_from_when_the_request_was_due():
    late = _req(due=1.0, sent=1.4, first=1.5, last=2.5, tokens=11)
    assert late.ttft_s(120.0) == pytest.approx(0.5)      # not 0.1
    assert late.tpot_s(120.0) == pytest.approx(0.1)
    assert _req(0, 0, 0.1, 0.1, 1).tpot_s(120.0) is None
    failed = _req(1.0, 1.0, None, None, 0, status=503)
    assert failed.ttft_s(120.0) == 120.0 and failed.tpot_s(120.0) == 120.0


def test_end_to_end_takes_tails_of_all_window_requests_and_rate_of_all_tokens():
    reqs = [_req(i * 0.1, i * 0.1, i * 0.1 + 0.2, i * 0.1 + 1.2, 11)
            for i in range(20)]
    reqs.append(_req(-1.0, -1.0, -0.8, 0.5, 21, phase="lead_in"))
    reqs.append(_req(1.9, 1.9, None, None, 0, status=503))
    out = stats.end_to_end(reqs, window_s=2.0, setup_s=5.0, fail_s=120.0)
    assert out["setup_s"] == 5.0
    assert out["ttft_p50_ms"] == pytest.approx(200.0)
    assert out["ttft_p95_ms"] == pytest.approx(200.0)     # 1 of 21 failed
    assert stats.end_to_end(reqs + [reqs[-1]] * 3, 2.0, 5.0, 120.0)[
        "ttft_p95_ms"] == pytest.approx(120000.0)
    assert out["tpot_p95_ms"] == pytest.approx(100.0)
    # 20 requests of 10 gaps in 1.0 s each, and the failed one: one gap of 120 s
    assert out["tpot_mean_ms"] == pytest.approx(1e3 * (20 * 1.0 + 120.0) / 201)
    # tokens that arrived inside [0, 2], whichever request they belong to:
    # request i streams 11 tokens from 0.1 i + 0.2 every 0.1 s, and the
    # lead-in one 21 from -0.8 every 0.065 s
    inside = sum(1 for i in range(20) for k in range(11)
                 if 0.1 * i + 0.2 + 0.1 * k <= 2.0 + 1e-9)
    inside += sum(1 for k in range(21) if 0.0 <= -0.8 + 0.065 * k <= 0.5)
    assert out["out_tokens_per_s"] == pytest.approx(inside / 2.0, abs=1.0)


# ----------------------------------------------------- the generator --


def _mix():
    with open(os.path.join(CHIP_DIR, "traffic", "chat.json")) as f:
        return json.load(f)


def test_the_schedule_is_the_mixs_own_and_the_seed_only_writes_the_prompts():
    mix = _mix()
    a = traffic.lengths(mix, 240, "window")
    b = traffic.lengths(mix, 240, "lead_in")
    assert a != b
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    assert a == traffic.lengths(mix, 240, "window")
    assert [o for _, o in a] != sorted(o for _, o in a)      # shuffled
    drawn = traffic.stratified(mix["prompt_tokens"], 240)
    assert min(drawn) >= 32 and max(drawn) <= 1800
    assert statistics.median(drawn) == pytest.approx(300, abs=3)
    assert all(p + o <= mix["max_total_tokens"] for p, o in a + b)
    # clipping a pair to the context gives up few prompt tokens in all
    assert abs(sum(p for p, _ in a) - sum(p for p, _ in b)) \
        < 0.02 * sum(p for p, _ in a)


def test_arrivals_fill_the_window_at_the_rate():
    mix = _mix()
    t1 = traffic.arrival_times(mix, 8.0, 30.0, "window")
    t2 = traffic.arrival_times(mix, 8.0, 30.0, "lead_in")
    assert len(t1) == len(t2) == 240
    assert t1[0] == 0.0 and t1 == sorted(t1) and t1[-1] < 30.0
    assert t1 != t2
    gaps = sorted(b - a for a, b in zip(t1, t1[1:] + [30.0]))
    assert gaps == pytest.approx(
        sorted(b - a for a, b in zip(t2, t2[1:] + [30.0])))
    # exponential gaps: the mean is 1/rate, the median ln 2 of it
    assert statistics.mean(gaps) == pytest.approx(1 / 8.0)
    assert statistics.median(gaps) == pytest.approx(math.log(2) / 8.0,
                                                    rel=0.05)
    bursty = traffic.arrival_times({**mix, "burst_size": 4}, 8.0, 30.0, "w")
    assert len(bursty) == 240 and len(set(bursty)) == 60


def test_prompts_have_their_length_and_share_only_what_the_mix_says():
    a = traffic.prompt_text(300, 5, 1, "")
    b = traffic.prompt_text(300, 5, 2, "")
    assert len(a.encode()) == len(b.encode()) == 300
    assert a[:6] != b[:6]            # they differ within the first KV page
    assert traffic.prompt_text(300, 5, 1, "") == a
    shared = traffic.shared_prefix({"shared_prefix_tokens": 64})
    c = traffic.prompt_text(300, 5, 1, shared)
    d = traffic.prompt_text(300, 6, 9, shared)
    assert len(c) == 300 and c[:64] == d[:64] == shared and c[64:] != d[64:]


# ---------------------------------------------------------- the loader --


def test_cells_load_and_owe_what_benchmark_json_lists():
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert "setup_s" in cell.owed(False) and len(cell.owed(False)) >= 2
        assert cell.owed(True)
        assert not set(cell.owed(True)) & set(cell.owed(False))


def _bench_with(tmp_path, change):
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    change(bench)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


def test_loader_refuses_what_it_does_not_know(tmp_path):
    with pytest.raises(spec.SpecError, match="unknown workload"):
        spec.load_cell("no-such-cell")

    def unknown_metric(b):
        b["per_layer"].append({
            "name": "no_such_metric", "unit": "ms", "better": "lower",
            "source": "host_clock", "layer": "scheduler",
            "moves": "setup_s"})
    cell = json.load(open(os.path.join(spec.REPO, "BENCHMARK.json")))[
        "workloads"][0]["name"]
    with pytest.raises(spec.SpecError, match="unknown layer_metrics"):
        spec.load_cell(cell, _bench_with(tmp_path, unknown_metric))

    def moves_nothing(b):
        b["per_layer"][0]["moves"] = "no_such_end_to_end"
    with pytest.raises(spec.SpecError, match="unknown metric"):
        spec.load_cell(cell, _bench_with(tmp_path, moves_nothing))
    with pytest.raises(spec.SpecError, match="unknown reader"):
        spec.load_reader("no_such_reader")
    with pytest.raises(spec.SpecError, match="device_kind"):
        spec.load_device("TPU v9 imaginary")
    assert spec.load_device("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


# ------------------------------------------------------- check_line.py --


GOOD = {"correct": True, "attempted": 240, "failed": 0,
        "metrics": {"setup_s": {"value": 104.2, "unit": "s"},
                    "ttft_p95_ms": {"value": 512.25, "unit": "ms"}},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 12000000000}}
OWED = {"setup_s": "s", "ttft_p95_ms": "ms"}


def _line(**change):
    obj = json.loads(json.dumps(GOOD))
    for key, value in change.items():
        node = obj
        *path, last = key.split("__")
        for k in path:
            node = node[k]
        if value is KeyError:
            del node[last]
        else:
            node[last] = value
    return json.dumps(obj)


def test_check_line_takes_a_good_line():
    assert problems_in(_line(), OWED, False) == []
    traced = _line(device__window_s=2.5, device__busy_s=2.5,
                   breakdown={"device_ops": [["fusion.1", 1.25]]})
    assert problems_in(traced, OWED, True) == []


@pytest.mark.parametrize("change, trace, says", [
    ({"device": KeyError}, False, "missing key 'device'"),
    ({"metrics__ttft_p95_ms": KeyError}, False, "owed and missing"),
    ({"metrics__ttft_p95_ms": {"value": 1.0}}, False, "lacks value or unit"),
    ({"metrics__ttft_p95_ms__value": float("nan")}, False, "not a finite"),
    ({"metrics__ttft_p95_ms__value": None}, False, "not a finite"),
    ({"metrics__ttft_p95_ms__unit": "milli seconds"}, False, "has the unit"),
    ({"metrics__ttft_p95_ms__unit": "x" * 17}, False, "has the unit"),
    ({"metrics__ttft_p95_ms__unit": "s"}, False, "BENCHMARK.json says"),
    ({"metrics__bad name": {"value": 1, "unit": "s"}}, False, "character"),
    ({"device__memory_peak_bytes": KeyError}, False, "memory_peak_bytes"),
    ({}, True, "window_s and"),
    ({"device__window_s": 2.5, "device__busy_s": 0.0}, True, "not above 0"),
    ({"device__window_s": 2.5, "device__busy_s": 2.6}, True, "at most"),
    ({"correct": "yes"}, False, "not true or false"),
    ({"attempted": 1.5}, False, "not a count"),
])
def test_check_line_refuses(change, trace, says):
    found = problems_in(_line(**change), OWED, trace)
    assert any(says in f for f in found), found


def test_check_line_refuses_what_is_not_an_object():
    assert problems_in("", OWED, False)
    assert problems_in("[1, 2]", OWED, False)
    assert problems_in("ready after 12 s", OWED, False)


def test_the_attention_pattern_matches_a_recorded_pallas_call():
    """tests/pallas_call_name.txt is the name the v5e trace of PR 23's step 0
    gave the Pallas decode-attention call."""
    import re

    with open(os.path.join(HERE, "pallas_call_name.txt")) as f:
        name = f.read().strip()
    for variant in ("chat", "sat"):
        with open(os.path.join(CHIP_DIR, "layer_metrics",
                               f"attn_kernel_busy_pct.{variant}.json")) as f:
            pattern = json.load(f)["args"]["pattern"]
        assert re.search(pattern, name)
        assert not re.search(pattern, "%copy.174 = bf16[32,8,2048,128] copy(")
    assert trace_reduce.short_name(name) == "closed_call bf16[32,16,128]"
