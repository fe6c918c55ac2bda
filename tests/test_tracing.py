"""In-engine observability: /debug/trace capture + per-phase histograms."""

import io
import json
import os
import threading
import urllib.request
import zipfile

import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine, PhaseTimer
from dynamo_tpu.engine.request import GenRequest
from dynamo_tpu.observability.tracing import new_trace_id
from dynamo_tpu.serving.api import ServingContext, make_server


def test_phase_timer_quantiles():
    t = PhaseTimer()
    for ms in (1, 1, 2, 4, 100):
        t.observe(ms / 1e3)
    snap = t.snapshot()
    assert snap["count"] == 5
    assert snap["p50_ms"] <= 4
    assert snap["max_ms"] == pytest.approx(100, rel=0.01)
    assert snap["p95_ms"] >= 50


@pytest.fixture(scope="module")
def server():
    cfg = EngineConfig(model="tiny-debug", page_size=4, num_pages=64,
                       max_num_seqs=2, max_seq_len=64)
    ctx = ServingContext(Engine(cfg), served_model="tiny-debug")
    srv = make_server(ctx, host="127.0.0.1", port=0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield ctx, f"http://127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()
    ctx.close()


def test_debug_trace_returns_nonempty_zip(server):
    ctx, base = server
    # generate under the trace so device work lands in the capture window
    def work():
        ctx.engine.generate(GenRequest("tr", [1, 2, 3], max_tokens=6,
                                       temperature=0.0, ignore_eos=True))
    w = threading.Thread(target=work)
    w.start()
    data = urllib.request.urlopen(f"{base}/debug/trace?duration_s=0.5",
                                  timeout=120).read()
    w.join()
    z = zipfile.ZipFile(io.BytesIO(data))
    assert z.namelist(), "trace zip is empty"


def test_worker_stats_include_phase_histograms(server):
    ctx, base = server
    ctx.engine.generate(GenRequest("ph", [1, 2, 3], max_tokens=4,
                                   temperature=0.0, ignore_eos=True))
    stats = json.load(urllib.request.urlopen(f"{base}/worker/stats",
                                             timeout=30))
    phases = stats["metrics"]["phases"]
    assert phases["prefill"]["count"] >= 1
    assert phases["decode_window"]["count"] >= 1
    assert phases["decode_step"]["p50_ms"] > 0


# ---------------------------------------------------------------------------
# a first token by stage, and the keys the benchmark's new metrics read
# ---------------------------------------------------------------------------
def _run_handle(ctx, rid, prompt, max_tokens, on_first=None):
    """One request through the serving layer's own GenerationHandle, as an
    HTTP handler drives it; returns the number of tokens emitted."""
    import time

    params = {"max_tokens": max_tokens, "temperature": 0.0, "top_p": 1.0,
              "top_k": 0, "ignore_eos": True}
    h = ctx.start_generation(rid, prompt, params,
                             received_at=time.monotonic() - 0.004)
    seen = []

    def emit(delta, finish, lp_entry):
        seen.append(delta)
        if len(seen) == 1 and on_first is not None:
            on_first()
        return True

    h.run(emit)
    return len(seen)


# plain: one whole-prompt prefill; chunked: the prompt in 8-token chunks
# with decode windows between them; mixed: its chunks ride the decode step
# of a request that is already streaming
_ADMISSIONS = {
    "plain": {},
    "chunked": {"prefill_chunk_tokens": 8},
    "mixed": {"prefill_chunk_tokens": 8, "mixed_batch_tokens": 8},
}


@pytest.mark.parametrize("admission", sorted(_ADMISSIONS))
def test_first_token_stages_sum_to_ttft(admission):
    cfg = EngineConfig(model="tiny-debug", page_size=4, num_pages=64,
                       max_num_seqs=2, max_seq_len=64,
                       **_ADMISSIONS[admission])
    ctx = ServingContext(Engine(cfg), served_model="tiny-debug")
    try:
        prompt = list(range(1, 30))
        if admission == "mixed":
            # the long prompt arrives while another request decodes
            second = threading.Thread(
                target=_run_handle, args=(ctx, "ft-b", prompt, 4))
            _run_handle(ctx, "ft-a", [3, 1, 4], 40, on_first=second.start)
            second.join()
            assert ctx.engine.metrics.mixed_count > 0, \
                "the prompt's chunks did not ride a decode step"
            want = 2
        else:
            _run_handle(ctx, "ft-a", prompt, 4)
            want = 1
        ft = ctx.engine.metrics.snapshot()["first_token"]
    finally:
        ctx.close()
    assert ft["count"] == want
    stages = [ft[k] for k in ("submit_s", "queue_s", "prefill_s", "emit_s")]
    assert sum(stages) == pytest.approx(ft["ttft_s"], abs=1e-9)
    # `received_at` was put 4 ms before the submit: that is the first stage
    assert ft["submit_s"] >= 0.004 * want
    assert all(s >= 0.0 for s in stages) and ft["prefill_s"] > 0.0
    # a sum and a count: the benchmark reads their deltas over a window
    assert ft["ttft_s"] / ft["count"] < 120.0


def test_worker_stats_carry_what_the_layer_metrics_read(server):
    """/worker/stats holds every counter that a benchmarks/chip/
    layer_metrics file of this program's stepline and first-token account
    names, and every key it had: three shipped metrics read those."""
    import glob
    import os

    ctx, base = server
    body = json.dumps({"model": "tiny-debug", "prompt": "abc",
                       "max_tokens": 4, "stream": True,
                       "ignore_eos": True}).encode()
    urllib.request.urlopen(urllib.request.Request(
        f"{base}/v1/completions", data=body,
        headers={"Content-Type": "application/json"}), timeout=120).read()
    stats = json.load(urllib.request.urlopen(f"{base}/worker/stats",
                                             timeout=30))

    def has(path):
        node = stats
        for key in path.split("."):
            if not isinstance(node, dict) or key not in node:
                return False
            node = node[key]
        return isinstance(node, (int, float))

    chip = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip", "layer_metrics")
    named = set()
    for path in glob.glob(os.path.join(chip, "*.json")):
        with open(path) as f:
            args = json.load(f).get("args", {})
        for value in args.values():
            for item in (value if isinstance(value, list) else [value]):
                if isinstance(item, str) and item.startswith(
                        ("timeline.", "metrics.")):
                    named.add(item)
    assert {"timeline.loop_wall_s", "timeline.drained.by.no_work",
            "metrics.first_token.ttft_s", "timeline.token_time.gaps",
            "timeline.token_time.row_s.prompt",
            "timeline.token_time.gap_max_s"} <= named
    # a phase that never ran has no entry under timeline.phases: the
    # shipped readers read an absent path as 0 (readers/stats_delta.py)
    missing = sorted(p for p in named if not has(p)
                     and not p.startswith("timeline.phases."))
    assert not missing, f"/worker/stats lacks {missing}"
    tl = stats["timeline"]
    assert {"wall_s", "steps", "phases", "untracked_s", "device", "bubble",
            "loop_wall_s", "loop", "drained", "token_time"} <= set(tl)
    assert set(tl["token_time"]) == {"cause_s", "row_s", "gaps",
                                     "gap_max_s", "worst"}
    assert set(tl["bubble"]) == {"gap_eater", "host_shares"}
    dr = tl["drained"]
    assert sum(dr["by"].values()) == pytest.approx(dr["total_s"], abs=1e-4)
    assert dr["total_s"] <= tl["loop_wall_s"] + 1e-6
    # the request came through an HTTP handler: all four stages are there
    ft = stats["metrics"]["first_token"]
    assert ft["count"] >= 1 and ft["submit_s"] > 0.0 and ft["emit_s"] > 0.0


def test_token_time_per_request_is_the_engines_account(server):
    """Three streamed requests: timeline.token_time grows by what their
    worker.decode spans carry, cause by cause; each span runs from the
    engine's stamp of the request's first token to that of its last, which
    is the sum of its three parts; the longest waits keep their record."""
    import time

    from dynamo_tpu.observability.timeline import CAUSES

    ctx, base = server

    def stats():
        return json.load(urllib.request.urlopen(f"{base}/worker/stats",
                                                timeout=30))

    def post(rid, prompt, max_tokens):
        body = json.dumps({"model": "tiny-debug", "prompt": prompt,
                           "max_tokens": max_tokens, "stream": True,
                           "ignore_eos": True}).encode()
        urllib.request.urlopen(urllib.request.Request(
            f"{base}/v1/completions", data=body,
            headers={"Content-Type": "application/json",
                     "x-request-id": rid}), timeout=120).read()

    before = stats()
    rids = ["c1" * 16, "c2" * 16, "c3" * 16]
    wants = (12, 7, 5)
    # the second arrives while the first decodes: one waits behind the
    # other's prompt; the third runs alone
    pair = [threading.Thread(target=post, args=(rid, "hello there " * k, n))
            for rid, k, n in zip(rids, (1, 2), wants)]
    for th in pair:
        th.start()
    for th in pair:
        th.join()
    post(rids[2], "abc", wants[2])
    ids = {new_trace_id(rid) for rid in rids}
    decode, deadline = [], time.monotonic() + 5.0
    while len(decode) < 3 and time.monotonic() < deadline:
        decode = [sp for sp in ctx.tracer.collector.snapshot()
                  if sp.name == "worker.decode" and sp.trace_id in ids]
        time.sleep(0.02)
    assert len(decode) == 3
    after = stats()
    tt0 = before["timeline"]["token_time"]
    tt1 = after["timeline"]["token_time"]
    for c in CAUSES:
        assert tt1["row_s"][c] - tt0["row_s"][c] == pytest.approx(
            sum(sp.attributes[c + "_s"] for sp in decode), abs=1e-5)
    # every token but a sequence's first ends a wait
    grew = tt1["gaps"] - tt0["gaps"]
    assert grew == sum(sp.attributes["tokens"] for sp in decode) \
        == sum(wants) - 3
    assert grew == (after["metrics"]["output_tokens"]
                    - before["metrics"]["output_tokens"]) - 3
    for sp in decode:
        a = sp.attributes
        parts = a["decode_s"] + a["prompt_s"] + a["drained_s"]
        assert (sp.end_ns - sp.start_ns) / 1e9 == pytest.approx(
            parts, abs=1e-5)
        assert 0.0 < a["gap_max_s"] <= parts + 1e-6
        assert a["completion_tokens"] == a["tokens"] + 1
    assert sum(tt1["row_s"].values()) > sum(tt0["row_s"].values())
    worst = tt1["worst"]
    assert 1 <= len(worst) <= 8
    assert [w["gap_s"] for w in worst] == sorted(
        (w["gap_s"] for w in worst), reverse=True)
    for w in worst:
        assert tt1["gap_max_s"] >= w["gap_s"]
        assert w["decode_s"] + w["prompt_s"] + w["drained_s"] \
            == pytest.approx(w["gap_s"], abs=3e-6)
        assert w["programs"] >= 0 and w["request_id"]
    # the thread's time by cause is its whole time (read between two of
    # the idle loop's ticks: the totals are folded one after the other)
    for _ in range(50):
        tl = stats()["timeline"]
        if abs(sum(tl["token_time"]["cause_s"].values())
               - tl["loop_wall_s"]) < 1e-4:
            break
        time.sleep(0.013)
    assert sum(tl["token_time"]["cause_s"].values()) == pytest.approx(
        tl["loop_wall_s"], abs=1e-4)
    # /debug/timeline?format=summary carries the same account
    summ = json.load(urllib.request.urlopen(
        f"{base}/debug/timeline?format=summary", timeout=30))
    assert summ["token_time"]["gaps"] >= tt1["gaps"]


def test_capture_annotates_the_stepline_and_then_stops(server):
    """During /debug/trace the engine thread's segments are profiler
    annotations; after it, none is open and none is made."""
    import time

    ctx, base = server
    tl = ctx.engine.timeline
    assert tl._tracing is False and tl._annotate is None
    seen = []
    real_start = tl.start_annotations

    def spy(annotate, annotate_step=None):
        def make(name, **kw):
            seen.append(name)
            return annotate(name, **kw)
        real_start(make, annotate_step)

    tl.start_annotations = spy
    try:
        worker = threading.Thread(target=_run_handle,
                                  args=(ctx, "ann", [1, 2, 3], 6))
        worker.start()
        urllib.request.urlopen(f"{base}/debug/trace?duration_s=0.5",
                               timeout=120).read()
        worker.join()
    finally:
        del tl.start_annotations
    assert "stepline/no_work" in seen
    assert {n for n in seen} <= {
        "stepline/" + k for k in ("admit", "page_alloc", "dispatch",
                                  "device_wait", "detok", "bank",
                                  "untracked", "between_steps", "no_work")}
    deadline = time.monotonic() + 5.0
    while tl._tracing and time.monotonic() < deadline:
        time.sleep(0.02)    # the thread closes its last one at its next tick
    assert tl._tracing is False and tl._ann is None and tl._ann_step is None
    n = len(seen)
    _run_handle(ctx, "ann2", [1, 2, 3], 3)
    assert len(seen) == n, "an annotation was made outside a capture"


def test_an_idle_capture_still_spans_its_length_on_the_device(server):
    """The capture touches every local device at its start and at its end,
    so the device's own events span the capture where the engine is idle.
    The chip benchmark's reducer takes its slice from those events, and a
    capture whose last 2.4 s of 3 were idle read as one of 0.6 s (PR 36)."""
    import tempfile

    from jax.profiler import ProfileData

    _, base = server
    data = urllib.request.urlopen(f"{base}/debug/trace?duration_s=1.2",
                                  timeout=120).read()
    with tempfile.TemporaryDirectory() as d:
        zipfile.ZipFile(io.BytesIO(data)).extractall(d)
        paths = [os.path.join(r, f) for r, _, fs in os.walk(d)
                 for f in fs if f.endswith(".xplane.pb")]
        assert paths
        # on the CPU the XLA client's thread lines are the device's
        ops = [ev for path in paths
               for plane in ProfileData.from_file(path).planes
               for line in plane.lines if line.name.startswith("tf_XLA")
               for ev in line.events]
    assert ops, "no operation ran on the device inside an idle capture"
    span_s = (max(ev.start_ns + ev.duration_ns for ev in ops)
              - min(ev.start_ns for ev in ops)) / 1e9
    assert span_s >= 1.1, span_s


def test_a_capture_samples_the_kernels_counters(server):
    """/worker/stats `trace_capture` after a capture: what the kernels
    were asked for every 0.25 s from its start mark to its end (benchmarks/chip/readers/capture_roofline.py reads a slice's
    own seconds out of these)."""
    _, base = server
    stats = json.load(urllib.request.urlopen(f"{base}/worker/stats",
                                             timeout=30))
    urllib.request.urlopen(f"{base}/debug/trace?duration_s=0.6",
                           timeout=120).read()
    cap = json.load(urllib.request.urlopen(
        f"{base}/worker/stats", timeout=30))["trace_capture"]
    assert cap["period_s"] == 0.25
    times = [s["t_s"] for s in cap["samples"]]
    assert len(times) == 4 and times == sorted(times)
    for got, due in zip(times, (0.0, 0.25, 0.5, 0.6)):
        assert due <= got < due + 0.2
    for s in cap["samples"]:
        assert set(s["metrics"]) == {"attn", "attn_kinds", "dsa", "moe",
                                      "sparse", "ssm", "conv",
                                      "admit_blocked"}
        assert s["metrics"]["attn"] == stats["metrics"]["attn"]  # idle


def test_capture_roofline_takes_both_sides_over_the_slice():
    """A chip that works through the first second of a 3 s capture and
    idles after: the slice (0.25 s off each end) holds 0.75 s of that
    work, the 1 Hz polls around it a second more. The reader holds the
    kernel's time in the slice against what the samples say was asked
    inside the slice; the bracketing reader overshoots it by a quarter."""
    import sys
    import types

    chip = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip")
    sys.path.insert(0, chip)
    try:
        from lib.spec import load_reader
        reader = load_reader("capture_roofline")
        bracketing = load_reader("kernel_roofline")
    finally:
        sys.path.remove(chip)
    args = {"cost": "gqa_paged_attention", "which": "decode",
            "layers_full": 2, "layers_window": 3, "heads_full": 48,
            "heads_window": 72, "kv_heads": 8, "head_dim": 128,
            "pattern": "^decode", "device": {
                "peak_bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}

    def counters(rows):  # KV rows a layer asked for so far, by kind
        return {"attn_kinds": {k: {"decode_kv_rows": rows}
                               for k in ("full", "window")}}

    rate = 4e6  # rows a second a layer while the chip works
    work = lambda t: rate * min(max(t, 0.0), 1.0)  # noqa: E731
    samples = [{"t_s": 0.25 * i, "metrics": counters(work(0.25 * i))}
               for i in range(13)]
    # the polls: the capture starts at 22.5 s of the window, the chip
    # worked since 21.5
    polls = [(float(t), {"metrics": counters(rate * min(max(t - 21.5, 0.0),
                                                        2.0)),
                         "device_kind": "cpu", "trace_capture":
                         {"period_s": 0.25, "samples": samples}
                         if t >= 26 else None})
             for t in range(0, 49)]
    kernel_s = 0.3  # the kernel's own time inside the slice
    ctx = types.SimpleNamespace(
        snapshots=polls, window_s=48.0,
        trace={"window_s": 2.5, "busy_s": 0.75, "op_s": {"decode": kernel_s}})
    asked_rows = work(2.75) - work(0.25)
    least_s = asked_rows * (2 + 3) * 8 * 128 * 2 * 2 / 819e9
    got = reader.read(ctx, args)
    assert got == pytest.approx(100.0 * least_s / kernel_s, rel=1e-6)
    assert 0.0 < got < 100.0
    assert bracketing.read(ctx, args) > 1.2 * got
    # a worker without the samples is read between the polls
    for _, stats in polls:
        stats["trace_capture"] = None
    assert reader.read(ctx, args) == bracketing.read(ctx, args)


def test_first_token_spans_share_the_request_trace_and_parent(server):
    """worker.submit / .queue / .prefill / .emit: one trace id (the
    request's), one parent (worker.request), end to end without a gap,
    and they are the stages the counters summed."""
    ctx, base = server
    before = ctx.engine.metrics.snapshot()["first_token"]
    rid = "ab" * 16
    body = json.dumps({"model": "tiny-debug", "prompt": "hello there",
                       "max_tokens": 4, "stream": True,
                       "ignore_eos": True}).encode()
    urllib.request.urlopen(urllib.request.Request(
        f"{base}/v1/completions", data=body,
        headers={"Content-Type": "application/json",
                 "x-request-id": rid}), timeout=120).read()
    after = ctx.engine.metrics.snapshot()["first_token"]
    import time

    request = []
    deadline = time.monotonic() + 5.0
    while not request and time.monotonic() < deadline:
        # the request's own span ends a moment after the body is read
        request = [sp for sp in ctx.tracer.collector.snapshot()
                   if sp.name == "worker.request" and sp.end_ns
                   and sp.trace_id == new_trace_id(rid)]
        time.sleep(0.02)
    request = request[-1]
    stages = [sp for sp in ctx.tracer.collector.snapshot(
                  trace_id=request.trace_id)
              if sp.name in ("worker.submit", "worker.queue",
                             "worker.prefill", "worker.emit")]
    assert [sp.name for sp in sorted(stages, key=lambda sp: sp.start_ns)] \
        == ["worker.submit", "worker.queue", "worker.prefill", "worker.emit"]
    decode = [sp for sp in ctx.tracer.collector.snapshot(
                  trace_id=request.trace_id) if sp.name == "worker.decode"]
    for sp in stages + decode:
        assert sp.trace_id == request.trace_id
        assert sp.parent_span_id == request.span_id
    ordered = sorted(stages, key=lambda sp: sp.start_ns)
    for a, b in zip(ordered, ordered[1:]):
        assert abs(a.end_ns - b.start_ns) <= 1  # one stamp ends a, starts b
    assert ordered[0].start_ns >= request.start_ns - 1_000_000
    # one source: the spans' lengths are what the counters grew by
    grew = {k: after[k] - before[k] for k in after}
    assert grew["count"] == 1
    for sp, key in zip(ordered, ("submit_s", "queue_s", "prefill_s",
                                 "emit_s")):
        assert (sp.end_ns - sp.start_ns) / 1e9 == pytest.approx(
            grew[key], abs=2e-6)
    assert sum((sp.end_ns - sp.start_ns) for sp in ordered) / 1e9 \
        == pytest.approx(grew["ttft_s"], abs=1e-5)
