"""Unit tests for dynamo_tpu.observability: traceparent codec, span
collector bounds, the kill switch, NATS header codec, and the /metrics
label-escaping regression (ISSUE 1 satellites)."""

import gc
import json
import sys
import time
import tracemalloc

import pytest

from dynamo_tpu.observability import context as obs_context
from dynamo_tpu.observability import tracing as obs_tracing
from dynamo_tpu.serving import nats as nats_mod
from dynamo_tpu.serving.metrics import Counter, Gauge, Histogram, Registry


# ------------------------------------------------------------ traceparent --

def test_traceparent_roundtrip_byte_exact():
    ctx = obs_context.TraceContext.new("req-abc")
    header = ctx.to_traceparent()
    parsed = obs_context.parse_traceparent(header)
    assert parsed == ctx
    # byte-exact: format(parse(s)) == s
    assert parsed.to_traceparent() == header
    canonical = "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01"
    assert obs_context.parse_traceparent(canonical).to_traceparent() \
        == canonical


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-short-b7ad6b7169203331-01",
    "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331",  # missing flags
    "00-" + "0" * 32 + "-b7ad6b7169203331-01",  # all-zero trace id
    "00-0af7651916cd43dd8448eb211c80319c-" + "0" * 16 + "-01",  # zero span
    "ff-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01",  # bad version
    "00-0AF7651916CD43DD8448EB211C80319Z-b7ad6b7169203331-01",  # non-hex
])
def test_traceparent_rejects_malformed(bad):
    assert obs_context.parse_traceparent(bad) is None


def test_traceparent_future_version_accepted():
    # spec: parse unknown (non-ff) versions by the first four fields
    v1 = "01-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01-extra"
    ctx = obs_context.parse_traceparent(v1)
    assert ctx is not None
    assert ctx.trace_id == "0af7651916cd43dd8448eb211c80319c"


def test_deterministic_ids_from_request_id():
    a1 = obs_context.new_trace_id("req-1")
    a2 = obs_context.new_trace_id("req-1")
    b = obs_context.new_trace_id("req-2")
    assert a1 == a2 != b
    assert len(a1) == 32 and int(a1, 16)  # hex, non-zero
    # two un-seeded calls must not collide
    assert obs_context.new_trace_id() != obs_context.new_trace_id()


def test_extract_context_falls_back_to_request_id():
    class H(dict):
        def get(self, k, default=None):
            return super().get(k.lower(), default)

    ctx = obs_context.extract_context(H({"x-request-id": "abc"}))
    assert ctx is not None
    assert ctx.trace_id == obs_context.new_trace_id("abc")
    # explicit traceparent wins over the fallback
    tp = obs_context.TraceContext.new().to_traceparent()
    ctx2 = obs_context.extract_context(
        H({"traceparent": tp, "x-request-id": "abc"}))
    assert ctx2.to_traceparent() == tp
    assert obs_context.extract_context(H({})) is None


# ------------------------------------------------------------------ spans --

def test_span_parent_child_links_and_export():
    col = obs_tracing.SpanCollector(64)
    tr = obs_tracing.Tracer("svc-a", col)
    root = tr.start_span("root", attributes={"k": "v"})
    child = tr.start_span("child", parent=root)
    child.add_event("hop", {"n": 1})
    child.set_status("OK")
    child.end()
    root.end()
    spans = {s["name"]: s for s in obs_tracing.iter_otlp_spans(col.export())}
    assert spans["child"]["parentSpanId"] == spans["root"]["spanId"]
    assert spans["child"]["traceId"] == spans["root"]["traceId"]
    assert spans["root"]["parentSpanId"] == ""
    assert int(spans["root"]["startTimeUnixNano"]) <= \
        int(spans["root"]["endTimeUnixNano"])
    assert spans["child"]["events"][0]["name"] == "hop"
    # export filters
    assert list(obs_tracing.iter_otlp_spans(
        col.export(trace_id=root.trace_id)))
    assert not list(obs_tracing.iter_otlp_spans(
        col.export(trace_id="f" * 32)))
    # the payload is json-serializable (the /debug/spans contract)
    json.dumps(col.export())


def test_span_mutation_after_end_is_dropped():
    col = obs_tracing.SpanCollector(8)
    tr = obs_tracing.Tracer("svc", col)
    s = tr.start_span("x")
    s.end()
    end_ns = s.end_ns
    s.set_attribute("late", True)
    s.add_event("late")
    s.end()  # idempotent
    assert len(col) == 1
    assert "late" not in s.attributes and not s.events
    assert s.end_ns == end_ns


def test_ring_buffer_bounded_and_no_heap_growth():
    """Acceptance: capped buffer (<= 2048 default) and zero heap growth
    across 10k traced requests."""
    assert obs_tracing.DEFAULT_BUFFER_SPANS == 2048
    col = obs_tracing.SpanCollector(256)
    tr = obs_tracing.Tracer("svc", col)

    def one_request(i):
        root = tr.start_span("req", trace_seed=f"r{i}")
        tr.start_span("child", parent=root).end()
        root.end()

    for i in range(2000):  # warm the ring past capacity
        one_request(i)
    assert len(col) == 256
    gc.collect()
    tracemalloc.start()
    base = tracemalloc.take_snapshot()
    for i in range(10_000):
        one_request(i)
    gc.collect()
    grown = sum(st.size_diff for st in
                tracemalloc.take_snapshot().compare_to(base, "filename")
                if st.size_diff > 0)
    tracemalloc.stop()
    assert len(col) == 256  # still capped
    # ring churn allocates transiently but retains ~nothing: allow slack
    # for interpreter-internal caches only
    assert grown < 256 * 1024, f"heap grew {grown} bytes over 10k requests"


def test_kill_switch_short_circuits(monkeypatch):
    col = obs_tracing.SpanCollector(8)
    tr = obs_tracing.Tracer("svc", col)
    monkeypatch.setenv("DYNAMO_TPU_TRACE", "0")
    assert not obs_tracing.tracing_enabled()
    s = tr.start_span("x")
    assert s is obs_tracing.NOOP_SPAN
    assert not s.recording
    with s as inner:  # full surface is a no-op
        inner.set_attribute("a", 1).add_event("e").set_status("ERROR")
    assert len(col) == 0
    # a noop parent starts a NEW root once tracing is back on
    monkeypatch.setenv("DYNAMO_TPU_TRACE", "1")
    child = tr.start_span("y", parent=s)
    assert child.recording and child.parent_span_id is None
    child.end()


def test_collector_trace_ids_most_recent_first():
    col = obs_tracing.SpanCollector(16)
    tr = obs_tracing.Tracer("svc", col)
    for seed in ("a", "b", "c"):
        tr.start_span("s", trace_seed=seed).end()
    ids = col.trace_ids()
    assert ids[0] == obs_context.new_trace_id("c")
    assert ids[-1] == obs_context.new_trace_id("a")


# ----------------------------------------------------------- NATS headers --

def test_nats_header_codec_roundtrip():
    h = {"traceparent": "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01",
         "x-request-id": "r1"}
    raw = nats_mod.encode_headers(h)
    assert raw.startswith(b"NATS/1.0\r\n") and raw.endswith(b"\r\n\r\n")
    assert nats_mod.decode_headers(raw) == h
    # CR/LF smuggling is neutralized: the value cannot mint a new header
    evil = nats_mod.encode_headers({"k": "a\r\nInjected: x"})
    decoded = nats_mod.decode_headers(evil)
    assert "injected" not in decoded
    assert decoded["k"] == "a  Injected: x"
    assert nats_mod.decode_headers(None) == {}
    assert nats_mod.decode_headers(b"garbage-no-colon\r\n") == {}


def test_nats_hpub_delivers_hmsg_headers():
    broker = nats_mod.MiniNatsBroker()
    try:
        sub = nats_mod.NatsClient(broker.url, name="sub")
        pub = nats_mod.NatsClient(broker.url, name="pub")
        import queue as q_mod

        got: "q_mod.Queue" = q_mod.Queue()
        sub.subscribe("t.headers", got.put)
        import time

        time.sleep(0.1)  # let the SUB land before publishing
        pub.publish("t.headers", b"payload",
                    headers={"traceparent": "00-" + "a" * 32 + "-"
                             + "b" * 16 + "-01"})
        msg = got.get(timeout=5)
        assert msg.data == b"payload"
        assert msg.parsed_headers()["traceparent"].startswith("00-")
        # plain publishes still arrive headerless
        pub.publish("t.headers", b"plain")
        msg2 = got.get(timeout=5)
        assert msg2.data == b"plain" and msg2.headers is None
        sub.close()
        pub.close()
    finally:
        broker.close()


# ------------------------------------------------- /metrics label escaping --

def test_metrics_label_escaping_adversarial():
    """Acceptance: /metrics survives `\"`, `\\` and newline label values."""
    r = Registry()
    c = Counter("esc_total", "help", r)
    g = Gauge("esc_gauge", "help", r)
    h = Histogram("esc_hist", "help", r, buckets=(1.0,))
    evil = 'quo"te back\\slash new\nline'
    c.inc(model=evil)
    g.set(1.0, model=evil)
    h.observe(0.5, model=evil)
    text = r.expose()
    # single-line series only: the newline must be escaped, not literal
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        name, _, _ = line.partition("{")
        assert name.split("_")[0] in ("esc",), line
    assert 'model="quo\\"te back\\\\slash new\\nline"' in text
    # every series line still parses as  name{labels} value
    import re

    for line in text.splitlines():
        if line.startswith("#"):
            continue
        assert re.match(r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? \S+$', line), line


# ------------------------------------------- the frontend's 503 says why --

@pytest.mark.parametrize("case,want", [
    ("nobody_registered",
     "candidates=0, breaker_skipped=0, health_skipped=0"),
    ("breaker_open", "candidates=1, breaker_skipped=1, health_skipped=0"),
    ("unhealthy", "candidates=1, breaker_skipped=0, health_skipped=1"),
])
def test_no_live_worker_503_names_its_reason(case, want):
    """`no live worker` carries why the router had no candidate, in the
    message (the benchmark logs a refused probe's message) and on the
    request's span."""
    import urllib.error
    import urllib.request

    from dynamo_tpu.serving.api import serve_forever_in_thread
    from dynamo_tpu.serving.frontend import (
        FrontendContext, make_frontend_server,
    )

    fctx = FrontendContext()
    url = "http://127.0.0.1:9"   # nothing listens there; never dialled
    if case != "nobody_registered":
        health = {"state": "suspect"} if case == "unhealthy" else None
        fctx.router.register(url, "m", "agg", stats={
            "max_num_seqs": 4, "free_pages": 9, "total_pages": 9,
            **({"health": health} if health else {})})
    if case == "breaker_open":
        for _ in range(fctx.router.breakers.threshold):
            fctx.router.breakers.record_failure(url)
    srv = make_frontend_server(fctx, "127.0.0.1", 0)
    serve_forever_in_thread(srv)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{srv.server_address[1]}"
                "/v1/chat/completions",
                data=json.dumps({"model": "m", "messages": [
                    {"role": "user", "content": "x"}]}).encode(),
                headers={"Content-Type": "application/json"}), timeout=30)
        assert ei.value.code == 503
        message = json.loads(ei.value.read())["error"]["message"]
        assert message == f"no live worker for model 'm' ({want})"
        # the handler ends its span after the response is written: the
        # client can be here first (and then sees an earlier case's span
        # last), so give the collector a moment
        deadline = time.monotonic() + 5.0
        while True:
            spans = [sp for sp in fctx.tracer.collector.snapshot()
                     if sp.attributes.get("router.no_worker_reason")]
            if ((spans and spans[-1].attributes[
                    "router.no_worker_reason"] == want)
                    or time.monotonic() > deadline):
                break
            time.sleep(0.01)
        assert spans and spans[-1].attributes[
            "router.no_worker_reason"] == want
        assert spans[-1].status_code == "ERROR"
        assert want in spans[-1].status_message
    finally:
        srv.shutdown()
