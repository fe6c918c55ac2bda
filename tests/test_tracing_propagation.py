"""End-to-end distributed trace propagation (ISSUE 1 acceptance):

one request served through the disagg path (frontend -> decode worker ->
prefill worker, real HTTP) yields ONE trace with >= 5 spans across >= 3
components, retrievable from /debug/spans?trace_id=..., with correct
parent/child links and monotonic timestamps; the context also survives a
NATS-plane round trip via message headers; `traceparent` round-trips
byte-exactly through the whole stack."""

import json
import threading
import time
import urllib.request

import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.observability import context as obs_context
from dynamo_tpu.observability import tracing as obs_tracing

KW = dict(model="tiny-debug", page_size=4, num_pages=64, max_num_seqs=4,
          max_seq_len=64)


def _post_chat(base, content, headers=None, max_tokens=6):
    body = {"model": "tiny-debug",
            "messages": [{"role": "user", "content": content}],
            "max_tokens": max_tokens, "temperature": 0, "ignore_eos": True}
    h = {"Content-Type": "application/json"}
    h.update(headers or {})
    return urllib.request.urlopen(urllib.request.Request(
        f"{base}/v1/chat/completions", data=json.dumps(body).encode(),
        headers=h), timeout=120)


def _spans_for(base, trace_id, min_spans, deadline_s=10.0, require=()):
    """Poll /debug/spans until the trace has at least `min_spans` AND every
    span name in `require` — span ends race the response write by
    microseconds, and e.g. frontend.request only lands in the collector
    AFTER the client has read the full body, so counting alone can return
    a snapshot that satisfies min_spans from worker spans only."""
    deadline = time.monotonic() + deadline_s
    spans = []
    while time.monotonic() < deadline:
        with urllib.request.urlopen(
                f"{base}/debug/spans?trace_id={trace_id}", timeout=10) as r:
            payload = json.loads(r.read())
        spans = [(rs["resource"]["attributes"][0]["value"]["stringValue"], sp)
                 for rs in payload["resourceSpans"]
                 for ss in rs["scopeSpans"]
                 for sp in ss["spans"]]
        if (len(spans) >= min_spans
                and set(require) <= {sp["name"] for _, sp in spans}):
            return payload, spans
        time.sleep(0.05)
    return payload, spans


@pytest.fixture(scope="module")
def disagg_stack():
    """frontend + prefill + decode workers over real HTTP (the
    tests/test_disagg.py topology, tracing-focused)."""
    from dynamo_tpu.serving.api import (
        ServingContext, make_server, serve_forever_in_thread,
    )
    from dynamo_tpu.serving.frontend import FrontendContext, make_frontend_server

    shared = Engine(EngineConfig(**KW))  # shared params only
    pe = Engine(EngineConfig(**{**KW, "disaggregation_mode": "prefill",
                                "disaggregation_bootstrap_port": 0}),
                params=shared.params)
    pctx = ServingContext(pe, "tiny-debug")
    psrv = make_server(pctx, "127.0.0.1", 0)
    serve_forever_in_thread(psrv)
    prefill_url = f"http://127.0.0.1:{psrv.server_address[1]}"

    de = Engine(EngineConfig(**{**KW, "disaggregation_mode": "decode"}),
                params=shared.params)
    dctx = ServingContext(de, "tiny-debug", prefill_urls=[prefill_url])
    dsrv = make_server(dctx, "127.0.0.1", 0)
    serve_forever_in_thread(dsrv)
    decode_url = f"http://127.0.0.1:{dsrv.server_address[1]}"

    fctx = FrontendContext()
    fsrv = make_frontend_server(fctx, "127.0.0.1", 0)
    serve_forever_in_thread(fsrv)
    frontend_url = f"http://127.0.0.1:{fsrv.server_address[1]}"
    for url, mode in ((prefill_url, "prefill"), (decode_url, "decode")):
        body = json.dumps({"url": url, "model": "tiny-debug", "mode": mode,
                           "stats": {"max_num_seqs": 4, "free_pages": 60,
                                     "total_pages": 64}}).encode()
        urllib.request.urlopen(urllib.request.Request(
            frontend_url + "/internal/register", data=body,
            headers={"Content-Type": "application/json"}), timeout=10)

    yield {"frontend": frontend_url, "decode": decode_url,
           "prefill": prefill_url}
    fsrv.shutdown()
    dsrv.shutdown()
    psrv.shutdown()
    dctx.close()
    pctx.close()


def test_disagg_trace_spans_three_components(disagg_stack):
    frontend = disagg_stack["frontend"]
    resp = _post_chat(frontend, "trace me through disagg")
    out = json.loads(resp.read())
    assert out["usage"]["completion_tokens"] == 6
    trace_id = resp.headers.get("X-Request-Id")
    assert trace_id and len(trace_id) == 32, \
        "minted x-request-id should be the trace id"

    payload, spans = _spans_for(
        frontend, trace_id, min_spans=5,
        require=("frontend.request", "router.pick", "worker.request",
                 "disagg.prefill_rpc", "disagg.kv_pull",
                 "worker.prefill_only", "worker.decode"))
    names = {sp["name"] for _, sp in spans}
    services = {svc for svc, _ in spans}

    # >= 5 spans across >= 3 distinct components
    assert len(spans) >= 5, names
    assert {"frontend", "worker-decode", "worker-prefill"} <= services
    assert {"frontend.request", "router.pick", "worker.request",
            "disagg.prefill_rpc", "disagg.kv_pull",
            "worker.prefill_only", "worker.decode"} <= names

    # one trace: every span carries the advertised trace id
    assert all(sp["traceId"] == trace_id for _, sp in spans)

    # parent/child links resolve inside the trace, and the hierarchy is
    # the real call chain
    by_id = {sp["spanId"]: sp for _, sp in spans}
    by_name = {sp["name"]: sp for _, sp in spans}
    for _, sp in spans:
        if sp["parentSpanId"]:
            assert sp["parentSpanId"] in by_id, \
                f"dangling parent for {sp['name']}"
    assert by_name["frontend.request"]["parentSpanId"] == ""
    assert by_name["router.pick"]["parentSpanId"] == \
        by_name["frontend.request"]["spanId"]
    decode_req = next(sp for svc, sp in spans
                      if svc == "worker-decode"
                      and sp["name"] == "worker.request")
    assert decode_req["parentSpanId"] == \
        by_name["frontend.request"]["spanId"]
    assert by_name["disagg.prefill_rpc"]["parentSpanId"] == \
        decode_req["spanId"]
    prefill_req = next(sp for svc, sp in spans
                       if svc == "worker-prefill"
                       and sp["name"] == "worker.request")
    assert prefill_req["parentSpanId"] == \
        by_name["disagg.prefill_rpc"]["spanId"]
    assert by_name["worker.prefill_only"]["parentSpanId"] == \
        prefill_req["spanId"]

    # monotonic timestamps: every span ends at/after it starts, and no
    # child starts before its parent (all one process here, so the clocks
    # are directly comparable)
    for _, sp in spans:
        assert int(sp["startTimeUnixNano"]) <= int(sp["endTimeUnixNano"]), \
            sp["name"]
        if sp["parentSpanId"] and sp["parentSpanId"] in by_id:
            parent = by_id[sp["parentSpanId"]]
            assert int(sp["startTimeUnixNano"]) >= \
                int(parent["startTimeUnixNano"]) - 1_000_000, \
                f"{sp['name']} starts before its parent"

    # the same trace is visible from the WORKERS' /debug/spans too
    _, dspans = _spans_for(disagg_stack["decode"], trace_id, min_spans=5)
    assert {sp["name"] for _, sp in dspans} >= {"worker.request",
                                                "disagg.kv_pull"}


def test_inbound_traceparent_honored_byte_exact(disagg_stack):
    frontend = disagg_stack["frontend"]
    parent = obs_context.TraceContext.new("client-root")
    header = parent.to_traceparent()
    resp = _post_chat(frontend, "client-supplied trace context",
                      headers={"traceparent": header,
                               "x-request-id": "client-rid-1"})
    json.loads(resp.read())
    # inbound x-request-id echoes back byte-exact
    assert resp.headers.get("X-Request-Id") == "client-rid-1"

    _, spans = _spans_for(frontend, parent.trace_id, min_spans=5,
                          require=("frontend.request",))
    assert spans, "spans must join the CLIENT's trace id"
    by_name = {sp["name"]: sp for _, sp in spans}
    fr = by_name["frontend.request"]
    # the frontend span hangs off the client's exact span id — i.e. the
    # traceparent header survived parse/format byte-exactly
    assert fr["traceId"] == parent.trace_id
    assert fr["parentSpanId"] == parent.span_id
    assert obs_context.parse_traceparent(header).to_traceparent() == header


def test_trace_kill_switch_e2e(disagg_stack, monkeypatch):
    monkeypatch.setenv("DYNAMO_TPU_TRACE", "0")
    frontend = disagg_stack["frontend"]
    resp = _post_chat(frontend, "untraced request goes through")
    out = json.loads(resp.read())
    assert out["usage"]["completion_tokens"] == 6
    rid = resp.headers.get("X-Request-Id")
    assert rid  # request ids still mint with tracing off
    monkeypatch.setenv("DYNAMO_TPU_TRACE", "1")
    # no spans were recorded for it (x-request-id seeds the trace id
    # deterministically, so we know exactly where they would have been)
    would_be = obs_context.new_trace_id(rid)
    time.sleep(0.2)
    with urllib.request.urlopen(
            f"{frontend}/debug/spans?trace_id={would_be}", timeout=10) as r:
        payload = json.loads(r.read())
    assert not list(obs_tracing.iter_otlp_spans(payload))


def test_nats_plane_roundtrip_preserves_trace():
    """frontend -> NATS (HPUB message headers) -> worker loopback HTTP:
    the worker's spans must join the frontend's trace."""
    from dynamo_tpu.serving.api import ServingContext, make_server
    from dynamo_tpu.serving.frontend import (
        FrontendContext, make_frontend_server,
    )
    from dynamo_tpu.serving.nats import MiniNatsBroker
    from dynamo_tpu.serving.nats_plane import WorkerNatsPlane
    from dynamo_tpu.serving.router import Router

    broker = MiniNatsBroker()
    wctx = ServingContext(
        Engine(EngineConfig(**{**KW, "max_num_seqs": 2})),
        served_model="tiny-debug")
    wsrv = make_server(wctx, host="127.0.0.1", port=0)
    threading.Thread(target=wsrv.serve_forever, daemon=True).start()
    worker_url = f"http://127.0.0.1:{wsrv.server_address[1]}"
    plane = WorkerNatsPlane(broker.url, worker_url, "tiny-debug")

    router = Router(heartbeat_ttl=float("inf"))
    router.register(worker_url, "tiny-debug", "agg")
    fctx = FrontendContext(router, nats_url=broker.url)
    fsrv = make_frontend_server(fctx, host="127.0.0.1", port=0)
    threading.Thread(target=fsrv.serve_forever, daemon=True).start()
    frontend = f"http://127.0.0.1:{fsrv.server_address[1]}"
    time.sleep(0.1)
    try:
        resp = _post_chat(frontend, "over the nats plane")
        out = json.loads(resp.read())
        assert out["usage"]["completion_tokens"] == 6
        trace_id = resp.headers.get("X-Request-Id")
        assert trace_id and len(trace_id) == 32

        _, spans = _spans_for(frontend, trace_id, min_spans=4,
                              require=("frontend.request", "worker.request"))
        by_name = {sp["name"]: (svc, sp) for svc, sp in spans}
        assert "frontend.request" in by_name
        svc, fr = by_name["frontend.request"]
        assert any(a["key"] == "transport"
                   and a["value"]["stringValue"] == "nats"
                   for a in fr["attributes"]), \
            "request must actually have ridden the NATS plane"
        # worker joined the same trace THROUGH the NATS message headers
        svc_w, wr = by_name["worker.request"]
        assert svc_w == "worker-agg"
        assert wr["traceId"] == trace_id
        assert wr["parentSpanId"] == fr["spanId"]
        assert {"worker.queue", "worker.prefill", "worker.decode"} <= set(
            by_name), "engine phase bridge spans missing"
    finally:
        fsrv.shutdown()
        plane.close()
        wsrv.shutdown()
        wctx.close()
        broker.close()
