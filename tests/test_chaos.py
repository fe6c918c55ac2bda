"""Chaos suite: drives every registered fault point through the real
serving topology (frontend + workers over real sockets) and asserts the
failure-domain invariants (ISSUE 2 / docs/robustness.md):

- bounded failover never duplicates a generation;
- the circuit breaker completes an open -> half_open -> closed cycle;
- a propagated deadline sheds with 504 + Retry-After within budget+1s;
- admission control sheds with 429 instead of queueing;
- a NATS partition falls back to HTTP;
- disagg prefill failover leaves the prefill page ledger balanced.

Runs under `make chaos-check` with a pinned DYNAMO_TPU_FAULT_SEED; the
fault plane's per-point seeded RNGs make each test's injected-failure
schedule a deterministic replay. Tests are order-dependent ONLY through
the final coverage assertion (cumulative fired_total), which is why the
Makefile target passes -p no:randomly.
"""

import http.client
import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.robustness import faults
from dynamo_tpu.robustness.breaker import BreakerBoard
from dynamo_tpu.serving.api import (
    ServingContext, make_server, serve_forever_in_thread,
)
from dynamo_tpu.serving.frontend import FrontendContext, make_frontend_server
from dynamo_tpu.serving.router import Router

MODEL = "tiny-debug"
KW = dict(model=MODEL, page_size=4, num_pages=128, max_num_seqs=4,
          max_seq_len=128)


def post(url, path, body, headers=None, timeout=60, raw=False):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST")
    resp = urllib.request.urlopen(req, timeout=timeout)
    return resp if raw else json.loads(resp.read())


def chat_body(text, max_tokens=4, **kw):
    return {"model": MODEL,
            "messages": [{"role": "user", "content": text}],
            "max_tokens": max_tokens, "temperature": 0, "ignore_eos": True,
            **kw}


@pytest.fixture(scope="module")
def stack():
    """Frontend + one agg worker over real sockets; a short-cooldown
    breaker board so the half-open transition is testable in seconds."""
    plane = faults.reset_plane()
    engine = Engine(EngineConfig(**KW))
    wctx = ServingContext(engine, MODEL)
    wsrv = make_server(wctx, "127.0.0.1", 0)
    serve_forever_in_thread(wsrv)
    worker_url = f"http://127.0.0.1:{wsrv.server_address[1]}"

    router = Router(breakers=BreakerBoard(threshold=3, cooldown_s=0.5))
    fctx = FrontendContext(router=router)
    fsrv = make_frontend_server(fctx, "127.0.0.1", 0)
    serve_forever_in_thread(fsrv)
    frontend_url = f"http://127.0.0.1:{fsrv.server_address[1]}"

    stack = {"frontend": frontend_url, "worker": worker_url,
             "fctx": fctx, "wctx": wctx, "plane": plane}
    register(stack)
    yield stack
    plane.clear()
    fsrv.shutdown()
    wsrv.shutdown()
    wctx.close()


def register(stack):
    post(stack["frontend"], "/internal/register", {
        "url": stack["worker"], "model": MODEL, "mode": "agg",
        "stats": {"max_num_seqs": 4, "free_pages": 100, "total_pages": 128},
    })


# --------------------------------------------------------------------------
# fault plane mechanics
# --------------------------------------------------------------------------
def test_fault_plane_is_seed_deterministic():
    a = faults.FaultPlane(seed=7)
    b = faults.FaultPlane(seed=7)
    c = faults.FaultPlane(seed=8)
    spec = {"nats.partition": {"times": -1, "p": 0.35}}
    for p in (a, b, c):
        p.configure(spec)
    fires = {p: [p.check("nats.partition") is not None for _ in range(200)]
             for p in (a, b, c)}
    assert fires[a] == fires[b], "same seed must replay byte-identically"
    assert fires[a] != fires[c], "different seed must diverge"
    assert any(fires[a]) and not all(fires[a])


def test_fault_plane_rejects_unknown_names():
    plane = faults.FaultPlane(seed=1)
    with pytest.raises(ValueError):
        plane.configure({"no.such.fault": {}})
    with pytest.raises(ValueError):
        plane.configure({"nats.partition": {"bogus_field": 1}})


def test_fault_http_config_roundtrip(stack):
    out = post(stack["frontend"], "/internal/faults",
               {"seed": 99, "faults": {"nats.partition": {"times": 2}}})
    assert out["armed"]["nats.partition"]["times"] == 2
    assert out["seed"] == 99
    snap = json.loads(urllib.request.urlopen(
        stack["frontend"] + "/internal/faults", timeout=10).read())
    assert "nats.partition" in snap["armed"]
    assert set(snap["registry"]) == set(faults.REGISTRY)
    with pytest.raises(urllib.error.HTTPError) as ei:
        post(stack["frontend"], "/internal/faults",
             {"faults": {"nope": {}}})
    assert ei.value.code == 400
    stack["plane"].clear()


# --------------------------------------------------------------------------
# connect-refused failover + the breaker cycle
# --------------------------------------------------------------------------
def _worker_requests_total(stack) -> float:
    m = stack["wctx"].metrics.requests_total
    with m._lock:
        return sum(m._values.values())


def test_connect_refused_fails_over_without_duplicating(stack):
    """A pre-send connect failure is retry-safe: with a second (live) route
    available the request must still succeed — and exactly one generation
    runs. The same physical worker is registered under two url aliases so
    the failover re-pick has somewhere to go."""
    plane, fctx = stack["plane"], stack["fctx"]
    register(stack)
    alias = stack["worker"].replace("127.0.0.1", "localhost")
    post(stack["frontend"], "/internal/register", {
        "url": alias, "model": MODEL, "mode": "agg",
        "stats": {"max_num_seqs": 4, "free_pages": 100, "total_pages": 128}})
    before = _worker_requests_total(stack)
    plane.configure({"frontend.connect_refused": {"times": 1}})
    out = post(stack["frontend"], "/v1/chat/completions",
               chat_body("failover probe"))
    plane.clear()
    assert out["usage"]["completion_tokens"] == 4
    assert _worker_requests_total(stack) == before + 1, \
        "failover duplicated the generation"
    # cleanup: later tests assume exactly one registered worker and a
    # clean breaker slate
    post(stack["frontend"], "/internal/deregister", {"url": alias})
    post(stack["frontend"], "/internal/deregister", {"url": stack["worker"]})
    register(stack)
    fctx.router.breakers.record_success(alias)
    fctx.router.breakers.record_success(stack["worker"])


def test_breaker_opens_half_opens_closes(stack):
    """The acceptance-criteria cycle: 3 consecutive connect failures open
    the breaker (fast-503 while open), the cooldown admits one half-open
    probe, and the probe's success closes it."""
    plane, fctx = stack["plane"], stack["fctx"]
    url = stack["worker"]
    board = fctx.router.breakers
    board.record_success(url)  # reset any state left by earlier tests

    plane.configure({"frontend.connect_refused": {"times": 3}})
    for i in range(3):
        register(stack)  # the heartbeat re-adding the flapping worker
        with pytest.raises(urllib.error.HTTPError) as ei:
            post(stack["frontend"], "/v1/chat/completions",
                 chat_body(f"breaker probe {i}"))
        assert ei.value.code == 502  # sole worker refused -> no failover left
    assert board.state(url) == "open"

    # open: the worker is not a candidate even though it is registered
    register(stack)
    with pytest.raises(urllib.error.HTTPError) as ei:
        post(stack["frontend"], "/v1/chat/completions",
             chat_body("while open"))
    assert ei.value.code == 503
    assert ei.value.headers.get("Retry-After") is not None

    # /metrics exports state 2 (open) for this worker
    metrics = urllib.request.urlopen(stack["frontend"] + "/metrics",
                                     timeout=10).read().decode()
    assert "dynamo_frontend_breaker_state" in metrics
    assert any(ln.startswith("dynamo_frontend_breaker_state{") and url in ln
               and ln.rstrip().endswith(" 2")
               for ln in metrics.splitlines())
    assert "dynamo_frontend_breaker_open_total" in metrics

    time.sleep(0.6)  # cooldown (0.5s board) elapses
    assert board.state(url) == "half_open"

    # half-open: the next pick IS the probe; the fault budget is spent, so
    # the probe succeeds and closes the breaker
    out = post(stack["frontend"], "/v1/chat/completions",
               chat_body("half-open probe"))
    assert out["usage"]["completion_tokens"] == 4
    assert board.state(url) == "closed"
    plane.clear()


def test_failed_probe_reopens_breaker():
    """Unit-level: a half-open probe failure restarts the cooldown."""
    t = [0.0]
    board = BreakerBoard(threshold=2, cooldown_s=10.0, clock=lambda: t[0])
    for _ in range(2):
        board.record_failure("u")
    assert board.state("u") == "open"
    assert not board.would_allow("u")
    t[0] += 11
    assert board.state("u") == "half_open"
    assert board.would_allow("u")
    board.on_picked("u")          # probe taken...
    assert not board.would_allow("u")  # ...only one at a time
    board.record_failure("u")     # probe failed
    assert board.state("u") == "open"
    t[0] += 11
    board.on_picked("u")
    board.record_success("u")
    assert board.state("u") == "closed"


# --------------------------------------------------------------------------
# deadline propagation
# --------------------------------------------------------------------------
def test_deadline_504_within_budget_plus_one(stack):
    """Acceptance criterion: a 2 s deadline against a stalled worker
    returns 504 within 3 s; the same request un-injected completes."""
    plane = stack["plane"]
    register(stack)
    plane.configure({"worker.read_stall": {"times": 1, "delay_s": 5.0}})
    t0 = time.monotonic()
    with pytest.raises(urllib.error.HTTPError) as ei:
        post(stack["frontend"], "/v1/chat/completions",
             chat_body("stalled"), headers={"x-deadline": "2"}, timeout=30)
    elapsed = time.monotonic() - t0
    assert ei.value.code == 504
    assert ei.value.headers.get("Retry-After") is not None
    assert elapsed < 3.0, f"deadline overshot: {elapsed:.2f}s"

    plane.clear()
    register(stack)  # the timeout deregistered nothing, but re-add anyway
    stack["fctx"].router.breakers.record_success(stack["worker"])
    out = post(stack["frontend"], "/v1/chat/completions",
               chat_body("not stalled"), headers={"x-deadline": "10"})
    assert out["usage"]["completion_tokens"] == 4


def test_exhausted_deadline_sheds_before_routing(stack):
    register(stack)
    t0 = time.monotonic()
    with pytest.raises(urllib.error.HTTPError) as ei:
        post(stack["frontend"], "/v1/chat/completions",
             chat_body("already late"), headers={"x-deadline": "0"})
    assert ei.value.code == 504
    assert time.monotonic() - t0 < 1.0
    # the worker never saw it: shed happened before the dial
    assert ei.value.headers.get("Retry-After") is not None


def test_deadline_header_reaches_worker(stack):
    """The worker's request span records the PROPAGATED (shrunken) budget,
    proving the header rode the hop rather than being re-defaulted."""
    register(stack)
    resp = post(stack["frontend"], "/v1/chat/completions",
                chat_body("carry my budget"),
                headers={"x-deadline": "33.5"}, raw=True)
    resp.read()
    trace_id = resp.headers.get("X-Request-Id")
    # the worker ends its request span after the last frame is written:
    # under load the client can ask before that, so poll briefly
    for _ in range(50):
        spans = json.loads(urllib.request.urlopen(
            stack["worker"] + f"/debug/spans?trace_id={trace_id}",
            timeout=10).read())
        worker_spans = [sp for rs in spans["resourceSpans"]
                        for ss in rs["scopeSpans"] for sp in ss["spans"]
                        if sp["name"] == "worker.request"]
        if worker_spans:
            break
        time.sleep(0.1)
    assert worker_spans, "worker.request span missing"
    attrs = {a["key"]: a["value"] for a in worker_spans[-1]["attributes"]}
    got = float(attrs["deadline_s"].get("doubleValue")
                or attrs["deadline_s"].get("intValue"))
    assert 0 < got <= 33.5, f"deadline did not propagate: {got}"


# --------------------------------------------------------------------------
# admission control
# --------------------------------------------------------------------------
def test_admission_control_429(stack):
    """With max_inflight=1, a stalled request holds the only slot and the
    next request sheds 429 + Retry-After instead of queueing."""
    plane = stack["plane"]
    register(stack)
    fctx = stack["fctx"]
    old_max = fctx.max_inflight
    fctx.max_inflight = 1
    plane.configure({"worker.read_stall": {"times": 1, "delay_s": 1.5}})
    errs = {}

    def stalled():
        try:
            post(stack["frontend"], "/v1/chat/completions",
                 chat_body("slot holder"), timeout=30)
        except urllib.error.HTTPError as e:
            errs["holder"] = e.code
    t = threading.Thread(target=stalled, daemon=True)
    try:
        t.start()
        # wait until the holder actually OCCUPIES the slot — otherwise the
        # overflow request could win the race, absorb the stall fault, and
        # the test would assert on the wrong request
        wait_until = time.monotonic() + 2.0
        while time.monotonic() < wait_until:
            with fctx._inflight_lock:
                if fctx._inflight >= 1:
                    break
            time.sleep(0.01)
        with fctx._inflight_lock:
            assert fctx._inflight >= 1, "slot holder never got admitted"
        with pytest.raises(urllib.error.HTTPError) as ei:
            post(stack["frontend"], "/v1/chat/completions",
                 chat_body("overflow"), timeout=5)
        assert ei.value.code == 429
        assert ei.value.headers.get("Retry-After") is not None
    finally:
        t.join(timeout=30)
        fctx.max_inflight = old_max
        plane.clear()
    assert errs.get("holder") is None, f"slot holder failed: {errs}"


# --------------------------------------------------------------------------
# NATS partition -> HTTP fallback
# --------------------------------------------------------------------------
def test_nats_partition_falls_back_to_http(stack):
    from dynamo_tpu.serving.nats import MiniNatsBroker, NatsClient

    plane = stack["plane"]
    register(stack)
    broker = MiniNatsBroker()
    fctx = stack["fctx"]
    assert fctx.nats is None
    fctx.nats = NatsClient(broker.url, name="chaos-frontend")
    try:
        plane.configure({"nats.partition": {"times": 1}})
        out = post(stack["frontend"], "/v1/chat/completions",
                   chat_body("partitioned"))
        assert out["usage"]["completion_tokens"] == 4
        assert plane.snapshot()["fired"]["nats.partition"] == 1
    finally:
        plane.clear()
        nc, fctx.nats = fctx.nats, None
        nc.close()
        broker.close()


# --------------------------------------------------------------------------
# crash mid-decode: truncate, never re-dispatch
# --------------------------------------------------------------------------
def test_crash_mid_decode_truncates_stream(stack):
    plane, wctx = stack["plane"], stack["wctx"]
    register(stack)
    plane.configure({"worker.crash_mid_decode": {"times": 1}})
    resp = post(stack["frontend"], "/v1/chat/completions",
                chat_body("crash me", max_tokens=16, stream=True), raw=True)
    body = resp.read().decode()
    plane.clear()
    # the stream STARTED (2xx head already on the wire) then died: the
    # error rides an SSE event, and the stream is truncated short
    assert "stream_error" in body or "[DONE]" not in body
    # invariant: the engine aborted the request — nothing left running
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and wctx.engine.num_active:
        time.sleep(0.05)
    assert wctx.engine.num_active == 0
    assert not wctx.engine.pending


def test_engine_fault_points_fire_without_false_positives(stack):
    """The engine-seam fault points (docs/robustness.md "Engine watchdog
    & quarantine") fire inside the real dispatch/readback seams. The
    heavy trip -> resurrection -> quarantine drills live in
    tests/test_watchdog.py; this drill keeps the suite-wide coverage
    invariant honest AND pins the no-false-positive side: sub-deadline
    slowness must not trip the watchdog."""
    plane, wctx = stack["plane"], stack["wctx"]
    register(stack)
    plane.configure({
        "engine.device_hang": {"times": 1, "delay_s": 0.01},
        "engine.device_slow": {"times": 1, "delay_s": 0.01},
    })
    out = post(stack["frontend"], "/v1/chat/completions",
               chat_body("sub-deadline slowness", max_tokens=4))
    assert out["choices"][0]["finish_reason"] == "length"
    assert wctx.engine.watchdog.health == "healthy", \
        "sub-deadline slowness must not trip the watchdog"
    # NaN sentinel: exactly the poisoned stream aborts, typed "error"
    plane.configure({"engine.device_nan": {"times": 1}})
    out = post(stack["frontend"], "/v1/chat/completions",
               chat_body("poison me", max_tokens=4))
    plane.clear()
    assert out["choices"][0]["finish_reason"] == "error"
    assert wctx.engine.watchdog.summary()[
        "integrity_faults_total"].get("logits", 0) >= 1
    assert wctx.engine.watchdog.health == "healthy", \
        "an integrity fault aborts the stream, never the engine"
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and wctx.engine.num_active:
        time.sleep(0.05)
    assert wctx.engine.num_active == 0


def test_reset_after_headers_is_terminal(stack):
    """Reset AFTER response headers: the request provably reached the
    worker, so the frontend answers 502 and must NOT re-dispatch."""
    plane, wctx = stack["plane"], stack["wctx"]
    register(stack)
    m = wctx.metrics.requests_total
    with m._lock:
        before = sum(m._values.values())
    plane.configure({"worker.reset_after_headers": {"times": 1}})
    with pytest.raises(urllib.error.HTTPError) as ei:
        post(stack["frontend"], "/v1/chat/completions",
             chat_body("reset me"), timeout=30)
    assert ei.value.code == 502
    assert "not retried" in json.loads(ei.value.read())["error"]["message"]
    plane.clear()
    with m._lock:
        after = sum(m._values.values())
    assert after == before + 1, "the generation ran more than once"


# --------------------------------------------------------------------------
# disagg: prefill failover under injected refusal, ledger balanced
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def disagg_stack(stack):
    """Prefill worker + decode worker (shared params so the KV handoff is
    coherent); the decode side knows the prefill under TWO url aliases so
    an injected refusal on the first pick can fail over to the second."""
    prefill_engine = Engine(
        EngineConfig(**{**KW, "disaggregation_mode": "prefill"}))
    pctx = ServingContext(prefill_engine, MODEL)
    psrv = make_server(pctx, "127.0.0.1", 0)
    serve_forever_in_thread(psrv)
    pport = psrv.server_address[1]

    decode_engine = Engine(
        EngineConfig(**{**KW, "disaggregation_mode": "decode"}),
        params=prefill_engine.params)
    dctx = ServingContext(
        decode_engine, MODEL,
        prefill_urls=[f"http://127.0.0.1:{pport}",
                      f"http://localhost:{pport}"])
    dsrv = make_server(dctx, "127.0.0.1", 0)
    serve_forever_in_thread(dsrv)
    decode_url = f"http://127.0.0.1:{dsrv.server_address[1]}"

    yield {"decode": decode_url, "prefill": f"http://127.0.0.1:{pport}",
           "pctx": pctx, "dctx": dctx, "plane": stack["plane"]}
    dsrv.shutdown()
    psrv.shutdown()
    dctx.close()
    pctx.close()


def test_disagg_prefill_failover_ledger_balanced(disagg_stack):
    plane = disagg_stack["plane"]
    pengine = disagg_stack["pctx"].engine
    plane.configure({"disagg.prefill_connect_refused": {"times": 1}})
    out = post(disagg_stack["decode"], "/v1/chat/completions",
               chat_body("disagg failover"), timeout=120)
    plane.clear()
    assert out["usage"]["completion_tokens"] == 4
    # the injected refusal was pre-send: exactly one prefill ran, and its
    # parked pages were released after the pull — the parked-KV ledger
    # must drain to empty (nothing leaked, nothing duplicated)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and pengine._parked:
        time.sleep(0.05)
    assert not pengine._parked, \
        f"prefill ledger unbalanced: parked KV leaked ({set(pengine._parked)})"


def test_slow_prefill_sheds_on_deadline(disagg_stack):
    """worker.slow_prefill eats the whole budget on the prefill side; the
    decode worker's prefill RPC times out -> 5xx shed, no infinite hold."""
    plane = disagg_stack["plane"]
    plane.configure({"worker.slow_prefill": {"times": 1, "delay_s": 3.0}})
    t0 = time.monotonic()
    with pytest.raises(urllib.error.HTTPError) as ei:
        post(disagg_stack["decode"], "/v1/chat/completions",
             chat_body("slow prefill"), headers={"x-deadline": "1.5"},
             timeout=30)
    elapsed = time.monotonic() - t0
    plane.clear()
    assert ei.value.code in (500, 503, 504)
    assert elapsed < 2.5, f"deadline overshot: {elapsed:.2f}s"


# --------------------------------------------------------------------------
# graceful drain: SIGTERM semantics (admission off, handoff, deregister)
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def drain_stack():
    """A dedicated frontend + two agg workers SHARING params, so a drain
    handoff's spliced continuation is comparable byte-for-byte."""
    eng_a = Engine(EngineConfig(**KW))
    eng_b = Engine(EngineConfig(**KW), params=eng_a.params)
    ctxs, srvs, urls = [], [], []
    for eng in (eng_a, eng_b):
        ctx = ServingContext(eng, MODEL)
        srv = make_server(ctx, "127.0.0.1", 0)
        serve_forever_in_thread(srv)
        ctxs.append(ctx)
        srvs.append(srv)
        urls.append(f"http://127.0.0.1:{srv.server_address[1]}")
    fctx = FrontendContext(router=Router())
    fsrv = make_frontend_server(fctx, "127.0.0.1", 0)
    serve_forever_in_thread(fsrv)
    yield {"frontend": f"http://127.0.0.1:{fsrv.server_address[1]}",
           "fctx": fctx, "wctxs": ctxs, "urls": urls,
           "plane": faults.get_plane()}
    fsrv.shutdown()
    for srv in srvs:
        srv.shutdown()
    for ctx in ctxs:
        ctx.close()


def _register_drain(stack, only=None):
    for url in (stack["urls"] if only is None else only):
        post(stack["frontend"], "/internal/register", {
            "url": url, "model": MODEL, "mode": "agg",
            "stats": {"max_num_seqs": 4, "free_pages": 100,
                      "total_pages": 128}})


def test_drain_rejects_new_requests_and_fails_over(drain_stack):
    """Draining worker: direct requests shed 503 + Retry-After; via the
    frontend the 503 fails over to the healthy replica, so a rolling
    restart never surfaces an error to clients."""
    ctx_a = drain_stack["wctxs"][0]
    _register_drain(drain_stack)
    ctx_a.begin_drain()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            post(drain_stack["urls"][0], "/v1/chat/completions",
                 chat_body("direct while draining"))
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After") is not None
        out = post(drain_stack["frontend"], "/v1/chat/completions",
                   chat_body("hitless failover"))
        assert out["usage"]["completion_tokens"] == 4
        # the healthy worker served it
        m = drain_stack["wctxs"][1].metrics.requests_total
        with m._lock:
            assert sum(m._values.values()) >= 1
    finally:
        ctx_a.draining.clear()


def test_drain_handoff_completes_inflight_stream(drain_stack):
    """SIGTERM mid-stream (simulated via the drain state machine the
    signal handler drives): the in-flight journaled stream hands off and
    COMPLETES byte-identically on the surviving worker; the drained
    worker deregisters cleanly and its engine quiesces."""
    plane = drain_stack["plane"]
    fctx = drain_stack["fctx"]
    ctx_a, ctx_b = drain_stack["wctxs"]
    url_a = drain_stack["urls"][0]
    # reference (both up, no drain)
    _register_drain(drain_stack)
    ref = post(drain_stack["frontend"], "/v1/chat/completions",
               chat_body("drain handoff probe", max_tokens=12,
                         stream=True), raw=True).read().decode()
    ref_content = "".join(
        (c.get("delta") or {}).get("content") or ""
        for block in ref.split("\n\n")
        if block.strip().startswith("data: ")
        and block.strip() != "data: [DONE]"
        for c in json.loads(block.strip()[len("data: "):])["choices"])

    # pin the stream to worker A, stalled long enough to drain under it
    post(drain_stack["frontend"], "/internal/deregister",
         {"url": drain_stack["urls"][1]})
    _register_drain(drain_stack, only=[url_a])
    plane.configure({"worker.read_stall": {"times": 1, "delay_s": 0.8}})
    result = {}

    def run_stream():
        try:
            resp = post(drain_stack["frontend"], "/v1/chat/completions",
                        chat_body("drain handoff probe", max_tokens=12,
                                  stream=True), raw=True, timeout=60)
            result["body"] = resp.read().decode()
        except Exception as e:  # surfaced by the main thread's asserts
            result["error"] = e

    t = threading.Thread(target=run_stream, daemon=True)
    t.start()
    wait_until = time.monotonic() + 5.0
    while time.monotonic() < wait_until:
        with fctx._inflight_lock:
            if fctx._inflight >= 1:
                break
        time.sleep(0.01)
    # SIGTERM on A: admission off, handoff in-flight, deregister
    _register_drain(drain_stack, only=[drain_stack["urls"][1]])
    try:
        ctx_a.begin_drain()
        ctx_a.request_handoff()
        post(drain_stack["frontend"], "/internal/deregister",
             {"url": url_a})
        t.join(timeout=60)
        plane.clear()
        assert "error" not in result, f"stream failed: {result.get('error')}"
        body = result["body"]
        events = [b.strip()[len("data: "):] for b in body.split("\n\n")
                  if b.strip().startswith("data: ")]
        assert events[-1] == "[DONE]", "handoff must COMPLETE the stream"
        content = "".join(
            (c.get("delta") or {}).get("content") or ""
            for e in events if e != "[DONE]"
            for c in json.loads(e)["choices"])
        assert content == ref_content, "handoff corrupted the stream"
        # deregistered cleanly: the frontend no longer lists worker A
        workers = json.loads(urllib.request.urlopen(
            drain_stack["frontend"] + "/internal/workers",
            timeout=10).read())["workers"]
        assert url_a not in [w["url"] for w in workers]
        # the drained engine quiesced (handoff aborted its half)
        assert ctx_a.drain(drain_s=5.0, handoff_grace_s=0.1)
        assert ctx_a.engine.num_active == 0 and not ctx_a.engine.pending
    finally:
        plane.clear()
        ctx_a.draining.clear()
        ctx_a.drain_handoff.clear()


# --------------------------------------------------------------------------
# HA frontend plane (ISSUE 11 acceptance; docs/robustness.md "HA frontend
# plane"): three frontend replicas over one NATS broker — worker membership
# relays fleet-wide, a frontend killed mid-stream is resumable through a
# peer byte-identically, and per-tenant QoS caps hold across the fleet.
# --------------------------------------------------------------------------
HA_TENANTS = json.dumps([
    {"name": "burst", "max_inflight": 4},
    {"name": "steady", "max_inflight": 0},   # 0 = uncapped
])


def _sse_events(text):
    return [b.strip()[len("data: "):] for b in text.split("\n\n")
            if b.strip().startswith("data: ")]


def _sse_content(events):
    return "".join(
        (c.get("delta") or {}).get("content") or ""
        for e in events if e != "[DONE]"
        for c in json.loads(e)["choices"])


def _make_ha_frontends(broker_url, n=3):
    """n FrontendContexts sharing one NATS broker, gossip threads off
    (tests drive publish_now() for determinism). The chaos workers speak
    HTTP only, so the NATS *request* plane is disarmed after construction
    (else every proxy stalls on its 5s dead-letter head timeout); the HA
    planes hold their own client reference and keep replicating."""
    saved = {k: os.environ.get(k)
             for k in ("DYNAMO_TPU_FRONTEND_ID", "DYNAMO_TPU_TENANTS")}
    os.environ["DYNAMO_TPU_TENANTS"] = HA_TENANTS
    fronts = []
    try:
        for i in range(n):
            os.environ["DYNAMO_TPU_FRONTEND_ID"] = f"fe-chaos-{i}"
            fctx = FrontendContext(router=Router(heartbeat_ttl=600.0),
                                   nats_url=broker_url,
                                   gossip_interval_s=0)
            nc = fctx.nats
            fctx.nats = None  # HTTP relay only; HA planes keep `nc`
            srv = make_frontend_server(fctx, "127.0.0.1", 0)
            serve_forever_in_thread(srv)
            fronts.append({
                "ctx": fctx, "srv": srv, "nc": nc,
                "url": f"http://127.0.0.1:{srv.server_address[1]}"})
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return fronts


def _close_ha_frontends(fronts):
    for f in fronts:
        if not f.get("dead"):
            f["srv"].shutdown()
        try:
            f["nc"].close()
        except Exception:  # noqa: BLE001 — teardown best-effort
            pass


@pytest.fixture(scope="module")
def ha_fleet():
    """Socket-light HA plane: broker + three frontend replicas, NO
    engines. Covers membership gossip and fleet-wide QoS in tier-1."""
    from dynamo_tpu.serving.nats import MiniNatsBroker

    broker = MiniNatsBroker()
    fronts = _make_ha_frontends(broker.url)
    yield {"broker": broker, "fronts": fronts}
    _close_ha_frontends(fronts)
    broker.close()


@pytest.fixture(scope="module")
def ha_stack():
    """Full HA topology for the kill-a-frontend drill: three replicas plus
    TWO agg workers SHARING params (so a cross-frontend resume is
    comparable byte-for-byte). Workers register on replica A ONLY — B and
    C must learn them through the worker-membership relay."""
    from dynamo_tpu.serving.nats import MiniNatsBroker

    broker = MiniNatsBroker()
    eng_a = Engine(EngineConfig(**KW))
    eng_b = Engine(EngineConfig(**KW), params=eng_a.params)
    wctxs, wsrvs, wurls = [], [], []
    for eng in (eng_a, eng_b):
        ctx = ServingContext(eng, MODEL)
        srv = make_server(ctx, "127.0.0.1", 0)
        serve_forever_in_thread(srv)
        wctxs.append(ctx)
        wsrvs.append(srv)
        wurls.append(f"http://127.0.0.1:{srv.server_address[1]}")
    fronts = _make_ha_frontends(broker.url)
    for wurl in wurls:
        post(fronts[0]["url"], "/internal/register", {
            "url": wurl, "model": MODEL, "mode": "agg",
            "stats": {"max_num_seqs": 4, "free_pages": 100,
                      "total_pages": 128}})
    yield {"broker": broker, "fronts": fronts, "workers": wurls,
           "wctxs": wctxs}
    _close_ha_frontends(fronts)
    for srv in wsrvs:
        srv.shutdown()
    for ctx in wctxs:
        ctx.close()
    broker.close()


def _wait_for(pred, timeout_s=10.0, what="condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    pytest.fail(f"timed out waiting for {what}")


@pytest.mark.ha
def test_ha_worker_membership_gossips_to_all_replicas(ha_fleet):
    """A register heard by ONE replica lands on all of them (source=peer);
    an explicit deregister is authoritative fleet-wide."""
    fronts = ha_fleet["fronts"]
    url = "http://192.0.2.10:8000"  # TEST-NET: registered, never dialed
    post(fronts[1]["url"], "/internal/register", {
        "url": url, "model": MODEL, "mode": "agg",
        "stats": {"max_num_seqs": 4, "free_pages": 9, "total_pages": 16}})
    for f in fronts:
        _wait_for(lambda f=f: url in [w.url for w in
                                      f["ctx"].router.alive(("agg",))],
                  what=f"register relay to {f['ctx'].frontend_id}")
    # the receiving replica holds a direct registration; its peers peer-
    # sourced copies (the TTL-churn fix keys purge accounting off this)
    with fronts[1]["ctx"].router._lock:
        assert fronts[1]["ctx"].router._workers[url].source == "direct"
    with fronts[0]["ctx"].router._lock:
        assert fronts[0]["ctx"].router._workers[url].source == "peer"
    post(fronts[1]["url"], "/internal/deregister", {"url": url})
    for f in fronts:
        _wait_for(lambda f=f: url not in [w.url for w in
                                          f["ctx"].router.alive(("agg",))],
                  what="deregister relay")


@pytest.mark.ha
def test_ha_fleet_wide_tenant_qos_over_10k_streams(ha_fleet):
    """10k admission decisions sprayed round-robin across the three
    replicas: the `burst` tenant (cap 4) holds every stream it wins and
    must end up with exactly FOUR fleet-wide — not 4 per replica — while
    the uncapped `steady` tenant is never shed. Drives the same
    FrontendContext.admit()/release() path the HTTP edge uses; gossip is
    flushed with publish_now() after every burst admission so the test is
    deterministic rather than staleness-window dependent."""
    ctxs = [f["ctx"] for f in ha_fleet["fronts"]]

    def fleet_view(ctx, tenant):
        local = ctx.tenant_admission.snapshot()["inflight"].get(tenant, 0)
        return local + ctx.tenant_gossip.peer_counts().get(tenant, 0)

    holders, shed_burst, steady_ok = [], 0, 0
    for i in range(10_000):
        ctx = ctxs[i % 3]
        if i % 2 == 0:
            ok, reason, retry_after = ctx.admit("burst")
            if ok:
                holders.append(ctx)
                ctx.tenant_gossip.publish_now()
                want = len(holders)
                for peer in ctxs:
                    _wait_for(
                        lambda peer=peer: fleet_view(peer, "burst") == want,
                        what=f"gossip convergence at {want} in-flight")
            else:
                shed_burst += 1
                assert reason == "inflight"
                assert retry_after > 0
        else:
            ok, reason, _ = ctx.admit("steady")
            assert ok, (f"steady tenant shed at i={i} ({reason}): "
                        "fleet-wide caps must never leak across tenants")
            ctx.release("steady")
            steady_ok += 1
        if i % 1000 == 999:  # keep snapshots inside the staleness bound
            for c in ctxs:
                c.tenant_gossip.publish_now()
    assert len(holders) == 4, \
        f"burst cap must bind FLEET-wide (got {len(holders)} admitted)"
    assert shed_burst == 5_000 - 4
    assert steady_ok == 5_000
    for ctx in ctxs:
        assert ctx.tenant_gossip.live_peers() == 2
    for ctx in holders:
        ctx.release("burst")
        ctx.tenant_gossip.publish_now()
    _wait_for(lambda: all(fleet_view(c, "burst") == 0 for c in ctxs),
              what="release convergence")


@pytest.mark.ha
def test_ha_kill_frontend_mid_stream_resumes_byte_identical(ha_stack):
    """THE acceptance drill: kill replica A mid-stream; the client
    reconnects to replica B with a `dynamo_resume` cursor and the spliced
    stream is byte-identical to a fault-free run. B learned the workers
    only via gossip and the seam only via the replicated journal — nothing
    from A survives except what rode NATS."""
    fronts = ha_stack["fronts"]
    a, b, c = fronts[0], fronts[1], fronts[2]
    for f in fronts:
        _wait_for(lambda f=f: len(f["ctx"].router.alive(("agg",))) == 2,
                  what="worker membership relay")
    body = chat_body("ha kill-frontend probe", max_tokens=96, stream=True)

    # fault-free reference through replica C
    ref = post(c["url"], "/v1/chat/completions", body, raw=True,
               timeout=120).read().decode()
    ref_events = _sse_events(ref)
    assert ref_events[-1] == "[DONE]"
    ref_content = _sse_content(ref_events)
    assert len(ref_content) > 8, "reference stream too short to cut"

    # stream through replica A, reading incrementally off the raw socket;
    # cut as early as possible (first content chars) so the worker is
    # still generating when the replica dies
    port_a = int(a["url"].rsplit(":", 1)[1])
    conn = http.client.HTTPConnection("127.0.0.1", port_a, timeout=60)
    conn.request("POST", "/v1/chat/completions", json.dumps(body).encode(),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    rid, delivered = None, ""
    while rid is None or len(delivered) < 2:
        line = resp.readline().decode("utf-8", "replace").strip()
        assert line != "data: [DONE]", "stream finished before the kill"
        if not line.startswith("data:"):
            continue
        chunk = json.loads(line[len("data:"):].strip())
        if rid is None and chunk.get("id"):
            rid = str(chunk["id"])
        for ch in chunk.get("choices") or []:
            delivered += (ch.get("delta") or {}).get("content") or ""
    # hard-kill A: sever the client socket AND stop the listener — from
    # here on, everything the resume needs must come from the NATS planes
    try:
        conn.sock.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass
    conn.sock.close()
    a["srv"].shutdown()
    a["dead"] = True

    # the checkpoint-before-data invariant: B's replicated journal must
    # already cover every char the client saw
    def journal_ready():
        rec = b["ctx"].journal_plane.lookup(rid)
        return (rec is not None and rec.resumable
                and rec.checkpoint_chars >= len(delivered))
    _wait_for(journal_ready, what="journal replication past the seam")

    resume_body = dict(body)
    resume_body["dynamo_resume"] = {"response_id": rid,
                                    "delivered_chars": len(delivered)}
    tail_events = _sse_events(
        post(b["url"], "/v1/chat/completions", resume_body, raw=True,
             timeout=120).read().decode())
    assert tail_events[-1] == "[DONE]", "resumed stream must COMPLETE"
    for e in tail_events:
        if e != "[DONE]":
            assert json.loads(e)["id"] == rid, \
                "the continuation must keep the original response id"
    tail = _sse_content(tail_events)
    assert delivered + tail == ref_content, \
        "cross-frontend resume must be byte-identical to the fault-free run"

    # B re-published the tombstone: a second resume of the same stream is
    # refused fleet-wide instead of re-running generation past EOS
    _wait_for(lambda: getattr(
        c["ctx"].journal_plane.lookup(rid), "done", False),
        what="done tombstone replication")
    with pytest.raises(urllib.error.HTTPError) as ei:
        post(b["url"], "/v1/chat/completions", resume_body)
    assert ei.value.code == 409
    metrics = urllib.request.urlopen(b["url"] + "/metrics",
                                     timeout=10).read().decode()
    assert 'dynamo_frontend_ha_resumes_total{outcome="resumed"}' in metrics


@pytest.mark.ha
def test_ha_frontend_metrics_scrape_valid(ha_fleet):
    """The new dynamo_frontend_ha_* families must pass the exposition
    validator in both classic and OpenMetrics form."""
    from metrics_lint import assert_valid_scrape

    base = ha_fleet["fronts"][1]["url"]
    for accept, om in ((None, False),
                       ("application/openmetrics-text", True)):
        req = urllib.request.Request(base + "/metrics")
        if accept:
            req.add_header("Accept", accept)
        text = urllib.request.urlopen(req, timeout=30).read().decode()
        assert_valid_scrape(text, openmetrics=om)
        assert "dynamo_frontend_ha_journal_streams" in text


# --------------------------------------------------------------------------
# exposition validity across every chaos topology (ISSUE 6 acceptance)
# --------------------------------------------------------------------------
def test_metrics_scrape_valid_on_every_topology(stack, disagg_stack,
                                                drain_stack):
    """After the whole suite's faults, failovers, drains and disagg
    traffic, EVERY process's /metrics page — classic text and OpenMetrics
    — must still pass the exposition validator (tests/metrics_lint.py)."""
    from metrics_lint import assert_valid_scrape

    endpoints = {
        "agg.frontend": stack["frontend"],
        "agg.worker": stack["worker"],
        "disagg.prefill": disagg_stack["prefill"],
        "disagg.decode": disagg_stack["decode"],
        "drain.frontend": drain_stack["frontend"],
        "drain.worker_a": drain_stack["urls"][0],
        "drain.worker_b": drain_stack["urls"][1],
    }
    for who, base in endpoints.items():
        for accept, om in ((None, False),
                           ("application/openmetrics-text", True)):
            req = urllib.request.Request(base + "/metrics")
            if accept:
                req.add_header("Accept", accept)
            text = urllib.request.urlopen(req, timeout=30).read().decode()
            try:
                assert_valid_scrape(text, openmetrics=om)
            except AssertionError as e:
                raise AssertionError(f"{who} ({accept or 'text'}): {e}")


# --------------------------------------------------------------------------
# coverage: every registered fault point fired at least once
# --------------------------------------------------------------------------
def test_every_fault_point_fired(stack, disagg_stack):
    fired = stack["plane"].snapshot()["fired_total"]
    missing = [n for n in faults.REGISTRY if not fired.get(n)]
    assert not missing, (
        f"fault points never triggered by this suite: {missing} "
        f"(fired: {fired})")
