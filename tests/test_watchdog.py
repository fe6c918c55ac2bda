"""Engine watchdog & device-fault quarantine suite (`make watchdog-check`,
marker `watchdog`).

Covers docs/robustness.md "Engine watchdog & quarantine" end to end:

- unit: deadline derivation (floor -> EWMA x margin -> env/ctor override),
  one trip per arming, the healthy/suspect/resurrecting/quarantined state
  machine (second trip inside DYNAMO_TPU_QUARANTINE_WINDOW_S quarantines
  permanently; quarantine is terminal), sentinels count without changing
  health — all driven through the injectable clock, no engine;
- engine: a fatal step trips + resurrects inline byte-identically, and a
  repeat inside the window quarantines; the KV-page checksum sentinel
  (DYNAMO_TPU_INTEGRITY=full) drops a corrupted demoted block and the
  recompute path recovers byte-identically;
- serving: a quarantined worker sheds /v1/* with Retry-After, fails
  /ready + /health while /live stays 200, refuses /internal/rollout
  fast, and still reports state on /worker/stats + /metrics;
- router: heartbeat health filters suspect/quarantined workers out of
  pick() (explain carries health_skipped);
- planner/operator: the frontend's per-worker health gauge parses into
  quarantined counts/URLs, and quarantine_tick deletes exactly the
  quarantined pod (by podIP) so the Deployment replaces it;
- chaos drills (fault plane, DYNAMO_TPU_FAULT_SEED pinned by the make
  gate): engine.device_nan poisons exactly one stream (finish_reason
  "error") while the co-batched tenant completes byte-identically; an
  engine.device_hang blows the step deadline — the stream hands off and
  resumes byte-identically on a peer while the wedged engine resurrects
  in place and serves again.

The engine-boot drills are demoted to the slow tier via
tests/slow_tier.txt; `make watchdog-check` runs everything here
directly. The cheap no-false-positive invariant (sub-deadline
engine.device_slow never trips) lives in tier-1 test_chaos.py.
"""

import copy
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from dynamo_tpu.robustness import faults
from dynamo_tpu.robustness.watchdog import (
    DEADLINE_ENV, HEALTH_CODES, INTEGRITY_ENV, QUARANTINE_WINDOW_ENV,
    EngineWatchdog, integrity_mode,
)

pytestmark = pytest.mark.watchdog

MODEL = "tiny-debug"
KW = dict(model=MODEL, page_size=4, num_pages=128, max_num_seqs=4,
          max_seq_len=128)
PROMPT = [3, 1, 4, 1, 5, 9, 2, 6]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# unit: deadline derivation
# ---------------------------------------------------------------------------
def test_deadline_floor_then_ewma_then_override():
    clk = FakeClock()
    wd = EngineWatchdog(clock=clk)
    try:
        # pre-EWMA: the floor alone (warmup steps must not trip)
        assert wd.deadline_s() == wd.floor_s

        wd.device_enter("dispatch")
        clk.t += 0.5
        wd.device_exit("dispatch")
        assert wd.summary()["ewma_s"] == pytest.approx(0.5)
        assert wd.deadline_s() == pytest.approx(
            max(wd.floor_s, 0.5 * wd.margin))

        # EWMA folds (alpha=0.2): 0.8*0.5 + 0.2*0.1
        wd.device_enter("dispatch")
        clk.t += 0.1
        wd.device_exit("dispatch")
        assert wd.summary()["ewma_s"] == pytest.approx(0.42)
        assert wd.deadline_s() == pytest.approx(
            max(wd.floor_s, 0.42 * wd.margin))
    finally:
        wd.stop()

    # ctor override beats the EWMA
    wd2 = EngineWatchdog(deadline_s=1.25, clock=clk)
    wd2.device_enter("d")
    clk.t += 9.0
    wd2.device_exit("d")
    assert wd2.deadline_s() == 1.25
    wd2.stop()


def test_slow_window_that_comes_back_is_not_a_hang():
    """ROADMAP S1: decode seams of milliseconds hold the EWMA down, so the
    floor is the deadline; a drained 16-step window of 0.2 s that took 2.5 s
    once (a slow episode of the device, seen on the chip) must stay under
    it, with the 20x margin to spare over a healthy one of 0.4 s."""
    clk = FakeClock()
    wd = EngineWatchdog(clock=clk)
    try:
        for _ in range(50):  # the EWMA a serving engine has: ~10 ms seams
            wd.device_enter("dispatch")
            clk.t += 0.010
            wd.device_exit("dispatch")
        assert wd.deadline_s() == wd.floor_s
        assert wd.floor_s >= wd.margin * 0.4
        assert wd.floor_s > 2.5
    finally:
        wd.stop()


def test_env_knobs_configure_deadline_and_window(monkeypatch):
    monkeypatch.setenv(DEADLINE_ENV, "3.5")
    monkeypatch.setenv(QUARANTINE_WINDOW_ENV, "42")
    wd = EngineWatchdog()
    assert wd.deadline_s() == 3.5
    assert wd.quarantine_window_s == 42.0
    wd.stop()
    # garbage degrades to the derived deadline, not a crash
    monkeypatch.setenv(DEADLINE_ENV, "not-a-number")
    wd = EngineWatchdog()
    assert wd.deadline_s() == wd.floor_s
    wd.stop()
    monkeypatch.setenv(INTEGRITY_ENV, "full")
    assert integrity_mode() == "full"
    monkeypatch.setenv(INTEGRITY_ENV, "bogus")
    assert integrity_mode() == "logits"  # unknown -> default


def test_tripped_seam_never_poisons_the_ewma():
    clk = FakeClock()
    wd = EngineWatchdog(quarantine_window_s=10.0, clock=clk)
    try:
        wd.device_enter("dispatch")
        clk.t += 0.2
        wd.device_exit("dispatch")
        ewma = wd.summary()["ewma_s"]
        # a seam the monitor tripped folds nothing on its late return
        wd.device_enter("dispatch")
        with wd._lock:
            wd._armed[2] = True  # as the monitor marks it
        clk.t += 500.0
        wd.device_exit("dispatch")
        assert wd.summary()["ewma_s"] == ewma
    finally:
        wd.stop()


# ---------------------------------------------------------------------------
# unit: monitor + state machine
# ---------------------------------------------------------------------------
def test_monitor_trips_once_per_arming():
    wd = EngineWatchdog(deadline_s=0.05)  # real clock: drive the monitor
    trips = []
    wd.on_trip = lambda kind, seam: trips.append((kind, seam))
    try:
        wd.device_enter("dispatch")
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and wd.health == "healthy":
            time.sleep(0.01)
        assert wd.health == "suspect"
        # one trip per arming: the monitor must not machine-gun the seam
        time.sleep(0.2)
        assert wd.summary()["trips_total"] == {"hung_dispatch": 1}
        assert trips == [("hung_dispatch", "dispatch")]
        last = wd.summary()["last_trip"]
        assert last["kind"] == "hung_dispatch" and last["seam"] == "dispatch"
        wd.device_exit("dispatch")
    finally:
        wd.stop()


def test_second_trip_inside_window_quarantines_terminally():
    clk = FakeClock()
    states = []
    wd = EngineWatchdog(quarantine_window_s=10.0, clock=clk)
    wd.on_health = states.append
    try:
        wd.trip("hung_dispatch", seam="dispatch", escalate=False)
        assert wd.health == "suspect" and not wd.ok_for_traffic
        clk.t += 5.0  # inside the window
        wd.trip("fatal_step", seam="step", escalate=False)
        assert wd.health == "quarantined"
        assert wd.health_code == HEALTH_CODES["quarantined"] == 3
        # terminal: nothing leaves quarantine, not even a resurrection
        assert not wd._transition("healthy")
        assert not wd._transition("resurrecting")
        clk.t += 1000.0
        wd.trip("hung_dispatch", escalate=False)
        assert wd.health == "quarantined"
        assert states == ["suspect", "quarantined"]
        assert wd.summary()["trips_total"] == {"hung_dispatch": 2,
                                               "fatal_step": 1}
    finally:
        wd.stop()


def test_trip_outside_window_stays_suspect():
    clk = FakeClock()
    wd = EngineWatchdog(quarantine_window_s=10.0, clock=clk)
    try:
        wd.trip("hung_dispatch", escalate=False)
        clk.t += 100.0  # the first trip ages out of the window
        wd.trip("hung_dispatch", escalate=False)
        assert wd.health == "suspect"
    finally:
        wd.stop()


def test_integrity_faults_count_without_health_change():
    wd = EngineWatchdog()
    try:
        wd.record_integrity_fault("logits", ["r-1"], where="prefill")
        wd.record_integrity_fault("kv_checksum", [], block="deadbeef")
        wd.record_integrity_fault("logits", ["r-2"], where="prefill")
        assert wd.health == "healthy" and wd.ok_for_traffic
        assert wd.summary()["integrity_faults_total"] == {
            "logits": 2, "kv_checksum": 1}
    finally:
        wd.stop()


# ---------------------------------------------------------------------------
# router: heartbeat health filters pick()
# ---------------------------------------------------------------------------
def test_router_skips_suspect_and_quarantined_workers():
    from dynamo_tpu.serving.router import Router

    r = Router()
    stats = {"max_num_seqs": 4, "free_pages": 100, "total_pages": 128}
    r.register("http://a", MODEL, "agg",
               stats={**stats, "health": {"state": "quarantined"}})
    r.register("http://b", MODEL, "agg",
               stats={**stats, "health": "suspect"})
    r.register("http://c", MODEL, "agg", stats=dict(stats))  # pre-watchdog
    for i in range(8):
        explain = {}
        w = r.pick(MODEL, f"k{i}", explain=explain)
        assert w is not None and w.url == "http://c"
        assert explain["health_skipped"] == 2
    # every replica unhealthy: shed at the frontend, don't pick a corpse
    r.deregister("http://c")
    assert r.pick(MODEL, "kx") is None


# ---------------------------------------------------------------------------
# planner signals + operator replacement
# ---------------------------------------------------------------------------
def test_parse_metrics_counts_quarantined_workers():
    from dynamo_tpu.planner.signals import PoolSignals, parse_metrics_text

    page = (
        "dynamo_frontend_queued_requests 3\n"
        'dynamo_frontend_worker_health{worker="http://10.0.0.5:8000"} 3\n'
        'dynamo_frontend_worker_health{worker="http://10.0.0.6:8000"} 0\n'
        'dynamo_frontend_worker_health{worker="http://10.0.0.7:8000"} 1\n'
    )
    out = parse_metrics_text(page)
    assert out["quarantined"] == 1
    assert out["quarantined_workers"] == ["http://10.0.0.5:8000"]
    # suspect (1) and resurrecting (2) are transient: not dead capacity
    assert PoolSignals().quarantined == 0


def _quarantine_dgd(mat):
    return {
        "apiVersion": mat.API_VERSION,
        "kind": mat.DGD_KIND,
        "metadata": {"name": "quar-demo", "namespace": "dynamo",
                     "uid": "u-q1"},
        "spec": {"services": {
            "Frontend": {"componentType": "frontend", "replicas": 1},
            "Worker": {"componentType": "worker", "replicas": 2},
        }},
    }


def _pod(mat, name, ip, labels):
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": "dynamo",
                         "labels": dict(labels)},
            "status": {"podIP": ip}}


def test_operator_quarantine_tick_replaces_exactly_the_victim_pod():
    from dynamo_tpu.operator import materialize as mat
    from dynamo_tpu.operator.controller import Controller
    from dynamo_tpu.operator.k8s_client import K8sClient
    from dynamo_tpu.planner.signals import SignalsCollector
    from tests.fake_k8s import FakeK8s

    page = {"body": (
        'dynamo_frontend_worker_health{worker="http://10.0.0.5:8000"} 3\n'
        'dynamo_frontend_worker_health{worker="http://10.0.0.6:8000"} 0\n'
    )}
    with FakeK8s() as fake:
        client = K8sClient(fake.url)
        ctrl = Controller(client, namespace=None)
        ctrl.collector = SignalsCollector(
            fetch=lambda url, timeout_s: page["body"])
        client.create(mat.API_VERSION, mat.DGD_PLURAL, "dynamo",
                      _quarantine_dgd(mat))
        labels = {mat.NS_LABEL: mat.discovery_label_value("dynamo",
                                                          "quar-demo")}
        client.create("v1", "pods", "dynamo",
                      _pod(mat, "quar-demo-worker-a", "10.0.0.5", labels))
        client.create("v1", "pods", "dynamo",
                      _pod(mat, "quar-demo-worker-b", "10.0.0.6", labels))
        # an unrelated pod on the victim IP's namespace, different DGD
        client.create("v1", "pods", "dynamo",
                      _pod(mat, "bystander", "10.0.0.5",
                           {mat.NS_LABEL: "other"}))

        assert ctrl.quarantine_tick() == 1
        names = {p["metadata"]["name"]
                 for p in client.list("v1", "pods", "dynamo")}
        assert names == {"quar-demo-worker-b", "bystander"}

        # idempotent: the victim is already gone
        assert ctrl.quarantine_tick() == 0
        # an all-healthy fleet deletes nothing
        page["body"] = ('dynamo_frontend_worker_health'
                        '{worker="http://10.0.0.6:8000"} 0\n')
        assert ctrl.quarantine_tick() == 0
        assert {p["metadata"]["name"]
                for p in client.list("v1", "pods", "dynamo")} == names


# ---------------------------------------------------------------------------
# engine-level drills (slow tier; `make watchdog-check` runs them directly)
# ---------------------------------------------------------------------------
def _engine(**kw):
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import Engine

    base = dict(**KW, seed=0)
    base.update(kw)
    params = base.pop("params", None)
    if params is not None:
        return Engine(EngineConfig(**base), params=params)
    return Engine(EngineConfig(**base))


def _greedy(eng, rid, max_tokens=10):
    from dynamo_tpu.engine.request import GenRequest

    return eng.generate(GenRequest(rid, list(PROMPT),
                                   max_tokens=max_tokens, temperature=0.0,
                                   ignore_eos=True))


@pytest.mark.parametrize("quantization", [None, "w8a8"])
def test_fatal_step_inline_resurrection_then_quarantine(quantization):
    """w8a8 too: a quantized weight is a (values, scales) pair, and a
    resurrection that cannot restage it quarantines the worker for good
    (seen on the chip, PERF.md PR 25: every request after it got 503)."""
    faults.reset_plane()
    eng = _engine(**({"quantization": quantization} if quantization else {}))
    ref = _greedy(eng, "r0")

    # first fatal step: trip -> inline resurrection -> healthy, and the
    # rebuilt device state generates byte-identically
    eng.watchdog.on_fatal_step(RuntimeError("injected fatal step"))
    assert eng.watchdog.health == "healthy"
    assert eng.watchdog.summary()["trips_total"]["fatal_step"] == 1
    assert _greedy(eng, "r1") == ref

    # second fatal step inside the window: permanent quarantine
    eng.watchdog.on_fatal_step(RuntimeError("injected again"))
    assert eng.watchdog.health == "quarantined"
    assert not eng.watchdog.ok_for_traffic


def test_kv_checksum_sentinel_recovers_byte_identical(monkeypatch):
    monkeypatch.setenv(INTEGRITY_ENV, "full")
    faults.reset_plane()
    prefix = [(i * 7) % 290 + 3 for i in range(24)]
    other = [(i * 11) % 290 + 3 for i in range(30)]
    from dynamo_tpu.engine.request import GenRequest

    eng = _engine(num_pages=13, max_num_seqs=2, max_seq_len=64,
                  prefill_chunk_tokens=8, kvbm_host_blocks=32)
    assert eng.kvbm._checksum, "INTEGRITY=full must arm KV checksums"

    def gen(rid, toks):
        return eng.generate(GenRequest(rid, toks, max_tokens=4,
                                       temperature=0.0, ignore_eos=True))

    out1 = gen("t1", prefix)
    gen("fill", other)  # evicts (demotes) the prefix blocks to host
    assert eng.kvbm.stats()["demoted_blocks_total"] > 0
    assert eng.kvbm._crc, "demote must have recorded page checksums"
    # silent data corruption on the host tier: every stored CRC lies
    for h in list(eng.kvbm._crc):
        eng.kvbm._crc[h] ^= 1
    out2 = gen("t2", prefix)
    wd = eng.watchdog.summary()
    assert wd["integrity_faults_total"].get("kv_checksum", 0) >= 1, \
        "onboard must have caught the corrupted block"
    assert out2 == out1, \
        "the recompute path must recover byte-identically"
    assert eng.watchdog.health == "healthy"  # sentinel, not a trip


# ---------------------------------------------------------------------------
# serving drills over real sockets (slow tier)
# ---------------------------------------------------------------------------
def post(url, path, body, timeout=60, raw=False):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    resp = urllib.request.urlopen(req, timeout=timeout)
    return resp if raw else json.loads(resp.read())


def post_status(url, path, body, timeout=10):
    """Like post() but returns (status, body_bytes, headers) and never
    raises on HTTP errors — the shed-path probe."""
    try:
        resp = post(url, path, body, timeout=timeout, raw=True)
        return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def get_status(url, path, timeout=10):
    try:
        resp = urllib.request.urlopen(url + path, timeout=timeout)
        return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _sse_content(body):
    events = [b.strip()[len("data: "):] for b in body.split("\n\n")
              if b.strip().startswith("data: ")]
    assert events and events[-1] == "[DONE]", "stream must COMPLETE"
    return "".join(
        (c.get("delta") or {}).get("content") or ""
        for e in events if e != "[DONE]"
        for c in json.loads(e)["choices"])


def chat_body(text, max_tokens=4, **kw):
    return {"model": MODEL,
            "messages": [{"role": "user", "content": text}],
            "max_tokens": max_tokens, "temperature": 0, "ignore_eos": True,
            **kw}


def test_quarantined_worker_sheds_and_fails_readiness():
    from dynamo_tpu.serving.api import (
        ServingContext, make_server, serve_forever_in_thread,
    )

    faults.reset_plane()
    eng = _engine()
    ctx = ServingContext(eng, MODEL)
    srv = make_server(ctx, "127.0.0.1", 0)
    serve_forever_in_thread(srv)
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        assert get_status(url, "/ready")[0] == 200
        # two trips inside the (default 300s) window -> terminal
        eng.watchdog.trip("hung_dispatch", seam="dispatch", escalate=False)
        eng.watchdog.trip("hung_dispatch", seam="dispatch", escalate=False)
        assert eng.watchdog.health == "quarantined"

        # liveness stays green (don't crash-loop a pod the operator is
        # about to replace deliberately); readiness + health go red
        assert get_status(url, "/live")[0] == 200
        assert get_status(url, "/ready")[0] == 503
        assert get_status(url, "/health")[0] == 503

        # /v1/* sheds with Retry-After so the frontend retries a peer
        code, body, headers = post_status(
            url, "/v1/chat/completions", chat_body("shed me"))
        assert code == 503
        assert headers.get("Retry-After")
        assert b"quarantined" in body

        # a rollout must fail fast, not park on a dead engine's lock
        code, body, _ = post_status(url, "/internal/rollout",
                                    {"action": "status"})
        assert code == 503

        # observability of last resort still serves
        st, body = get_status(url, "/worker/stats")
        assert st == 200
        assert json.loads(body)["health"]["state"] == "quarantined"
        st, body = get_status(url, "/metrics")
        assert st == 200
        assert b"dynamo_engine_health 3" in body
    finally:
        srv.shutdown()
        ctx.close()


@pytest.fixture(scope="module")
def watchdog_stack():
    """Frontend + two workers SHARING params (handoff splices must be
    byte-comparable across the pair)."""
    from dynamo_tpu.serving.api import (
        ServingContext, make_server, serve_forever_in_thread,
    )
    from dynamo_tpu.serving.frontend import (
        FrontendContext, make_frontend_server,
    )
    from dynamo_tpu.serving.router import Router

    plane = faults.reset_plane()
    eng_a = _engine()
    eng_b = _engine(params=eng_a.params)
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
           "fctx": fctx, "wctxs": ctxs, "urls": urls, "plane": plane}
    plane.clear()
    fsrv.shutdown()
    for srv in srvs:
        srv.shutdown()
    for ctx in ctxs:
        ctx.close()


def _register(stack, only=None):
    for url in (stack["urls"] if only is None else only):
        post(stack["frontend"], "/internal/register", {
            "url": url, "model": MODEL, "mode": "agg",
            "stats": {"max_num_seqs": 4, "free_pages": 100,
                      "total_pages": 128}})


def test_nan_sentinel_aborts_exactly_the_poisoned_stream(watchdog_stack):
    """Co-tenancy: a NaN forward poisons stream 2's prefill — stream 2
    finishes "error", while co-batched stream 1 decodes on untouched and
    completes byte-identical to a fault-free run."""
    plane = watchdog_stack["plane"]
    ctx_a = watchdog_stack["wctxs"][0]
    eng_a = ctx_a.engine
    url_a = watchdog_stack["urls"][0]
    long_body = chat_body("co-tenant", max_tokens=48, stream=True)
    _register(watchdog_stack, only=[url_a])
    try:
        ref = _sse_content(post(watchdog_stack["frontend"],
                                "/v1/chat/completions", long_body,
                                raw=True).read().decode())
        result = {}

        def run():
            try:
                resp = post(watchdog_stack["frontend"],
                            "/v1/chat/completions", long_body,
                            raw=True, timeout=60)
                result["body"] = resp.read().decode()
            except Exception as e:
                result["error"] = e

        t = threading.Thread(target=run, daemon=True)
        t.start()
        # wait until stream 1 is INSTALLED (past prefill, decoding) so
        # the armed NaN can only hit the co-tenant's prefill
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and not getattr(eng_a, "seqs",
                                                          None):
            time.sleep(0.01)
        assert getattr(eng_a, "seqs", None), "stream 1 never installed"
        plane.configure({"engine.device_nan": {"times": 1}})
        poisoned = post(watchdog_stack["frontend"], "/v1/chat/completions",
                        chat_body("poison me"))
        assert poisoned["choices"][0]["finish_reason"] == "error", \
            "a poisoned stream must surface as an error, never 'stop'"
        assert not (poisoned["choices"][0]["message"].get("content") or "")
        t.join(timeout=60)
        assert "error" not in result, \
            f"co-tenant died: {result.get('error')}"
        assert _sse_content(result["body"]) == ref, \
            "the co-batched tenant must complete byte-identically"
        wd = eng_a.watchdog.summary()
        assert wd["integrity_faults_total"].get("logits", 0) >= 1
        assert eng_a.watchdog.health == "healthy", \
            "a sentinel aborts streams, never the engine"
    finally:
        plane.clear()
        post(watchdog_stack["frontend"], "/internal/deregister",
             {"url": url_a})


def _drain(eng, out):
    """Step the engine dry, filing each request's events under its id."""
    while eng.has_work:
        for ev in eng.step():
            out.setdefault(ev.request_id, []).append(ev)
    return out


@pytest.mark.parametrize("case", ["mixed_nan", "mixed_spec_nan", "mixed_oob"])
def test_fault_on_a_mixed_step_ends_exactly_one_stream(case, monkeypatch):
    """Where a prompt arrives while decodes are live, its chunks and its
    first token ride mixed steps (every cell of the benchmark): the
    sentinels owe those steps what they owe the idle paths. Non-finite
    logits under the prompt's last chunk (with and without verify windows
    in the same program) end that request and nothing else; an id outside
    the vocabulary in a mixed step's readback ends that slot and nothing
    else. Pages, slots and health come back as an idle engine's."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import Engine
    from dynamo_tpu.engine.request import GenRequest

    monkeypatch.setenv(INTEGRITY_ENV, "logits")
    spec = (dict(speculative_mode="ngram", num_speculative_tokens=2)
            if case == "mixed_spec_nan" else {})
    eng = Engine(EngineConfig(**KW, mixed_batch_tokens=8,
                              enable_prefix_caching=False, **spec))
    vocab = eng.model_cfg.vocab_size

    def live(rid):
        return GenRequest(rid, PROMPT, max_tokens=24, temperature=0.0,
                          ignore_eos=True)

    def late(rid):  # 12 tokens: two chunks of 8, the last rides a mixed step
        return GenRequest(rid, [7, 3, 9, 2, 8, 4, 6, 1, 5, 3, 2, 7],
                          max_tokens=6, temperature=0.0, ignore_eos=True)

    recorded = []
    real_record = eng.watchdog.record_integrity_fault
    monkeypatch.setattr(
        eng.watchdog, "record_integrity_fault",
        lambda sentinel, rids, **kw: (recorded.append((sentinel, rids, kw)),
                                      real_record(sentinel, rids, **kw)))
    try:
        ref_live = eng.generate(live("ref-live"))
        ref_late = eng.generate(late("ref-late"))
        idle = (eng.allocator.free_pages, sorted(eng._free_slots))
        mixed_before = eng.metrics.mixed_count

        eng.add_request(live("live"))
        out = {}
        while not eng.seqs:
            for ev in eng.step():
                out.setdefault(ev.request_id, []).append(ev)
        (slot_live,) = eng.seqs
        eng.add_request(late("late"))
        if case == "mixed_oob":
            real = eng._mixed[False]
            armed = [True]

            def corrupt(*args):
                ys, *rest = real(*args)
                if armed:  # the first mixed step only
                    armed.clear()
                    ys = (ys[0].at[0, slot_live].set(vocab),) + tuple(ys[1:])
                return (ys, *rest)

            eng._mixed[False] = corrupt
            victim, survivor, ref = "live", "late", ref_late
            sentinel = "decode_tokens"
        else:
            faults.get_plane().configure({"engine.device_nan": {"times": 1}})
            victim, survivor, ref = "late", "live", ref_live
            sentinel = "logits"
        _drain(eng, out)

        assert eng.metrics.mixed_count > mixed_before, "no mixed step ran"
        last = out[victim][-1]
        assert (last.token_id, last.finished, last.finish_reason) == (
            -1, True, "integrity_fault")
        assert [e.finish_reason for e in out[victim] if e.finished] == [
            "integrity_fault"]
        assert [e.token_id for e in out[survivor]] == ref, \
            "the other stream must finish with its usual tokens"
        assert out[survivor][-1].finish_reason == "length"
        assert (eng.allocator.free_pages, sorted(eng._free_slots)) == idle
        assert not eng.has_work and eng._inflight is None
        wd = eng.watchdog.summary()
        assert wd["integrity_faults_total"] == {sentinel: 1}
        assert not any(wd["trips_total"].values())
        assert eng.watchdog.health == "healthy"
        assert [r[0] for r in recorded] == [sentinel]
        if sentinel == "logits":
            assert recorded[0][2]["where"] == (
                "mixed_spec" if spec else "mixed")
    finally:
        faults.get_plane().clear()
        eng.watchdog.stop()


def test_hung_dispatch_handoff_resume_and_resurrection(watchdog_stack):
    """The headline drill: a device hang on worker A blows the step
    deadline — the monitor trips (suspect, shedding), the in-flight
    stream hands off mid-decode and resumes byte-identically on peer B,
    and once the wedged dispatch returns the lock, A resurrects in place
    and serves byte-identically again."""
    plane = watchdog_stack["plane"]
    ctx_a = watchdog_stack["wctxs"][0]
    eng_a = ctx_a.engine
    url_a, url_b = watchdog_stack["urls"]
    wd = eng_a.watchdog
    body = chat_body("hang the device", max_tokens=12, stream=True)
    _register(watchdog_stack)
    try:
        ref = _sse_content(post(watchdog_stack["frontend"],
                                "/v1/chat/completions", body,
                                raw=True).read().decode())
        # pin to A; the hang outlives the (overridden) deadline by far
        post(watchdog_stack["frontend"], "/internal/deregister",
             {"url": url_b})
        _register(watchdog_stack, only=[url_a])
        wd._deadline_override = 0.6
        plane.configure({"engine.device_hang": {"times": 1,
                                                "delay_s": 2.5}})
        result = {}

        def run():
            try:
                resp = post(watchdog_stack["frontend"],
                            "/v1/chat/completions", body,
                            raw=True, timeout=60)
                result["body"] = resp.read().decode()
            except Exception as e:
                result["error"] = e

        t = threading.Thread(target=run, daemon=True)
        t.start()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and not eng_a.has_work:
            time.sleep(0.01)
        assert eng_a.has_work, "the drill stream never reached worker A"
        # peer B is back before the trip fires the handoff
        _register(watchdog_stack, only=[url_b])
        t.join(timeout=60)
        assert "error" not in result, \
            f"stream died crossing the hang: {result.get('error')}"
        assert _sse_content(result["body"]) == ref, \
            "the resumed stream must be byte-identical to a clean run"
        assert wd.summary()["trips_total"].get("hung_dispatch", 0) >= 1

        # the wedged dispatch returned -> resurrection -> healthy again
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline and wd.health != "healthy":
            time.sleep(0.05)
        assert wd.health == "healthy", \
            f"A never resurrected (stuck {wd.health})"
        # and the rebuilt device state serves byte-identically, directly
        direct = post(url_a, "/v1/chat/completions",
                      dict(body, stream=False))
        assert direct["choices"][0]["message"]["content"] == ref
    finally:
        plane.clear()
        wd._deadline_override = None
        ctx_a.drain_handoff.clear()
        for u in watchdog_stack["urls"]:
            post(watchdog_stack["frontend"], "/internal/deregister",
                 {"url": u})


# ---------------------------------------------------------------------------
# compilation is not a hang: the derived deadline arms only once a warmup()
# has completed (derive_deadline), explicit overrides always
# ---------------------------------------------------------------------------
def _hold_seam(wd, seconds):
    wd.device_enter("dispatch")
    time.sleep(seconds)
    wd.device_exit("dispatch")


def test_derived_deadline_is_off_until_armed():
    """A first jit call compiles inside its dispatch seam for far longer
    than the floor; with derive_deadline off (an engine not yet warmed)
    that neither trips the derived deadline nor teaches the EWMA. Once it
    is on, the same seam trips."""
    wd = EngineWatchdog(floor_s=0.05, derive_deadline=False,
                        quarantine_window_s=0.0)
    try:
        _hold_seam(wd, 0.4)  # 8x the floor
        _hold_seam(wd, 0.4)
        s = wd.summary()
        assert s["state"] == "healthy" and s["trips_total"] == {}
        assert s["ewma_s"] is None  # compile time is not seam time
        wd.derive_deadline = True  # what the end of warmup() does
        wd.device_enter("dispatch")
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and wd.health == "healthy":
            time.sleep(0.01)
        assert wd.summary()["trips_total"] == {"hung_dispatch": 1}
        wd.device_exit("dispatch")
    finally:
        wd.stop()


def test_explicit_deadline_trips_while_derived_is_off():
    """A hang drill (env/ctor override) during warmup is still a hang."""
    wd = EngineWatchdog(deadline_s=0.05, derive_deadline=False)
    try:
        wd.device_enter("dispatch")
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and wd.health == "healthy":
            time.sleep(0.01)
        wd.device_exit("dispatch")
        assert wd.summary()["trips_total"] == {"hung_dispatch": 1}
    finally:
        wd.stop()


def test_only_a_completed_warmup_arms_the_derived_deadline(monkeypatch):
    """An engine that never warmed (--no-warmup, bench.py's step-to-warm
    loops) compiles in its first seams, so it never arms; warmup() disarms
    at its start and arms at its end, on an accelerator only. (Admission
    is stubbed, so nothing compiles: a real CPU warmup costs 20 s and is
    covered by tests/test_warmup.py.)"""
    import jax

    eng = _engine(num_pages=64, max_num_seqs=2, max_seq_len=32)
    wd = eng.watchdog
    during = []
    monkeypatch.setattr(
        eng, "add_request", lambda req: during.append(wd.derive_deadline))
    try:
        assert wd.derive_deadline is False  # fresh engine: not warmed
        wd.derive_deadline = True  # as a previous warmup left it
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        eng.warmup()
        assert during and not any(during)
        assert wd.derive_deadline is True
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        eng.warmup()
        assert wd.derive_deadline is False
    finally:
        wd.stop()
