"""Flight recorder suite (`make flight-check`, marker `flight`).

Covers observability/flight.py and its engine + HTTP wiring:

- ring mechanics: bounded capacity with drop accounting, empty-step
  elision, stale-draft flush, capacity-0 disable, monotonic seq ids;
- notes: draft attachment from the engine thread, standalone event
  records from producer threads (resume seams, aborts);
- dump: the crash/abort hook flushes the open draft flagged `aborted`
  and appends the dump marker — the forensic contract the chaos
  acceptance ("name the exact step/slot/tenant") rests on;
- filtering: `/debug/flight?n=&rid=&tenant=&kind=` payload semantics,
  including victim/beneficiary rid matching and n-after-filter;
- engine integration: a real tiny-engine run leaves admit/finish records
  with batch composition and phase timings; abort_all dumps; a resumed
  request notes its recovery seam;
- fatal-step path: EngineService records `fatal_step` then the
  abort_all dump, in that order;
- HTTP: worker `/debug/` index, `/debug/flight` live payload, and the
  `/debug/trace` 409-with-Retry-After when a capture already runs.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.engine.request import GenRequest
from dynamo_tpu.observability.flight import (
    FlightRecorder,
    debug_flight_payload,
)

pytestmark = pytest.mark.flight

MODEL = "tiny-debug"
KW = dict(model=MODEL, page_size=4, num_pages=128, max_num_seqs=4,
          max_seq_len=96)


# ---------------------------------------------------------------------------
# ring mechanics
# ---------------------------------------------------------------------------
def test_ring_bounded_with_drop_accounting():
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.begin()
        fr.phase("decode", 0.001, i=i)
        fr.commit()
    recs = fr.records()
    assert len(recs) == 4
    assert fr.steps_total == 10
    assert fr.dropped_total == 6
    # newest-last, monotonic seq survives the wrap
    assert [r["seq"] for r in recs] == [6, 7, 8, 9]
    assert recs[-1]["i"] == 9


def test_empty_steps_are_elided():
    fr = FlightRecorder(capacity=8)
    for _ in range(5):
        fr.begin()
        fr.commit()  # no segment, no decision: an idle engine tick
    assert fr.records() == []
    assert fr.steps_total == 0


def test_stale_draft_flushes_flagged_aborted():
    fr = FlightRecorder(capacity=8)
    fr.begin()
    fr.phase("prefill", 0.002)
    fr.begin()  # previous step unwound past commit (exception)
    fr.phase("decode", 0.001)
    fr.commit()
    recs = fr.records()
    assert len(recs) == 2
    assert recs[0]["kind"] == "prefill" and recs[0].get("aborted") is True
    assert recs[1]["kind"] == "decode" and "aborted" not in recs[1]


def test_phase_accumulation_rounds_only_at_snapshot():
    # regression: phase() used to round to 3-decimal ms PER ACCUMULATE,
    # so a thousand sub-half-microsecond segments summed to exactly 0.0;
    # accumulation is raw float seconds now, rounded once at record flush
    fr = FlightRecorder(capacity=4)
    fr.begin()
    for _ in range(1000):
        fr.phase("decode", 4e-7)  # 0.0004 ms: below per-accumulate rounding
    fr.commit()
    (rec,) = fr.records()
    assert rec["phases"]["decode"] == pytest.approx(0.4, abs=1e-3)


def test_capacity_zero_disables_every_hook():
    fr = FlightRecorder(capacity=0)
    assert not fr.enabled
    fr.begin()
    fr.phase("decode", 0.001)
    fr.note("admit", rid="r1")
    fr.commit()
    assert fr.records() == []
    dump = fr.dump("test")
    assert dump["records"] == []


def test_capacity_env(monkeypatch):
    monkeypatch.setenv("DYNAMO_TPU_FLIGHT_RECORDS", "7")
    assert FlightRecorder().capacity == 7
    monkeypatch.setenv("DYNAMO_TPU_FLIGHT_RECORDS", "bogus")
    assert FlightRecorder().capacity == 512
    monkeypatch.delenv("DYNAMO_TPU_FLIGHT_RECORDS")
    assert FlightRecorder().capacity == 512


def test_note_without_draft_commits_standalone_record():
    fr = FlightRecorder(capacity=8)
    fr.note("resume", rid="r9", tenant="acme", n_prior=3)
    recs = fr.records()
    assert len(recs) == 1
    assert recs[0]["kind"] == "event"
    assert recs[0]["events"][0] == {"ev": "resume", "rid": "r9",
                                    "tenant": "acme", "n_prior": 3}


def test_phases_accumulate_per_kind():
    fr = FlightRecorder(capacity=8)
    fr.begin()
    fr.phase("decode", 0.010)
    fr.phase("decode", 0.005)
    fr.phase("prefill_chunk", 0.002, take=8)
    fr.commit()
    rec = fr.records()[0]
    assert rec["kind"] == "decode+decode+prefill_chunk"
    assert rec["phases"]["decode"] == pytest.approx(15.0)
    assert rec["take"] == 8


def test_dump_flushes_open_draft_and_marks_reason():
    fr = FlightRecorder(capacity=8)
    fr.begin()
    fr.phase("decode", 0.001)
    fr.note("admit", rid="r1", slot=0, tenant="acme")
    out = fr.dump("abort_all", rids=["r1"])
    assert out["reason"] == "abort_all"
    recs = out["records"]
    # the half-finished step survives, flagged, with its decisions intact
    assert recs[-2]["kind"] == "decode" and recs[-2]["aborted"] is True
    assert recs[-2]["events"][0]["rid"] == "r1"
    assert recs[-1]["events"][0] == {"ev": "dump", "reason": "abort_all",
                                     "rids": ["r1"]}
    assert fr.records() == recs  # ring retains the dump for later scrapes


# ---------------------------------------------------------------------------
# filtering / payload
# ---------------------------------------------------------------------------
def _seeded_recorder():
    fr = FlightRecorder(capacity=32)
    fr.begin()
    fr.note("admit", rid="r1", slot=0, tenant="acme")
    fr.phase("prefill", 0.001)
    fr.commit(batch=[{"slot": 0, "rid": "r1", "tenant": "acme"}])
    fr.begin()
    fr.note("qos_preempt", victim_rid="r1", victim_tenant="acme",
            beneficiary_rid="r2", beneficiary_tenant="good")
    fr.phase("decode", 0.001)
    fr.commit(batch=[{"slot": 0, "rid": "r2", "tenant": "good"}])
    return fr


def test_payload_filters_by_rid_including_victims():
    fr = _seeded_recorder()
    p = debug_flight_payload(fr, {"rid": ["r1"]})
    assert p["size"] == 2
    # r1 matches its admit record AND the preempt record naming it victim
    assert p["matched"] == 2
    p2 = debug_flight_payload(fr, {"rid": ["r2"]})
    assert p2["matched"] == 1  # beneficiary + batch member of record 2


def test_payload_filters_by_tenant_and_kind():
    fr = _seeded_recorder()
    assert debug_flight_payload(fr, {"tenant": ["good"]})["matched"] == 1
    assert debug_flight_payload(fr, {"kind": ["prefill"]})["matched"] == 1
    assert debug_flight_payload(fr, {"tenant": ["nope"]})["matched"] == 0


def test_payload_n_applies_after_filter():
    fr = FlightRecorder(capacity=64)
    for i in range(20):
        fr.begin()
        fr.note("admit", rid=("hot" if i % 10 == 0 else f"r{i}"))
        fr.phase("decode", 0.001)
        fr.commit()
    p = debug_flight_payload(fr, {"rid": ["hot"], "n": ["1"]})
    # both "hot" records match; n=1 then keeps the newest — a busy ring
    # cannot wash out the request being chased
    assert p["matched"] == 2
    assert len(p["records"]) == 1
    p_all = debug_flight_payload(fr, {})
    assert p_all["matched"] == 20 and len(p_all["records"]) == 20


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def engine():
    return Engine(EngineConfig(**KW))


def _drain(eng):
    out = {}
    while eng.has_work:
        for ev in eng.step():
            if ev.token_id >= 0:
                out.setdefault(ev.request_id, []).append(ev.token_id)
    return out


def test_engine_run_leaves_structured_records(engine):
    start_seq = engine.flight.steps_total
    engine.add_request(GenRequest("fa", [1, 5, 9, 13], max_tokens=4,
                                  temperature=0.0, ignore_eos=True,
                                  tenant="acme"))
    engine.add_request(GenRequest("fb", [2, 7, 11], max_tokens=4,
                                  temperature=0.0, ignore_eos=True))
    out = _drain(engine)
    assert len(out["fa"]) == 4 and len(out["fb"]) == 4
    assert engine.flight.steps_total > start_seq
    recs = engine.flight.records()
    events = [e for r in recs for e in r.get("events", ())]
    admits = {e["rid"]: e for e in events if e["ev"] == "admit"}
    assert admits["fa"]["tenant"] == "acme"
    assert admits["fb"]["tenant"] == "default"
    assert "slot" in admits["fa"] and "pages" in admits["fa"]
    finishes = {e["rid"]: e for e in events if e["ev"] == "finish"}
    assert finishes["fa"]["reason"] in ("stop", "length")
    assert finishes["fa"]["n_out"] == 4
    # batch composition names every live slot with tenant identity
    batched = [r for r in recs if r.get("batch")]
    assert batched
    assert any(s["rid"] == "fa" and s["tenant"] == "acme"
               for r in batched for s in r["batch"])
    # phase timings present and positive
    assert any(v > 0 for r in batched
               for v in r.get("phases", {}).values())


def test_abort_all_dumps_naming_live_requests():
    eng = Engine(EngineConfig(**KW))
    eng.add_request(GenRequest("da", [1, 2, 3, 4], max_tokens=32,
                               temperature=0.0, ignore_eos=True,
                               tenant="acme"))
    for _ in range(3):
        eng.step()
    assert eng.num_active == 1
    ids = eng.abort_all()
    assert "da" in ids
    recs = eng.flight.records()
    dump_events = [e for r in recs for e in r.get("events", ())
                   if e["ev"] == "dump"]
    assert dump_events and dump_events[-1]["reason"] == "abort_all"
    assert "da" in dump_events[-1]["rids"]
    # the history before the dump names the exact slot/tenant admitted
    payload = debug_flight_payload(eng.flight, {"rid": ["da"]})
    admits = [e for r in payload["records"] for e in r.get("events", ())
              if e["ev"] == "admit" and e["rid"] == "da"]
    assert admits and admits[0]["tenant"] == "acme"
    assert isinstance(admits[0]["slot"], int)


def test_resume_seam_recorded(engine):
    engine.add_request(GenRequest(
        "rs1", [1, 5, 9, 13], max_tokens=3, temperature=0.0,
        ignore_eos=True, tenant="acme",
        prior_output_token_ids=[7, 8]))
    _drain(engine)
    seams = [e for r in engine.flight.records()
             for e in r.get("events", ()) if e["ev"] == "resume"]
    assert seams
    seam = [e for e in seams if e["rid"] == "rs1"][-1]
    assert seam["tenant"] == "acme" and seam["n_prior"] == 2


def test_fatal_step_note_precedes_abort_dump():
    from dynamo_tpu.serving.engine_service import EngineService

    class BoomEngine:
        has_work = True

        def __init__(self):
            self.flight = FlightRecorder(capacity=16)
            self.aborted = threading.Event()

        def step(self):
            self.has_work = False
            raise RuntimeError("injected: device OOM")

        def abort_all(self):
            self.flight.dump("abort_all", rids=["x"])
            self.aborted.set()
            return ["x"]

    eng = BoomEngine()
    svc = EngineService(eng)
    try:
        assert eng.aborted.wait(timeout=10)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            events = [e for r in eng.flight.records()
                      for e in r.get("events", ())]
            if [e["ev"] for e in events][-2:] == ["fatal_step", "dump"]:
                break
            time.sleep(0.02)
        evs = [e for r in eng.flight.records() for e in r.get("events", ())]
        assert [e["ev"] for e in evs][-2:] == ["fatal_step", "dump"]
        fatal = [e for e in evs if e["ev"] == "fatal_step"][0]
        assert "injected: device OOM" in fatal["error"]
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def server(engine):
    from dynamo_tpu.serving.api import (
        ServingContext, make_server, serve_forever_in_thread,
    )

    ctx = ServingContext(engine, MODEL)
    srv = make_server(ctx, "127.0.0.1", 0)
    serve_forever_in_thread(srv)
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    yield ctx, url
    srv.shutdown()
    ctx.close()


def _get_json(url, path):
    return json.loads(
        urllib.request.urlopen(url + path, timeout=30).read().decode())


def test_debug_index_lists_flight_and_costs(server):
    _, url = server
    idx = _get_json(url, "/debug/")["endpoints"]
    for ep in ("/debug/flight", "/debug/costs", "/debug/trace",
               "/debug/spans", "/debug/slo"):
        assert ep in idx and idx[ep]
    assert _get_json(url, "/debug")["endpoints"] == idx


def test_debug_flight_route_live_and_filtered(server):
    ctx, url = server
    ctx.engine.add_request(GenRequest("http1", [3, 1, 4], max_tokens=3,
                                      temperature=0.0, ignore_eos=True,
                                      tenant="web"))
    _drain(ctx.engine)
    p = _get_json(url, "/debug/flight?n=512")
    assert p["enabled"] and p["size"] > 0 and p["records"]
    filtered = _get_json(url, "/debug/flight?rid=http1")
    assert filtered["matched"] >= 1
    assert _get_json(url, "/debug/flight?tenant=web")["matched"] >= 1
    assert _get_json(url, "/debug/flight?tenant=nobody")["matched"] == 0


def test_debug_costs_route(server):
    ctx, url = server
    body = _get_json(url, "/debug/costs")
    assert body["segments_total"] > 0
    assert body["totals"]["chip_seconds"] > 0
    assert "default" in body["tenants"]


def test_trace_busy_returns_409_with_retry_after(server):
    ctx, url = server
    # occupy the capture slot as a concurrent capture would
    assert ctx._trace_lock.acquire(blocking=False)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url + "/debug/trace?duration_s=0.1",
                                   timeout=30)
        assert ei.value.code == 409
        assert int(ei.value.headers["Retry-After"]) >= 1
        body = json.loads(ei.value.read())
        assert "already running" in body["error"]["message"]
    finally:
        ctx._trace_lock.release()


def test_worker_stats_has_memory_and_costs(server):
    _, url = server
    st = _get_json(url, "/worker/stats")
    mem = st["memory"]
    tiers = mem["tiers"]["device"]
    assert sum(tiers.values()) == mem["pool"]["total_bytes"]
    assert st["costs"]["totals"]["chip_seconds"] > 0


def test_debug_timeline_route_live(server):
    ctx, url = server
    ctx.engine.add_request(GenRequest("tl1", [2, 7, 1], max_tokens=3,
                                      temperature=0.0, ignore_eos=True))
    _drain(ctx.engine)
    idx = _get_json(url, "/debug/")["endpoints"]
    assert "/debug/timeline" in idx
    summ = _get_json(url, "/debug/timeline?format=summary")
    assert summ["enabled"] and summ["steps"] > 0
    assert "bubble" in summ and "device" in summ
    trace = _get_json(url, "/debug/timeline?format=perfetto")
    evs = trace["traceEvents"]
    assert any(e["ph"] == "X" and e["pid"] == 1 for e in evs)
    raw = _get_json(url, "/debug/timeline?steps=4")
    assert raw["records"] and len(raw["records"]) <= 4
    st = _get_json(url, "/worker/stats")
    assert st["timeline"]["steps"] > 0
