"""Mid-stream request recovery suite (`make recovery-check`, marker
`recovery`): token-journaled continuation failover through the REAL
serving topology (frontend + workers over sockets).

The acceptance invariant (ISSUE 4): with `crash_mid_decode` armed on one
worker of a 2-worker agg topology, a greedy streaming request completes
with a byte-identical body versus the fault-free run — no duplicated,
missing, or reordered tokens at the recovery seam; same invariant for a
decode-side crash in the disagg topology with the parked prefill KV
ledger balanced afterwards.

Both workers of each topology share one parameter set, so the only thing
that can make outputs differ across the seam is the recovery plane
itself. Runs under a pinned DYNAMO_TPU_FAULT_SEED like the chaos suite.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.robustness import faults
from dynamo_tpu.serving import recovery
from dynamo_tpu.serving.api import (
    ServingContext, make_server, serve_forever_in_thread,
)
from dynamo_tpu.serving.frontend import FrontendContext, make_frontend_server

pytestmark = pytest.mark.recovery

MODEL = "tiny-debug"
KW = dict(model=MODEL, page_size=4, num_pages=128, max_num_seqs=4,
          max_seq_len=128)


def post(url, path, body, headers=None, timeout=120, raw=False):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST")
    resp = urllib.request.urlopen(req, timeout=timeout)
    return resp if raw else json.loads(resp.read())


def chat_body(text, max_tokens=12, **kw):
    return {"model": MODEL,
            "messages": [{"role": "user", "content": text}],
            "max_tokens": max_tokens, "temperature": 0, "ignore_eos": True,
            "stream": True, **kw}


def data_events(body_text):
    out = []
    for block in body_text.split("\n\n"):
        block = block.strip()
        if block.startswith("data: "):
            out.append(block[len("data: "):])
    return out


def chat_content(events):
    text = ""
    for e in events:
        if e == "[DONE]":
            continue
        for ch in json.loads(e).get("choices", []):
            d = (ch.get("delta") or {}).get("content")
            if d:
                text += d
            t = ch.get("text")
            if t:
                text += t
    return text


def counter_val(counter, **labels):
    key = tuple(sorted(labels.items()))
    with counter._lock:
        return counter._values.get(key, 0.0)


def stream(url, path, body, headers=None):
    resp = post(url, path, body, headers=headers, raw=True)
    text = resp.read().decode()
    return resp, text


@pytest.fixture(scope="module")
def stack():
    """Frontend + TWO agg workers sharing one parameter set."""
    plane = faults.reset_plane()
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
    fctx = FrontendContext()
    fsrv = make_frontend_server(fctx, "127.0.0.1", 0)
    serve_forever_in_thread(fsrv)
    stack = {
        "frontend": f"http://127.0.0.1:{fsrv.server_address[1]}",
        "fctx": fctx, "plane": plane,
        "workers": urls, "wctxs": ctxs,
    }
    register(stack)
    yield stack
    plane.clear()
    fsrv.shutdown()
    for srv in srvs:
        srv.shutdown()
    for ctx in ctxs:
        ctx.close()


def register(stack):
    for url in stack["workers"]:
        post(stack["frontend"], "/internal/register", {
            "url": url, "model": MODEL, "mode": "agg",
            "stats": {"max_num_seqs": 4, "free_pages": 100,
                      "total_pages": 128},
        })


def quiesce(stack):
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and any(
            c.engine.num_active or c.engine.pending
            for c in stack["wctxs"]):
        time.sleep(0.05)
    for c in stack["wctxs"]:
        assert not c.engine.num_active and not c.engine.pending


# ---------------------------------------------------------------------------
# acceptance: crash mid-decode -> byte-identical spliced stream
# ---------------------------------------------------------------------------
def test_crash_mid_decode_chat_stream_byte_identical(stack):
    plane, fctx = stack["plane"], stack["fctx"]
    register(stack)
    body = chat_body("recover me exactly", max_tokens=12)
    _, ref = stream(stack["frontend"], "/v1/chat/completions", body)
    ref_events = data_events(ref)
    assert ref_events[-1] == "[DONE]"
    assert "dynr" not in ref, "journal comments must never reach clients"

    before = counter_val(fctx.recovered_counter, phase="stream")
    plane.configure({"worker.crash_mid_decode": {"times": 1}})
    _, out = stream(stack["frontend"], "/v1/chat/completions", body)
    plane.clear()
    events = data_events(out)
    assert events[-1] == "[DONE]"
    assert "dynr" not in out
    # THE invariant: identical content, no dup/missing/reordered tokens
    assert chat_content(events) == chat_content(ref_events)
    # exactly one role preamble despite the splice
    roles = [e for e in events if e != "[DONE]"
             and any((c.get("delta") or {}).get("role")
                     for c in json.loads(e)["choices"])]
    assert len(roles) == 1
    assert counter_val(fctx.recovered_counter, phase="stream") == before + 1
    quiesce(stack)


def test_crash_mid_decode_completions_stream_byte_identical(stack):
    plane = stack["plane"]
    register(stack)
    body = {"model": MODEL, "prompt": "legacy completions recovery probe",
            "max_tokens": 10, "temperature": 0, "ignore_eos": True,
            "stream": True}
    _, ref = stream(stack["frontend"], "/v1/completions", body)
    plane.configure({"worker.crash_mid_decode": {"times": 1}})
    _, out = stream(stack["frontend"], "/v1/completions", body)
    plane.clear()
    assert data_events(out)[-1] == "[DONE]"
    assert chat_content(data_events(out)) == chat_content(data_events(ref))
    quiesce(stack)


def test_seeded_sampled_stream_recovers_identically(stack):
    """Sampled + seeded: the continuation resumes the identical
    position-folded PRNG chain, so the spliced stream matches the
    fault-free run byte for byte."""
    plane = stack["plane"]
    register(stack)
    body = chat_body("sampled seeded recovery", max_tokens=10,
                     temperature=0.8, seed=1234)
    _, ref = stream(stack["frontend"], "/v1/chat/completions", body)
    plane.configure({"worker.crash_mid_decode": {"times": 1}})
    _, out = stream(stack["frontend"], "/v1/chat/completions", body)
    plane.clear()
    assert chat_content(data_events(out)) == chat_content(data_events(ref))
    quiesce(stack)


def test_unseeded_sampled_stream_completes_exactly(stack):
    """Unseeded sampled stream: the worker pins an effective seed into the
    journal at stream start, so even here the continuation is exact —
    the spliced stream still delivers exactly max_tokens completion
    tokens (usage counts across the seam) and terminates cleanly."""
    plane = stack["plane"]
    register(stack)
    body = chat_body("unseeded sampled recovery", max_tokens=10,
                     temperature=0.9,
                     stream_options={"include_usage": True})
    plane.configure({"worker.crash_mid_decode": {"times": 1}})
    _, out = stream(stack["frontend"], "/v1/chat/completions", body)
    plane.clear()
    events = data_events(out)
    assert events[-1] == "[DONE]"
    usage = [json.loads(e)["usage"] for e in events if e != "[DONE]"
             and json.loads(e).get("usage")]
    assert usage and usage[-1]["completion_tokens"] == 10
    quiesce(stack)


def test_connect_phase_recovery_headers_and_counter(stack):
    """x-request-attempts / x-recovered ride the response head when a
    connect-phase failover carried the request; the recovered counter
    splits by phase."""
    plane, fctx = stack["plane"], stack["fctx"]
    register(stack)
    before = counter_val(fctx.recovered_counter, phase="connect")
    plane.configure({"frontend.connect_refused": {"times": 1}})
    resp = post(stack["frontend"], "/v1/chat/completions",
                {**chat_body("connect recovery"), "stream": False},
                raw=True)
    resp.read()
    plane.clear()
    assert resp.headers.get("x-request-attempts") == "2"
    assert resp.headers.get("x-recovered") == "1"
    assert counter_val(fctx.recovered_counter,
                       phase="connect") == before + 1
    # breaker hygiene for later tests
    for url in stack["workers"]:
        fctx.router.breakers.record_success(url)


def test_non_journaled_stream_still_truncates(stack):
    """n>1 streams are outside the journal's splice guarantees: a crash
    keeps PR 2's truncate semantics (in-stream error, no re-dispatch)."""
    plane = stack["plane"]
    register(stack)
    plane.configure({"worker.crash_mid_decode": {"times": 1}})
    _, out = stream(stack["frontend"], "/v1/chat/completions",
                    chat_body("two choices", max_tokens=8, n=2))
    plane.clear()
    assert "stream_error" in out or "[DONE]" not in out
    quiesce(stack)


def test_recovery_seam_span_attribute(stack):
    """The frontend span records recovery.seam_token_index so a spliced
    request is debuggable from /debug/spans."""
    plane, fctx = stack["plane"], stack["fctx"]
    register(stack)
    plane.configure({"worker.crash_mid_decode": {"times": 1}})
    resp, out = stream(stack["frontend"], "/v1/chat/completions",
                       chat_body("span seam probe", max_tokens=12))
    plane.clear()
    assert data_events(out)[-1] == "[DONE]"
    trace_id = resp.headers.get("X-Request-Id")
    # poll: frontend.request ENDS only after the client finished reading
    # the body, so the span lands in the ring buffer a beat after the
    # stream closes (same race test_tracing_propagation handles)
    attrs = None
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and attrs is None:
        spans = json.loads(urllib.request.urlopen(
            stack["frontend"] + f"/debug/spans?trace_id={trace_id}",
            timeout=10).read())
        fr = [sp for rs in spans["resourceSpans"]
              for ss in rs["scopeSpans"] for sp in ss["spans"]
              if sp["name"] == "frontend.request"]
        if fr:
            attrs = {a["key"]: a["value"] for a in fr[-1]["attributes"]}
        else:
            time.sleep(0.05)
    assert attrs is not None, "frontend.request span never landed"
    assert "recovery.seam_token_index" in attrs
    seam = int(attrs["recovery.seam_token_index"].get("intValue", 0))
    # crash_mid_decode fires after a token was CONSUMED and journaled:
    # the splice must be a true mid-stream continuation, not a full
    # regeneration
    assert seam >= 1
    quiesce(stack)


def test_reset_after_headers_stream_recovers_from_zero(stack):
    """Reset right after the SSE headers: nothing was delivered, so the
    continuation regenerates from an empty journal — and must still emit
    exactly one role preamble (role_sent=false rides the seam)."""
    plane = stack["plane"]
    register(stack)
    body = chat_body("reset stream probe", max_tokens=8)
    _, ref = stream(stack["frontend"], "/v1/chat/completions", body)
    plane.configure({"worker.reset_after_headers": {"times": 1}})
    _, out = stream(stack["frontend"], "/v1/chat/completions", body)
    plane.clear()
    events = data_events(out)
    assert events[-1] == "[DONE]"
    assert chat_content(events) == chat_content(data_events(ref))
    roles = [e for e in events if e != "[DONE]"
             and any((c.get("delta") or {}).get("role")
                     for c in json.loads(e)["choices"])]
    assert len(roles) == 1
    quiesce(stack)


def test_retry_after_jitter_bounds():
    from dynamo_tpu.serving.http_base import (
        RETRY_AFTER_CODES, retry_after_value,
    )

    assert set(RETRY_AFTER_CODES) == {429, 502, 503, 504}
    vals = {float(retry_after_value()) for _ in range(64)}
    assert all(0.8 <= v <= 1.2 for v in vals)
    assert len(vals) > 1, "Retry-After must be jittered, not constant"


def test_journal_seam_accounting():
    """Unit-level seam invariants: checkpoint-before-data means the
    journal can run ahead of delivery, never behind."""
    j = recovery.RequestJournal(enabled_=True)
    j.apply_comment(b'{"start": {"id": "chatcmpl-x", "seed": 7}}')
    j.apply_comment(b'{"n": 2, "c": 5, "t": [11, 12]}')
    j.on_data(b'{"choices": [{"delta": {"content": "hello"}}]}')
    assert j.recoverable and j.delivered_chars == 5
    assert j.seam_token_index == 2
    cont = j.continuation()
    assert cont["prior_tokens"] == [11, 12] and cont["seed"] == 7
    assert cont["response_id"] == "chatcmpl-x" and cont["role_sent"]
    # a gapped checkpoint (dropped comment) must poison the journal
    j.apply_comment(b'{"n": 9, "c": 6, "t": [13]}')
    assert not j.recoverable


def test_continuation_validation_rejects_garbage():
    with pytest.raises(ValueError):
        recovery.normalize_continuation({"prior_tokens": ["x"]})
    with pytest.raises(ValueError):
        recovery.normalize_continuation({"delivered_chars": -1})
    with pytest.raises(ValueError):
        recovery.normalize_continuation({"resume_key": [1]})
    ok = recovery.normalize_continuation(
        {"prior_tokens": [1], "delivered_chars": 0,
         "resume_key": [3, 4], "response_id": "cmpl-a", "seed": 9})
    assert ok["resume_key"] == [3, 4]


def test_resume_key_restores_exact_chain():
    """engine/sampling: a key snapshot restores the chain root bit-exactly,
    and GenRequest.resume_key overrides seed derivation."""
    import jax

    from dynamo_tpu.engine import sampling as smp

    key = jax.random.PRNGKey(99)
    snap = smp.key_snapshot(key)
    back = smp.key_from_snapshot(snap)
    assert smp.key_snapshot(back) == snap
    import numpy as np

    a = np.asarray(jax.random.fold_in(key, 17))
    b = np.asarray(jax.random.fold_in(back, 17))
    assert (a == b).all()


# ---------------------------------------------------------------------------
# KV demote on drain (KVBM host tier)
# ---------------------------------------------------------------------------
def test_drain_demotes_prefix_kv_to_host_tier():
    """A draining worker spills its prefix cache into the KVBM host tier
    (one batched gather) so peers can onboard the departing worker's
    warm prefixes."""
    eng = Engine(EngineConfig(**{**KW, "prefill_chunk_tokens": 8,
                                 "enable_prefix_caching": True,
                                 "kvbm_host_blocks": 32}))
    ctx = ServingContext(eng, MODEL)
    try:
        from dynamo_tpu.engine.request import GenRequest

        # through the service, whose thread alone may step the engine: a
        # second consumer (eng.generate here) can see `has_work` fall
        # before that thread has given the finished sequence's pages back
        list(ctx.service.stream(GenRequest(
            "warm", list(range(1, 20)), max_tokens=2, temperature=0.0,
            ignore_eos=True)))
        assert eng.prefix_cache.evictable() > 0
        demoted = ctx.drain_demote()
        assert demoted > 0
        assert eng.kvbm.pool.stats()["used_blocks"] > 0
        assert ctx.drain(drain_s=1.0, handoff_grace_s=0.1)
    finally:
        ctx.close()


# ---------------------------------------------------------------------------
# acceptance: disagg decode-side crash, ledger balanced
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def disagg_stack(stack):
    """One prefill worker + TWO decode workers (all sharing params) behind
    a dedicated frontend, so a decode-side crash can recover onto the
    surviving decode worker."""
    plane = stack["plane"]
    prefill_engine = Engine(
        EngineConfig(**{**KW, "disaggregation_mode": "prefill"}))
    pctx = ServingContext(prefill_engine, MODEL)
    psrv = make_server(pctx, "127.0.0.1", 0)
    serve_forever_in_thread(psrv)
    pport = psrv.server_address[1]

    dctxs, dsrvs, durls = [], [], []
    for _ in range(2):
        de = Engine(EngineConfig(**{**KW, "disaggregation_mode": "decode"}),
                    params=prefill_engine.params)
        dctx = ServingContext(de, MODEL,
                              prefill_urls=[f"http://127.0.0.1:{pport}"])
        dsrv = make_server(dctx, "127.0.0.1", 0)
        serve_forever_in_thread(dsrv)
        dctxs.append(dctx)
        dsrvs.append(dsrv)
        durls.append(f"http://127.0.0.1:{dsrv.server_address[1]}")

    fctx = FrontendContext()
    fsrv = make_frontend_server(fctx, "127.0.0.1", 0)
    serve_forever_in_thread(fsrv)
    frontend = f"http://127.0.0.1:{fsrv.server_address[1]}"
    for url in durls:
        post(frontend, "/internal/register", {
            "url": url, "model": MODEL, "mode": "decode",
            "stats": {"max_num_seqs": 4, "free_pages": 100,
                      "total_pages": 128}})
    yield {"frontend": frontend, "fctx": fctx, "pctx": pctx,
           "dctxs": dctxs, "plane": plane, "decode_urls": durls}
    fsrv.shutdown()
    for s in dsrvs:
        s.shutdown()
    psrv.shutdown()
    for c in dctxs:
        c.close()
    pctx.close()


@pytest.mark.slow
def test_disagg_decode_crash_recovers_and_ledger_balances(disagg_stack):
    plane = disagg_stack["plane"]
    pengine = disagg_stack["pctx"].engine
    body = chat_body("disagg decode crash", max_tokens=10)
    _, ref = stream(disagg_stack["frontend"], "/v1/chat/completions", body)
    assert data_events(ref)[-1] == "[DONE]"

    plane.configure({"worker.crash_mid_decode": {"times": 1}})
    _, out = stream(disagg_stack["frontend"], "/v1/chat/completions", body)
    plane.clear()
    events = data_events(out)
    assert events[-1] == "[DONE]"
    assert chat_content(events) == chat_content(data_events(ref))
    # the continuation re-prefilled under the same request id: the stale
    # park was replaced/released and the pull released the new one — the
    # parked-KV ledger must drain to empty
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and pengine._parked:
        time.sleep(0.05)
    assert not pengine._parked, \
        f"parked KV leaked: {set(pengine._parked)}"
