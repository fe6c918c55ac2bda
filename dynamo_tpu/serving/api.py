"""OpenAI-compatible HTTP server serving a local Engine — the aggregated-worker
path, equivalent to the reference's engine worker + frontend collapsed into one
pod (/root/reference/examples/deploy/vllm/agg.yaml).

Endpoints: GET /v1/models, POST /v1/chat/completions, POST /v1/completions
(both with SSE streaming), GET /metrics (Prometheus), GET /health, /live,
/ready, GET /worker/stats (router introspection).
"""

from __future__ import annotations

import logging
import os
import random
import threading
import time
import urllib.parse
from typing import Any, Dict, List, Optional

from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.engine.kv_cache import OutOfPages
from dynamo_tpu.engine.request import GenRequest
from dynamo_tpu.engine.tokenizer import get_tokenizer
from dynamo_tpu.observability import context as obs_context
from dynamo_tpu.observability.engine_metrics import device_report
from dynamo_tpu.observability import slo as obs_slo
from dynamo_tpu.observability import tracing as obs_tracing
from dynamo_tpu.robustness import faults
from dynamo_tpu.robustness.deadline import Deadline
from dynamo_tpu.serving import protocol as proto
from dynamo_tpu.serving import recovery
from dynamo_tpu.serving.engine_service import EngineService
from dynamo_tpu.serving.http_base import (
    JsonHTTPHandler,
    make_http_server,
    serve_forever_in_thread,  # noqa: F401  (re-export for callers/tests)
)
from dynamo_tpu.serving.metrics import FrontendMetrics, Gauge

log = logging.getLogger("dynamo_tpu.api")


class TraceBusy(RuntimeError):
    """A profiler capture is already in progress on this worker."""


# one-line descriptions behind GET /debug/ — the operator's map of the
# worker-side debug surface (the frontend has its own index)
WORKER_DEBUG_INDEX = {
    "/debug/spans": "recent request/engine spans (?trace_id=&n=)",
    "/debug/slo": "SLO attainment windows and violation breakdown",
    "/debug/flight": "engine flight recorder: per-step records with "
                     "batch composition, decisions, phase timings "
                     "(?n=&rid=&tenant=&kind=)",
    "/debug/costs": "per-tenant chip-seconds and HBM byte-seconds "
                    "attributed by the engine cost ledger",
    "/debug/timeline": "engine step timeline: exact phase intervals, "
                       "host-gap/bubble attribution "
                       "(?steps=&format=perfetto|summary|json&trace_id=)",
    "/debug/trace": "capture a jax.profiler trace zip (?duration_s=; "
                    "409 while another capture runs)",
}


class IncrementalDetokenizer:
    """Streaming detokenization with bounded re-decode (vLLM-style windows):
    each push decodes only the tokens since the last emitted boundary, holding
    back trailing bytes that don't yet form complete UTF-8."""

    def __init__(self, tokenizer):
        self.tok = tokenizer
        self.ids: List[int] = []
        self.prefix_offset = 0
        self.read_offset = 0
        self.emitted = ""

    def push(self, token_id: int) -> str:
        self.ids.append(token_id)
        prefix_text = self.tok.decode(self.ids[self.prefix_offset:self.read_offset])
        new_text = self.tok.decode(self.ids[self.prefix_offset:])
        if new_text.endswith("�"):
            return ""
        delta = new_text[len(prefix_text):]
        self.prefix_offset = self.read_offset
        self.read_offset = len(self.ids)
        self.emitted += delta
        return delta


class StopStringMatcher:
    """Detokenizer-aware stop-string handling: holds back the longest
    possible partial match so a stop string arriving across token boundaries
    is never leaked to the client, and truncates the output at the match."""

    def __init__(self, stops: List[str]):
        self.stops = stops
        self.hold = max((len(s) for s in stops), default=1) - 1
        self.buf = ""
        self.stopped = False

    def push(self, delta: str) -> tuple:
        """Returns (text_to_emit, stopped)."""
        if self.stopped:
            return "", True
        self.buf += delta
        best = -1
        for s in self.stops:
            i = self.buf.find(s)
            if i >= 0 and (best < 0 or i < best):
                best = i
        if best >= 0:
            self.stopped = True
            emit, self.buf = self.buf[:best], ""
            return emit, True
        if self.hold <= 0 or len(self.buf) <= self.hold:
            if self.hold <= 0:
                emit, self.buf = self.buf, ""
                return emit, False
            return "", False
        cut = len(self.buf) - self.hold
        emit, self.buf = self.buf[:cut], self.buf[cut:]
        return emit, False

    def flush(self) -> str:
        emit, self.buf = self.buf, ""
        return emit


class GenerationHandle:
    """A submitted request plus its event stream — submission (and its
    validation errors) happens strictly before any response bytes."""

    def __init__(self, ctx: "ServingContext", rid: str, prompt_ids: List[int],
                 params: dict, index: int = 0, trace_span=None,
                 deadline: Optional[Deadline] = None,
                 received_at: Optional[float] = None):
        self.ctx = ctx
        self.rid = rid
        self.index = index
        self.span = trace_span if trace_span is not None \
            else obs_tracing.NOOP_SPAN
        self.deadline = deadline
        self.stops: List[str] = params.get("stop") or []
        self.want_logprobs = params.get("logprobs") is not None
        # --- mid-stream recovery continuation (serving/recovery.py) ---
        # the journaled tokens the original worker already emitted become
        # extra PREFILL (prompt ⊕ emitted tokens) with the remaining token
        # budget; prior_output_token_ids keeps penalties/guided replay
        # honest and resume_key restores the exact sampling chain — the
        # same correctness contract as preemption-by-recompute
        self.journal_sink = None  # set by the handler on journaled streams
        rec = params.get("_recovery") if index == 0 else None
        self.recovery = rec
        prior = list(rec["prior_tokens"]) if rec else []
        self.prior_count = len(prior)
        max_tokens = params["max_tokens"]
        if prior:
            prompt_ids = list(prompt_ids) + prior
            max_tokens = max(1, max_tokens - len(prior))
        self.prompt_ids = prompt_ids
        # each choice of an n>1 request gets its own deterministic chain
        seed = params.get("seed")
        # user stop_token_ids pass through UNMODIFIED: the model-EOS merge
        # lives in engine._stop_ids_for (which knows model_cfg and the
        # ignore_eos exemption), so ignore_eos=true + stop_token_ids no
        # longer stops on model EOS (vLLM semantics)
        stop_ids = list(params.get("stop_token_ids") or [])
        self.req = GenRequest(
            rid,
            list(prompt_ids),
            max_tokens=max_tokens,
            temperature=params["temperature"],
            top_p=params["top_p"],
            top_k=params["top_k"],
            presence_penalty=params.get("presence_penalty", 0.0),
            frequency_penalty=params.get("frequency_penalty", 0.0),
            min_p=params.get("min_p", 0.0),
            logit_bias=params.get("logit_bias"),
            seed=None if seed is None else seed + index,
            logprobs=params.get("logprobs"),
            ignore_eos=params.get("ignore_eos", False),
            priority=params.get("priority", 0),
            guided_json=params.get("guided_json", False),
            stop_token_ids=stop_ids,
            prior_output_token_ids=prior,
            resume_key=(rec or {}).get("resume_key"),
            adapter=params.get("adapter"),
            # per-tenant QoS: the identity the handler resolved from the
            # request headers rides into the engine's weighted-fair
            # scheduler (and across preemption/recovery continuations)
            tenant=params.get("tenant"),
        )
        # when the handler received the request (monotonic): the first of
        # the first token's stamps. A library caller has no handler, and
        # its submit stage is empty.
        self.received_at = (received_at if received_at is not None
                            else self.req.arrival_time)
        self.tenant = self.req.tenant or "default"
        ctx.metrics.tenant_requests.inc(tenant=self.tenant)
        if self.req.adapter and ctx.lora_requests_total is not None:
            ctx.lora_requests_total.inc(adapter=self.req.adapter)
            if ctx.engine.lora is not None:
                ctx.engine.lora.note_request(self.req.adapter)
        if ctx.disagg_client is not None:
            # decode role: prefill remotely, pull KV, continue locally
            self.queue = ctx.disagg_client.start(self.req,
                                                 parent_span=self.span,
                                                 deadline=deadline)
        else:
            self.queue = ctx.service.submit(self.req)  # raises ValueError early
        ctx.metrics.requests_total.inc(model=ctx.served_model)
        ctx.metrics.isl.observe(len(prompt_ids), model=ctx.served_model)
        # collected when logprobs were requested: one protocol entry per token
        self.lp_entries: List[dict] = []

    def _lp_entry(self, ev) -> Optional[dict]:
        """Build (but don't commit) the protocol logprob entry for a token."""
        if not (self.want_logprobs and ev.logprob is not None):
            return None
        tok = self.ctx.tokenizer
        return proto.chat_logprob_entry(
            tok.decode([ev.token_id]), ev.logprob,
            [(tok.decode([tid]), lp) for tid, lp in (ev.top_logprobs or [])],
        )

    def _decode_span(self, ttft_s: float):
        """Open the worker.decode span at the first TokenEvent."""
        if not self.span.recording:
            return None
        eng = self.ctx.engine
        if self.req.adapter and eng.lora is not None:
            # the device slot is known once admission resolved it
            self.span.set_attributes({
                "lora.adapter": self.req.adapter,
                "lora.slot": eng.lora.slot_of(self.req.adapter) or 0,
            })
        return self.ctx.tracer.start_span(
            "worker.decode", parent=self.span,
            attributes={"ttft_s": round(ttft_s, 6)})

    def _first_token_written(self, phase: Optional[dict]) -> None:
        """The first frame with content is on the wire: put the first
        token's time down to its four stages, from five monotonic stamps
        (received_at, the request's arrival_time, and the engine's
        prefill start and `t_first` on TokenEvent.phase, now). The same
        stamps feed the cumulative counters the benchmark reads
        (/worker/stats metrics.first_token) and four back-dated spans,
        children of the request's span: worker.submit (parse, template,
        tokenise), worker.queue, worker.prefill, worker.emit (event
        queue, detokenise, frame). A first event without stamps (a
        disaggregated prefill elsewhere) gives neither."""
        if not phase or "t_first" not in phase:
            return
        t_written = time.monotonic()
        t_first = phase["t_first"]
        t_prefill = t_first - phase["prefill_s"]
        stamps = (self.received_at, self.req.arrival_time, t_prefill,
                  t_first, t_written)
        eng = self.ctx.engine
        eng.metrics.observe_first_token(
            *(b - a for a, b in zip(stamps, stamps[1:])))
        if not self.span.recording:
            return
        # spans live on the unix clock: one offset carries every stamp over
        to_ns = time.time_ns() - int(t_written * 1e9)
        prefill = eng.metrics.phases["prefill"]
        prefill_attributes = {
            "prompt_tokens": len(self.prompt_ids),
            # engine-wide quantiles ride along, so that one slow trace
            # carries the fleet context it should be judged against
            "engine.prefill.p50_ms": round(prefill.quantile_ms(0.5), 3),
            "engine.prefill.p95_ms": round(prefill.quantile_ms(0.95), 3)}
        names = ("worker.submit", "worker.queue", "worker.prefill",
                 "worker.emit")
        for name, a, b in zip(names, stamps, stamps[1:]):
            self.ctx.tracer.start_span(
                name, parent=self.span, start_ns=to_ns + int(a * 1e9),
                attributes=(prefill_attributes if name == "worker.prefill"
                            else None),
            ).end(end_ns=to_ns + int(max(a, b) * 1e9))

    def run(self, emit) -> tuple:
        """Drive the stream; emit(delta, finish|None, lp_entry|None) -> bool
        keeps going while True. A False return (client gone) aborts the
        engine request.

        Returns (text, finish_reason, completion_tokens)."""
        ctx, m = self.ctx, self.ctx.metrics
        model = ctx.served_model
        t0 = time.monotonic()
        t_prev: Optional[float] = None
        decode_span = None
        first_phase: Optional[dict] = None  # until the first frame is out
        detok = IncrementalDetokenizer(ctx.tokenizer)
        matcher = StopStringMatcher(self.stops) if self.stops else None
        text_parts: List[str] = []
        n_out = 0
        finish = "stop"
        # --- recovery journal bookkeeping (serving/recovery.py) ---
        consumed = self.prior_count  # tokens covered by the journal
        content_total = 0  # cumulative content chars (incl. primed text)
        pending_journal: List[int] = []  # tokens since the last checkpoint

        def checkpoint(extra: Optional[dict] = None) -> None:
            """Flush a journal checkpoint BEFORE the delta it covers goes
            on the wire — the journal may run ahead of delivery, never
            behind, which is the exactly-once seam invariant."""
            nonlocal pending_journal
            entry = {"n": consumed, "c": content_total, "t": pending_journal}
            if extra:
                entry.update(extra)
            pending_journal = []
            self.journal_sink(entry)

        if self.recovery is not None:
            # continuation: replay the journaled tokens through a fresh
            # detok/matcher pipeline (deterministic, so its output is
            # byte-identical to what the original worker delivered) and
            # re-emit exactly the chars past delivered_chars — the seam
            primed_parts: List[str] = []
            stopped_in_prior = False
            for t in self.recovery["prior_tokens"]:
                d = detok.push(t)
                if matcher is not None and not stopped_in_prior:
                    d, stopped_in_prior = matcher.push(d)
                primed_parts.append(d)
            primed = "".join(primed_parts)
            content_total = len(primed)
            catch_up = primed[self.recovery["delivered_chars"]:]
            if self.journal_sink is not None:
                checkpoint()
            if stopped_in_prior:
                # the stop string had fully arrived before the original
                # stream died: nothing left to generate
                text_parts.append(catch_up)
                emit(catch_up, "stop", None)
                ctx.service.abort(self.rid)
                m.duration.observe(time.monotonic() - t0, model=model)
                m.osl.observe(0, model=model)
                return catch_up, "stop", 0
            if catch_up:
                text_parts.append(catch_up)
                emit(catch_up, None, None)
        # the drain timeout is the request's REMAINING deadline budget
        # (frontend hop time already subtracted), not a fixed 600 s
        drain_timeout = (self.deadline.remaining()
                         if self.deadline is not None else None)
        for ev in ctx.service.drain(self.req, self.queue,
                                    timeout=drain_timeout):
            if (self.journal_sink is not None and not ev.finished
                    and ctx.drain_handoff.is_set()):
                # graceful drain, ACTIVE handoff: snapshot the sampling
                # chain, push the journal tail back to the frontend as
                # the final comment, and abort — the frontend splices a
                # continuation onto the same client stream elsewhere
                st = ctx.service.sampling_state(self.rid)
                checkpoint({"handoff": 1,
                            **({"key": st["key"]} if st else {})})
                ctx.service.abort(self.rid)
                finish = "handoff"
                break
            now = time.monotonic()
            # exemplar: the request's trace id rides the latency buckets,
            # so a p99 bucket resolves at /debug/spans?trace_id=...
            ex = self.span.trace_id if self.span.recording else None
            if t_prev is None:
                m.ttft.observe(now - t0, exemplar=ex, model=model)
                m.tenant_ttft.observe(now - t0, tenant=self.tenant)
                decode_span = self._decode_span(now - t0)
                first_phase = ev.phase
            else:
                m.itl.observe(now - t_prev, exemplar=ex, model=model)
                m.tenant_itl.observe(now - t_prev, tenant=self.tenant)
            t_prev = now
            delta = ""
            lp_entry = None
            if ev.token_id >= 0:
                n_out += 1
                consumed += 1
                pending_journal.append(ev.token_id)
                if ev.finished and ev.finish_reason == "stop":
                    # the finishing stop TOKEN is not content: HF decode
                    # skips specials, but the byte tokenizer cannot (a
                    # stop id < 256 would leak as a control byte), and
                    # logprobs must describe the returned text
                    pass
                else:
                    delta = detok.push(ev.token_id)
                    lp_entry = self._lp_entry(ev)
            stopped = False
            if matcher is not None and (delta or ev.finished):
                delta, stopped = matcher.push(delta)
                if not stopped and ev.finished:
                    delta += matcher.flush()
            if stopped:
                # stop string seen: truncate the text, DISCARD the stop
                # token's logprob entry (logprobs must match the returned
                # content), abort the engine side, report finish "stop"
                text_parts.append(delta)
                if self.journal_sink is not None and pending_journal:
                    if delta:
                        content_total += len(delta)
                    checkpoint()
                emit(delta, "stop", None)
                if not ev.finished:
                    ctx.service.abort(self.rid)
                finish = "stop"
                break
            if lp_entry is not None:
                self.lp_entries.append(lp_entry)
            fr = proto.map_finish_reason(ev.finish_reason) if ev.finished else None
            if ev.finished:
                finish = fr or "stop"
            text_parts.append(delta)
            if self.journal_sink is not None and pending_journal:
                # checkpoint EVERY consumed token, not just content-
                # bearing ones: a held-back token (UTF-8 / stop-string
                # holdback) is still committed state a continuation must
                # not re-sample differently — and the comment still lands
                # before the delta it may cover
                if delta:
                    content_total += len(delta)
                checkpoint()
            # emit on no-delta events too when they carry a logprob entry
            # (UTF-8 holdback): streaming logprobs are one entry per token
            if delta or ev.finished or lp_entry is not None:
                ok = emit(delta, fr, lp_entry)
                if first_phase is not None:
                    self._first_token_written(first_phase)
                    first_phase = None
                if not ok and not ev.finished:
                    log.info("client disconnected; aborting %s", self.rid)
                    ctx.service.abort(self.rid)
                    finish = "abort"
                    break
        dur = time.monotonic() - t0
        m.duration.observe(
            dur, exemplar=(self.span.trace_id if self.span.recording
                           else None), model=model)
        m.osl.observe(n_out, model=model)
        ctx.kv_gauge.set(ctx.engine.allocator.free_pages)
        if decode_span is not None:
            eng_ph = ctx.engine.metrics.phases
            decode_span.set_attributes({
                "completion_tokens": n_out,
                "finish_reason": finish,
                "engine.decode_step.p50_ms":
                    round(eng_ph["decode_step"].quantile_ms(0.5), 3),
                "engine.decode_step.p95_ms":
                    round(eng_ph["decode_step"].quantile_ms(0.95), 3),
            })
            decode_span.end()
        if (self.span.recording
                and dur >= obs_tracing.slow_request_threshold_s()):
            log.warning(
                "slow request %s: %.2fs model=%s trace_id=%s — "
                "GET /debug/spans?trace_id=%s", self.rid, dur, model,
                self.span.trace_id, self.span.trace_id)
        return "".join(text_parts), finish, n_out


# spot reclamation: default drain deadline when a /internal/reclaim
# notice arrives without one (cloud maintenance notices are typically
# 30-120s; align with the preemptible node pool's advertised grace)
RECLAIM_DEADLINE_ENV = "DYNAMO_TPU_RECLAIM_DEADLINE_S"
DEFAULT_RECLAIM_DEADLINE_S = 60.0

# hitless weight rollout (docs/robustness.md "Hitless weight rollout"):
# how /internal/rollout flips a busy engine when the request doesn't name
# a mode — `finish` arms the flip (in-flight streams complete on the old
# version, admissions hold), `handoff` pushes journaled streams' seams to
# the frontend for resume on a still-old-version peer and flips as soon
# as the engine empties (bounded by the grace below, then falls back to
# an armed finish flip for any non-journaled stragglers)
ROLLOUT_DRAIN_MODE_ENV = "DYNAMO_TPU_ROLLOUT_DRAIN_MODE"
ROLLOUT_HANDOFF_GRACE_S = 5.0


def _env_reclaim_deadline_s() -> float:
    try:
        return max(1.0, float(os.environ.get(RECLAIM_DEADLINE_ENV,
                                             DEFAULT_RECLAIM_DEADLINE_S)))
    except ValueError:
        return DEFAULT_RECLAIM_DEADLINE_S


class ServingContext:
    """Everything the request handlers need, bundled for the handler class."""

    def __init__(self, engine: Engine, served_model: str,
                 prefill_urls=None, frontend_url=None, kvbm_peers=None):
        self.engine = engine
        self.service = EngineService(engine)
        self.served_model = served_model
        self.tokenizer = get_tokenizer(engine.cfg.model, engine.cfg.model_path)
        self.metrics = FrontendMetrics()
        # per-tenant QoS identity (dynamo_tpu.qos): the engine built the
        # registry from cfg.tenants / DYNAMO_TPU_TENANTS — handlers resolve
        # every inference request's tenant against the same classes the
        # weighted-fair scheduler budgets with
        self.tenants = engine.tenant_registry
        self.kv_gauge = Gauge(
            "dynamo_worker_kv_free_pages", "Free KV pages", self.metrics.registry
        )
        # --- multi-LoRA adapter serving (dynamo_tpu.lora) ---
        self.lora_requests_total = None
        self.lora_loaded_gauge = None
        if engine.lora is not None:
            from dynamo_tpu.serving.metrics import CallbackCounter, Counter

            self.lora_requests_total = Counter(
                "dynamo_lora_requests_total",
                "Requests served under a LoRA adapter, by adapter",
                self.metrics.registry, labelnames=("adapter",),
            )
            CallbackCounter(
                "dynamo_lora_swaps_total",
                "Adapter loads into a device slot (incl. LRU swap reloads)",
                self.metrics.registry,
                lambda: engine.lora.swaps_total,
            )
            self.lora_loaded_gauge = Gauge(
                "dynamo_lora_loaded",
                "Adapters resident in device slots right now",
                self.metrics.registry,
            )
        # --- KVBM tiered block manager (dynamo_tpu.kvbm) ---
        self.kv_event_publisher = None  # attached by the worker entrypoint
        self.kvbm_source = None  # peer-pull server over the transfer plane
        if engine.kvbm is not None:
            self.engine.kvbm.tracer = None  # set below with the tracer
            from dynamo_tpu.serving.metrics import CallbackCounter

            kvbm = engine.kvbm
            for name, help_, attr in (
                ("dynamo_kvbm_host_hits_total",
                 "Prefix lookups served from the KVBM host/disk tier",
                 "host_hits_total"),
                ("dynamo_kvbm_host_misses_total",
                 "Prefix lookup tails the KVBM tiers could not serve",
                 "host_misses_total"),
                ("dynamo_kvbm_demoted_blocks_total",
                 "KV blocks demoted from device to the host tier",
                 "demoted_blocks_total"),
                ("dynamo_kvbm_onboarded_blocks_total",
                 "KV blocks onboarded back onto the device",
                 "onboarded_blocks_total"),
                ("dynamo_kvbm_peer_onboarded_blocks_total",
                 "KV blocks onboarded from a peer worker's host tier",
                 "peer_onboarded_blocks_total"),
                ("dynamo_kvbm_removed_blocks_total",
                 "KV blocks dropped from every tier",
                 "removed_blocks_total"),
                ("dynamo_kvbm_gate_recompute_total",
                 "Onboards skipped because recompute beat restore",
                 "gate_recompute_total"),
            ):
                CallbackCounter(name, help_, self.metrics.registry,
                                (lambda k=kvbm, a=attr: getattr(k, a)))
            self.kvbm_blocks_gauge = Gauge(
                "dynamo_kvbm_host_blocks",
                "KVBM host-pool occupancy by state", self.metrics.registry,
                labelnames=("state",))
            from dynamo_tpu.transfer.kv_transfer import HostTierSource

            self.kvbm_source = HostTierSource(kvbm)
            log.info("kvbm host tier serving peers on port %d",
                     self.kvbm_source.port)
            if kvbm_peers:
                self._wire_kvbm_peers(kvbm, kvbm_peers)
        self.staged_kv_gauge = None  # registered with DeviceKVSource below
        self.preempt_gauge = Gauge(
            "dynamo_worker_preempted_sequences",
            "Sequences preempted (recompute) under KV page pressure",
            self.metrics.registry,
        )
        # --- engine watchdog (dynamo_tpu/robustness/watchdog.py): the
        # health state machine drives readiness, the /v1 shed gate, and
        # the planner's capacity view; trips hand journaled streams off
        # to a peer exactly like a pre-drain
        from dynamo_tpu.serving.metrics import CallbackCounterVec

        wd = engine.watchdog
        self.health_gauge = Gauge(
            "dynamo_engine_health",
            "Engine health state machine: 0=healthy 1=suspect "
            "2=resurrecting 3=quarantined",
            self.metrics.registry)
        self.health_gauge.set(wd.health_code)
        CallbackCounterVec("dynamo_engine_watchdog_trips_total",
             "Watchdog trips by kind (hung_dispatch, fatal_step)",
             self.metrics.registry,
             lambda: {(("kind", k),): v
                      for k, v in wd.summary()["trips_total"].items()},
             labelnames=("kind",))
        CallbackCounterVec("dynamo_engine_integrity_faults_total",
             "Integrity sentinel trips by sentinel "
             "(logits, decode_tokens, kv_checksum)",
             self.metrics.registry,
             lambda: {(("sentinel", s),): v
                      for s, v in
                      wd.summary()["integrity_faults_total"].items()},
             labelnames=("sentinel",))
        wd.on_trip = self._on_watchdog_trip
        wd.on_health = self._on_engine_health
        # --- live elasticity (dynamo_tpu/elasticity): the active weight
        # version as a labelled gauge (1 on the live label), refreshed at
        # scrape with label death so a flip/rollback never leaves a stale
        # version row next to the live one
        self.weight_version_gauge = Gauge(
            "dynamo_engine_weight_version",
            "Active weight version (1 on the live `version` label; the "
            "staged/rollback buffers show in "
            "dynamo_memory_staged_weights_bytes)",
            self.metrics.registry, labelnames=("version",),
        )
        self._exported_weight_version: Optional[str] = None
        self.start_time = time.time()
        # --- graceful drain (SIGTERM; docs/robustness.md "Recovery
        # semantics") --- draining sheds NEW inference requests with 503;
        # drain_handoff makes journaled in-flight streams push their
        # journal back to the frontend and abort, so the frontend can
        # splice a continuation on another worker
        self.draining = threading.Event()
        self.drain_handoff = threading.Event()
        # --- spot reclamation (docs/robustness.md "Preemptible batch
        # tier") --- a POST /internal/reclaim notice (or the node's
        # maintenance signal, wired by the worker entrypoint) runs the
        # same drain state machine under a HARD deadline; reclaim_cb is
        # the entrypoint's hook that also deregisters and stops serving
        self.reclaiming = threading.Event()
        self.reclaim_done = threading.Event()
        self.reclaim_deadline_s: Optional[float] = None
        self.reclaim_cb = None  # (deadline_s) -> None, set by the worker
        # operator manifest `preemptible: true` (spot/reclaimable pool):
        # advertised in the worker heartbeat so frontends and the planner
        # know which capacity can vanish on a reclamation notice
        self.preemptible = os.environ.get(
            "DYNAMO_TPU_PREEMPTIBLE", "0").lower() not in ("", "0", "false")
        self._trace_lock = threading.Lock()  # one profiler capture at a time
        # distributed request tracing: one tracer per serving role; spans
        # land in the process-global ring buffer behind GET /debug/spans
        self.tracer = obs_tracing.Tracer(
            f"worker-{engine.cfg.disaggregation_mode or 'agg'}")
        # --- SLO plane (observability/slo.py): per-role burn rate from
        # this worker's own latency histograms; the role selector lets one
        # manifest give prefill pools a TTFT SLO and decode pools an ITL
        # SLO (the per-pool signals planner v2 scales on)
        self.slo = obs_slo.SLOEngine(
            self.metrics, role=engine.cfg.disaggregation_mode or "agg")
        # --- engine phase/utilization exposition (observability/
        # engine_metrics.py): PhaseTimer histograms, batch occupancy,
        # jit-compile counters, live roofline MFU/MBU on /metrics
        from dynamo_tpu.observability.engine_metrics import (
            attach_engine_metrics,
        )

        self.engine_bridge = attach_engine_metrics(
            self.metrics.registry, engine)
        # --- memory/cost exposition (observability/memory.py): exact KV
        # pool accounting by tier/tenant, device memory_stats gauges, and
        # the per-tenant cost counters off the engine's CostLedger
        from dynamo_tpu.observability.memory import attach_memory_metrics

        self.memory_bridge = attach_memory_metrics(
            self.metrics.registry, engine)
        from dynamo_tpu.serving.metrics import CallbackCounter as _CC

        _CC("dynamo_spans_dropped_total",
            "Finished spans evicted from the ring buffer before any "
            "scrape could lift them (size: DYNAMO_TPU_TRACE_BUFFER)",
            self.metrics.registry,
            lambda: self.tracer.collector.dropped_total)
        if engine.kvbm is not None:
            # kvbm.offload / kvbm.onboard spans land in this worker's ring
            # buffer (GET /debug/spans) like every other worker span
            engine.kvbm.tracer = self.tracer

        # --- disaggregation wiring (mirrors the reference's role flags,
        # /root/reference/examples/deploy/sglang/disagg.yaml:45-52) ---
        self.kv_source = None
        self.kv_device_source = None
        self.disagg_client = None
        mode = engine.cfg.disaggregation_mode
        if mode == "prefill":
            from dynamo_tpu.transfer.kv_transfer import DeviceKVSource, KVSource

            self.kv_source = KVSource(
                engine, port=engine.cfg.disaggregation_bootstrap_port
            )
            log.info("prefill role: KV bootstrap on port %d", self.kv_source.port)
            if engine.cfg.disaggregation_transfer_backend == "ici":
                # cross-process leg of the ici plane: stage parked KV for
                # device-buffer pulls (TCP KVSource stays as the fallback)
                self.kv_device_source = DeviceKVSource(engine)
                # registered only alongside the source: workers without the
                # device plane must not expose a label-less zero series
                self.staged_kv_gauge = Gauge(
                    "dynamo_worker_staged_kv_gathers",
                    "Device-plane staged KV gathers by state (leaked = "
                    "expired un-released, still pinning HBM)",
                    self.metrics.registry, labelnames=("state",),
                )
        elif mode == "decode":
            from dynamo_tpu.serving.disagg import DisaggDecodeClient, PrefillPool

            self.disagg_client = DisaggDecodeClient(
                self, PrefillPool(prefill_urls, frontend_url)
            )

    def _wire_kvbm_peers(self, kvbm, peers) -> None:
        """Cross-worker onboard: on a host-tier miss, try each configured
        peer's host tier over the transfer plane (kv_transfer.fetch_host_
        blocks) before falling back to recompute."""
        from dynamo_tpu.transfer.kv_transfer import fetch_host_blocks

        parsed = []
        for p in peers:
            host, _, port = p.strip().rpartition(":")
            if host and port.isdigit():
                parsed.append((host, int(port)))
        if not parsed:
            return

        def peer_fetch(hashes):
            hexes = [h.hex() for h in hashes]
            for host, port in parsed:
                try:
                    got = fetch_host_blocks(host, port, hexes)
                except (ConnectionError, OSError, TimeoutError) as e:
                    log.debug("kvbm peer %s:%d unreachable: %s",
                              host, port, e)
                    continue
                if got:
                    return got
            return []

        kvbm.peer_fetch = peer_fetch
        log.info("kvbm cross-worker onboard enabled: %d peer(s)",
                 len(parsed))

    def register_kv_route(self, prompt_token_ids, routing_text: str) -> None:
        """Feed the KV event publisher one request's (token-chain,
        text-chain) association — `routing_text` must be the canonical
        text the FRONTEND hashes for routing (completions: the prompt
        string; chat: json.dumps(messages)). No-op without a publisher.
        The chain is seeded with the engine's ACTIVE weight-version
        namespace so the keys match what the engine publishes; a request
        that registers just before a flip and admits just after simply
        loses its routing events (the plane is advisory)."""
        if self.kv_event_publisher is None:
            return
        try:
            self.kv_event_publisher.register(
                prompt_token_ids, routing_text, self.engine.cfg.page_size,
                namespace=self.engine._kv_namespace(None))
        except Exception:
            log.exception("kv route registration failed")

    def refresh_weight_gauge(self) -> None:
        v = self.engine.weights.version
        prev = self._exported_weight_version
        if prev is not None and prev != v:
            self.weight_version_gauge.remove(version=prev)
        self.weight_version_gauge.set(1, version=v)
        self._exported_weight_version = v

    def attach_kv_event_publisher(self, publisher) -> None:
        self.kv_event_publisher = publisher
        self.engine.set_kv_event_sink(publisher.on_engine_event)

    def capture_trace(self, duration_s: float) -> bytes:
        """Capture a jax.profiler trace for `duration_s` and return it as a
        zip of the trace directory (XProf/TensorBoard-loadable). The
        in-engine tracing story from SURVEY §5 — the deployment-level SLA
        profiler (dynamo_tpu.profiler) covers pre-deploy planning; this
        covers live per-step behavior."""
        import io
        import shutil
        import tempfile
        import zipfile

        import jax

        # non-blocking: a capture sleeps up to 30s, and the old blocking
        # acquire parked a second HTTP thread for that whole window —
        # concurrent captures now fail fast (the route answers 409)
        if not self._trace_lock.acquire(blocking=False):
            raise TraceBusy("a profiler capture is already running")
        try:
            d = tempfile.mkdtemp(prefix="dynamo-trace-")
            try:
                jax.profiler.start_trace(d)
                # for the length of the capture, and only then, the
                # stepline's segments are profiler annotations: the
                # host's phases on the device's clock, in the same file
                timeline = self.engine.timeline
                timeline.start_annotations(
                    jax.profiler.TraceAnnotation,
                    jax.profiler.StepTraceAnnotation)
                try:
                    # the capture window IS the critical section:
                    # _trace_lock serializes profiler runs and the acquire
                    # above is non-blocking (concurrent callers 409
                    # instead of parking)
                    time.sleep(min(max(duration_s, 0.05), 30.0))  # dynalint: off blocking-under-lock
                finally:
                    timeline.stop_annotations()
                    jax.profiler.stop_trace()
                buf = io.BytesIO()
                with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as z:
                    for root, _, files in os.walk(d):
                        for f in files:
                            full = os.path.join(root, f)
                            z.write(full, os.path.relpath(full, d))
                return buf.getvalue()
            finally:
                # temp-dir cleanup before releasing the (non-blocking-
                # acquire) capture lock: a new capture must never race an
                # old capture's teardown for the profiler singleton
                shutil.rmtree(d, ignore_errors=True)  # dynalint: off blocking-under-lock
        finally:
            self._trace_lock.release()

    def begin_drain(self) -> None:
        """Stop admission NOW: new /v1 + /disagg/prefill requests shed 503
        (+ Retry-After) so a retrying client or the frontend's 503
        failover lands them on another replica. In-flight requests keep
        running until they finish or hand off."""
        self.draining.set()

    def _on_watchdog_trip(self, kind: str, seam: str) -> None:
        """Watchdog trip (monitor or scheduler thread): hand journaled
        in-flight streams off to a peer exactly like a pre-drain. The
        nudge is load-bearing — a wedged engine emits no TokenEvents, so
        blocked handlers would never observe drain_handoff without it."""
        self.request_handoff()
        self.service.nudge_all()

    def _on_engine_health(self, state: str) -> None:
        from dynamo_tpu.robustness.watchdog import HEALTH_CODES

        self.health_gauge.set(HEALTH_CODES.get(state, 0))
        if state == "healthy" and not self.draining.is_set():
            # resurrection done: stop asking streams to hand off — but
            # never un-drain a worker that is draining for its own
            # reasons (SIGTERM, reclaim, pre-drain)
            self.drain_handoff.clear()

    def request_handoff(self) -> None:
        """Ask journaled in-flight streams to hand off: each pushes its
        journal tail (token seam + sampling-key snapshot) back to the
        frontend as the final stream comment and aborts; the frontend
        splices a continuation on another worker. Non-journaled requests
        are unaffected (they finish or time out under the drain bound)."""
        self.drain_handoff.set()

    def drain_demote(self) -> int:
        """Demote every sole-owned prefix-cache page to the KVBM host
        tier (one batched device gather) so surviving peers can serve the
        departing worker's prefixes via the cross-worker host-tier fetch.
        No-op without a KVBM tier. Returns pages demoted."""
        eng = self.engine
        if eng.prefix_cache is None or eng.kvbm is None:
            return 0
        with eng._exec_lock:
            return eng.kvbm.demote_all(eng.prefix_cache)

    def drain(self, drain_s: float = 30.0,
              handoff_grace_s: float = 5.0) -> bool:
        """The drain state machine (worker SIGTERM / chaos tests):
        draining -> (grace: finish naturally) -> handoff -> quiesce ->
        demote KV to the host tier. Returns True when the engine emptied
        within the budget."""
        eng = self.engine
        self.begin_drain()
        t0 = time.monotonic()
        deadline = t0 + max(0.0, drain_s)
        grace_end = min(deadline, t0 + max(0.0, handoff_grace_s))
        while time.monotonic() < grace_end and (eng.num_active
                                                or eng.pending):
            time.sleep(0.05)
        if eng.num_active or eng.pending:
            self.request_handoff()
        while time.monotonic() < deadline and (eng.num_active
                                               or eng.pending):
            time.sleep(0.1)
        demoted = self.drain_demote()
        if demoted:
            log.info("drain: demoted %d prefix pages to the host tier",
                     demoted)
        return not (eng.num_active or eng.pending)

    def reclaim(self, deadline_s: float) -> Dict[str, Any]:
        """Spot/maintenance reclamation notice: this worker's capacity
        disappears in `deadline_s` seconds, hard. Runs the drain state
        machine with the deadline as its bound — handoff is requested
        almost immediately (natural-finish grace is at most a quarter of
        the notice, never the luxury 5s default), journaled streams push
        their seams to the frontend, prefix KV demotes to the host tier
        for peer fetch, and the entrypoint's reclaim_cb (when wired)
        deregisters and stops the server. Idempotent: a second notice
        reports the in-progress drain. Returns the ack payload."""
        eng = self.engine
        first = not self.reclaiming.is_set()
        if first:
            self.reclaiming.set()
            self.reclaim_deadline_s = deadline_s
            eng.flight.note(
                "reclaim", deadline_s=round(deadline_s, 3),
                active=eng.num_active, pending=len(eng.pending))
            log.warning("reclamation notice: %.1fs to drain %d active / "
                        "%d pending", deadline_s, eng.num_active,
                        len(eng.pending))
            self.begin_drain()
            cb = self.reclaim_cb

            def _run():
                try:
                    if cb is not None:
                        cb(deadline_s)
                    else:
                        self.drain(drain_s=deadline_s,
                                   handoff_grace_s=min(5.0,
                                                       deadline_s / 4.0))
                finally:
                    self.reclaim_done.set()

            threading.Thread(target=_run, daemon=True,
                             name="reclaim").start()
        return {"reclaiming": True, "first_notice": first,
                "deadline_s": self.reclaim_deadline_s,
                "active_seqs": eng.num_active,
                "pending": len(eng.pending)}

    def rollout(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """POST /internal/rollout: the per-pod hot-swap control surface
        the operator's progressive fleet rollout drives (one action per
        request; `stage_flip` is the controller's single round trip).
        StageError maps to the handler's RuntimeError->503 path, so a
        refused stage (headroom, tree mismatch, version conflict) is
        retry-later to the controller and never touches the live tree."""
        from dynamo_tpu.elasticity.weights import StageError  # noqa: F401

        eng = self.engine
        wm = eng.weights
        action = (body.get("action") or "status").lower()
        if action == "status":
            out = wm.stats()
            out.update(active_seqs=eng.num_active,
                       pending=len(eng.pending))
            return out
        if action == "stage":
            return wm.stage(
                body.get("version") or "",
                model_path=body.get("model_path"),
                seed=body.get("seed"),
                quantization=body.get("quantization"))
        if action in ("flip", "stage_flip"):
            if action == "stage_flip":
                want = body.get("version") or ""
                if want and want == wm.version:
                    # idempotent: a controller retry after a timed-out
                    # round trip lands on an already-flipped pod
                    return {"version": wm.version, "state": "live",
                            "already": True}
                if wm.staged_version != want:
                    wm.stage(
                        want,
                        model_path=body.get("model_path"),
                        seed=body.get("seed"),
                        quantization=body.get("quantization"))
            mode = (body.get("mode")
                    or os.environ.get(ROLLOUT_DRAIN_MODE_ENV, "finish")
                    or "finish").lower()
            if mode not in ("finish", "handoff"):
                raise proto.BadRequest(
                    f"mode {mode!r} not in ('finish', 'handoff')")
            if mode == "handoff" and eng.num_active:
                return self._flip_with_handoff(wm)
            return wm.flip(mode="finish")
        if action == "rollback":
            if wm.previous_version is None and wm.staged_version:
                # the pod never flipped (stage resident / flip armed):
                # dropping the staged tree IS the rollback — admissions
                # reopen and the original version keeps serving
                wm.abort_stage()
                return {"version": wm.version, "state": "rolled_back",
                        "rolled_back": None}
            return wm.rollback()
        if action == "commit":
            return wm.commit()
        if action == "abort":
            return {"aborted": wm.abort_stage(), "version": wm.version}
        raise proto.BadRequest(
            f"action {action!r} not in (status, stage, flip, stage_flip, "
            "rollback, commit, abort)")

    def _flip_with_handoff(self, wm) -> Dict[str, Any]:
        """Handoff-mode flip: journaled in-flight streams push their seams
        to the frontend (which resumes them on a peer still serving the
        old version — the HA plane's normal continuation path) and the
        pointer flips the moment the engine empties. Unlike drain, the
        worker STAYS in service: admission never closes, the handoff flag
        clears, and post-flip requests land on the new version here."""
        eng = self.engine
        self.drain_handoff.set()
        deadline = time.monotonic() + ROLLOUT_HANDOFF_GRACE_S
        try:
            while time.monotonic() < deadline and eng.num_active:
                time.sleep(0.05)
        finally:
            self.drain_handoff.clear()
        if eng.num_active:
            # non-journaled stragglers: never flip under them — fall back
            # to the armed finish flip (they complete on the old version)
            eng.flight.note("rollout_handoff_stragglers",
                            active=eng.num_active)
            return wm.flip(mode="finish")
        return wm.flip(mode="now")

    def close(self):
        if self.kv_source is not None:
            self.kv_source.close()
        if self.kvbm_source is not None:
            self.kvbm_source.close()
        self.service.close()

    def start_generation(self, rid, prompt_ids, params, index: int = 0,
                         trace_span=None, deadline=None,
                         received_at=None) -> "GenerationHandle":
        return GenerationHandle(self, rid, prompt_ids, params, index=index,
                                trace_span=trace_span, deadline=deadline,
                                received_at=received_at)

    def start_choices(self, rid, prompt_ids, params,
                      trace_span=None, deadline=None,
                      received_at=None) -> List["GenerationHandle"]:
        """Submit all n choices of a request (choice i streams under
        request_id '<rid>-i'). Submission is all-or-nothing: a rejection on
        choice k aborts choices 0..k-1 before re-raising."""
        n = params.get("n", 1)
        handles: List[GenerationHandle] = []
        try:
            for i in range(n):
                handles.append(GenerationHandle(
                    self, f"{rid}-{i}" if n > 1 else rid,
                    prompt_ids, params, index=i, trace_span=trace_span,
                    deadline=deadline, received_at=received_at,
                ))
        except Exception:
            for h in handles:
                self.service.abort(h.rid)
            raise
        return handles


def run_choices(handles: List["GenerationHandle"], emit_for) -> List[tuple]:
    """Drive n choice streams concurrently; emit_for(handle) returns that
    choice's emit callback (already thread-safe). Returns the per-choice
    (text, finish_reason, completion_tokens) in choice order; the first
    choice failure propagates after all threads settle."""
    if len(handles) == 1:
        return [handles[0].run(emit_for(handles[0]))]
    results: List[Optional[tuple]] = [None] * len(handles)
    errors: List[Optional[BaseException]] = [None] * len(handles)

    def drive(i: int):
        try:
            results[i] = handles[i].run(emit_for(handles[i]))
        except BaseException as e:  # noqa: BLE001 — reported to the client
            errors[i] = e

    threads = [threading.Thread(target=drive, args=(i,), daemon=True)
               for i in range(len(handles))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for e in errors:
        if e is not None:
            raise e
    return results  # type: ignore[return-value]


class _Handler(JsonHTTPHandler):
    ctx: ServingContext  # bound by make_server
    _span = obs_tracing.NOOP_SPAN  # set per-request in do_POST

    # ------------------------------------------------------------- routes --
    def _model_ids(self) -> List[str]:
        """Served model ids: the base plus one '<base>:<adapter>' entry per
        host-registered adapter (multi-LoRA addressing)."""
        ids = [self.ctx.served_model]
        lora = self.ctx.engine.lora
        if lora is not None:
            ids += [f"{self.ctx.served_model}:{n}" for n in lora.names()]
        return ids

    def do_GET(self):
        path = self.path.split("?")[0]
        if path == "/v1/models":
            self._json(200, proto.models_response(self._model_ids()))
        elif path.startswith("/v1/models/"):
            mid = path[len("/v1/models/"):]
            if mid in self._model_ids():
                self._json(200, proto.model_response(mid))
            else:
                self._error(404, f"model {mid!r} not found", "not_found")
        elif path == "/v1/adapters":
            lora = self.ctx.engine.lora
            if lora is None:
                self._error(400, "this worker serves no adapters "
                            "(--lora-slots is 0)")
                return
            st = lora.stats()
            self._json(200, {
                "object": "list",
                "data": lora.describe(),
                "slots": {"total": st["slots_total"],
                          "free": st["slots_free"]},
            })
        elif path == "/metrics":
            self.ctx.preempt_gauge.set(
                self.ctx.engine.metrics.num_preempted)
            if self.ctx.lora_loaded_gauge is not None:
                self.ctx.lora_loaded_gauge.set(
                    len(self.ctx.engine.lora.resident()))
            if self.ctx.engine.kvbm is not None:
                pool = self.ctx.engine.kvbm.pool.stats()
                self.ctx.kvbm_blocks_gauge.set(pool["used_blocks"],
                                               state="used")
                self.ctx.kvbm_blocks_gauge.set(pool["capacity_blocks"],
                                               state="capacity")
            ds = self.ctx.kv_device_source
            if ds is not None:
                # scrape-time refresh: leaked > 0 flags a decode peer that
                # stages and crashes before pulling (HBM pinned until
                # /disagg/release) — alertable without log spelunking
                live, leaked = ds.counts()  # one lock/sweep: no double count
                self.ctx.staged_kv_gauge.set(live, state="staged")
                self.ctx.staged_kv_gauge.set(leaked, state="leaked")
            self.ctx.slo.refresh_gauges()
            self.ctx.engine_bridge.refresh()  # live MFU/MBU + warmup gauges
            self.ctx.memory_bridge.refresh()  # KV-pool/tier/tenant bytes
            self.ctx.refresh_weight_gauge()  # active weight version label
            self.ctx.health_gauge.set(  # watchdog health state machine
                self.ctx.engine.watchdog.health_code)
            body, ctype = self.ctx.metrics.registry.scrape(
                self.headers.get("Accept"))
            self._raw(200, body, ctype)
        elif path == "/live":
            # liveness stays 200 through suspect/resurrecting — killing
            # the pod mid-resurrection would turn every recoverable trip
            # into a full replacement. Quarantine is the operator's cue
            # to replace, and that rides readiness, not liveness.
            self._json(200, {"status": "ok", "uptime_s": round(
                time.time() - self.ctx.start_time, 1)})
        elif path in ("/health", "/ready"):
            wd = self.ctx.engine.watchdog
            if not wd.ok_for_traffic:
                # the quarantine invariant: a worker that cannot prove
                # progress is provably out of rotation — readiness 503
                # pulls it from k8s endpoints and the router's breakers
                self._error(503, f"engine {wd.health}",
                            "service_unavailable",
                            headers={"Retry-After": "5"})
                return
            self._json(200, {"status": "ok", "uptime_s": round(
                time.time() - self.ctx.start_time, 1)})
        elif path == "/debug/spans":
            from urllib.parse import parse_qs, urlparse

            qs = parse_qs(urlparse(self.path).query)
            self._json(200, obs_tracing.spans_debug_payload(
                qs, self.ctx.tracer.collector))
        elif path == "/debug/slo":
            from urllib.parse import parse_qs, urlparse

            qs = parse_qs(urlparse(self.path).query)
            self._json(200, obs_slo.debug_slo_payload(self.ctx.slo, qs))
        elif path == "/internal/faults":
            self._json(200, faults.http_payload())
        elif path == "/debug/trace":
            from urllib.parse import parse_qs, urlparse

            qs = parse_qs(urlparse(self.path).query)
            try:
                dur = float((qs.get("duration_s") or ["1.0"])[0])
            except ValueError:
                self._error(400, "duration_s must be a number")
                return
            try:
                data = self.ctx.capture_trace(dur)
            except TraceBusy as e:
                # another capture holds the profiler (they sleep up to
                # 30s); tell the client when to come back instead of
                # parking this thread on the lock
                self._error(409, str(e), "conflict",
                            headers={"Retry-After": str(int(dur) + 1)})
                return
            except Exception as e:
                log.exception("trace capture failed")
                self._error(503, f"trace capture failed: {e}",
                            "service_unavailable")
                return
            self._raw(200, data, "application/zip")
        elif path in ("/debug", "/debug/"):
            self._json(200, {"endpoints": WORKER_DEBUG_INDEX})
        elif path == "/debug/flight":
            from urllib.parse import parse_qs, urlparse

            from dynamo_tpu.observability.flight import debug_flight_payload

            qs = parse_qs(urlparse(self.path).query)
            self._json(200, debug_flight_payload(
                self.ctx.engine.flight, qs))
        elif path == "/debug/timeline":
            from urllib.parse import parse_qs, urlparse

            from dynamo_tpu.observability.timeline import (
                timeline_debug_payload,
            )

            qs = parse_qs(urlparse(self.path).query)
            self._json(200, timeline_debug_payload(
                self.ctx.engine.timeline, qs,
                collector=self.ctx.tracer.collector))
        elif path == "/debug/costs":
            self._json(200, self.ctx.engine.cost.rollup())
        elif path == "/worker/stats":
            import dataclasses

            eng = self.ctx.engine
            out = {
                "model": self.ctx.served_model,
                "active_seqs": eng.num_active,
                "pending": len(eng.pending),
                "free_pages": eng.allocator.free_pages,
                "total_pages": eng.cfg.num_pages,
                "max_num_seqs": eng.cfg.max_num_seqs,
                "disaggregation_mode": eng.cfg.disaggregation_mode,
                # platform / device_kind / device_count as JAX reports
                # them, versions, compiled programs and the attention
                # implementations traced
                **device_report(eng),
                # watchdog health state machine + trip/sentinel counters
                # (the same summary the heartbeat carries to frontends)
                "health": eng.watchdog.summary(),
                # the full effective EngineConfig: profiles, engine-config
                # files, and CLI flags all merge before the engine starts,
                # so operators need the RESOLVED values, not the manifest
                "config": dataclasses.asdict(eng.cfg),
                "metrics": eng.metrics.snapshot(),
            }
            if eng.cfg.speculative_mode != "off":
                # speculation health at a glance: acceptance_rate is
                # accepted/draft (the knob docs/perf.md "Speculative
                # decoding v2" tunes K against), mean_accept_len the
                # per-window histogram mean
                m = eng.metrics
                out["spec"] = {
                    "mode": eng.cfg.speculative_mode,
                    "drafter": eng.drafter_name,
                    "num_speculative_tokens": eng.cfg.num_speculative_tokens,
                    "ngram_lookup": eng.cfg.ngram_lookup,
                    "draft_tokens": m.spec_draft_tokens,
                    "accepted_tokens": m.spec_accepted_tokens,
                    "acceptance_rate": (
                        round(m.spec_accepted_tokens / m.spec_draft_tokens, 4)
                        if m.spec_draft_tokens else 0.0),
                    "mean_accept_len": (
                        round(m.spec_accept_sum / m.spec_accept_count, 4)
                        if m.spec_accept_count else 0.0),
                    # Speculation v3: per-drafter acceptance (the drafter
                    # label of the dynamo_engine_spec_* series), the
                    # draft engine's pool/rollback books, and the
                    # adaptive-K controller's live per-slot windows
                    "by_drafter": {
                        d: {
                            "draft_tokens": m.spec_draft_by.get(d, 0),
                            "accepted_tokens": m.spec_accepted_by.get(d, 0),
                            "acceptance_rate": (
                                round(m.spec_accepted_by.get(d, 0)
                                      / m.spec_draft_by[d], 4)
                                if m.spec_draft_by.get(d) else 0.0),
                        }
                        for d in sorted(set(m.spec_draft_by)
                                        | set(m.spec_count_by))},
                }
                if eng.draft is not None:
                    out["spec"]["draft_engine"] = eng.draft.stats()
                if eng._adaptive is not None:
                    out["spec"]["adaptive_k"] = {
                        "k_max": eng._adaptive.k_max,
                        "slots": eng._adaptive.snapshot(),
                    }
            # live elasticity: active/staged/previous weight versions and
            # the double-buffer bytes (what the rollout controller polls)
            out["weights"] = eng.weights.stats()
            pc = getattr(eng, "prefix_cache", None)
            if pc is not None:
                out["prefix_cache"] = pc.stats()
            if eng.lora is not None:
                out["lora"] = eng.lora.stats()
            if eng.qos is not None:
                # per-tenant QoS: budget balances, token totals, and the
                # defer/preempt counters the isolation tests assert on
                out["qos"] = eng.qos.stats()
            if eng.kvbm is not None:
                out["kvbm"] = eng.kvbm.stats()
                if self.ctx.kvbm_source is not None:
                    out["kvbm"]["peer_port"] = self.ctx.kvbm_source.port
                if self.ctx.kv_event_publisher is not None:
                    out["kvbm"]["events"] = (
                        self.ctx.kv_event_publisher.stats())
            dc = self.ctx.disagg_client
            if dc is not None:
                # which KV plane requests ACTUALLY used (an ici deployment
                # that degraded to dcn shows up here, not just in a log)
                out["transfer_planes"] = dict(dc.plane_counts)
            ds = self.ctx.kv_device_source
            if ds is not None:
                # stage ledger health: leaked > 0 means a decode peer is
                # staging and crashing before pull/release, pinning HBM
                live, leaked = ds.counts()
                out["staged_kv"] = {"live": live, "leaked": leaked}
            # exact KV books by tier/tenant + per-tenant cost rollup —
            # the same numbers the dynamo_memory_*/dynamo_tenant_cost_*
            # series export, in one JSON read for dynamo_top and the
            # frontend's fleet aggregation
            try:
                out["memory"] = self.ctx.memory_bridge.accountant.snapshot()
            except Exception:
                log.exception("memory snapshot failed in /worker/stats")
            out["costs"] = eng.cost.rollup()
            out["timeline"] = eng.timeline.summary()
            self._json(200, out)
        else:
            self._error(404, f"no route {path}")

    def do_POST(self):
        self._received_at = time.monotonic()
        path = self.path.split("?")[0]
        if (self.ctx.draining.is_set()
                and path.startswith(("/v1/", "/disagg/prefill"))):
            # graceful drain: admission is OFF before anything else — a
            # 503 here is retry-safe by construction (nothing ran), and
            # the frontend fails it over to another replica. The disagg
            # stage/release routes stay up: decode peers must still
            # finish in-flight KV pulls against this worker.
            self._error(503, "worker draining; retry another replica",
                        "service_unavailable")
            return
        if (not self.ctx.engine.watchdog.ok_for_traffic
                and path.startswith(("/v1/", "/disagg/prefill"))):
            # watchdog shed: a suspect/resurrecting/quarantined engine
            # takes no new inference work. Deliberately NOT routed
            # through ctx.draining — recovery must not un-drain a worker
            # that is draining for its own reasons.
            self._error(
                503,
                f"engine {self.ctx.engine.watchdog.health}; "
                "retry another replica",
                "service_unavailable", headers={"Retry-After": "5"})
            return
        # robustness plane: read-stall / reset-after-headers fault points
        # (no-ops unless armed; control-plane routes are exempt)
        self._fault_gate()
        # request span: child of the frontend's span when a traceparent
        # arrived (HTTP header, or bridged off NATS message headers by
        # nats_plane), else a fresh root seeded by x-request-id
        span = obs_tracing.NOOP_SPAN
        self._deadline = None
        self._tenant = "default"
        if path in ("/v1/chat/completions", "/v1/completions",
                    "/disagg/prefill"):
            parent = obs_context.extract_context(self.headers)
            inbound_rid = ((self.headers.get("x-request-id") or "").strip()
                           or None)
            # per-tenant QoS: trust the frontend's resolved identity
            # (x-dynamo-tenant) when present, else resolve from the
            # client's own headers — the agg single-pod path IS the edge
            self._tenant = self.ctx.tenants.resolve(self.headers,
                                                    trusted=True)
            # the propagated deadline budget (x-deadline) keeps counting
            # down on this hop; requests arriving already-exhausted shed
            # with 504 before taking an engine slot
            self._deadline = Deadline.from_headers(self.headers)
            span = self.ctx.tracer.start_span(
                "worker.request", parent=parent, kind="server",
                trace_seed=inbound_rid,
                attributes={
                    "http.path": path,
                    "worker.mode":
                        self.ctx.engine.cfg.disaggregation_mode or "agg",
                    "deadline_s": round(self._deadline.budget_s, 3),
                    "model": self.ctx.served_model,
                    "tenant.id": self._tenant,
                })
            rid = inbound_rid or (span.trace_id if span.recording else None)
            if rid:
                self.set_request_id(rid)
        self._span = span
        try:
            try:
                if self._deadline is not None and self._deadline.expired:
                    raise TimeoutError(
                        "deadline budget exhausted before processing; "
                        "request shed")
                if path == "/v1/chat/completions":
                    self._chat(self._read_json_body())
                elif path == "/v1/completions":
                    self._completion(self._read_json_body())
                elif path == "/disagg/prefill":
                    self._disagg_prefill(self._read_json_body())
                elif path == "/disagg/stage":
                    self._disagg_stage(self._read_json_body())
                elif path == "/disagg/release":
                    self._disagg_release(self._read_json_body())
                elif path == "/v1/adapters":
                    self._adapters_post(self._read_json_body())
                elif path == "/internal/faults":
                    try:
                        self._json(200, faults.http_configure(
                            self._read_json_body()))
                    except ValueError as e:
                        raise proto.BadRequest(str(e))
                elif path == "/internal/drain":
                    # planner v2 pre-drain: the operator marks this pod a
                    # scale-down victim and asks it to start shedding /
                    # handing off BEFORE the Deployment shrink delivers
                    # SIGTERM (which runs the same, idempotent drain)
                    try:
                        body = self._read_json_body()
                    except Exception:  # noqa: BLE001 — body is optional
                        body = {}
                    self.ctx.begin_drain()
                    if body.get("handoff"):
                        self.ctx.request_handoff()
                    self._json(200, {"draining": True,
                                     "active_seqs":
                                         self.ctx.engine.num_active,
                                     "pending":
                                         len(self.ctx.engine.pending)})
                elif path == "/internal/rollout":
                    # hitless weight rollout control surface (docs/
                    # robustness.md "Hitless weight rollout"): stage /
                    # flip / rollback / commit / status. Stays reachable
                    # while draining (it is not a /v1 route) so a fleet
                    # rollback can still reach a pod mid-drain.
                    try:
                        body = self._read_json_body()
                    except Exception:  # noqa: BLE001 — body is optional
                        body = {}
                    wd = self.ctx.engine.watchdog
                    if not wd.ok_for_traffic:
                        # fail fast instead of parking this HTTP thread
                        # on a wedged engine's exec lock — the operator's
                        # tick stays bounded and retries once the
                        # resurrection (or pod replacement) lands
                        self._error(
                            503, f"engine {wd.health}; rollout refused",
                            "service_unavailable",
                            headers={"Retry-After": "5"})
                        return
                    self._json(200, self.ctx.rollout(body))
                elif path == "/internal/reclaim":
                    # spot/maintenance reclamation notice: this replica's
                    # capacity disappears in deadline_s seconds — ack
                    # immediately, drain under the hard deadline in the
                    # background (docs/robustness.md "Preemptible batch
                    # tier")
                    try:
                        body = self._read_json_body()
                    except Exception:  # noqa: BLE001 — body is optional
                        body = {}
                    qs = urllib.parse.parse_qs(
                        urllib.parse.urlsplit(self.path).query)
                    raw = (qs.get("deadline_s", [None])[0]
                           if qs.get("deadline_s")
                           else body.get("deadline_s"))
                    try:
                        deadline_s = (float(raw) if raw is not None
                                      else _env_reclaim_deadline_s())
                    except (TypeError, ValueError):
                        raise proto.BadRequest(
                            f"invalid deadline_s {raw!r}")
                    if deadline_s <= 0:
                        raise proto.BadRequest(
                            "deadline_s must be > 0")
                    self._json(200, self.ctx.reclaim(deadline_s))
                else:
                    self._error(404, f"no route {path}")
            except Exception as e:
                span.set_status("ERROR", f"{type(e).__name__}: {e}")
                raise
        except proto.BadRequest as e:
            self._fail(400, str(e))
        except OutOfPages as e:  # transient capacity: client should retry
            self._fail(503, str(e), "service_unavailable")
        except RuntimeError as e:  # disagg dependency unavailable
            self._fail(503, str(e), "service_unavailable")
        except ValueError as e:  # engine-level rejection (over-length, ...)
            self._fail(400, str(e))
        except TimeoutError as e:
            self._fail(504, str(e), "timeout")
        except Exception:
            log.exception("request failed")
            self._fail(500, "internal error", "internal_error")
        finally:
            span.end()

    def _fail(self, code: int, msg: str, etype: str = "invalid_request_error"):
        if code >= 500:
            # the worker-side error-rate SLO source (observability/slo.py);
            # 4xx are the client's problem and never burn budget
            self.ctx.metrics.errors_total.inc(
                model=self.ctx.served_model, code=str(code))
        if self.sse_started:
            self._sse_error(msg)
        else:
            self._error(code, msg, etype)

    # ------------------------------------------------------------ handlers --
    def _disagg_prefill(self, body):
        """Prefill-role RPC: run the prompt, park KV, return the bootstrap
        coordinates for the decode side's pull."""
        ctx = self.ctx
        if ctx.kv_source is None:
            raise proto.BadRequest(
                "this worker is not in --disaggregation-mode prefill"
            )
        rid = body.get("request_id")
        ids = body.get("prompt_token_ids")
        if not rid or not isinstance(ids, list) or not ids:
            raise proto.BadRequest("need request_id and prompt_token_ids")
        lp = body.get("logprobs")
        seed = body.get("seed")
        req = GenRequest(
            rid, [int(t) for t in ids],
            temperature=float(body.get("temperature", 0.0)),
            top_p=float(body.get("top_p", 1.0)),
            top_k=int(body.get("top_k", 0)),
            min_p=float(body.get("min_p", 0.0)),
            logit_bias={int(k): float(v)
                        for k, v in (body.get("logit_bias") or {}).items()}
            or None,
            seed=int(seed) if seed is not None else None,
            logprobs=int(lp) if lp is not None else None,
            guided_json=bool(body.get("guided_json", False)),
            # multi-LoRA: the decode role forwards its request's adapter so
            # the prefill runs under the same weights the decode will
            adapter=body.get("adapter") or None,
            # per-tenant QoS: the decode role forwards the resolved tenant
            # so prefill-side spans/metrics agree with the decode side
            tenant=body.get("tenant") or None,
        )
        if req.tenant:
            self._tenant = req.tenant
        else:
            req.tenant = self._tenant  # header-resolved (x-dynamo-tenant)
        self.ctx.metrics.tenant_requests.inc(tenant=self._tenant)
        self._span.set_attribute("request.id", rid)
        faults.sleep_point("worker.slow_prefill")
        if self._deadline is not None and self._deadline.expired:
            # the stall (queueing, chaos, or a slow peer) ate the whole
            # budget: shed BEFORE running a prefill nobody will pull
            raise TimeoutError(
                "deadline budget exhausted before prefill; request shed")
        t0 = time.monotonic()
        with ctx.tracer.start_span(
                "worker.prefill_only", parent=self._span,
                attributes={"request.id": rid,
                            "prompt_tokens": len(ids)}) as pspan:
            first, n_tokens, extras = ctx.engine.prefill_only(req)
            eng_ph = ctx.engine.metrics.phases
            pspan.set_attributes({
                "engine.prefill.p50_ms":
                    round(eng_ph["prefill"].quantile_ms(0.5), 3),
                "engine.prefill.p95_ms":
                    round(eng_ph["prefill"].quantile_ms(0.95), 3),
            })
        ctx.metrics.ttft.observe(
            time.monotonic() - t0,
            exemplar=(self._span.trace_id if self._span.recording else None),
            model=ctx.served_model)
        ctx.metrics.requests_total.inc(model=ctx.served_model)
        ctx.metrics.isl.observe(n_tokens, model=ctx.served_model)
        self._json(200, {
            "request_id": rid,
            "first_token": first,
            "n_tokens": n_tokens,
            "bootstrap_port": ctx.kv_source.port,
            "transfer_backend": ctx.engine.cfg.disaggregation_transfer_backend,
            # staging itself is lazy (/disagg/stage) so a TCP-pulling peer
            # never pins a gathered device copy in the transfer server
            "device_transfer": bool(ctx.kv_device_source is not None
                                    and ctx.kv_device_source.eligible),
            **extras,
        })

    def _disagg_stage(self, body):
        """Stage a parked sequence's KV with the transfer server and return
        the device-pull coordinates (called by an ici decode peer just
        before it pulls)."""
        ctx = self.ctx
        if ctx.kv_device_source is None:
            raise proto.BadRequest(
                "this worker does not serve device-buffer KV transfer")
        rid = body.get("request_id")
        if not rid:
            raise proto.BadRequest("need request_id")
        try:
            staged = ctx.kv_device_source.stage(rid)
        except KeyError:
            raise proto.BadRequest(f"unknown request {rid!r}")
        if staged is None:
            raise proto.BadRequest("device-buffer staging unavailable")
        self._json(200, {"request_id": rid, **staged})

    def _disagg_release(self, body):
        """Decode-side ack for a device-buffer KV pull: free the parked
        pages (the TCP plane acks in-stream; the TTL sweep covers peers
        that crash between pull and release)."""
        ctx = self.ctx
        if ctx.kv_source is None:
            raise proto.BadRequest(
                "this worker is not in --disaggregation-mode prefill"
            )
        rid = body.get("request_id")
        if not rid:
            raise proto.BadRequest("need request_id")
        ctx.engine.release_parked(rid)
        if ctx.kv_device_source is not None:
            # forget the staged gather too, so the stage ledger (and its
            # array refs) doesn't wait out the TTL for well-behaved peers
            ctx.kv_device_source.mark_released(rid)
        self._json(200, {"request_id": rid, "released": True})

    def _adapters_post(self, body):
        """Runtime adapter management (POST /v1/adapters):
        {"name": n, "path": p}           register (host store; device lazy)
        {"name": n, "path": p, "load": true}   register + pin into a slot
        {"name": n, "unload": true}      drop the device slot (host stays)
        {"name": n, "remove": true}      unregister entirely
        """
        from dynamo_tpu.lora.registry import NoFreeAdapterSlot

        lora = self.ctx.engine.lora
        if lora is None:
            raise proto.BadRequest(
                "this worker serves no adapters (--lora-slots is 0)")
        name = body.get("name")
        if not isinstance(name, str) or not name:
            raise proto.BadRequest("'name' is required")
        try:
            if body.get("remove"):
                lora.unregister(name)
                self._json(200, {"name": name, "removed": True})
                return
            if body.get("unload"):
                was = lora.unload(name)
                self._json(200, {"name": name, "unloaded": was})
                return
            if body.get("path"):
                lora.register(name, path=str(body["path"]))
            elif not lora.known(name):
                raise proto.BadRequest(
                    f"unknown adapter {name!r} (give 'path' to register)")
            slot = None
            if body.get("load"):
                slot = lora.acquire_slot(name)
        except NoFreeAdapterSlot as e:
            self._error(503, str(e), "service_unavailable")
            return
        except (ValueError, KeyError) as e:
            raise proto.BadRequest(str(e))
        self._json(200, {"name": name, "registered": True,
                         "resident": lora.slot_of(name) is not None,
                         **({"slot": slot} if slot is not None else {})})

    def _check_model(self, model: str) -> Optional[str]:
        """Validate the request's model id; returns the adapter name when
        the id uses '<base>:<adapter>' addressing (multi-LoRA), else None."""
        bases = (self.ctx.served_model, self.ctx.engine.cfg.model)
        if model in bases:
            return None
        adapter = None
        for b in bases:
            if model.startswith(b + ":"):
                adapter = model[len(b) + 1:]
                break
        lora = self.ctx.engine.lora
        if adapter and lora is not None and lora.known(adapter):
            return adapter
        raise proto.BadRequest(
            f"model {model!r} not served (serving {self.ctx.served_model!r}"
            + (f" + adapters {lora.names()}" if lora is not None else "")
            + ")"
        )

    # ------------------------------------------- mid-stream recovery ----
    def _journal_comment(self, obj) -> None:
        """One recovery-journal record as an SSE comment frame. Rides the
        response stream itself, so the journal dies with the connection
        exactly when the frontend stops needing it."""
        self._write_chunk(recovery.comment_frame(obj))

    def _setup_recovery(self, body, p, stream_gated: bool = False):
        """Continuation + journaling plumbing (serving/recovery.py).

        Returns (rec, journaling): `rec` is the validated inbound
        ``dynamo_recovery`` continuation (streaming only), `journaling`
        whether this stream should emit journal comments. For a journaled
        UNSEEDED sampled stream the effective seed is pinned here and
        journaled, so a continuation resumes the identical chain.
        `stream_gated` marks streams whose text is gated/buffered (auto
        tool-choice) — delivered chars there aren't a pure function of
        the token ids, so they are not journaled."""
        rec = body.get(recovery.RECOVERY_BODY_KEY)
        if rec is not None:
            try:
                rec = recovery.normalize_continuation(rec)
            except ValueError as e:
                raise proto.BadRequest(str(e))
        journaling = bool(self.headers.get(recovery.JOURNAL_HEADER)
                          and p["stream"] and p.get("n", 1) == 1
                          and not stream_gated)
        if rec is not None and p["stream"]:
            p["_recovery"] = rec
            if p["seed"] is None and rec.get("seed") is not None:
                p["seed"] = rec["seed"]
        if journaling and p["seed"] is None and p["temperature"] > 0:
            p["seed"] = random.getrandbits(31)
        return (rec if p["stream"] else None), journaling

    def _chat(self, body):
        p = proto.parse_chat_request(body)
        p["adapter"] = self._check_model(p["model"])
        p["tenant"] = self._tenant
        tools, tc = p["tools"], p["tool_choice"]
        forced_tool = isinstance(tc, tuple)  # ("function", name)
        if forced_tool:
            if p["stream"]:
                raise proto.BadRequest(
                    "streaming is not supported with a forced tool_choice")
            # the forced call's arguments are produced by the JSON-guided
            # decoder: one complete JSON object
            p["guided_json"] = True
        prompt_text = self.ctx.tokenizer.apply_chat_template(
            p["messages"], tools=tools if tc != "none" else None)
        prompt_ids = self.ctx.tokenizer.encode(prompt_text)
        # KV event plane: associate this request's token-block chain with
        # the canonical text the frontend's router hashed (json.dumps of
        # the messages — serving/frontend.py builds the same string)
        import json as _json

        self.ctx.register_kv_route(prompt_ids, _json.dumps(p["messages"]))
        # a recovery continuation reuses the ORIGINAL response id so the
        # spliced stream's chunks stay self-consistent for the client
        rec, journaling = self._setup_recovery(
            body, p, stream_gated=(tools is not None and tc == "auto"))
        rid = (rec or {}).get("response_id") or proto.new_id("chatcmpl")
        self._span.set_attribute("request.id", rid)
        handles = self.ctx.start_choices(  # may raise -> 400
            rid, prompt_ids, p, trace_span=self._span,
            deadline=self._deadline, received_at=self._received_at)

        if p["stream"]:
            with_null = p.get("include_usage", False)
            self._start_sse()
            lock = threading.Lock()
            if journaling:
                handles[0].journal_sink = self._journal_comment
                self._journal_comment(
                    {"start": {"id": rid, "seed": p.get("seed")}})
            if rec is None or not rec.get("role_sent"):
                # a continuation skips the role preamble when the
                # original stream already delivered it
                for h in handles:
                    self._sse_chunk(
                        proto.chat_chunk(rid, p["model"],
                                         {"role": "assistant"},
                                         None, with_usage_null=with_null,
                                         index=h.index)
                    )

            # tool_choice "auto": gate each choice's stream so a leading
            # '{' buffers until finish and can become ONE tool_calls
            # delta; anything else streams as before
            gating = tools is not None and tc == "auto"

            def emit_for(h):
                gate = proto.AutoToolStreamGate() if gating else None

                def emit(delta, finish, lp_entry) -> bool:
                    with lock:
                        ok = True
                        entries = ([lp_entry] if lp_entry is not None
                                   else [])
                        if gate is not None:
                            delta, entries = gate.feed(delta, lp_entry)
                            if finish is not None:
                                call, held, held_lp = gate.finish(tools, tc)
                                if call is not None:
                                    finish = "tool_calls"
                                    ok = self._sse_chunk(proto.chat_chunk(
                                        rid, p["model"],
                                        proto.tool_call_chunk_delta(call),
                                        None, with_usage_null=with_null,
                                        index=h.index)) and ok
                                else:
                                    delta += held
                                    entries = entries + held_lp
                        if delta or entries:
                            ok = self._sse_chunk(proto.chat_chunk(
                                rid, p["model"], {"content": delta}, None,
                                with_usage_null=with_null, index=h.index,
                                logprob_entries=(
                                    entries if entries
                                    else (None if not h.want_logprobs else [])
                                ),
                            )) and ok
                        if finish is not None:
                            ok = self._sse_chunk(proto.chat_chunk(
                                rid, p["model"], {}, finish,
                                with_usage_null=with_null, index=h.index,
                            )) and ok
                        return ok
                return emit

            results = run_choices(handles, emit_for)
            if any(r[1] == "handoff" for r in results):
                # active drain handoff: end the chunked body WITHOUT
                # [DONE] — the frontend relay reads that as a mid-stream
                # failure and splices the journaled continuation
                self._end_sse()
                return
            if p.get("include_usage"):
                # usage describes the LOGICAL request: original prompt
                # length, and completion tokens across the recovery seam
                self._sse_chunk(proto.usage_chunk(
                    rid, p["model"], "chat.completion.chunk",
                    len(prompt_ids),
                    sum(r[2] for r in results)
                    + sum(h.prior_count for h in handles),
                ))
            self._sse_chunk("[DONE]")
            self._end_sse()
        else:
            results = run_choices(handles,
                                  lambda h: (lambda d, f, lp: True))

            def tool_call_for(text, finish):
                # forced: only a stop-finished object is a candidate (a
                # length cutoff stays honest text), and extract_tool_call
                # re-validates the JSON so a user stop-string truncation
                # can never ship unparseable arguments
                if tc == "none" or tools is None:
                    return None
                if forced_tool and finish != "stop":
                    return None
                return proto.extract_tool_call(text, tools, tc)

            choices = [
                proto.chat_choice(
                    h.index, text, finish,
                    h.lp_entries if h.want_logprobs else None,
                    tool_call=tool_call_for(text, finish),
                )
                for h, (text, finish, _) in zip(handles, results)
            ]
            self._json(
                200,
                proto.chat_completion_response(
                    rid, p["model"], choices, len(prompt_ids),
                    sum(r[2] for r in results),
                ),
            )

    def _completion(self, body):
        p = proto.parse_completion_request(body)
        p["adapter"] = self._check_model(p["model"])
        p["tenant"] = self._tenant
        prompt_ids = self.ctx.tokenizer.encode(p["prompt"])
        # KV event plane: the frontend routes completions on the raw
        # prompt string — the same canonical text registered here
        self.ctx.register_kv_route(prompt_ids, p["prompt"])
        rec, journaling = self._setup_recovery(body, p)
        rid = (rec or {}).get("response_id") or proto.new_id("cmpl")
        self._span.set_attribute("request.id", rid)
        handles = self.ctx.start_choices(rid, prompt_ids, p,
                                         trace_span=self._span,
                                         deadline=self._deadline,
                                         received_at=self._received_at)

        def lp_block(h):
            if not h.want_logprobs:
                return None
            return proto.completion_logprobs(
                [e["token"] for e in h.lp_entries],
                [e["logprob"] for e in h.lp_entries],
                [[(a["token"], a["logprob"]) for a in e["top_logprobs"]]
                 for e in h.lp_entries],
            )

        if p["stream"]:
            self._start_sse()
            lock = threading.Lock()
            if journaling:
                handles[0].journal_sink = self._journal_comment
                self._journal_comment(
                    {"start": {"id": rid, "seed": p.get("seed")}})

            def emit_for(h):
                def emit(delta, finish, lp_entry) -> bool:
                    if not (delta or finish is not None
                            or lp_entry is not None):
                        return True
                    with lock:
                        choice = {"index": h.index, "text": delta,
                                  "finish_reason": finish}
                        if lp_entry is not None:
                            choice["logprobs"] = proto.completion_logprobs(
                                [lp_entry["token"]], [lp_entry["logprob"]],
                                [[(a["token"], a["logprob"])
                                  for a in lp_entry["top_logprobs"]]],
                            )
                        chunk = {
                            "id": rid, "object": "text_completion",
                            "created": int(time.time()), "model": p["model"],
                            "choices": [choice],
                        }
                        if p.get("include_usage"):
                            chunk["usage"] = None
                        return self._sse_chunk(chunk)
                return emit

            results = run_choices(handles, emit_for)
            if any(r[1] == "handoff" for r in results):
                # drain handoff: no [DONE] — the frontend splices on
                self._end_sse()
                return
            if p.get("include_usage"):
                self._sse_chunk(proto.usage_chunk(
                    rid, p["model"], "text_completion", len(prompt_ids),
                    sum(r[2] for r in results)
                    + sum(h.prior_count for h in handles),
                ))
            self._sse_chunk("[DONE]")
            self._end_sse()
        else:
            results = run_choices(handles,
                                  lambda h: (lambda d, f, lp: True))
            choices = [
                proto.completion_choice(h.index, text, finish, lp_block(h))
                for h, (text, finish, _) in zip(handles, results)
            ]
            self._json(
                200,
                proto.completion_response(
                    rid, p["model"], choices, len(prompt_ids),
                    sum(r[2] for r in results),
                ),
            )


def make_server(ctx: ServingContext, host: str = "0.0.0.0", port: int = 8000):
    return make_http_server(_Handler, {"ctx": ctx}, host, port)
