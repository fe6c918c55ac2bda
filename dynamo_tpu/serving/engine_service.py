"""Background scheduler thread bridging the synchronous Engine to concurrent
HTTP handlers via per-request event queues.

This is the in-process analogue of the reference's worker runtime loop: HTTP
threads enqueue GenRequests; one scheduler thread drives Engine.step() and
fans TokenEvents out to stream queues.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Dict, Iterator, Optional

from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.engine.request import GenRequest, TokenEvent
from dynamo_tpu.robustness import deadline as ddl
from dynamo_tpu.robustness import faults

log = logging.getLogger("dynamo_tpu.service")


class EngineService:
    def __init__(self, engine: Engine):
        self.engine = engine
        self._queues: Dict[str, "queue.Queue[TokenEvent]"] = {}
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = False
        # resurrection (watchdog escalation thread) tears streams down via
        # engine.abort_all — flush their queues so waiting handlers see a
        # final event instead of polling a dead request forever. Set on
        # the RAW engine: a ReplicatedEngine wrapper proxies reads, not
        # writes, and abort_all runs on the inner object
        getattr(engine, "engine", engine).on_abort_all = self._flush_aborted
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="engine-scheduler")
        self._thread.start()

    # ------------------------------------------------------------- lifecycle
    def close(self):
        self._stop = True
        self._wake.set()
        self._thread.join(timeout=10)
        # the stepline's watcher thread lives as long as the loop it times
        timeline = getattr(self.engine, "timeline", None)
        if timeline is not None:
            timeline.close()

    # --------------------------------------------------------------- intake
    def submit(self, req: GenRequest) -> "queue.Queue[TokenEvent]":
        """Validate and enqueue; raises ValueError BEFORE any output starts,
        so HTTP handlers can reject with a clean status line."""
        faults.sleep_point("worker.slow_prefill")
        q: "queue.Queue[TokenEvent]" = queue.Queue()
        with self._lock:
            self._queues[req.request_id] = q
        try:
            self.engine.add_request(req)
        except ValueError:
            with self._lock:
                self._queues.pop(req.request_id, None)
            raise
        self._wake.set()
        return q

    def abort(self, request_id: str):
        self.engine.abort_request(request_id)
        self._wake.set()

    def attach(self, request_id: str) -> "queue.Queue[TokenEvent]":
        """Register an event queue for a request that enters the engine via a
        side door (disagg KV import) rather than add_request()."""
        q: "queue.Queue[TokenEvent]" = queue.Queue()
        with self._lock:
            self._queues[request_id] = q
        return q

    def detach(self, request_id: str):
        with self._lock:
            self._queues.pop(request_id, None)

    def wake(self):
        self._wake.set()

    def nudge_all(self) -> None:
        """Push a synthetic no-op event to every open stream queue.  A
        wedged engine emits nothing, so handles blocked in drain() would
        never observe a drain-handoff signal; the nudge wakes them (the
        handoff branch runs before token processing, and token_id=-1 with
        finished=False is ignored everywhere else)."""
        with self._lock:
            for rid, q in list(self._queues.items()):
                q.put(TokenEvent(rid, -1, 0, False, None))

    def _flush_aborted(self, ids) -> None:
        """engine.on_abort_all hook: terminate the stream queues of every
        torn-down request (idempotent — a queue already popped by the
        fatal-step path is simply absent)."""
        with self._lock:
            for rid in ids:
                q = self._queues.pop(rid, None)
                if q is not None:
                    q.put(TokenEvent(rid, -1, 0, True, "abort"))

    def sampling_state(self, request_id: str):
        """Resumable sampling-state export (engine.export_sampling_state):
        the drain-handoff path journals this so a continuation on another
        worker resumes the exact PRNG chain. None once the request left
        the engine."""
        return self.engine.export_sampling_state(request_id)

    def stream(self, req: GenRequest,
               timeout: Optional[float] = None) -> Iterator[TokenEvent]:
        """Submit and yield TokenEvents until the request finishes."""
        q = self.submit(req)
        return self.drain(req, q, timeout)

    def drain(self, req: GenRequest, q: "queue.Queue[TokenEvent]",
              timeout: Optional[float] = None) -> Iterator[TokenEvent]:
        """Yield TokenEvents for an already-submitted request.

        `timeout` is the request's remaining deadline budget (propagated
        from the client's x-deadline header); None falls back to the
        operator's DYNAMO_TPU_DEADLINE_S default — the former hard-coded
        600 s."""
        if timeout is None:
            timeout = ddl.default_budget_s()
        deadline = time.monotonic() + timeout
        try:
            while True:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    self.abort(req.request_id)
                    raise TimeoutError(
                        f"request {req.request_id} exceeded its "
                        f"{timeout:.1f}s deadline budget")
                try:
                    # short poll so a server shutdown can't strand the handler;
                    # a slow first token (jit compile) just keeps polling until
                    # the overall deadline
                    ev = q.get(timeout=min(remaining, 5.0))
                except queue.Empty:
                    continue
                yield ev
                if ev.finished:
                    return
                if faults.check("worker.crash_mid_decode") is not None:
                    # the worker "crashes" with tokens already delivered:
                    # abort the engine side and die mid-stream — the
                    # frontend either resumes the journaled continuation
                    # on another worker (recovery plane) or truncates;
                    # it never re-runs the whole generation
                    self.abort(req.request_id)
                    raise ConnectionResetError(
                        "injected fault: worker.crash_mid_decode")
        finally:
            with self._lock:
                self._queues.pop(req.request_id, None)

    # ------------------------------------------------------------ scheduler
    def _run(self):
        idle_tick = getattr(self.engine, "idle_tick", None)
        # the stepline accounts for this thread's whole time: what lies
        # outside step() is `no_work` or `between_steps` (event fan-out,
        # lock hand-off, the GIL), declared here as each begins
        timeline = getattr(self.engine, "timeline", None)
        loop_state = (timeline.loop_state if timeline is not None
                      else lambda name: None)
        while not self._stop:
            if not self.engine.has_work:
                loop_state("no_work")
                if idle_tick is not None:
                    # multi-host leader: heartbeat the replication plane so
                    # idle followers' pending collective never times out
                    idle_tick()
                self._wake.wait(timeout=0.05)
                self._wake.clear()
                continue
            loop_state("between_steps")
            try:
                events = self.engine.step()
            except Exception as e:
                log.exception("engine step failed; aborting in-flight requests")
                flight = getattr(self.engine, "flight", None)
                if flight is not None:
                    # name the failure before abort_all() dumps the ring —
                    # the dump tail then ends with [fatal_step, dump]
                    flight.note("fatal_step", error=repr(e))
                watchdog = getattr(self.engine, "watchdog", None)
                if watchdog is not None:
                    # health state machine: suspect -> in-place
                    # resurrection (this thread is NOT wedged — it caught
                    # the error), or permanent quarantine on repeat trips.
                    # Resurrection's abort_all flushes our queues via the
                    # on_abort_all hook, so every waiter sees a final
                    # event and the worker's advertised health changes
                    # BEFORE it takes new work.
                    watchdog.on_fatal_step(e)
                else:
                    ids = self.engine.abort_all()
                    with self._lock:
                        for rid in ids:
                            q = self._queues.pop(rid, None)
                            if q is not None:
                                q.put(TokenEvent(rid, -1, 0, True, "abort"))
                time.sleep(0.5)
                continue
            if events:
                with self._lock:
                    for ev in events:
                        q = self._queues.get(ev.request_id)
                        if q is not None:
                            q.put(ev)
