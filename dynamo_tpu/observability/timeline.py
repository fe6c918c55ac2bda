"""Stepline: unified per-step timeline, host-bubble accounting, Perfetto
export.

The ROADMAP's zero-bubble engine-loop item is gated on measurement:
"acceptance = phase accounting shows inter-dispatch host gap near zero".
This module is that measurement substrate — an always-on, low-overhead
per-step timeline the engine's `step()` feeds with precise monotonic
phase intervals:

- ``admit``       — scheduling/admission host work (aborts, queue picks,
                    adapter resolution, prefix lookups, slot install);
- ``page_alloc``  — KV page provisioning (allocator, eviction, preempt);
- ``dispatch``    — host time launching device programs (arg staging,
                    jit call until control returns);
- ``device_wait`` — blocking readback of device results (np.asarray on
                    program outputs, first-token sampling sync);
- ``detok``       — token-event production: stop checks, host mirrors,
                    logprob decoration, slot teardown;
- ``bank``        — end-of-step accounting (QoS budgets, flight commit).

Phases nest with *pause* semantics: entering an inner phase closes the
outer phase's open segment and reopens it on exit, so every recorded
interval is exclusive self-time and the per-step segments are disjoint
by construction.  Conservation therefore holds exactly:
``sum(phase self-times) + gap = step wall time``, where ``gap`` is the
host time no instrumented phase claimed.

Separately, each ``dispatch`` entry samples the **inter-dispatch host
gap** — wall time between device program N returning control and
program N+1 launching (clamped at 0: async scheduling legitimately
dispatches program N+1, a decode window or a mixed step that carries a
prompt's chunk, before materializing program N).  This is the
number the zero-bubble PR must drive to ~0; it exports as
``dynamo_engine_host_gap_seconds`` and the per-phase digests ride the
existing ``dynamo_engine_phase_seconds{phase}`` histogram as additional
label values (observability/engine_metrics.py).

The record also spans the engine thread's time OUTSIDE ``step()`` when a
loop driver (serving/engine_service.py ``_run``) declares it with
``loop_state()``: ``between_steps`` (event fan-out, lock hand-off, the
GIL, with work pending) and ``no_work`` (``has_work`` false, waiting to
be woken).  ``loop_wall_s`` = step wall + both, conserved the same way.

**Drained time.**  The device is *drained* from the exit of a
``device_wait`` that leaves no dispatched program unfinished to the exit
of the next ``dispatch``.  Programs run in dispatch order, so a wait on
program k proves every program <= k done; ``phase("device_wait",
upto=ticket)`` names k (``dispatch_seq`` read after the dispatch), and
a wait without a ticket is on the newest program.  Every segment of the
thread's time that lies in a drained interval (phases, ``untracked``,
``between_steps``, ``no_work``; a ``device_wait`` there is an implicit
program such as first-token sampling and is left out) is summed into
``drained.by``: a measurement of what the host did while the device had
nothing to do, which ``bubble`` reports as shares of ``loop_wall_s``.
What opens an interval under async scheduling (engine/engine.py): a
program that finds nothing in flight (the first of a busy spell, an
arrival on an idle engine, a prompt that ends with nobody decoding), no
headroom or no pages behind the program in flight, the last sequence of a
batch leaving, the exits that drop the device carry (abort, preemption,
kv_oom, an integrity fault), and a final chunk whose request keeps the
read-at-once order (a guided request, a preempted continuation with
penalties: ``Engine._joins``).  A prompt's chunks, its first token and a
sequence's end open none: each is dispatched, sampled and installed in
the carry, or retired in the carry, behind the program in flight
(``metrics.mixed_behind``, ``metrics.first_tokens_behind`` over
``metrics.num_admitted``, ``metrics.finishes_behind``).

**One clock with the device.**  While a profiler capture is open
(``start_annotations``, serving/api.py ``capture_trace``) every segment
is also a ``jax.profiler.TraceAnnotation`` named ``stepline/<segment>``
and each step a ``StepTraceAnnotation``, so the trace holds the host's
phases on the profiler's clock beside the device's operations.

**Token time by cause.**  Every second of the thread's time also goes
to exactly one of three causes (``token_time.cause_s``; their sum is
``loop_wall_s``): ``drained`` while no dispatched program is unfinished,
whatever the thread does (a ``device_wait`` there waits on an implicit
program, first-token sampling, and goes to the kind it declares);
otherwise the kind of the OLDEST unfinished program, declared at its
``phase("dispatch", kind=...)``: ``decode`` (decode rows only: a fused
window, a verify; also what an undeclared dispatch is) or ``prompt`` (it
carries prompt tokens: a mixed step, a chunk, a prefill).  A ticket keeps
its kind until a ``device_wait`` proves it finished, so this is the host's
knowledge: under async scheduling the device may already run program k+1
when the host learns that k is done.  Two windows are both ``decode``; a
mixed step dispatched behind window k waits as ``decode`` until k is read
and as ``prompt`` from then on, with nothing drained between.  A sequence
holds a `TokenWait` from its first token on (`token_start`); at every
emission `token_gap` puts the causes' growth since its last one into
``token_time.row_s``, counts its tokens in ``token_time.gaps`` and keeps
the 8 longest single waits (``token_time.worst``; sequences that sat out
the same wait in one emission share a record).  Σ ``row_s`` is the
seconds live sequences spent between their first and last tokens;
over ``gaps`` it is the mean time per output token on this thread's clock.

Record keeping follows the flight recorder's single-writer draft
pattern: `Engine.step()` runs under `_exec_lock` on one scheduler
thread, so the draft and phase stack are touched lock-free; the only
lock is a tiny mutex around ring append/snapshot.  Exact interval
records keep BOTH a monotonic anchor (interval math) and a
``time.time_ns`` wall anchor, so the Perfetto export shares a clock
domain with the request spans in observability/tracing.py (which are
``time_ns`` natively) — one Chrome Trace Event JSON file shows a
request end-to-end through the engine.

Exposure:

- ``GET /debug/timeline?steps=N&format=perfetto|summary|json`` on every
  worker (`timeline_debug_payload`);
- ``StepTimeline.summary()`` rides `/worker/stats` and the worker
  heartbeat, so frontends roll the bubble attribution up fleet-wide
  (`merge_summaries`) without scrape fan-out — same pattern as the
  per-tenant cost ledger;
- `scripts/dynamo_top.py` renders the per-worker phase/bubble panel.

Knobs: ``DYNAMO_TPU_TIMELINE`` (0/false/off/no disables; default on),
``DYNAMO_TPU_TIMELINE_RECORDS`` (ring depth; 0 keeps the streaming
digests but drops the exact-interval ring; unset = 256).
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional

log = logging.getLogger("dynamo_tpu.timeline")

DEFAULT_CAPACITY = 256
CAPACITY_ENV = "DYNAMO_TPU_TIMELINE_RECORDS"
ENABLE_ENV = "DYNAMO_TPU_TIMELINE"

# instrumented phase names, in pipeline order
PHASES = ("admit", "page_alloc", "dispatch", "device_wait", "detok", "bank")
# phases during which the DEVICE is (or may be) busy on our behalf
DEVICE_PHASES = frozenset(("dispatch", "device_wait"))
# the engine thread's time that no phase claims: inside step() ...
UNTRACKED = "untracked"
# ... and outside it, as the loop driver declares it (loop_state)
LOOP_STATES = ("between_steps", "no_work")
# what a drained interval is split over (device_wait is never drained
# time: the device works for whoever waits on it)
DRAINED_KEYS = ("admit", "page_alloc", "dispatch", "detok", "bank",
                UNTRACKED) + LOOP_STATES
ANNOTATION_PREFIX = "stepline/"
# what a second of the thread's time, and of a live sequence's wait, is put
# down to (token_time): the kind of the oldest unfinished program, or none
CAUSES = ("decode", "prompt", "drained")
_DRAINED_CAUSE = CAUSES.index("drained")
WORST_KEPT = 8  # single waits kept in token_time.worst


def _env_capacity() -> int:
    raw = os.environ.get(CAPACITY_ENV, "")
    try:
        return int(raw) if raw.strip() else DEFAULT_CAPACITY
    except ValueError:
        log.warning("bad %s=%r; using default %d", CAPACITY_ENV, raw,
                    DEFAULT_CAPACITY)
        return DEFAULT_CAPACITY


def _env_enabled() -> bool:
    raw = os.environ.get(ENABLE_ENV, "").strip().lower()
    return raw not in ("0", "false", "off", "no")


class PhaseDigest:
    """Streaming duration histogram: quarter-octave log buckets
    0.25ms..~8.2s — the engine PhaseTimer's exact bucket scheme, so the
    exposition bridge serves both under one
    ``dynamo_engine_phase_seconds`` series without a second edge set."""

    _EDGES_MS = [0.25 * 2 ** (i / 4) for i in range(61)]  # 0.25ms .. ~8.2s

    __slots__ = ("count", "sum_s", "buckets")

    def __init__(self):
        self.count = 0
        self.sum_s = 0.0
        self.buckets = [0] * (len(self._EDGES_MS) + 1)

    def observe(self, seconds: float) -> None:
        self.count += 1
        self.sum_s += seconds
        ms = seconds * 1e3
        lo, hi = 0, len(self._EDGES_MS)
        while lo < hi:  # first edge >= ms (binary search; 61 edges)
            mid = (lo + hi) // 2
            if ms <= self._EDGES_MS[mid]:
                hi = mid
            else:
                lo = mid + 1
        self.buckets[lo] += 1

    def quantile_ms(self, q: float) -> float:
        """Geometric-midpoint estimate of the q-quantile (PhaseTimer's
        scheme; worst-case error ~9%)."""
        if self.count == 0:
            return 0.0
        target = q * self.count
        seen = 0
        for i, n in enumerate(self.buckets):
            seen += n
            if seen >= target:
                if i >= len(self._EDGES_MS):
                    return self._EDGES_MS[-1]
                hi = self._EDGES_MS[i]
                lo_edge = self._EDGES_MS[i - 1] if i > 0 else hi / 2 ** 0.25
                return (lo_edge * hi) ** 0.5
        return self._EDGES_MS[-1]


class _Phase:
    """Reusable-shape context manager for one instrumented phase; kept
    allocation-light because several open per engine step."""

    __slots__ = ("_tl", "_name", "_upto", "_kind", "_watched")

    def __init__(self, tl: "StepTimeline", name: str,
                 upto: Optional[int] = None, kind: str = "decode"):
        self._tl = tl
        self._name = name
        self._upto = upto
        self._kind = kind
        self._watched = False

    def __enter__(self) -> "_Phase":
        # device seams feed the engine watchdog even when the timeline
        # draft is closed (disabled timeline, disagg prefill outside
        # step()) — hang detection must not depend on record keeping
        watch = self._tl.watch
        if watch is not None and self._name in DEVICE_PHASES:
            self._watched = watch
            watch.device_enter(self._name)
        self._tl._enter(self._name, self._upto, self._kind)
        return self

    def __exit__(self, *exc) -> bool:
        self._tl._exit()
        watch, self._watched = self._watched, False
        if watch:
            watch.device_exit(self._name)
        return False


class TokenWait:
    """One sequence's token-time account, from its first token on: the
    causes' totals as they stood at its last emission, and what its waits
    since the first came to.  The engine hands the same object to a
    preempted sequence's continuation: its client keeps waiting."""

    __slots__ = ("mark", "done_seq", "sums", "gap_max_s", "tokens", "t_last")

    def __init__(self, mark: List[float], done_seq: int, t: float):
        self.mark = mark
        self.done_seq = done_seq
        self.sums = [0.0] * len(CAUSES)
        self.gap_max_s = 0.0
        self.tokens = 0  # emitted after the first
        self.t_last = t  # monotonic stamp of the last emission

    def phase(self) -> Dict[str, float]:
        """What the event that ends the sequence carries on
        `TokenEvent.phase` (serving/api.py: span worker.decode)."""
        out = {c + "_s": v for c, v in zip(CAUSES, self.sums)}
        out.update(gap_max_s=self.gap_max_s, tokens=self.tokens,
                   t_last=self.t_last)
        return out


class StepTimeline:
    """Bounded ring of exact per-step phase intervals + streaming
    per-phase digests + inter-dispatch host-gap accounting."""

    def __init__(self, capacity: Optional[int] = None,
                 enabled: Optional[bool] = None):
        if capacity is None:
            capacity = _env_capacity()
        if enabled is None:
            enabled = _env_enabled()
        self.capacity = max(0, int(capacity))
        self.enabled = bool(enabled)
        self._ring: "collections.deque[Dict[str, Any]]" = collections.deque(  # guarded_by: _lock
            maxlen=max(1, self.capacity))
        self._lock = threading.Lock()
        self._seq = 0  # guarded_by: _lock — monotonic id, survives wrap
        self.steps_total = 0
        self.dropped_total = 0
        # lifetime streaming digests (scheduler-thread writes; scrape
        # reads are monotonic-safe the same way PhaseTimer's are)
        self.digests: Dict[str, PhaseDigest] = {p: PhaseDigest()
                                                for p in PHASES}
        self.gap_digest = PhaseDigest()  # inter-dispatch host-gap samples
        self.phase_totals: Dict[str, float] = {p: 0.0 for p in PHASES}
        self.host_gap_total_s = 0.0
        self.wall_total_s = 0.0
        # open per-step draft + phase stack; engine scheduler thread only
        self._draft: Optional[Dict[str, Any]] = None
        self._stack: List[List[Any]] = []  # [name, segment_open_monotonic]
        self._last_return: Optional[float] = None  # device ctrl-return mark
        # the thread's time outside step(), by loop state; None until a
        # loop driver calls loop_state() (a library caller of step() has
        # no loop, and its own time between steps is not the engine's)
        self.loop_totals: Dict[str, float] = {s: 0.0 for s in LOOP_STATES}
        self._loop_state: Optional[str] = None
        self._loop_t = 0.0  # start of the open segment outside step()
        # drained-time attribution: the open segment [_cur_t, now) of the
        # thread is `_cur_name`; dispatched/finished program counters say
        # whether the device has anything left to run
        self.drained_by: Dict[str, float] = {k: 0.0 for k in DRAINED_KEYS}
        self.drained_total_s = 0.0
        self.drained_count = 0
        self.dispatch_seq = 0  # programs dispatched (ticket of the newest)
        self._done_seq = 0  # newest program a device_wait proved finished
        self._drained = False
        self._cur_name: Optional[str] = None
        self._cur_t = 0.0
        # token time by cause: (ticket, cause) of the dispatched programs no
        # device_wait has proved finished, oldest first; the causes' lifetime
        # totals (sequences hold marks into them, so reset() moves a base
        # instead of zeroing them); what live sequences' waits came to
        self._flying: "collections.deque[tuple]" = collections.deque()
        self._wait_cause = 0  # of the open device_wait, if drained
        self._cause = [0.0] * len(CAUSES)
        self._cause_base = [0.0] * len(CAUSES)
        self.row_s = [0.0] * len(CAUSES)
        self.token_gaps = 0
        self._worst: List[Dict[str, Any]] = []  # longest first
        self._worst_last: Optional[Dict[str, Any]] = None  # newest record
        self._worst_floor = 0.0  # a wait must pass it to be kept
        # profiler annotations, only while a capture is open
        self._tracing = False  # the one test a segment pays outside one
        self._annotate: Optional[Any] = None
        self._annotate_step: Optional[Any] = None
        self._ann: Optional[Any] = None
        self._ann_step: Optional[Any] = None
        # optional EngineWatchdog: device-phase enter/exit mirror — hang
        # detection coverage tracks stepline instrumentation exactly
        self.watch: Optional[Any] = None

    # ------------------------------------------------------ engine thread --
    def reset(self) -> None:
        """Zero the streaming digests and drop the ring (engine
        reset_metrics: post-warmup / bench phase boundaries, so bubble
        baselines exclude compile-time outliers).  Any open draft is
        discarded; `seq` keeps counting so record ids stay unique."""
        with self._lock:
            self._ring.clear()
        self.steps_total = 0
        self.dropped_total = 0
        self.digests = {p: PhaseDigest() for p in PHASES}
        self.gap_digest = PhaseDigest()
        self.phase_totals = {p: 0.0 for p in PHASES}
        self.host_gap_total_s = 0.0
        self.wall_total_s = 0.0
        self._draft = None
        self._stack = []
        self._last_return = None
        self.loop_totals = {s: 0.0 for s in LOOP_STATES}
        self._loop_t = self._cur_t = time.monotonic()
        self.drained_by = {k: 0.0 for k in DRAINED_KEYS}
        self.drained_total_s = 0.0
        self.drained_count = 0
        self._cur_name = self._loop_state
        self._cause_base = list(self._cause)
        self.row_s = [0.0] * len(CAUSES)
        self.token_gaps = 0
        self._worst = []
        self._worst_last = None
        self._worst_floor = 0.0

    def loop_state(self, name: str) -> None:
        """The loop driver's declaration, on the engine thread, of what it
        is about to do outside step(): `between_steps` (work pending) or
        `no_work` (about to wait).  Closes the open outside segment into
        its state's total; repeated calls with one state just fold it, so
        a scrape never misses more than one idle tick."""
        if not self.enabled or self._draft is not None:
            return
        now = time.monotonic()
        if self._loop_state is not None:
            self.loop_totals[self._loop_state] += now - self._loop_t
        self._loop_state = name
        self._loop_t = now
        self._mark(now, name)

    def start_annotations(self, annotate, annotate_step=None) -> None:
        """From now on every segment is also `annotate("stepline/<name>")`
        and every step `annotate_step("stepline/step", step_num=n)`:
        context managers, entered and left on the engine thread
        (jax.profiler.TraceAnnotation / StepTraceAnnotation while a
        capture is open).  Any thread may call this."""
        self._annotate = annotate
        self._annotate_step = annotate_step
        self._tracing = True

    def stop_annotations(self) -> None:
        """The engine thread closes what it has open at its next segment
        boundary and stops annotating."""
        self._annotate = None
        self._annotate_step = None

    def _fold(self, now: float) -> None:
        """Put [_cur_t, now) of the open segment down: to its cause, and
        if drained to its segment.  What is unfinished is `_flying` and
        `_drained` as they stand here; they only change at a boundary,
        after its _mark."""
        cur = self._cur_name
        dur = now - self._cur_t
        if cur is not None and dur > 0:
            if self._flying:
                self._cause[self._flying[0][1]] += dur
            elif cur == "device_wait":
                self._cause[self._wait_cause] += dur
            else:
                self._cause[_DRAINED_CAUSE] += dur
            if self._drained and cur != "device_wait":
                self.drained_by[cur] += dur
                self.drained_total_s += dur
        self._cur_t = now

    def _mark(self, now: float, name: Optional[str]) -> None:
        """Segment boundary: [_cur_t, now) was `_cur_name`, `name` opens."""
        self._fold(now)
        self._cur_name = name
        if self._tracing:
            self._reannotate(name)

    def _reannotate(self, name: Optional[str]) -> None:
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        make = self._annotate
        if make is None:
            self._close_step_annotation()
            self._tracing = False
        elif name is not None:
            self._ann = make(ANNOTATION_PREFIX + name)
            self._ann.__enter__()

    def _close_step_annotation(self) -> None:
        ann, self._ann_step = self._ann_step, None
        if ann is not None:
            ann.__exit__(None, None, None)

    def begin_step(self) -> None:
        """Open the draft for one `Engine.step()`.  A draft still open
        from a previous begin means that step unwound past commit
        (exception): finalize what it measured, flagged, never lose it."""
        if not self.enabled:
            return
        if self._draft is not None:
            self._finalize(aborted=True)
        now = time.monotonic()
        self._draft = {"t0": now, "t0_unix_ns": time.time_ns(),
                       "segs": [], "gaps": []}
        self._stack = []
        if self._tracing:
            self._mark(now, None)  # the step's annotation holds its segments
            make = self._annotate_step
            if make is not None:
                self._ann_step = make(ANNOTATION_PREFIX + "step",
                                      step_num=self.steps_total)
                self._ann_step.__enter__()
        self._mark(now, UNTRACKED)

    def phase(self, name: str, upto: Optional[int] = None,
              kind: str = "decode") -> _Phase:
        """Context manager for one instrumented phase of the open step.
        No-op outside an open draft (disabled timeline, or engine paths
        like the disagg prefill role that run outside step()).  `upto`,
        on a `device_wait`, is the ticket (`dispatch_seq` after its
        dispatch) of the program waited for; without it the wait is on
        the newest program.  `kind`, on a `dispatch`, is what the program
        carries (CAUSES: `prompt` if any prompt token, else `decode`); on
        a `device_wait`, what the implicit program it waits on belongs to
        if the device is drained."""
        return _Phase(self, name, upto, kind)

    def _enter(self, name: str, upto: Optional[int] = None,
               kind: str = "decode") -> None:
        d = self._draft
        if d is None:
            return
        now = time.monotonic()
        stack = self._stack
        if stack:
            # nested phase: PAUSE the outer one — close its open segment
            # so recorded intervals are exclusive self-time, disjoint by
            # construction (the conservation invariant rests on this)
            outer = stack[-1]
            if now > outer[1]:
                d["segs"].append((outer[0], outer[1] - d["t0"],
                                  now - d["t0"]))
        if name == "dispatch" and self._last_return is not None:
            # inter-dispatch host gap: device program N returned control
            # at _last_return; program N+1 launches now. Clamped — async
            # scheduling dispatches N+1 before materializing N.
            d["gaps"].append(max(0.0, now - self._last_return))
        stack.append([name, now, upto, CAUSES.index(kind)])
        self._mark(now, name)
        if name == "device_wait":
            self._wait_cause = stack[-1][3]

    def _exit(self) -> None:
        d = self._draft
        stack = self._stack
        if d is None or not stack:
            return
        now = time.monotonic()
        top = stack.pop()
        if now > top[1]:
            d["segs"].append((top[0], top[1] - d["t0"], now - d["t0"]))
        if stack:
            stack[-1][1] = now  # resume the paused outer phase
        self._mark(now, stack[-1][0] if stack else UNTRACKED)
        if top[0] in DEVICE_PHASES:
            self._last_return = now
            if top[0] == "dispatch":
                # the device has work again: a drained interval ends here
                self.dispatch_seq += 1
                self._flying.append((self.dispatch_seq, top[3]))
                if self._drained:
                    self._drained = False
                    self.drained_count += 1
            else:
                done = self.dispatch_seq if top[2] is None else top[2]
                if done > self._done_seq:
                    self._done_seq = done
                self._drained = self._done_seq >= self.dispatch_seq
                flying = self._flying
                while flying and flying[0][0] <= self._done_seq:
                    flying.popleft()

    # ------------------------------------------------ token time by cause --
    def fold(self) -> float:
        """Bring the account up to now (the open segment stays open) and
        return now, on the monotonic clock."""
        now = time.monotonic()
        if self.enabled:
            self._fold(now)
        return now

    def token_start(self) -> Optional[TokenWait]:
        """A sequence's first token is out: its waits count from the
        account as it stands (call `fold` first).  None when disabled."""
        if not self.enabled:
            return None
        return TokenWait(list(self._cause), self._done_seq, self._cur_t)

    def token_gap(self, wait: TokenWait, tokens: int,
                  request_id: str) -> None:
        """An emission gave `wait`'s sequence `tokens` tokens: the causes'
        growth since its last one is one wait of that sequence, ended by
        these tokens.  Reads the account as the last boundary left it (the
        `detok` phase every emission opens, or a `fold`)."""
        cause, mark, sums, row = self._cause, wait.mark, wait.sums, self.row_s
        parts = [c - m for c, m in zip(cause, mark)]
        gap = sum(parts)
        for i, part in enumerate(parts):
            mark[i] = cause[i]
            sums[i] += part
            row[i] += part
        programs = self._done_seq - wait.done_seq
        wait.done_seq = self._done_seq
        wait.tokens += tokens
        wait.t_last = self._cur_t
        self.token_gaps += tokens
        if gap > wait.gap_max_s:
            wait.gap_max_s = gap
        last = self._worst_last
        if last is not None and last["_t"] == self._cur_t \
                and last["gap_s"] == gap:
            # the same wait, sat out by another sequence of this emission:
            # one record, so that the 8 kept are 8 waits and not one stall
            last["sequences"] += 1
        elif gap > self._worst_floor:
            rec = self._worst_last = {
                "gap_s": gap, "programs": programs, "sequences": 1,
                "t_unix_ns": time.time_ns(), "request_id": request_id,
                "_t": self._cur_t}
            rec.update((c + "_s", p) for c, p in zip(CAUSES, parts))
            # a new list, swapped in whole: summary() reads from any thread
            worst = sorted(self._worst + [rec], key=lambda r: -r["gap_s"])
            self._worst = worst[:WORST_KEPT]
            if len(worst) >= WORST_KEPT:
                self._worst_floor = self._worst[-1]["gap_s"]

    def commit_step(self, **fields: Any) -> None:
        """Finalize the open step record.  Steps that measured nothing
        (no phase ran) are dropped — an idle engine tick must not wash
        real history out of the ring."""
        if not self.enabled:
            return
        self._finalize(aborted=False, **fields)

    def _finalize(self, aborted: bool, **fields: Any) -> None:
        d, self._draft = self._draft, None
        if d is None:
            return
        now = time.monotonic()
        # an exception may unwind past open phases: close them newest-
        # first so the segments stay disjoint
        while self._stack:
            top = self._stack.pop()
            if now > top[1]:
                d["segs"].append((top[0], top[1] - d["t0"], now - d["t0"]))
            if self._stack:
                self._stack[-1][1] = now
        if self._tracing:
            self._mark(now, None)
            self._close_step_annotation()
        self._mark(now, self._loop_state)
        if not d["segs"]:
            return  # its time stays in the open segment outside step()
        if self._loop_state is not None:
            self.loop_totals[self._loop_state] += d["t0"] - self._loop_t
            self._loop_t = now
        wall = now - d["t0"]
        sums: Dict[str, float] = {}
        for name, s0, s1 in d["segs"]:
            sums[name] = sums.get(name, 0.0) + (s1 - s0)
        # conservation residue: host time inside the step no instrumented
        # phase claimed (>= 0 by construction — segments are disjoint and
        # within [t0, now])
        gap = max(0.0, wall - sum(sums.values()))
        for name, tot in sums.items():
            dg = self.digests.get(name)
            if dg is not None:
                dg.observe(tot)
                self.phase_totals[name] += tot
        for g in d["gaps"]:
            self.gap_digest.observe(g)
            self.host_gap_total_s += g
        self.wall_total_s += wall
        self.steps_total += 1
        rec: Dict[str, Any] = {
            "t0_unix_ns": d["t0_unix_ns"],
            "wall_s": wall,
            "phases": {k: round(v, 9) for k, v in sums.items()},
            "segs": [(n, round(s0, 9), round(s1, 9))
                     for n, s0, s1 in d["segs"]],
            "gap_s": gap,
            "host_gap": [round(g, 9) for g in d["gaps"]],
        }
        if aborted:
            rec["aborted"] = True
        rec.update(fields)
        if self.capacity > 0:
            self._append(rec)

    # --------------------------------------------------------- internals ---
    def _append(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            rec["seq"] = self._seq
            self._seq += 1
            if len(self._ring) == self._ring.maxlen:
                self.dropped_total += 1
            self._ring.append(rec)

    def records(self, n: Optional[int] = None) -> List[Dict[str, Any]]:
        with self._lock:
            out = list(self._ring)
        if n is not None and n > 0:
            out = out[-n:]
        return out

    # ----------------------------------------------------------- summary ---
    def _token_time(self) -> Dict[str, Any]:
        worst = [{k: round(v, 6) if isinstance(v, float) else v
                  for k, v in rec.items() if k != "_t"}
                 for rec in self._worst]
        return _token_time_of(
            [c - b for c, b in zip(self._cause, self._cause_base)],
            self.row_s, self.token_gaps, worst)

    def summary(self) -> Dict[str, Any]:
        """Bubble-attribution rollup: per-phase p50/p95 + share of step
        wall time, the inter-dispatch host-gap distribution, and which
        host phase eats the gap.  Rides /worker/stats and the heartbeat
        (fleet rollup via merge_summaries)."""
        wall = self.wall_total_s
        phases: Dict[str, Any] = {}
        for name in PHASES:
            dg = self.digests[name]
            if not dg.count:
                continue
            phases[name] = {
                "count": dg.count,
                "total_s": round(self.phase_totals[name], 6),
                "p50_ms": round(dg.quantile_ms(0.5), 3),
                "p95_ms": round(dg.quantile_ms(0.95), 3),
                "share": round(self.phase_totals[name] / wall, 4)
                if wall else 0.0,
            }
        tracked = sum(self.phase_totals.values())
        gd = self.gap_digest
        out: Dict[str, Any] = {
            "enabled": self.enabled,
            "steps": self.steps_total,
            "wall_s": round(wall, 6),
            "phases": phases,
            "host_gap": {
                "count": gd.count,
                "total_s": round(self.host_gap_total_s, 6),
                "p50_ms": round(gd.quantile_ms(0.5), 3),
                "p95_ms": round(gd.quantile_ms(0.95), 3),
                "share": round(self.host_gap_total_s / wall, 4)
                if wall else 0.0,
            },
            "untracked_s": round(max(0.0, wall - tracked), 6),
            # the thread's whole time: step wall + the loop's two states
            "loop_wall_s": round(wall + sum(self.loop_totals.values()), 6),
            "loop": {s: round(t, 6) for s, t in self.loop_totals.items()},
            "drained": {
                "total_s": round(self.drained_total_s, 6),
                "count": self.drained_count,
                "by": {k: round(t, 6) for k, t in self.drained_by.items()},
            },
            "token_time": self._token_time(),
        }
        bubble = _bubble_attribution(out["drained"]["by"],
                                     out["loop_wall_s"])
        if bubble is not None:
            out["bubble"] = bubble
        return out


def _token_time_of(cause_s, row_s, gaps: int,
                   worst: List[Dict[str, Any]]) -> Dict[str, Any]:
    return {
        "cause_s": {c: round(v, 6) for c, v in zip(CAUSES, cause_s)},
        "row_s": {c: round(v, 6) for c, v in zip(CAUSES, row_s)},
        "gaps": gaps,
        "gap_max_s": worst[0]["gap_s"] if worst else 0.0,
        "worst": worst,
    }


def _bubble_attribution(drained_by: Dict[str, float],
                        loop_wall: float) -> Optional[Dict[str, Any]]:
    """What the host did while the device was drained, as shares of the
    engine thread's time, largest first.  The eater is the largest that
    host work can shrink: `no_work` is the absence of requests."""
    ranked = sorted(((n, t) for n, t in drained_by.items() if t > 0),
                    key=lambda kv: -kv[1])
    eaters = [n for n, _ in ranked if n != "no_work"]
    if not eaters or loop_wall <= 0:
        return None
    return {
        "gap_eater": eaters[0],
        "host_shares": {n: round(t / loop_wall, 4) for n, t in ranked},
    }


def merge_summaries(summaries: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Fleet-wide rollup of per-worker `summary()` payloads (heartbeat
    aggregation on the frontend).  Totals and shares merge exactly;
    quantiles don't survive summarization, so the merged view reports
    worst-worker p95 per phase instead."""
    agg: Dict[str, Any] = {
        "steps": 0, "wall_s": 0.0, "untracked_s": 0.0, "loop_wall_s": 0.0,
        "phases": {},
        "host_gap": {"count": 0, "total_s": 0.0, "p95_ms_max": 0.0},
        "drained": {"total_s": 0.0, "count": 0, "by": {}},
    }
    cause_s, row_s = [0.0] * len(CAUSES), [0.0] * len(CAUSES)
    gaps, worst = 0, []
    for s in summaries:
        if not s:
            continue
        tt = s.get("token_time") or {}  # absent: a worker from before it
        for i, c in enumerate(CAUSES):
            cause_s[i] += (tt.get("cause_s") or {}).get(c, 0.0)
            row_s[i] += (tt.get("row_s") or {}).get(c, 0.0)
        gaps += tt.get("gaps", 0)
        worst += tt.get("worst") or []
        agg["steps"] += s.get("steps", 0)
        agg["wall_s"] += s.get("wall_s", 0.0)
        agg["untracked_s"] += s.get("untracked_s", 0.0)
        # a worker from before the loop account: its steps are its loop
        agg["loop_wall_s"] += s.get("loop_wall_s", s.get("wall_s", 0.0))
        dr = s.get("drained") or {}
        agg["drained"]["total_s"] += dr.get("total_s", 0.0)
        agg["drained"]["count"] += dr.get("count", 0)
        for name, t in (dr.get("by") or {}).items():
            agg["drained"]["by"][name] = (
                agg["drained"]["by"].get(name, 0.0) + t)
        hg = s.get("host_gap") or {}
        agg["host_gap"]["count"] += hg.get("count", 0)
        agg["host_gap"]["total_s"] += hg.get("total_s", 0.0)
        agg["host_gap"]["p95_ms_max"] = max(
            agg["host_gap"]["p95_ms_max"], hg.get("p95_ms", 0.0))
        for name, ph in (s.get("phases") or {}).items():
            t = agg["phases"].setdefault(
                name, {"count": 0, "total_s": 0.0, "p95_ms_max": 0.0})
            t["count"] += ph.get("count", 0)
            t["total_s"] += ph.get("total_s", 0.0)
            t["p95_ms_max"] = max(t["p95_ms_max"], ph.get("p95_ms", 0.0))
    wall = agg["wall_s"]
    if wall > 0:
        for ph in agg["phases"].values():
            ph["share"] = round(ph["total_s"] / wall, 4)
        agg["host_gap"]["share"] = round(
            agg["host_gap"]["total_s"] / wall, 4)
    agg["wall_s"] = round(agg["wall_s"], 6)
    agg["untracked_s"] = round(agg["untracked_s"], 6)
    agg["loop_wall_s"] = round(agg["loop_wall_s"], 6)
    agg["drained"]["total_s"] = round(agg["drained"]["total_s"], 6)
    agg["drained"]["by"] = {n: round(t, 6)
                            for n, t in agg["drained"]["by"].items()}
    agg["host_gap"]["total_s"] = round(agg["host_gap"]["total_s"], 6)
    for ph in agg["phases"].values():
        ph["total_s"] = round(ph["total_s"], 6)
    agg["token_time"] = _token_time_of(
        cause_s, row_s, gaps,
        sorted(worst, key=lambda r: -r["gap_s"])[:WORST_KEPT])
    bubble = _bubble_attribution(agg["drained"]["by"], agg["loop_wall_s"])
    if bubble is not None:
        agg["bubble"] = bubble
    return agg


# ------------------------------------------------------- Perfetto export ---

_ENGINE_PID = 1
_SPAN_PID = 2


def _arg_value(v: Any) -> Any:
    return v if isinstance(v, (str, int, float, bool)) or v is None \
        else str(v)


def perfetto_trace(timeline: "StepTimeline", collector=None,
                   steps: int = 128,
                   trace_id: Optional[str] = None) -> Dict[str, Any]:
    """Chrome Trace Event JSON (the array format Perfetto/chrome://tracing
    ingest): engine step phases + step-boundary markers on one track,
    request spans on per-service tracks, all on the unix-epoch clock in
    microseconds — step records anchor ``time.time_ns`` at begin, and
    tracing spans are ``time_ns`` natively, so a request's spans line up
    with the engine steps that served it."""
    events: List[Dict[str, Any]] = [
        {"name": "process_name", "ph": "M", "pid": _ENGINE_PID,
         "args": {"name": "engine"}},
        {"name": "thread_name", "ph": "M", "pid": _ENGINE_PID, "tid": 1,
         "args": {"name": "engine.step"}},
    ]
    for rec in timeline.records(steps):
        base_us = rec["t0_unix_ns"] / 1e3
        events.append({
            "name": "step", "ph": "i", "s": "t", "cat": "engine",
            "ts": round(base_us, 3), "pid": _ENGINE_PID, "tid": 1,
            "args": {"seq": rec.get("seq"),
                     "wall_ms": round(rec["wall_s"] * 1e3, 3),
                     "gap_ms": round(rec["gap_s"] * 1e3, 3),
                     "host_gap_ms": [round(g * 1e3, 3)
                                     for g in rec.get("host_gap", [])]},
        })
        for name, s0, s1 in rec["segs"]:
            events.append({
                "name": name, "ph": "X", "cat": "engine",
                "ts": round(base_us + s0 * 1e6, 3),
                "dur": round((s1 - s0) * 1e6, 3),
                "pid": _ENGINE_PID, "tid": 1,
                "args": {"step": rec.get("seq")},
            })
    if collector is not None:
        tids: Dict[str, int] = {}
        for sp in collector.snapshot(trace_id=trace_id):
            if sp.end_ns is None:
                continue
            tid = tids.setdefault(sp.service, len(tids) + 1)
            events.append({
                "name": sp.name, "ph": "X", "cat": "request",
                "ts": round(sp.start_ns / 1e3, 3),
                "dur": round((sp.end_ns - sp.start_ns) / 1e3, 3),
                "pid": _SPAN_PID, "tid": tid,
                "args": {"trace_id": sp.trace_id, "span_id": sp.span_id,
                         **{k: _arg_value(v)
                            for k, v in sp.attributes.items()}},
            })
        if tids:
            events.append({"name": "process_name", "ph": "M",
                           "pid": _SPAN_PID, "args": {"name": "requests"}})
            for service, tid in tids.items():
                events.append({"name": "thread_name", "ph": "M",
                               "pid": _SPAN_PID, "tid": tid,
                               "args": {"name": service}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def timeline_debug_payload(timeline: "StepTimeline",
                           qs: Dict[str, List[str]],
                           collector=None) -> Dict[str, Any]:
    """Build the `GET /debug/timeline` response from parsed query params.

    ``steps`` bounds the records considered (default 128);
    ``format=perfetto`` emits Chrome Trace Event JSON (optionally
    filtered to one request via ``trace_id=``), ``format=summary`` the
    bubble-attribution rollup, anything else the raw interval records."""
    def one(key: str) -> Optional[str]:
        vals = qs.get(key) or []
        return vals[0] if vals and vals[0] != "" else None

    try:
        n = int(one("steps") or 128)
    except ValueError:
        n = 128
    fmt = (one("format") or "json").lower()
    if fmt == "perfetto":
        return perfetto_trace(timeline, collector, steps=n,
                              trace_id=one("trace_id"))
    if fmt == "summary":
        return timeline.summary()
    return {
        "enabled": timeline.enabled,
        "capacity": timeline.capacity,
        "size": len(timeline.records()),
        "steps_total": timeline.steps_total,
        "dropped_total": timeline.dropped_total,
        "records": timeline.records(n),
        "summary": timeline.summary(),
    }
