"""Stepline: unified per-step timeline, host-bubble accounting, Perfetto
export.

The ROADMAP's zero-bubble engine-loop item is gated on measurement: the
device must not idle between one program and the next.  This module is
that measurement substrate — an always-on, low-overhead per-step
timeline the engine's `step()` feeds with precise monotonic
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

The per-phase digests ride the existing
``dynamo_engine_phase_seconds{phase}`` histogram as additional label
values (observability/engine_metrics.py).

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

**The device's own end** (``device``).  ``drained`` and ``token_time``
are the host's knowledge: a program counts as unfinished until a
``device_wait`` has read it, so behind a program in flight the chip may
long be idle while the host stages the next one, and neither account
sees it.  The device account takes each program's end from the device.
A dispatch hands over an output of its program that the next program
does not take as donated (``phase("dispatch").done_when(array)``: the
tokens the host reads later anyway, or the logits); one daemon thread a
timeline, the watcher, takes the handles in ticket order, blocks until
the array is ready (the GIL is released meanwhile), stamps
``time.monotonic()``, drops the handle and posts ``(ticket, t_done)`` to
a deque that the engine thread empties at its next segment boundary
(``_mark``).  The engine thread pays one queue put a dispatch.  Where
its own ``device_wait(upto=k)`` returns, that exit is a second stamp of
the same event; the account keeps whichever it sees first, which is the
earlier one but for a stamp taken before the exit and posted after it
(one GIL hand-off).  The thread starts at the first handle, ends with
``close()`` (serving/engine_service.py) or when the timeline is
collected, and a disabled timeline starts none; ``reset()`` abandons it
with its stamps in flight and the next handle starts another, and so
does a dispatch that finds ``WATCH_BACKLOG`` handles behind it: a thread
that waits on a hung program holds nothing back for long.  A handle that
raises (a deleted buffer, a backend that died) is stamped as of then.

With ``enq_k`` the exit of program k's ``dispatch`` (the lower reading
of busy: the runtime is handed the program a little before its dispatch
returns; ``busy_enter_s`` keeps the upper reading, from the dispatch's
enter, and docs/observability.md says what the device trace made of
both) and ``done_k`` its stamp: ``start_k = max(enq_k, done_{k-1})``,
``busy_k = done_k - start_k``, ``idle_before_k = max(0, enq_k -
done_{k-1})``.  ``busy_s + idle_s`` is ``loop_wall_s`` (under a loop
driver; a caller of ``step()`` without one owns the time between its
steps, and a program in flight through it is busy all the same), ``busy_by`` splits busy by the
program's declared kind and ``idle_by`` cuts every idle interval by the
thread's segments that overlap it, ``device_wait`` included (an implicit
program such as first-token sampling carries no ticket, see below).
While every dispatched program is stamped, a segment goes to ``idle_by``
as it closes; while one is unstamped the time counts as busy and the
closed segments are kept in a tail, so that a stamp, which arrives after
the segments it falls in, can cut ``[done_k, enq_{k+1})`` out of them.
The tail is dropped up to the oldest unstamped program's start at every
stamp and holds at most ``TAIL_SEGMENTS`` segments (some ten seconds of
a busy engine); what a stamp finds older than the tail is charged whole
to ``untracked`` and counted in ``device.late_stamps``.  A summary read
between stamps counts the time since the last settled instant as busy of
the oldest unstamped program's kind.  ``row_idle_s`` charges each live
sequence, at every emission, the growth of ``idle_s`` since its last one
(beside ``token_time.row_s``, not inside it); over ``token_time.gaps``
it is the milliseconds of a token's time in which the chip ran nothing.
``idle_worst`` keeps the 8 longest idle intervals, ranked by what is not
``no_work`` (the part host work can shrink); ``programs`` counts the
tickets stamped since the reset and ``unstamped`` those dispatched and
not yet stamped, 0 whenever the engine is idle; ``stamp_skew_ms`` is the
account's own error bar: watcher's stamp less the wait's exit, over the
tickets whose ``device_wait`` blocked for more than 1 ms.  Programs that
carry no ticket (the ``save_state`` / ``restore_state`` copies, first-
token sampling, a disaggregated prefill, ``import_kv``, the capture's
marks) run in order between two ticketed ones: where the next ticketed
program is already enqueued they fall inside its busy interval, where
the device is otherwise empty they read as idle under the segment the
thread was in (``device_wait`` for a sampling it waits on).

**One clock with the device.**  While a profiler capture is open
(``start_annotations``, serving/api.py ``capture_trace``) every segment
is also a ``jax.profiler.TraceAnnotation`` named ``stepline/<segment>``
and each step a ``StepTraceAnnotation``, so the trace holds the host's
phases on the profiler's clock beside the device's operations; a
``dispatch`` carries ``ticket=`` (the one its program gets) and ``kind=``
as annotation arguments and a ``device_wait`` the ticket it waits for,
so a run of device operations can be joined to the program's record.

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
when the host learns that k is done (``device`` above has the device's
own end).  Two windows are both ``decode``; a
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
import queue
import threading
import time
import weakref
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
WORST_KEPT = 8  # single waits kept in token_time.worst, gaps in idle_worst
# the device account (`device`): what an idle interval is cut over (every
# segment of the thread: a `device_wait` on an implicit program too) ...
IDLE_KEYS = PHASES + (UNTRACKED,) + LOOP_STATES
KINDS = CAUSES[:2]  # ... and what a program's busy time goes to
TAIL_SEGMENTS = 4096  # closed segments kept for a stamp to cut against
SKEW_WAIT_S = 1e-3  # a device_wait that blocked longer measures the skew
SKEWS_KEPT = 1024  # newest skews the quantiles are taken over
WATCH_BACKLOG = 64  # handles behind a watcher that has stopped answering


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

    __slots__ = ("_tl", "_name", "_upto", "_kind", "_watched", "_done")

    def __init__(self, tl: "StepTimeline", name: str,
                 upto: Optional[int] = None, kind: str = "decode"):
        self._tl = tl
        self._name = name
        self._upto = upto
        self._kind = kind
        self._watched = False
        self._done = ()

    def done_when(self, handle: Any, steps: int = 1, rows: int = 0) -> None:
        """Inside a `dispatch`: `handle` is an output of the program just
        launched that no later program takes as donated; the program is
        finished when it is ready (the watcher thread blocks on it, the
        engine thread never).  `steps` and `rows` go to the program's
        record: the decode steps it fuses, the batch rows it carries."""
        self._done = (handle, steps, rows)

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
        done, self._done = self._done, ()
        self._tl._exit(*done)
        watch, self._watched = self._watched, False
        if watch:
            watch.device_exit(self._name)
        return False


class TokenWait:
    """One sequence's token-time account, from its first token on: the
    causes' totals as they stood at its last emission, and what its waits
    since the first came to.  The engine hands the same object to a
    preempted sequence's continuation: its client keeps waiting."""

    __slots__ = ("mark", "idle_mark", "done_seq", "sums", "gap_max_s",
                 "tokens", "t_last")

    def __init__(self, mark: List[float], done_seq: int, t: float,
                 idle_mark: float = 0.0):
        self.mark = mark
        self.idle_mark = idle_mark  # the device's lifetime idle, likewise
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


def _watch(inbox: "queue.SimpleQueue", stamps: "collections.deque") -> None:
    """The watcher thread: handles in ticket order, each waited for,
    stamped and dropped.  Holds neither the timeline nor, beyond the wait,
    a handle; None ends it."""
    while True:
        item = inbox.get()
        if item is None:
            return
        ticket, handle = item
        item = None
        try:
            if len(handle.devices()) > 1:  # a mesh: one local shard will do
                handle = handle.addressable_shards[0].data
            handle.block_until_ready()
        except Exception:  # deleted under us, or the backend died: as of now
            pass
        handle = None
        stamps.append((ticket, time.monotonic()))


class _Program:
    """A dispatched program until its stamp arrives."""

    __slots__ = ("ticket", "kind", "t_enter", "t_enq", "unix_ns", "steps",
                 "rows", "idle_before_s")

    def __init__(self, ticket: int, kind: int, t_enter: float, t_enq: float,
                 unix_ns: int, steps: int, rows: int):
        self.ticket = ticket
        self.kind = kind  # index into KINDS
        self.t_enter = t_enter  # its dispatch's enter and exit
        self.t_enq = t_enq
        self.unix_ns = unix_ns  # t_enq on the step record's wall anchor
        self.steps = steps
        self.rows = rows
        self.idle_before_s = 0.0


class _DeviceAccount:
    """The stepline's `device` account (module docstring, "The device's own
    end"): written on the engine thread only, but for `stamps`, which the
    watcher appends to.  `t` is the instant up to which the device's time
    is settled into `busy_by` / `idle_by`: the newest stamped program's
    end, or the start of the oldest unstamped one, or (nothing unstamped)
    the thread's last segment boundary."""

    def __init__(self, now: float, keep_records: bool):
        self.keep_records = keep_records
        self.finished: List[Dict[str, Any]] = []  # records the owner takes
        self.idle_life = 0.0  # never zeroed: TokenWait marks point into it
        self.unix0_ns = 0  # wall clock less monotonic, as the newest step had it
        self._inbox: Optional["queue.SimpleQueue"] = None
        self._stop: Optional[Any] = None  # weakref.finalize: ends the watcher
        self.thread: Optional[threading.Thread] = None
        self.zero(now)

    def zero(self, now: float) -> None:
        self.stop_watcher()
        self.finished = []
        self.stamps: "collections.deque[tuple]" = collections.deque()
        self.flying: "collections.deque[_Program]" = collections.deque()
        self.tail: "collections.deque[tuple]" = collections.deque(
            maxlen=TAIL_SEGMENTS)
        self.t = now
        self.done = now  # the newest stamp (busy_enter_s counts from it)
        self.busy_by = [0.0] * len(KINDS)
        self.busy_enter_s = 0.0
        self.idle_by: Dict[str, float] = {k: 0.0 for k in IDLE_KEYS}
        self.idle_s = 0.0
        self.programs = 0
        self.late_stamps = 0
        self.gap_by: Dict[str, float] = {}  # the open idle interval
        self.gap_t0 = now
        self.worst: List[Dict[str, Any]] = []  # longest first
        self.worst_floor = 0.0
        self.idle_digest = PhaseDigest()  # idle_before_s of every program
        self.skews: "collections.deque[float]" = collections.deque(
            maxlen=SKEWS_KEPT)
        self.skew_count = 0
        self.seen = (0, 0.0)  # newest watcher stamp taken: (ticket, t)
        self.waited = (0, 0.0)  # newest wait that blocked: (ticket, exit)
        # (busy, busy from enter, idle, t, anything unstamped): swapped
        # in whole at every settled instant, for totals() on any thread
        self.pub = (0.0, 0.0, 0.0, now, False)

    # ------------------------------------------------------- the watcher --
    def post(self, owner: Any, ticket: int, handle: Any) -> None:
        if self._inbox is not None and self._inbox.qsize() > WATCH_BACKLOG:
            # it sits on a program that does not end (a hung device): the
            # handles behind it are its own to drop, the next get another
            self.stop_watcher()
        if self._inbox is None:
            self._inbox = queue.SimpleQueue()
            self.thread = threading.Thread(
                target=_watch, args=(self._inbox, self.stamps), daemon=True,
                name="stepline-device-watch")
            self._stop = weakref.finalize(owner, self._inbox.put, None)
            self.thread.start()
        self._inbox.put((ticket, handle))

    def stop_watcher(self) -> Optional[threading.Thread]:
        """Tell the watcher to end behind what it holds, and forget it: its
        stamps go to a deque nobody reads any more."""
        thread, self.thread, self._inbox = self.thread, None, None
        if self._stop is not None:
            self._stop()
            self._stop = None
        return thread

    # ----------------------------------------------------- engine thread --
    def fold(self, t0: float, t1: float, name: Optional[str]) -> None:
        """The thread's segment [t0, t1) has closed."""
        if self.flying:
            self.tail.append((t0, t1, name))  # busy, until a stamp says not
            return
        if name is not None:
            self._idle(name, t1 - t0)
        else:  # a caller without a loop, between its steps: no account's
            self.pub = (self.pub[0], self.pub[1], self.idle_s, t1, False)
        self.t = t1

    def _idle(self, name: str, dur: float) -> None:
        self.idle_by[name] += dur
        self.idle_s += dur
        self.idle_life += dur
        self.gap_by[name] = self.gap_by.get(name, 0.0) + dur

    def _cut(self, a: float, b: float) -> None:
        """The device idled through [a, b): charge it to the segments of
        the tail that overlap it."""
        tail = self.tail
        head = tail[0][0] if tail else b
        if head > a:  # older than the tail: nothing to cut against
            self._idle(UNTRACKED, min(head, b) - a)
            self.late_stamps += 1
        for s0, s1, name in tail:
            if s0 >= b:
                break
            lo, hi = max(s0, a), min(s1, b)
            if hi > lo and name is not None:
                self._idle(name, hi - lo)

    def _close_gap(self, prog: _Program, t0: float) -> None:
        """The idle interval that began at t0 ends with `prog`'s start."""
        by, self.gap_by = self.gap_by, {}
        total = sum(by.values(), 0.0)
        prog.idle_before_s = total
        self.idle_digest.observe(total)
        if total - by.get("no_work", 0.0) > self.worst_floor:
            rec = {"idle_s": total, "by": by, "before_ticket": prog.ticket,
                   "kind": KINDS[prog.kind],
                   "t_unix_ns": self.unix0_ns + int(t0 * 1e9)}
            # a new list, swapped in whole: summary() reads from any thread
            worst = sorted(self.worst + [rec], key=_idle_rank)
            self.worst = worst[:WORST_KEPT]
            if len(worst) >= WORST_KEPT:
                self.worst_floor = -_idle_rank(self.worst[-1])

    def enq(self, prog: _Program) -> None:
        """`prog` was handed to the runtime at prog.t_enq, the boundary
        the thread has just folded up to."""
        if not self.flying:
            self._close_gap(prog, self.gap_t0)
        self.flying.append(prog)
        self.pub = (sum(self.busy_by), self.busy_enter_s, self.idle_s,
                    self.t, True)

    def settle(self, ticket: int, t_done: float, now: float) -> None:
        """Every program up to `ticket` was finished at t_done; the thread
        has folded up to `now`.  Their records go to `finished`."""
        flying = self.flying
        while flying and flying[0].ticket <= ticket:
            p = flying.popleft()
            t = max(t_done, self.t)
            busy = t - self.t
            self.busy_by[p.kind] += busy
            self.busy_enter_s += t - max(min(p.t_enter, t), self.done)
            self.programs += 1
            if self.keep_records:
                self.finished.append({
                    "ticket": p.ticket, "kind": KINDS[p.kind],
                    "steps": p.steps, "rows": p.rows,
                    "t_enter": round(p.t_enter, 9),
                    "t_enq": round(p.t_enq, 9), "t_done": round(t, 9),
                    "t_enq_unix_ns": p.unix_ns, "busy_s": round(busy, 9),
                    "idle_before_s": round(p.idle_before_s, 9)})
            self.t = self.done = t
            if flying:
                nxt = flying[0]
                if nxt.t_enq > t:
                    self._cut(t, nxt.t_enq)
                    self.t = nxt.t_enq
                self._close_gap(nxt, t)
                tail = self.tail
                while tail and tail[0][1] <= self.t:
                    tail.popleft()
            else:
                self._cut(t, now)
                self.gap_t0 = t
                self.t = now
                self.tail.clear()
        self.pub = (sum(self.busy_by), self.busy_enter_s, self.idle_s,
                    self.t, bool(flying))

    def take_stamps(self, now: float) -> None:
        stamps = self.stamps
        while stamps:
            ticket, t = self.seen = stamps.popleft()
            if self.waited[0] == ticket:
                self._skew(t - self.waited[1])
            self.settle(ticket, t, now)

    def waited_for(self, ticket: int, blocked_s: float,
                   now: float) -> None:
        """The thread's own device_wait on `ticket` has returned at `now`:
        a second stamp of the same event."""
        if blocked_s > SKEW_WAIT_S:
            seen, t_seen = self.seen
            if seen < ticket:  # the watcher's stamp is still to come
                self.waited = (ticket, now)
            elif seen == ticket and t_seen >= now - blocked_s:
                self._skew(t_seen - now)
            # else the program was finished before this wait began: it
            # blocked on an implicit program, which measures nothing here
        self.settle(ticket, now, now)

    def _skew(self, s: float) -> None:
        self.skews.append(s)
        self.skew_count += 1
        self.waited = (0, 0.0)

    # ----------------------------------------------------------- readers --
    def totals(self, now: float) -> Dict[str, float]:
        """busy_s, busy_enter_s and idle_s as of `now`, from any thread:
        the time since the settled instant is busy while a program is
        unstamped."""
        busy, enter, idle, t, flying = self.pub
        rest = max(0.0, now - t)
        busy_rest = rest if flying else 0.0
        return {"busy_s": round(busy + busy_rest, 6),
                "busy_enter_s": round(enter + busy_rest, 6),
                "idle_s": round(idle + rest - busy_rest, 6)}

    def summary(self, upto: float, row_idle_s: float) -> Dict[str, Any]:
        busy_by = list(self.busy_by)
        rest = 0.0
        try:
            head = self.flying[0]
        except IndexError:
            pass
        else:
            rest = max(0.0, upto - self.t)
            busy_by[head.kind] += rest
        skews = sorted(self.skews)

        def skew_ms(q: float) -> float:
            if not skews:
                return 0.0
            return round(1e3 * skews[min(len(skews) - 1,
                                         int(q * len(skews)))], 4)

        return _device_of(
            busy_by, dict(self.idle_by),
            {"busy_enter_s": self.busy_enter_s + rest,
             "programs": self.programs, "unstamped": len(self.flying),
             "late_stamps": self.late_stamps, "row_idle_s": row_idle_s},
            [dict(rec, idle_s=round(rec["idle_s"], 6),
                  by={k: round(v, 6) for k, v in rec["by"].items()})
             for rec in self.worst],
            {"p50": skew_ms(0.5), "p95": skew_ms(0.95),
             "count": self.skew_count})


def _idle_rank(rec: Dict[str, Any]) -> float:
    """idle_worst's order: longest first by what is not `no_work`."""
    return rec["by"].get("no_work", 0.0) - rec["idle_s"]


def _device_of(busy_by, idle_by: Dict[str, float], sums: Dict[str, Any],
               worst: List[Dict[str, Any]],
               skew: Dict[str, Any]) -> Dict[str, Any]:
    """`device` as summary() and merge_summaries give it; `sums` holds what
    merges by addition beside the two splits."""
    return {
        "busy_s": round(sum(busy_by), 6),
        "idle_s": round(sum(idle_by.values()), 6),
        "programs": sums["programs"],
        "unstamped": sums["unstamped"],
        "busy_by": {k: round(v, 6) for k, v in zip(KINDS, busy_by)},
        "busy_enter_s": round(sums["busy_enter_s"], 6),
        "idle_by": {k: round(v, 6) for k, v in idle_by.items()},
        "row_idle_s": round(sums["row_idle_s"], 6),
        "idle_worst": worst,
        "late_stamps": sums["late_stamps"],
        "stamp_skew_ms": skew,
    }


class StepTimeline:
    """Bounded ring of exact per-step phase intervals + streaming
    per-phase digests + the drained, token-time and device accounts."""

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
        self.phase_totals: Dict[str, float] = {p: 0.0 for p in PHASES}
        self.wall_total_s = 0.0
        # open per-step draft + phase stack; engine scheduler thread only
        self._draft: Optional[Dict[str, Any]] = None
        # [name, segment_open_monotonic, upto, kind, entered_monotonic]
        self._stack: List[List[Any]] = []
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
        # the device account, and a record a finished program beside the
        # steps' ring
        self._dev = _DeviceAccount(time.monotonic(), self.capacity > 0)
        self.row_idle_s = 0.0
        self._programs: "collections.deque[Dict[str, Any]]" = collections.deque(  # guarded_by: _lock
            maxlen=max(1, self.capacity))
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
            self._programs.clear()
        self.steps_total = 0
        self.dropped_total = 0
        self.digests = {p: PhaseDigest() for p in PHASES}
        self.phase_totals = {p: 0.0 for p in PHASES}
        self.wall_total_s = 0.0
        self._draft = None
        self._stack = []
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
        # the device account starts over, its watcher with it: stamps in
        # flight are forgotten, programs in flight read as idle time
        self._dev.zero(self._cur_t)
        self.row_idle_s = 0.0

    def close(self) -> None:
        """End the watcher thread (the loop driver's close): behind the
        handles it holds, or never if the device hangs under one, which a
        daemon thread may."""
        thread = self._dev.stop_watcher()
        if thread is not None:
            thread.join(timeout=2.0)

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
        if dur > 0:
            self._dev.fold(self._cur_t, now, cur)
        self._cur_t = now

    def _mark(self, now: float, name: Optional[str]) -> None:
        """Segment boundary: [_cur_t, now) was `_cur_name`, `name` opens."""
        self._fold(now)
        dev = self._dev
        if dev.stamps:
            dev.take_stamps(now)
            self._keep_programs()
        self._cur_name = name
        if self._tracing:
            self._reannotate(name)

    def _keep_programs(self) -> None:
        """The records of the programs the account has just settled."""
        dev = self._dev
        if dev.finished:
            recs, dev.finished = dev.finished, []
            with self._lock:
                self._programs.extend(recs)

    def _reannotate(self, name: Optional[str]) -> None:
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        make = self._annotate
        if make is None:
            self._close_step_annotation()
            self._tracing = False
        elif name is not None:
            if name in DEVICE_PHASES and self._stack:
                # the ticket this dispatch's program gets, or the one this
                # wait is on: what joins the device's operations to it
                _, _, upto, kind, _ = self._stack[-1]
                if name == "dispatch":
                    upto = self.dispatch_seq + 1
                elif upto is None:
                    upto = self.dispatch_seq
                self._ann = make(ANNOTATION_PREFIX + name, ticket=upto,
                                 kind=CAUSES[kind])
            else:
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
        self._draft = {"t0": now, "t0_unix_ns": time.time_ns(), "segs": []}
        self._dev.unix0_ns = self._draft["t0_unix_ns"] - int(now * 1e9)
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
        stack.append([name, now, upto, CAUSES.index(kind), now])
        self._mark(now, name)
        if name == "device_wait":
            self._wait_cause = stack[-1][3]

    def _exit(self, handle: Any = None, steps: int = 1,
              rows: int = 0) -> None:
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
            dev = self._dev
            if top[0] == "dispatch":
                # the device has work again: a drained interval ends here
                self.dispatch_seq += 1
                self._flying.append((self.dispatch_seq, top[3]))
                if self._drained:
                    self._drained = False
                    self.drained_count += 1
                dev.enq(_Program(
                    self.dispatch_seq, top[3], top[4], now,
                    d["t0_unix_ns"] + int((now - d["t0"]) * 1e9),
                    steps, rows))
                if handle is not None:
                    dev.post(self, self.dispatch_seq, handle)
            else:
                done = self.dispatch_seq if top[2] is None else top[2]
                if done > self._done_seq:
                    self._done_seq = done
                self._drained = self._done_seq >= self.dispatch_seq
                flying = self._flying
                while flying and flying[0][0] <= self._done_seq:
                    flying.popleft()
                dev.waited_for(done, now - top[1], now)
                self._keep_programs()

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
        return TokenWait(list(self._cause), self._done_seq, self._cur_t,
                         self._dev.idle_life)

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
        idle = self._dev.idle_life
        self.row_idle_s += idle - wait.idle_mark
        wait.idle_mark = idle
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
        self.wall_total_s += wall
        self.steps_total += 1
        rec: Dict[str, Any] = {
            "t0_unix_ns": d["t0_unix_ns"],
            "wall_s": wall,
            "phases": {k: round(v, 9) for k, v in sums.items()},
            "segs": [(n, round(s0, 9), round(s1, 9))
                     for n, s0, s1 in d["segs"]],
            "gap_s": gap,
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

    def programs(self, n: Optional[int] = None) -> List[Dict[str, Any]]:
        """The newest finished programs' records, oldest first: {ticket,
        kind, steps, rows, t_enter, t_enq, t_done (monotonic seconds),
        t_enq_unix_ns, busy_s, idle_before_s}."""
        with self._lock:
            out = list(self._programs)
        if n is not None and n > 0:
            out = out[-n:]
        return out

    @property
    def idle_digest(self) -> PhaseDigest:
        """idle_before_s of every program since the reset
        (dynamo_engine_device_idle_seconds)."""
        return self._dev.idle_digest

    def device_idle_by(self) -> Dict[str, float]:
        return dict(self._dev.idle_by)

    def device_totals(self) -> Dict[str, float]:
        """{busy_s, busy_enter_s, idle_s} as of now, from any thread (a
        capture's samples: serving/api.py _sleep_sampling)."""
        return self._dev.totals(time.monotonic())

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
        wall time, what the host did while the device was drained, token
        time by cause, and the device's own busy and idle time.  Rides /worker/stats and the heartbeat
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
        out: Dict[str, Any] = {
            "enabled": self.enabled,
            "steps": self.steps_total,
            "wall_s": round(wall, 6),
            "phases": phases,
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
            "device": self._dev.summary(self._cur_t, self.row_idle_s),
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
        "drained": {"total_s": 0.0, "count": 0, "by": {}},
    }
    cause_s, row_s = [0.0] * len(CAUSES), [0.0] * len(CAUSES)
    gaps, worst = 0, []
    busy_by = [0.0] * len(KINDS)
    idle_by = {k: 0.0 for k in IDLE_KEYS}
    dev = {"busy_enter_s": 0.0, "programs": 0, "unstamped": 0,
           "late_stamps": 0, "row_idle_s": 0.0}
    idle_worst: List[Dict[str, Any]] = []
    skew = {"p50": 0.0, "p95": 0.0, "count": 0}
    for s in summaries:
        if not s:
            continue
        dv = s.get("device") or {}  # absent: a worker from before it
        for i, k in enumerate(KINDS):
            busy_by[i] += (dv.get("busy_by") or {}).get(k, 0.0)
        for k, t in (dv.get("idle_by") or {}).items():
            idle_by[k] = idle_by.get(k, 0.0) + t
        for k in dev:
            dev[k] += dv.get(k, 0)
        idle_worst += dv.get("idle_worst") or []
        sk = dv.get("stamp_skew_ms") or {}
        skew["count"] += sk.get("count", 0)
        for q in ("p50", "p95"):  # the worst worker's, by size
            skew[q] = max(skew[q], sk.get(q, 0.0), key=abs)
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
    agg["wall_s"] = round(agg["wall_s"], 6)
    agg["untracked_s"] = round(agg["untracked_s"], 6)
    agg["loop_wall_s"] = round(agg["loop_wall_s"], 6)
    agg["drained"]["total_s"] = round(agg["drained"]["total_s"], 6)
    agg["drained"]["by"] = {n: round(t, 6)
                            for n, t in agg["drained"]["by"].items()}
    for ph in agg["phases"].values():
        ph["total_s"] = round(ph["total_s"], 6)
    agg["token_time"] = _token_time_of(
        cause_s, row_s, gaps,
        sorted(worst, key=lambda r: -r["gap_s"])[:WORST_KEPT])
    agg["device"] = _device_of(
        busy_by, idle_by, dev,
        sorted(idle_worst, key=_idle_rank)[:WORST_KEPT], skew)
    bubble = _bubble_attribution(agg["drained"]["by"], agg["loop_wall_s"])
    if bubble is not None:
        agg["bubble"] = bubble
    return agg


# ------------------------------------------------------- Perfetto export ---

_ENGINE_PID = 1
_SPAN_PID = 2
_STEP_TID = 1
_DEVICE_TID = 2


def _arg_value(v: Any) -> Any:
    return v if isinstance(v, (str, int, float, bool)) or v is None \
        else str(v)


def perfetto_trace(timeline: "StepTimeline", collector=None,
                   steps: int = 128,
                   trace_id: Optional[str] = None) -> Dict[str, Any]:
    """Chrome Trace Event JSON (the array format Perfetto/chrome://tracing
    ingest): engine step phases + step-boundary markers on one track,
    the device's programs on a `device` track under the same process,
    request spans on per-service tracks, all on the unix-epoch clock in
    microseconds — step records anchor ``time.time_ns`` at begin, and
    tracing spans are ``time_ns`` natively, so a request's spans line up
    with the engine steps that served it.  A program's bar runs from its
    start (``t_done - busy_s``) to its stamp, and a flow with the ticket
    as id joins the ``dispatch`` segment that launched it to the bar:
    request -> host phase -> device program in one file, with no profiler
    capture."""
    events: List[Dict[str, Any]] = [
        {"name": "process_name", "ph": "M", "pid": _ENGINE_PID,
         "args": {"name": "engine"}},
        {"name": "thread_name", "ph": "M", "pid": _ENGINE_PID,
         "tid": _STEP_TID, "args": {"name": "engine.step"}},
        {"name": "thread_name", "ph": "M", "pid": _ENGINE_PID,
         "tid": _DEVICE_TID, "args": {"name": "device"}},
    ]
    programs = timeline.programs(steps)
    # one anchor for every bar (the newest program's), so that rounding
    # and the wall clock's drift between steps never make two overlap
    anchor_us = (programs[-1]["t_enq_unix_ns"] / 1e3
                 - programs[-1]["t_enq"] * 1e6) if programs else 0.0
    for p in programs:
        start_us = round(anchor_us + (p["t_done"] - p["busy_s"]) * 1e6, 3)
        end_us = round(anchor_us + p["t_done"] * 1e6, 3)
        events.append({
            "name": p["kind"], "ph": "X", "cat": "device",
            "ts": start_us, "dur": round(end_us - start_us, 3),
            "pid": _ENGINE_PID, "tid": _DEVICE_TID,
            "args": {"ticket": p["ticket"], "steps": p["steps"],
                     "rows": p["rows"],
                     "idle_before_ms": round(p["idle_before_s"] * 1e3, 3)},
        })
        # the flow leaves the middle of the dispatch segment, on the
        # anchor of the step that drew it
        launch_us = round(p["t_enq_unix_ns"] / 1e3
                          - (p["t_enq"] - p["t_enter"]) * 5e5, 3)
        flow = {"name": "launch", "cat": "device", "id": p["ticket"],
                "pid": _ENGINE_PID}
        events.append({**flow, "ph": "s", "ts": launch_us,
                       "tid": _STEP_TID})
        events.append({**flow, "ph": "f", "bp": "e", "ts": start_us,
                       "tid": _DEVICE_TID})
    for rec in timeline.records(steps):
        base_us = rec["t0_unix_ns"] / 1e3
        events.append({
            "name": "step", "ph": "i", "s": "t", "cat": "engine",
            "ts": round(base_us, 3), "pid": _ENGINE_PID, "tid": _STEP_TID,
            "args": {"seq": rec.get("seq"),
                     "wall_ms": round(rec["wall_s"] * 1e3, 3),
                     "gap_ms": round(rec["gap_s"] * 1e3, 3)},
        })
        for name, s0, s1 in rec["segs"]:
            events.append({
                "name": name, "ph": "X", "cat": "engine",
                "ts": round(base_us + s0 * 1e6, 3),
                "dur": round((s1 - s0) * 1e6, 3),
                "pid": _ENGINE_PID, "tid": _STEP_TID,
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
        "programs": timeline.programs(n),
        "summary": timeline.summary(),
    }
