"""Engine watchdog & device-fault quarantine.

Every robustness plane so far (journaled recovery, drain handoff, burn-
gated rollouts) assumes the engine itself stays sane.  It does not: a
hung device dispatch wedges ``step()`` under ``_exec_lock`` forever, and
a silently-corrupted forward (NaN logits, bad chip) streams garbage with
a 200 status.  This module closes that failure domain with one
invariant: *the engine is either provably making progress or provably
out of rotation*.

Three cooperating pieces:

1. **Hung-dispatch watchdog.**  The stepline already brackets every
   device seam with ``dispatch``/``device_wait`` phases; the timeline
   mirrors those seams into :meth:`EngineWatchdog.device_enter` /
   :meth:`device_exit`.  A lazy monitor thread checks the armed seam
   against a deadline (``DYNAMO_TPU_STEP_DEADLINE_S`` override, else a
   warmup-measured seam-time EWMA x margin with a floor).  A blown
   deadline *trips* the watchdog: the worker goes ``suspect``, serving
   sheds ``/v1/*`` with 503, the flight recorder dumps the open draft,
   and the escalation ladder fires.

2. **Health state machine.** ::

       healthy -> suspect -> resurrecting -> healthy
                     |
                     +--> quarantined        (terminal)

   The escalation ladder resurrects a suspect engine in place (fresh KV
   pool, re-``device_put`` weights through the elasticity staging path,
   re-warmup) once the wedged dispatch returns control; journaled
   streams hand off through the drain-handoff plane meanwhile and
   resume byte-identically on a peer.  Repeated trips within
   ``DYNAMO_TPU_QUARANTINE_WINDOW_S`` mean the device is not coming
   back: the worker is quarantined permanently, readiness goes 503, the
   operator replaces the pod and planner capacity excludes it.

3. **Integrity sentinels** (``DYNAMO_TPU_INTEGRITY=off|logits|full``).
   A finite-check on prefill logits rides the existing first-token
   readback (no extra device sync) and a host-side sanity check covers
   decode-window readbacks; ``full`` adds KV-page checksums at the KVBM
   demote/onboard boundary.  A tripped sentinel aborts ONLY the
   poisoned streams with a typed ``integrity_fault`` flight event —
   never the process, and never the health state machine (corruption is
   per-batch; hangs are per-device).

Trip handling runs on the monitor thread and deliberately never touches
``_exec_lock`` — the whole point is that the scheduler thread may be
wedged under it.  Resurrection runs on a separate escalation thread
that *does* block on the lock: a simulated hang eventually returns and
resurrection proceeds; a real hang never returns, which leaves the
worker suspect and shedding until the operator replaces the pod —
exactly the "provably out of rotation" half of the invariant.

Env knobs (registered in dynalint KNOWN_ENV):

- ``DYNAMO_TPU_STEP_DEADLINE_S`` — hard per-seam deadline override;
  unset derives ``max(floor, ewma * margin)`` from observed seam times.
  The derived deadline rests on one guarantee: a warmed engine never
  compiles inside a seam.  A first ``jit`` call compiles inside its
  ``dispatch`` seam — tens of seconds per program at 7B widths, which
  would read as two hangs and a quarantine before ``/ready`` — so the
  engine arms it (``derive_deadline``) only once an ``Engine.warmup()``
  has completed, and only on a real accelerator: an engine that was
  never warmed (``--no-warmup``, ``bench.py``'s step-to-warm loops) and
  CPU runs (tests, local development) compile mid-seam at will.  While
  it is off, seams neither trip it nor feed the EWMA.  An explicit
  override trips everywhere, warmup included — CI drills set it;
- ``DYNAMO_TPU_QUARANTINE_WINDOW_S`` (default 300) — two trips inside
  this window quarantine the worker permanently;
- ``DYNAMO_TPU_INTEGRITY`` (default ``logits``) — sentinel tier.
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import time
from typing import Callable, Deque, Dict, List, Optional

log = logging.getLogger("dynamo_tpu.watchdog")

DEADLINE_ENV = "DYNAMO_TPU_STEP_DEADLINE_S"
QUARANTINE_WINDOW_ENV = "DYNAMO_TPU_QUARANTINE_WINDOW_S"
INTEGRITY_ENV = "DYNAMO_TPU_INTEGRITY"

DEFAULT_QUARANTINE_WINDOW_S = 300.0
# never trip a seam faster than this. The EWMA is one mean over all seams
# and millisecond dispatch seams hold it near 10 ms, so on a warmed engine
# the floor IS the deadline — and the longest seam of a healthy engine is
# the wait for a drained 16-step decode window, 0.2-0.4 s on a v5e. At 2 s
# one slow episode of the device (a 0.2 s window that took 2.0-2.5 s and
# came back; ROADMAP S1) read as a hang, aborted every stream and ended in
# quarantine. 8 s is that seam times the 20x margin the EWMA was meant to
# give it.
DEFAULT_DEADLINE_FLOOR_S = 8.0
# EWMA multiplier: decode seams are milliseconds, so even 20x stays far
# below human-visible; a genuine hang overshoots by orders of magnitude
DEFAULT_DEADLINE_MARGIN = 20.0
EWMA_ALPHA = 0.2
# monitor thread parks itself after this long with no armed seam: the
# thread pins watchdog -> engine (params, KV pool) via its bound-method
# target, so an idle monitor would keep a retired engine immortal.
# device_enter restarts it on the next dispatch.
MONITOR_IDLE_EXIT_S = 5.0

# /metrics encoding of health (docs/robustness.md)
HEALTH_CODES = {"healthy": 0, "suspect": 1, "resurrecting": 2,
                "quarantined": 3}

INTEGRITY_MODES = ("off", "logits", "full")


def integrity_mode() -> str:
    """Resolved ``DYNAMO_TPU_INTEGRITY`` tier; unknown values fall back
    to the default ``logits`` (cheap, always worth it)."""
    raw = os.environ.get(INTEGRITY_ENV, "logits").strip().lower()
    return raw if raw in INTEGRITY_MODES else "logits"


def _env_deadline() -> Optional[float]:
    raw = os.environ.get(DEADLINE_ENV, "").strip()
    if not raw:
        return None
    try:
        v = float(raw)
        return v if v > 0 else None
    except ValueError:
        log.warning("bad %s=%r; deriving deadline from EWMA", DEADLINE_ENV,
                    raw)
        return None


def _env_quarantine_window() -> float:
    raw = os.environ.get(QUARANTINE_WINDOW_ENV, "").strip()
    if not raw:
        return DEFAULT_QUARANTINE_WINDOW_S
    try:
        return max(0.0, float(raw))
    except ValueError:
        return DEFAULT_QUARANTINE_WINDOW_S


class IntegrityFault(RuntimeError):
    """A sentinel caught device-side corruption (non-finite logits,
    out-of-range token, KV checksum mismatch).  Carries the poisoned
    request ids so callers abort exactly those streams and nothing
    else."""

    def __init__(self, sentinel: str, rids: List[str], detail: str = ""):
        self.sentinel = sentinel
        self.rids = list(rids)
        super().__init__(
            f"integrity fault [{sentinel}] rids={self.rids} {detail}".strip())


class EngineWatchdog:
    """Per-engine health state machine + hung-dispatch monitor.

    Constructed by the engine next to its StepTimeline; the timeline
    forwards device-phase enter/exit events here (``timeline.watch``),
    which keeps the seam coverage exactly equal to the stepline's
    instrumentation — any newly instrumented device seam is watched for
    free.
    """

    def __init__(self, engine: Optional[object] = None,
                 deadline_s: Optional[float] = None,
                 quarantine_window_s: Optional[float] = None,
                 margin: float = DEFAULT_DEADLINE_MARGIN,
                 floor_s: float = DEFAULT_DEADLINE_FLOOR_S,
                 derive_deadline: bool = True,
                 clock: Callable[[], float] = time.monotonic):
        self.engine = engine
        self._clock = clock
        # False = seams may hold a compilation: only an explicit override
        # (env/ctor/test) trips the monitor and no seam feeds the EWMA.
        # The engine flips it on when a warmup() completes on a real
        # accelerator (Engine.warmup)
        self.derive_deadline = derive_deadline
        self._deadline_override = (deadline_s if deadline_s is not None
                                   else _env_deadline())
        self.quarantine_window_s = (
            quarantine_window_s if quarantine_window_s is not None
            else _env_quarantine_window())
        self.margin = margin
        self.floor_s = floor_s

        self._lock = threading.Lock()
        self._state = "healthy"  # guarded_by: _lock
        self._armed: Optional[List] = None  # guarded_by: _lock — [seam, t0, tripped]
        self._ewma_s: Optional[float] = None  # guarded_by: _lock
        self._trip_times: Deque[float] = collections.deque(maxlen=32)  # guarded_by: _lock
        self.trips_total: Dict[str, int] = {}  # guarded_by: _lock — by kind
        self.integrity_faults_total: Dict[str, int] = {}  # guarded_by: _lock — by sentinel
        self.last_trip: Optional[Dict[str, object]] = None  # guarded_by: _lock

        # hooks fired OUTSIDE the lock (serving wires shed/handoff/metrics)
        self.on_trip: Optional[Callable[[str, str], None]] = None
        self.on_health: Optional[Callable[[str], None]] = None

        self._monitor: Optional[threading.Thread] = None  # guarded_by: _lock
        self._resurrector: Optional[threading.Thread] = None  # guarded_by: _lock
        self._stop = threading.Event()

    # ------------------------------------------------------------- health --
    @property
    def health(self) -> str:
        with self._lock:
            return self._state

    @property
    def health_code(self) -> int:
        return HEALTH_CODES[self.health]

    @property
    def ok_for_traffic(self) -> bool:
        """Gate for /v1/* admission and readiness: only a healthy engine
        takes new work."""
        return self.health == "healthy"

    def _transition(self, state: str) -> bool:
        """Set health under the lock; fire on_health outside it.  A
        quarantined worker never leaves quarantine (terminal)."""
        with self._lock:
            if self._state == "quarantined" and state != "quarantined":
                return False
            if self._state == state:
                return False
            self._state = state
        log.warning("engine health -> %s", state)
        cb = self.on_health
        if cb is not None:
            try:
                cb(state)
            except Exception:
                log.exception("on_health hook failed")
        return True

    # --------------------------------------------------- seam arm / disarm --
    def device_enter(self, seam: str) -> None:
        """A device dispatch/readback seam opened (timeline hook).  Arms
        the deadline and lazily starts the monitor."""
        now = self._clock()
        with self._lock:
            self._armed = [seam, now, False]
            started = self._monitor is not None and self._monitor.is_alive()
        if not started:
            self._start_monitor()

    def device_exit(self, seam: str) -> None:
        """Seam closed in time: disarm and fold the duration into the
        EWMA the derived deadline rests on."""
        now = self._clock()
        with self._lock:
            armed = self._armed
            self._armed = None
            if armed is None or armed[2] or not self.derive_deadline:
                # nothing armed, this seam already tripped (a late return
                # from a tripped seam must not poison the EWMA), or the
                # seam may have held a compilation (minutes, not the
                # milliseconds the deadline is derived from)
                return
            dt = max(0.0, now - armed[1])
            if self._ewma_s is None:
                self._ewma_s = dt
            else:
                self._ewma_s = ((1.0 - EWMA_ALPHA) * self._ewma_s
                                + EWMA_ALPHA * dt)

    def deadline_s(self) -> float:
        """Effective per-seam deadline: env/ctor override wins, else
        EWMA x margin with a floor (pre-EWMA: just the floor)."""
        if self._deadline_override is not None:
            return self._deadline_override
        with self._lock:
            ewma = self._ewma_s
        if ewma is None:
            return self.floor_s
        return max(self.floor_s, ewma * self.margin)

    # ------------------------------------------------------------ monitor --
    def _start_monitor(self) -> None:
        with self._lock:
            if self._monitor is not None and self._monitor.is_alive():
                return
            self._monitor = threading.Thread(
                target=self._monitor_loop, name="engine-watchdog",
                daemon=True)
            self._monitor.start()

    def _monitor_loop(self) -> None:
        idle_since: Optional[float] = None
        while not self._stop.is_set():
            deadline = self.deadline_s()
            # the derived deadline arms only on a warmed engine on a real
            # accelerator; an explicit override always arms
            armable = (self._deadline_override is not None
                       or self.derive_deadline)
            tripped_seam = None
            now = self._clock()
            with self._lock:
                armed = self._armed
                if (armable and armed is not None and not armed[2]
                        and now - armed[1] > deadline):
                    armed[2] = True  # one trip per arming
                    tripped_seam = armed[0]
                if armed is None:
                    if idle_since is None:
                        idle_since = now
                    elif now - idle_since > MONITOR_IDLE_EXIT_S:
                        # park: no seam armed for a while — drop the
                        # thread so a retired engine is collectible;
                        # device_enter restarts it on the next dispatch
                        self._monitor = None
                        return
                else:
                    idle_since = None
            if tripped_seam is not None:
                self.trip("hung_dispatch", seam=tripped_seam,
                          deadline_s=deadline)
            # poll an order of magnitude finer than the deadline so
            # detection latency stays << the deadline itself
            self._stop.wait(max(0.01, min(0.25, deadline / 10.0)))

    def stop(self) -> None:
        """Engine shutdown: stop the monitor thread."""
        self._stop.set()

    # --------------------------------------------------------------- trips --
    def trip(self, kind: str, seam: str = "", escalate: bool = True,
             **fields) -> None:
        """A blown deadline or fatal step.  Runs on the monitor (or
        scheduler) thread and NEVER takes the engine exec lock — the
        scheduler may be wedged under it.  Marks the worker suspect,
        flight-dumps, fires on_trip, and launches the escalation ladder
        (or quarantines on repeat trips inside the window)."""
        now = self._clock()
        with self._lock:
            recent = [t for t in self._trip_times
                      if now - t <= self.quarantine_window_s]
            self._trip_times.append(now)
            self.trips_total[kind] = self.trips_total.get(kind, 0) + 1
            self.last_trip = {"kind": kind, "seam": seam, "t": now, **fields}
            quarantine = len(recent) >= 1  # this trip is the 2nd in window
        eng = self.engine
        if eng is not None and getattr(eng, "flight", None) is not None:
            try:
                eng.flight.note("watchdog_trip", kind=kind, seam=seam,
                                **fields)
                eng.flight.dump(f"watchdog_{kind}")
            except Exception:
                log.exception("watchdog flight dump failed")
        if quarantine:
            log.error("watchdog trip kind=%s seam=%s — repeat inside "
                      "%.1fs window, quarantining permanently",
                      kind, seam, self.quarantine_window_s)
            self._transition("quarantined")
        else:
            log.error("watchdog trip kind=%s seam=%s deadline=%s",
                      kind, seam, fields.get("deadline_s"))
            self._transition("suspect")
        cb = self.on_trip
        if cb is not None:
            try:
                cb(kind, seam)
            except Exception:
                log.exception("on_trip hook failed")
        if not quarantine and escalate:
            self._start_resurrector()

    def on_fatal_step(self, err: BaseException) -> None:
        """engine_service's fatal-step path: the scheduler thread itself
        caught the error, so it is NOT wedged — trip, then resurrect
        inline on this thread (deterministic: no escalation thread, no
        window where a broken engine takes another step)."""
        self.trip("fatal_step", seam="step", escalate=False,
                  error=repr(err))
        if self.health == "suspect":
            self._resurrect()
        elif self.health == "quarantined" and self.engine is not None:
            # permanently out of rotation — still tear down the streams
            # so every waiting handler sees a final event
            try:
                self.engine.abort_all()
            except Exception:
                log.exception("quarantine teardown failed")

    def record_integrity_fault(self, sentinel: str, rids: List[str],
                               **fields) -> None:
        """A sentinel caught corruption.  Counted and flight-noted, but
        health does NOT change: the poisoned streams are aborted and the
        engine keeps serving co-batched tenants."""
        with self._lock:
            self.integrity_faults_total[sentinel] = (
                self.integrity_faults_total.get(sentinel, 0) + 1)
        eng = self.engine
        if eng is not None and getattr(eng, "flight", None) is not None:
            try:
                eng.flight.note("integrity_fault", sentinel=sentinel,
                                rids=list(rids), **fields)
            except Exception:
                log.exception("integrity flight note failed")
        log.error("integrity fault sentinel=%s rids=%s", sentinel,
                  list(rids))

    # --------------------------------------------------------- escalation --
    def _start_resurrector(self) -> None:
        with self._lock:
            if self._resurrector is not None and self._resurrector.is_alive():
                return
            self._resurrector = threading.Thread(
                target=self._resurrect, name="engine-resurrector",
                daemon=True)
            self._resurrector.start()

    def _resurrect(self) -> None:
        """Escalation ladder tail: block until the wedged dispatch
        returns control (RLock), then rebuild device state in place.  A
        real device hang never returns the lock — the worker stays
        suspect and shedding until the operator replaces the pod."""
        eng = self.engine
        if eng is None:
            return
        lock = getattr(eng, "_exec_lock", None)
        try:
            if lock is not None:
                lock.acquire()
            try:
                if self.health == "quarantined":
                    return
                self._transition("resurrecting")
                eng.resurrect()
            finally:
                if lock is not None:
                    lock.release()
        except Exception:
            log.exception("engine resurrection failed — quarantining")
            self._transition("quarantined")
            return
        if self._transition("healthy"):
            log.warning("engine resurrected in place; serving again")

    # ----------------------------------------------------------- snapshot --
    def summary(self) -> Dict[str, object]:
        """Rides /worker/stats and the heartbeat (frontend health gauge,
        router filter)."""
        with self._lock:
            if self._deadline_override is not None:
                deadline = self._deadline_override
            elif self._ewma_s is None:
                deadline = self.floor_s
            else:
                deadline = max(self.floor_s, self._ewma_s * self.margin)
            return {
                "state": self._state,
                "code": HEALTH_CODES[self._state],
                "trips_total": dict(self.trips_total),
                "integrity_faults_total": dict(self.integrity_faults_total),
                "ewma_s": self._ewma_s,
                "deadline_s": deadline,
                "last_trip": dict(self.last_trip) if self.last_trip else None,
            }
