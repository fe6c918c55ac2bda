"""Stepline suite (`make flight-check`, marker `flight`).

Covers observability/timeline.py and its engine + HTTP wiring:

- phase-stack mechanics: pause semantics (nested phases record exclusive
  self-time), disjoint segments, exception unwind, idle-step elision;
- conservation: on a REAL tiny-engine run, every record's phase
  self-times are disjoint, live inside [0, wall], and sum + gap equals
  the step wall time — the invariant the zero-bubble acceptance reads;
- the device account: busy and idle time from the programs' own
  completion stamps (posted by hand here, on a clock the test moves),
  conserved against `loop_wall_s`, idle cut by the thread's segments,
  `row_idle_s`, `idle_worst`, the stamp's skew, reset() and
  merge_summaries; on a real engine the watcher stamps every ticket and
  ends with the loop, and the streams do not change with it;
- Perfetto export: deterministic golden over stub records + a fixed
  tracing span — schema-valid Chrome Trace Event JSON whose engine
  steps and request spans share the unix-epoch microsecond clock;
- /debug/timeline payload formats (json / summary / perfetto / steps=);
- fleet rollup: merge_summaries totals, worst-worker p95, bubble
  attribution;
- the loop account: step wall + `between_steps` + `no_work` =
  `loop_wall_s` exactly, `no_work` only while the engine has no work;
- drained time: what a `device_wait` leaves in flight decides whether the
  device is drained, every drained second is put down to one segment,
  and `bubble` follows `drained.by`;
- token time by cause: every second of the thread goes to the kind of
  the oldest unfinished program or to `drained`, the three sum to
  `loop_wall_s`, a sequence's waits are charged to them at each emission,
  the 8 longest keep their record, reset() and merge_summaries follow;
- profiler annotations exist only between start_ and stop_annotations;
- disabled mode + ring bounds + overhead budget of the on path.
"""

import json

import pytest

from dynamo_tpu.observability import timeline as timeline_mod
from dynamo_tpu.observability.timeline import (
    CAUSES,
    DRAINED_KEYS,
    IDLE_KEYS,
    PHASES,
    PhaseDigest,
    StepTimeline,
    merge_summaries,
    perfetto_trace,
    timeline_debug_payload,
)

pytestmark = pytest.mark.flight

MODEL = "tiny-debug"
KW = dict(model=MODEL, page_size=4, num_pages=128, max_num_seqs=4,
          max_seq_len=96)


def _assert_record_conserves(rec, tol=1e-6):
    """The conservation contract for one step record."""
    wall = rec["wall_s"]
    assert wall >= 0.0
    # segments disjoint, ordered, inside [0, wall]
    prev_end = 0.0
    for name, s0, s1 in rec["segs"]:
        assert name in PHASES
        assert s0 >= prev_end - tol
        assert s1 >= s0
        assert s1 <= wall + tol
        prev_end = s1
    # sum of phase self-times + gap == wall
    total = sum(rec["phases"].values())
    assert rec["gap_s"] >= 0.0
    assert abs(total + rec["gap_s"] - wall) < tol


# ---------------------------------------------------------------------------
# phase-stack mechanics
# ---------------------------------------------------------------------------
def test_nested_phases_record_exclusive_self_time():
    tl = StepTimeline(capacity=8, enabled=True)
    tl.begin_step()
    with tl.phase("admit"):
        with tl.phase("page_alloc"):
            pass
        with tl.phase("dispatch"):
            pass
    with tl.phase("bank"):
        pass
    tl.commit_step()
    (rec,) = tl.records()
    names = [s[0] for s in rec["segs"]]
    # outer phase pauses around each inner phase: admit appears as
    # multiple exclusive segments interleaved with the nested ones
    assert "page_alloc" in names and "dispatch" in names
    assert names[0] == "admit" and names[-1] == "bank"
    _assert_record_conserves(rec)
    # per-phase sums aggregate the split segments
    seg_sum = {}
    for name, s0, s1 in rec["segs"]:
        seg_sum[name] = seg_sum.get(name, 0.0) + (s1 - s0)
    for name, tot in rec["phases"].items():
        assert abs(seg_sum[name] - tot) < 1e-6


def test_idle_steps_are_elided_and_unwind_is_flagged():
    tl = StepTimeline(capacity=8, enabled=True)
    tl.begin_step()
    tl.commit_step()  # measured nothing: an idle engine tick
    assert tl.records() == []
    assert tl.steps_total == 0
    # a step that unwound past commit (exception) finalizes flagged on
    # the next begin, with its open phases closed newest-first
    tl.begin_step()
    tl._enter("admit")
    tl._enter("dispatch")
    tl.begin_step()
    tl.commit_step()
    (rec,) = tl.records()
    assert rec.get("aborted") is True
    _assert_record_conserves(rec)


def test_ring_bounded_and_capacity_zero_keeps_digests():
    tl = StepTimeline(capacity=4, enabled=True)
    for _ in range(10):
        tl.begin_step()
        with tl.phase("admit"):
            pass
        tl.commit_step()
    assert len(tl.records()) == 4
    assert tl.steps_total == 10
    assert tl.dropped_total == 6
    assert [r["seq"] for r in tl.records()] == [6, 7, 8, 9]
    # capacity 0: no exact records, but the streaming digests still run
    tl0 = StepTimeline(capacity=0, enabled=True)
    tl0.begin_step()
    with tl0.phase("admit"):
        pass
    tl0.commit_step()
    assert tl0.records() == []
    assert tl0.steps_total == 1
    assert tl0.digests["admit"].count == 1


def test_disabled_timeline_is_inert():
    tl = StepTimeline(capacity=8, enabled=False)
    tl.begin_step()
    with tl.phase("admit"):
        pass
    tl.commit_step()
    assert tl.records() == []
    assert tl.steps_total == 0
    assert tl.summary()["enabled"] is False
    # phase() outside any open draft is a no-op too (enabled timeline,
    # engine paths that run outside step() like the disagg prefill role)
    tl2 = StepTimeline(capacity=8, enabled=True)
    with tl2.phase("dispatch"):
        pass
    assert tl2.records() == []


def test_phase_digest_matches_engine_bucket_scheme():
    from dynamo_tpu.engine.engine import PhaseTimer

    assert PhaseDigest._EDGES_MS == PhaseTimer._EDGES_MS
    dg = PhaseDigest()
    pt = PhaseTimer()
    for ms in (0.1, 0.3, 1.0, 7.7, 100.0, 9000.0):
        dg.observe(ms / 1e3)
        pt.observe(ms / 1e3)
    assert dg.buckets == pt.buckets
    assert dg.quantile_ms(0.5) == pt.quantile_ms(0.5)


# ---------------------------------------------------------------------------
# conservation on a real engine
# ---------------------------------------------------------------------------
def test_engine_run_conserves_step_wall_time():
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import Engine
    from dynamo_tpu.engine.request import GenRequest

    eng = Engine(EngineConfig(**KW))
    assert eng.timeline.enabled
    eng.add_request(GenRequest("ca", [1, 5, 9, 13], max_tokens=8,
                               temperature=0.0, ignore_eos=True))
    eng.add_request(GenRequest("cb", [2, 7, 11], max_tokens=8,
                               temperature=0.0, ignore_eos=True))
    while eng.has_work:
        eng.step()
    recs = eng.timeline.records()
    assert recs, "a real run must leave timeline records"
    for rec in recs:
        _assert_record_conserves(rec)
    # the run dispatched device programs: the device phases were measured
    phases_seen = {s[0] for r in recs for s in r["segs"]}
    assert "dispatch" in phases_seen
    assert "admit" in phases_seen
    # commit_step's fields ride the record
    assert all("active" in r for r in recs)
    # summary coherence: shares sum to <= 1 + gap share tolerance
    summ = eng.timeline.summary()
    assert summ["steps"] == len([r for r in recs]) + eng.timeline.dropped_total
    tracked = sum(p["total_s"] for p in summ["phases"].values())
    assert tracked <= summ["wall_s"] + 1e-6
    assert summ["untracked_s"] >= 0.0


def test_mixed_steps_behind_the_pipeline_open_no_drained_interval():
    """window -> mixed -> mixed -> final on a real engine: every mixed step
    is dispatched while a program is unfinished, so no drained interval
    opens before any of the three, and none at the final chunk either: its
    program stays in flight with the prompt's first token in it, and the
    next window is dispatched behind it."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import Engine
    from dynamo_tpu.engine.request import GenRequest

    eng = Engine(EngineConfig(**KW, num_scheduler_steps=4,
                              prefill_chunk_tokens=8, mixed_batch_tokens=8))
    tl = eng.timeline
    eng.add_request(GenRequest("live", [1, 2, 3], max_tokens=40,
                               temperature=0.0, ignore_eos=True))
    for _ in range(3):
        eng.step()
    eng.add_request(GenRequest("long", list(range(1, 23)), max_tokens=4,
                               temperature=0.0, ignore_eos=True))
    seen = []
    ragged = eng._ragged_step

    def watched(events, inf, drafted, lag=0, **kw):
        # (interval open?, closed so far, steps unread) at the dispatch
        seen.append((tl._drained, tl.drained_count, lag))
        return ragged(events, inf, drafted, lag=lag, **kw)

    eng._ragged_step = watched
    count0 = tl.drained_count
    while eng._inflight is not None or eng.pending:
        eng.step()
    assert [d for d, _, _ in seen] == [False] * 3
    assert [n for _, n, _ in seen] == [count0] * 3
    # behind a window of ONE step (a prompt just admitted keeps fused
    # windows out of its first chunk's way), then behind two chunks
    assert [lag for _, _, lag in seen] == [1, 1, 1]
    # counted at the dispatch, and at the read: the final chunk is unread
    assert (eng.metrics.mixed_behind, eng.metrics.mixed_count) == (3, 2)
    assert eng._pending_win.joiner.req.request_id == "long"
    assert not tl._drained and tl.drained_count == count0
    eng.step()  # the next window, dispatched behind it; then its read
    assert (eng.metrics.mixed_count, eng.metrics.first_tokens_behind) == (3, 1)
    assert not tl._drained and tl.drained_count == count0
    while len(eng.seqs) == 2:
        eng.step()
    assert tl.drained_count == count0  # "long" left behind a program too
    while eng.has_work:
        eng.step()
    summ = tl.summary()
    # the summary rounds each cause to a microsecond
    assert sum(summ["token_time"]["cause_s"].values()) == pytest.approx(
        summ["loop_wall_s"], abs=1e-4)


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------
class _StubTimeline:
    def __init__(self, recs, programs=()):
        self._recs = recs
        self._programs = list(programs)

    def records(self, n=None):
        return self._recs[-n:] if n else list(self._recs)

    def programs(self, n=None):
        return self._programs[-n:] if n else list(self._programs)


_BASE_NS = 1_754_000_000_000_000_000  # fixed epoch anchor


def _stub_records():
    return [
        {
            "seq": 0,
            "t0_unix_ns": _BASE_NS,
            "wall_s": 0.010,
            "phases": {"admit": 0.002, "dispatch": 0.005,
                       "device_wait": 0.002},
            "segs": [("admit", 0.0, 0.002), ("dispatch", 0.002, 0.007),
                     ("device_wait", 0.007, 0.009)],
            "gap_s": 0.001,
        },
        {
            "seq": 1,
            "t0_unix_ns": _BASE_NS + 10_000_000,
            "wall_s": 0.008,
            "phases": {"dispatch": 0.006, "detok": 0.001},
            "segs": [("dispatch", 0.0, 0.006), ("detok", 0.006, 0.007)],
            "gap_s": 0.001,
        },
    ]


def _stub_programs():
    """The two dispatch segments' programs, on a monotonic clock that read
    50.0 when step 0 began: the first ran 6 ms from its dispatch's exit,
    the second was launched 2 ms before that and so started at its end."""
    return [
        {"ticket": 1, "kind": "prompt", "steps": 1, "rows": 1,
         "t_enter": 50.002, "t_enq": 50.007, "t_done": 50.013,
         "t_enq_unix_ns": _BASE_NS + 7_000_000, "busy_s": 0.006,
         "idle_before_s": 0.0},
        {"ticket": 2, "kind": "decode", "steps": 16, "rows": 3,
         "t_enter": 50.010, "t_enq": 50.016, "t_done": 50.030,
         "t_enq_unix_ns": _BASE_NS + 16_000_000, "busy_s": 0.014,
         "idle_before_s": 0.003},
    ]


def _stub_collector():
    from dynamo_tpu.observability.tracing import Span, SpanCollector

    col = SpanCollector(capacity=16)
    # a request span overlapping step 0 on the same epoch clock
    sp = Span("http POST /v1/completions", "trace-1", "span-1", None,
              "SERVER", "worker-agg", col, start_ns=_BASE_NS + 1_000_000)
    sp.set_attribute("rid", "req-1")
    sp.set_attribute("pages", [1, 2])  # non-primitive: must stringify
    sp.end(end_ns=_BASE_NS + 6_000_000)
    # an unfinished span must NOT export (no duration)
    Span("open", "trace-1", "span-2", None, "SERVER", "worker-agg", col)
    return col


def test_perfetto_trace_schema_and_shared_clock_domain():
    trace = perfetto_trace(_StubTimeline(_stub_records(), _stub_programs()),
                           collector=_stub_collector(), steps=128)
    # deterministic, JSON-round-trippable
    blob = json.dumps(trace, sort_keys=True)
    assert json.loads(blob) == trace
    assert trace["displayTimeUnit"] == "ms"
    events = trace["traceEvents"]
    for ev in events:
        assert ev["ph"] in ("M", "i", "X", "s", "f")
        assert isinstance(ev["name"], str)
        if ev["ph"] != "M":
            assert isinstance(ev["ts"], float)
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0
        if ev["ph"] == "i":
            assert ev["s"] == "t"
    # every phase segment exports as a complete event on the engine track
    engine_x = [e for e in events if e["ph"] == "X" and e["pid"] == 1
                and e["tid"] == 1]
    assert [e["name"] for e in engine_x] == [
        "admit", "dispatch", "device_wait", "dispatch", "detok"]
    # the device's programs: a `device` track under the engine's process,
    # bars that do not overlap, each joined by a flow (id = ticket) that
    # leaves inside the dispatch segment that launched it
    assert [e["args"]["name"] for e in events if e["ph"] == "M"
            and e["name"] == "thread_name" and e["pid"] == 1] == [
        "engine.step", "device"]
    bars = [e for e in events if e["ph"] == "X" and e.get("tid") == 2
            and e["pid"] == 1]
    assert [(b["name"], b["args"]["ticket"]) for b in bars] == [
        ("prompt", 1), ("decode", 2)]
    assert bars[0]["ts"] + bars[0]["dur"] <= bars[1]["ts"]
    assert bars[0]["dur"] == pytest.approx(6000.0, abs=0.01)
    assert bars[1]["args"] == {"ticket": 2, "steps": 16, "rows": 3,
                               "idle_before_ms": 3.0}
    dispatches = [e for e in engine_x if e["name"] == "dispatch"]
    for bar, seg in zip(bars, dispatches):
        start, end = [e for e in events if e["ph"] in "sf"
                      and e["id"] == bar["args"]["ticket"]]
        assert (start["ph"], start["tid"]) == ("s", 1)
        assert seg["ts"] < start["ts"] < seg["ts"] + seg["dur"]
        assert (end["ph"], end["bp"], end["tid"]) == ("f", "e", 2)
        assert end["ts"] == bar["ts"]
    # step-boundary instants, one per record
    assert len([e for e in events if e["ph"] == "i"]) == 2
    # request span rides pid 2 with its service-named thread
    span_x = [e for e in events if e["ph"] == "X" and e["pid"] == 2]
    assert len(span_x) == 1  # the unfinished span is skipped
    (sx,) = span_x
    assert sx["args"]["trace_id"] == "trace-1"
    assert sx["args"]["pages"] == "[1, 2]"  # stringified, still JSON-safe
    thread_names = [e for e in events
                    if e["ph"] == "M" and e["name"] == "thread_name"
                    and e["pid"] == 2]
    assert thread_names and thread_names[0]["args"]["name"] == "worker-agg"
    # SHARED CLOCK DOMAIN: the span (epoch ns -> us) lands inside step 0's
    # wall interval on the exported timebase
    step0 = next(e for e in events if e["ph"] == "i")
    assert step0["ts"] <= sx["ts"]
    assert sx["ts"] + sx["dur"] <= step0["ts"] + 10_000  # 10ms in us


def test_debug_payload_formats():
    tl = StepTimeline(capacity=8, enabled=True)
    tl.begin_step()
    with tl.phase("admit"):
        pass
    tl.commit_step()
    # default json: records + summary + ring stats
    p = timeline_debug_payload(tl, {})
    assert p["enabled"] and p["steps_total"] == 1
    assert len(p["records"]) == 1
    assert "summary" in p
    # steps= bounds records, bad values fall back
    assert len(timeline_debug_payload(tl, {"steps": ["1"]})["records"]) == 1
    assert "records" in timeline_debug_payload(tl, {"steps": ["bogus"]})
    # summary format
    s = timeline_debug_payload(tl, {"format": ["summary"]})
    assert s["steps"] == 1 and "phases" in s and "device" in s
    # perfetto format (no collector wired: engine track only)
    t = timeline_debug_payload(tl, {"format": ["perfetto"]})
    assert "traceEvents" in t
    assert any(e["ph"] == "X" for e in t["traceEvents"])


# ---------------------------------------------------------------------------
# fleet rollup
# ---------------------------------------------------------------------------
def test_merge_summaries_totals_and_bubble():
    def mk(wall, admit_s, gap_s, p95):
        return {
            "enabled": True, "steps": 10, "wall_s": wall,
            "untracked_s": 0.0,
            "phases": {"admit": {"count": 10, "total_s": admit_s,
                                 "p50_ms": p95 / 2, "p95_ms": p95,
                                 "share": admit_s / wall}},
        }

    a, b = mk(1.0, 0.2, 0.05, 4.0), mk(2.0, 0.4, 0.10, 9.0)
    # `a` is a worker from before the loop account: its steps are its loop
    b["loop_wall_s"] = 3.0
    a["drained"] = {"total_s": 0.3, "count": 3,
                    "by": {"admit": 0.2, "dispatch": 0.1}}
    b["drained"] = {"total_s": 1.4, "count": 4,
                    "by": {"admit": 0.3, "no_work": 1.1}}
    merged = merge_summaries([a, b, {}])
    assert merged["steps"] == 20
    assert abs(merged["wall_s"] - 3.0) < 1e-9
    adm = merged["phases"]["admit"]
    assert adm["count"] == 20
    assert abs(adm["total_s"] - 0.6) < 1e-9
    assert adm["p95_ms_max"] == 9.0  # worst worker, quantiles don't merge
    assert abs(adm["share"] - 0.2) < 1e-6
    assert "device" in merged
    assert abs(merged["loop_wall_s"] - 4.0) < 1e-9
    dr = merged["drained"]
    assert dr["count"] == 7 and abs(dr["total_s"] - 1.7) < 1e-9
    assert dr["by"] == {"admit": 0.5, "dispatch": 0.1, "no_work": 1.1}
    # bubble follows the merged drained.by, as shares of the loop's wall;
    # the absence of requests is listed but is never the eater
    assert merged["bubble"]["gap_eater"] == "admit"
    assert merged["bubble"]["host_shares"] == {
        "no_work": 0.275, "admit": 0.125, "dispatch": 0.025}
    # no drained account at all (old workers only): no bubble, as before
    assert "bubble" not in merge_summaries([mk(1.0, 0.2, 0.05, 4.0)])


# ---------------------------------------------------------------------------
# the loop account and drained time, on a clock the test moves
# ---------------------------------------------------------------------------
T0 = 100.0  # where the moved clock starts


class _Clock:
    """Stands in for the `time` module inside observability/timeline.py."""

    def __init__(self):
        self.t = T0

    def monotonic(self):
        return self.t

    def time_ns(self):
        return int(self.t * 1e9)


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(timeline_mod, "time", c)
    return c


def _play(tl, clock, script):
    """Run a script of (op, arg, seconds) on the timeline: `loop` declares
    a loop state, `begin`/`commit` bracket a step, `enter`/`exit` a phase
    (`enter` takes a name, (name, upto) or (name, upto, kind)), `idle` is
    time inside a step
    that no phase claims, `stamp` = (ticket, seconds since T0) posts the
    watcher thread's completion stamp as of now, `first` makes a sequence's TokenWait under the
    name `arg` in `tl.waits` and `emit` = (name, tokens) charges it an
    emission. The clock moves by `seconds` AFTER each op, so that is how
    long the segment the op opened lasts."""
    for op, arg, seconds in script:
        if op == "loop":
            tl.loop_state(arg)
        elif op == "begin":
            tl.begin_step()
        elif op == "commit":
            tl.commit_step()
        elif op == "enter":
            name, upto, *kind = arg if isinstance(arg, tuple) else (arg, None)
            tl._enter(name, upto, *kind)
        elif op == "exit":
            tl._exit()
        elif op == "first":
            tl.fold()
            tl.__dict__.setdefault("waits", {})[arg] = tl.token_start()
        elif op == "emit":
            tl.token_gap(tl.waits[arg[0]], arg[1], arg[0])
        elif op == "stamp":  # the watcher's: (ticket, seconds since T0)
            tl._dev.stamps.append((arg[0], T0 + arg[1]))
        clock.t += seconds


def _sync_step(wait_s=0.0):
    """dispatch one program, wait for it: the device ends drained."""
    return [("begin", None, 0.0), ("enter", "dispatch", 0.002),
            ("exit", None, 0.0), ("enter", "device_wait", wait_s),
            ("exit", None, 0.0)]


# each case: (script, expected drained.by without the zeros, intervals)
_DRAINED_CASES = {
    # a drained device through the rest of the step, the loop's fan-out,
    # an idle wait, and the next step up to its dispatch's exit
    "sync_then_everything": (
        [("loop", "between_steps", 0.0)] + _sync_step(0.010) + [
            ("idle", None, 0.001),
            ("enter", "detok", 0.003), ("exit", None, 0.0),
            ("enter", "bank", 0.0005), ("exit", None, 0.0),
            ("commit", None, 0.0007),          # fan-out: between_steps
            ("loop", "no_work", 0.050),
            ("loop", "between_steps", 0.0002),
            ("begin", None, 0.0),
            ("enter", "admit", 0.004),
            ("enter", "page_alloc", 0.0015), ("exit", None, 0.0005),
            ("exit", None, 0.0),
            ("enter", "dispatch", 0.002), ("exit", None, 0.0),
            ("enter", "device_wait", 0.010), ("exit", None, 0.0),
            ("commit", None, 0.0)],
        {"untracked": 0.001, "detok": 0.003, "bank": 0.0005,
         "between_steps": 0.0009, "no_work": 0.050, "admit": 0.0045,
         "page_alloc": 0.0015, "dispatch": 0.002}, 1),
    # async scheduling: window k+1 is dispatched, THEN window k is waited
    # for. A program is still in flight: nothing here is drained time
    "async_wait_on_the_older_program": (
        [("begin", None, 0.0), ("enter", "dispatch", 0.002),
         ("exit", None, 0.0), ("commit", None, 0.0),
         ("begin", None, 0.0), ("enter", "dispatch", 0.002),
         ("exit", None, 0.0),
         ("enter", ("device_wait", 1), 0.010), ("exit", None, 0.0),
         ("enter", "detok", 0.003), ("exit", None, 0.0),
         ("commit", None, 0.0)],
        {}, 0),
    # a chunk dispatched after the window and waited for: programs run in
    # order, so the older window is done too, whatever its own wait says
    "wait_on_the_newest_covers_the_older": (
        [("begin", None, 0.0), ("enter", "dispatch", 0.002),
         ("exit", None, 0.0), ("enter", "dispatch", 0.002),
         ("exit", None, 0.0),
         ("enter", "device_wait", 0.010), ("exit", None, 0.0),
         ("enter", "detok", 0.003), ("exit", None, 0.0),
         ("enter", ("device_wait", 1), 0.0001), ("exit", None, 0.0),
         ("enter", "detok", 0.002), ("exit", None, 0.0),
         ("commit", None, 0.0)],
        {"detok": 0.005}, 0),
    # mixed steps ride the pipeline: a window, then a prompt's three chunks
    # each dispatched BEFORE the program ahead of it is waited for; only
    # the final chunk's own readback (its logits give the first token)
    # leaves the device drained, up to the next window's dispatch
    "mixed_steps_behind_the_pipeline": (
        [("begin", None, 0.0), ("enter", "dispatch", 0.002),
         ("exit", None, 0.0), ("commit", None, 0.0005)]
        + [op for ticket in (1, 2) for op in (
            ("begin", None, 0.0), ("enter", "page_alloc", 0.001),
            ("exit", None, 0.0), ("enter", "dispatch", 0.003),
            ("exit", None, 0.0),
            ("enter", ("device_wait", ticket), 0.010), ("exit", None, 0.0),
            ("enter", "detok", 0.002), ("exit", None, 0.0),
            ("commit", None, 0.0005))]
        + [("begin", None, 0.0), ("enter", "dispatch", 0.003),
           ("exit", None, 0.0),
           ("enter", ("device_wait", 3), 0.010), ("exit", None, 0.0),
           ("enter", "detok", 0.002), ("exit", None, 0.0),
           ("enter", ("device_wait", 4), 0.012), ("exit", None, 0.0),
           ("enter", "detok", 0.001), ("exit", None, 0.0),
           ("enter", "device_wait", 0.0015), ("exit", None, 0.0),
           ("enter", "admit", 0.0005), ("exit", None, 0.0),
           ("enter", "dispatch", 0.002), ("exit", None, 0.0),
           ("commit", None, 0.0)],
        {"detok": 0.001, "admit": 0.0005, "dispatch": 0.002}, 1),
    # a device_wait inside a drained interval (first-token sampling is an
    # implicit program) is not drained time; the interval goes on after it
    "a_wait_while_drained_is_left_out": (
        _sync_step(0.010) + [
            ("enter", "detok", 0.001), ("exit", None, 0.0),
            ("enter", "device_wait", 0.004), ("exit", None, 0.0),
            ("enter", "detok", 0.002), ("exit", None, 0.0),
            ("commit", None, 0.0)],
        {"detok": 0.003}, 0),
}


@pytest.mark.parametrize("case", sorted(_DRAINED_CASES))
def test_drained_time_is_put_down_to_segments(clock, case):
    script, want, intervals = _DRAINED_CASES[case]
    tl = StepTimeline(capacity=8, enabled=True)
    t_start = clock.t
    _play(tl, clock, script)
    summ = tl.summary()
    dr = summ["drained"]
    assert set(dr["by"]) == set(DRAINED_KEYS)
    got = {k: v for k, v in dr["by"].items() if v}
    assert got == pytest.approx(want, abs=1e-9)
    assert dr["count"] == intervals  # closed by a dispatch's exit
    # every drained second is in exactly one segment, and all of them are
    # the thread's time
    assert sum(dr["by"].values()) == pytest.approx(dr["total_s"], abs=1e-6)
    assert dr["total_s"] <= summ["loop_wall_s"] + 1e-9
    assert summ["loop_wall_s"] <= clock.t - t_start + 1e-9
    # bubble keeps its keys and follows drained.by
    host = {k: v for k, v in got.items() if k != "no_work"}
    if host:
        bub = summ["bubble"]
        assert set(bub) == {"gap_eater", "host_shares"}
        assert bub["gap_eater"] == max(host, key=host.get)
        assert bub["host_shares"] == pytest.approx(
            {k: round(v / summ["loop_wall_s"], 4) for k, v in got.items()})
    else:
        assert "bubble" not in summ


def test_loop_conservation(clock):
    """Σ phases + untracked + between_steps + no_work = loop_wall_s =
    the clock's own elapsed time, from the first declaration on."""
    tl = StepTimeline(capacity=8, enabled=True)
    clock.t += 5.0          # before any loop driver: nobody's time
    t_start = clock.t
    script, _, _ = _DRAINED_CASES["sync_then_everything"]
    _play(tl, clock, script + [("loop", "no_work", 0.25),
                               ("loop", "no_work", 0.05),
                               ("loop", "between_steps", 0.0)])
    summ = tl.summary()
    parts = (sum(p["total_s"] for p in summ["phases"].values())
             + summ["untracked_s"] + sum(summ["loop"].values()))
    assert parts == pytest.approx(summ["loop_wall_s"], abs=1e-6)
    assert summ["loop_wall_s"] == pytest.approx(clock.t - t_start, abs=1e-6)
    assert summ["loop"] == pytest.approx(
        {"between_steps": 0.0009, "no_work": 0.35}, abs=1e-6)
    # the step's own account is what it was: two shipped metrics read it
    assert summ["steps"] == 2
    assert summ["wall_s"] == pytest.approx(0.0345, abs=1e-6)
    assert summ["untracked_s"] == pytest.approx(0.001, abs=1e-6)
    # a library caller of step() declares no loop: its time between steps
    # is not the engine's, and loop_wall_s is the steps' wall
    lib = StepTimeline(capacity=8, enabled=True)
    _play(lib, clock, _sync_step(0.01) + [("commit", None, 3.0)]
          + _sync_step(0.01) + [("commit", None, 0.0)])
    ls = lib.summary()
    assert ls["loop_wall_s"] == pytest.approx(ls["wall_s"]) \
        == pytest.approx(0.024)
    assert ls["drained"]["by"]["between_steps"] == 0.0
    # reset() starts the account over and keeps the declared state
    tl.reset()
    clock.t += 0.5
    tl.loop_state("no_work")
    rs = tl.summary()
    assert rs["loop_wall_s"] == pytest.approx(0.5) and rs["steps"] == 0
    assert rs["loop"]["between_steps"] == pytest.approx(0.5)


class _StubEngine:
    """What EngineService._run needs of an engine: has_work, step(), and
    a timeline. Each step is one dispatch and its wait."""

    def __init__(self, steps):
        self.timeline = StepTimeline(capacity=8, enabled=True)
        self.remaining = steps
        self.no_work_seen_while_busy = []
        self.on_abort_all = None

    @property
    def has_work(self):
        return self.remaining > 0

    def step(self):
        import time

        tl = self.timeline
        self.no_work_seen_while_busy.append(tl.loop_totals["no_work"])
        tl.begin_step()
        with tl.phase("dispatch"):
            time.sleep(0.002)
        with tl.phase("device_wait"):
            pass
        tl.commit_step()
        self.remaining -= 1
        return []


def test_engine_loop_declares_no_work_only_without_work():
    import time

    from dynamo_tpu.serving.engine_service import EngineService

    eng = _StubEngine(steps=20)
    svc = EngineService(eng)
    try:
        deadline = time.monotonic() + 10.0
        while eng.has_work and time.monotonic() < deadline:
            time.sleep(0.005)
        assert not eng.has_work
        time.sleep(0.2)     # a few idle ticks of 50 ms
    finally:
        svc.close()
    tl = eng.timeline
    # while the engine had work the loop never declared `no_work` ...
    assert eng.no_work_seen_while_busy == [0.0] * 20
    summ = tl.summary()
    assert summ["steps"] == 20
    # ... and once it had none, the waiting is there, drained: every
    # step's wait left nothing in flight
    assert summ["loop"]["no_work"] >= 0.1
    assert summ["loop"]["between_steps"] > 0.0
    assert summ["drained"]["by"]["no_work"] == pytest.approx(
        summ["loop"]["no_work"], abs=0.06)
    assert summ["drained"]["count"] == 19
    parts = (sum(p["total_s"] for p in summ["phases"].values())
             + summ["untracked_s"] + sum(summ["loop"].values()))
    assert parts == pytest.approx(summ["loop_wall_s"], abs=1e-4)


# ---------------------------------------------------------------------------
# token time by cause
# ---------------------------------------------------------------------------
def _dispatch(kind, host_s=0.002):
    return [("enter", ("dispatch", None, kind), host_s), ("exit", None, 0.0)]


def _wait(upto, wait_s, kind="decode"):
    return [("enter", ("device_wait", upto, kind), wait_s),
            ("exit", None, 0.0)]


# each case: (script, expected token_time.cause_s without the zeros)
_CAUSE_CASES = {
    # async scheduling: window 2 is dispatched, then window 1 waited for,
    # then window 3 dispatched and 2 waited for. A window is in flight
    # throughout, so after the first dispatch nothing is drained; the host
    # may learn late that 1 is done, but 1 and 2 are both `decode`
    "async_windows_are_all_decode": (
        [("loop", "between_steps", 0.001), ("begin", None, 0.0)]
        + _dispatch("decode") + [("commit", None, 0.0005),
                                 ("begin", None, 0.0)]
        + _dispatch("decode") + _wait(1, 0.010)
        + [("enter", "detok", 0.003), ("exit", None, 0.0),
           ("commit", None, 0.0005), ("begin", None, 0.0)]
        + _dispatch("decode") + _wait(2, 0.012)
        + [("enter", "detok", 0.003), ("exit", None, 0.0),
           ("commit", None, 0.0)],
        {"drained": 0.003, "decode": 0.033}),
    # a mixed step first materializes the pending window (a wait on ticket
    # 1 with nothing newer: the device drains), then dispatches its own
    # program, which carries a chunk: the wait on it is `prompt`, and
    # what follows its readback is drained again
    "a_mixed_step_after_a_pending_window": (
        [("loop", "between_steps", 0.0), ("begin", None, 0.0)]
        + _dispatch("decode") + [("commit", None, 0.0005),
                                 ("begin", None, 0.0)]
        + _wait(1, 0.008)
        + [("enter", "detok", 0.002), ("exit", None, 0.0),
           ("enter", "page_alloc", 0.001), ("exit", None, 0.0)]
        + _dispatch("prompt", 0.004) + _wait(None, 0.028)
        + [("enter", "detok", 0.002), ("exit", None, 0.0),
           ("commit", None, 0.0)],
        {"drained": 0.002 + 0.002 + 0.001 + 0.004 + 0.002,
         "decode": 0.0005 + 0.008, "prompt": 0.028}),
    # the mixed step's own readback leaves the device drained; the first
    # token's sampling is then an implicit program, and a prompt's
    "a_drained_wait_on_first_token_sampling": (
        [("loop", "between_steps", 0.0), ("begin", None, 0.0)]
        + _dispatch("prompt") + _wait(None, 0.030)
        + [("enter", "detok", 0.001), ("exit", None, 0.0)]
        + _wait(None, 0.0015, "prompt")
        + [("enter", "detok", 0.0005), ("exit", None, 0.0),
           ("commit", None, 0.0)],
        {"drained": 0.002 + 0.001 + 0.0005, "prompt": 0.030 + 0.0015}),
    # a mixed step dispatched behind the pending window, the window read
    # afterwards (Engine._mixed_step under async scheduling): the window's
    # wait is `decode`, what follows behind the chunk is `prompt`, and
    # nothing is drained until the chunk's own readback
    "a_mixed_step_behind_a_pending_window": (
        [("loop", "between_steps", 0.0), ("begin", None, 0.0)]
        + _dispatch("decode") + [("commit", None, 0.0005),
                                 ("begin", None, 0.0),
                                 ("enter", "page_alloc", 0.001),
                                 ("exit", None, 0.0)]
        + _dispatch("prompt", 0.004) + _wait(1, 0.004)
        + [("enter", "detok", 0.002), ("exit", None, 0.0)]
        + _wait(2, 0.024)
        + [("enter", "detok", 0.002), ("exit", None, 0.0),
           ("commit", None, 0.0)],
        {"drained": 0.002 + 0.002,
         "decode": 0.0005 + 0.001 + 0.004 + 0.004,
         "prompt": 0.002 + 0.024}),
    # the OLDEST unfinished program decides: a chunk dispatched behind a
    # window waits as `decode` until the window is proved done, a window
    # behind a chunk as `prompt`; a wait on the newest ends both
    "the_oldest_unfinished_program_decides": (
        [("loop", "between_steps", 0.0), ("begin", None, 0.0)]
        + _dispatch("decode") + _dispatch("prompt", 0.003)
        + _wait(1, 0.010) + [("idle", None, 0.001)]
        + _dispatch("decode", 0.002) + _wait(None, 0.020)
        + [("commit", None, 0.004), ("loop", "no_work", 0.050),
           ("loop", "between_steps", 0.0)],
        {"drained": 0.002 + 0.004 + 0.050, "decode": 0.003 + 0.010,
         "prompt": 0.001 + 0.002 + 0.020}),
}


@pytest.mark.parametrize("case", sorted(_CAUSE_CASES))
def test_every_second_goes_to_one_cause(clock, case):
    script, want = _CAUSE_CASES[case]
    tl = StepTimeline(capacity=8, enabled=True)
    t_start = clock.t
    _play(tl, clock, script + [("loop", "between_steps", 0.0)])
    summ = tl.summary()
    cause = summ["token_time"]["cause_s"]
    assert tuple(cause) == CAUSES
    assert {k: v for k, v in cause.items() if v} == pytest.approx(
        want, abs=1e-9)
    # exact by construction: one add a segment boundary
    assert sum(cause.values()) == pytest.approx(summ["loop_wall_s"],
                                                abs=1e-6)
    assert summ["loop_wall_s"] == pytest.approx(clock.t - t_start, abs=1e-6)
    # what the older drained account calls drained is `drained` here too
    assert cause["drained"] >= summ["drained"]["total_s"] - 1e-9


def test_token_gaps_are_charged_to_the_causes(clock):
    """Two sequences over three emissions: each wait is the causes' growth
    since the sequence's last emission, its parts sum to it, and the sums
    are what the sequences' own accounts (TokenEvent.phase) hold."""
    tl = StepTimeline(capacity=8, enabled=True)
    _play(tl, clock,
          [("loop", "between_steps", 0.0), ("begin", None, 0.0)]
          + _dispatch("prompt") + _wait(None, 0.020, "prompt")
          + [("first", "a", 0.0), ("commit", None, 0.001),
             ("begin", None, 0.0)]
          + _dispatch("decode") + _wait(None, 0.016)
          + [("enter", "detok", 0.0), ("emit", ("a", 16), 0.001),
             ("exit", None, 0.0), ("commit", None, 0.0),
             ("begin", None, 0.0)]
          # b's prompt rides a mixed step: a waits behind it
          + _dispatch("prompt", 0.003) + _wait(None, 0.040)
          + [("enter", "detok", 0.0), ("emit", ("a", 1), 0.0),
             ("first", "b", 0.001), ("exit", None, 0.0),
             ("commit", None, 0.0005), ("begin", None, 0.0)]
          + _dispatch("decode") + _wait(None, 0.017)
          + [("enter", "detok", 0.0), ("emit", ("a", 16), 0.0),
             ("emit", ("b", 16), 0.001), ("exit", None, 0.0),
             ("commit", None, 0.0)])
    tt = tl.summary()["token_time"]
    a, b = tl.waits["a"].phase(), tl.waits["b"].phase()
    assert tt["gaps"] == 49 == a["tokens"] + b["tokens"]
    assert a["tokens"] == 33 and b["tokens"] == 16
    for c in CAUSES:
        assert tt["row_s"][c] == pytest.approx(a[c + "_s"] + b[c + "_s"],
                                               abs=1e-9)
    # a: drained 1 ms fan-out + 2 ms dispatch, 16 ms window | 1 ms detok,
    # 3 ms dispatch drained, 40 ms mixed | 1 ms detok + 0.5 + 2 drained,
    # 17 ms window; b: from its first token on
    assert a == pytest.approx(
        {"decode_s": 0.033, "prompt_s": 0.040, "drained_s": 0.0105,
         "gap_max_s": 0.044, "tokens": 33,
         "t_last": tl.waits["a"].t_last}, abs=1e-9)
    assert b == pytest.approx(
        {"decode_s": 0.017, "prompt_s": 0.0, "drained_s": 0.0035,
         "gap_max_s": 0.0205, "tokens": 16,
         "t_last": tl.waits["b"].t_last}, abs=1e-9)
    # Sigma row_s = Sigma over sequences of (last - first emitted token)
    assert sum(tt["row_s"].values()) == pytest.approx(0.0835 + 0.0205,
                                                      abs=1e-9)
    assert tt["gap_max_s"] == pytest.approx(0.044, abs=1e-6)
    # a's last wait and b's only one are the same 20.5 ms of one emission:
    # one record for the two sequences
    assert [(w["request_id"], w["sequences"]) for w in tt["worst"]] == [
        ("a", 1), ("a", 2), ("a", 1)]
    for w in tt["worst"]:
        assert w["gap_s"] == pytest.approx(
            w["decode_s"] + w["prompt_s"] + w["drained_s"], abs=2e-6)
        assert w["gap_s"] <= tt["gap_max_s"] and w["programs"] == 1
        assert w["t_unix_ns"] > 0
    assert tt["worst"][0]["prompt_s"] == pytest.approx(0.040, abs=1e-6)


def test_the_eight_longest_waits_are_kept_and_reset_starts_over(clock):
    tl = StepTimeline(capacity=8, enabled=True)
    script = [("loop", "between_steps", 0.0), ("begin", None, 0.0),
              ("first", "s", 0.0)]
    for i in range(12):
        script += _dispatch("decode", 0.0) + _wait(None, 0.001 * (i + 1)) \
            + [("enter", "detok", 0.0), ("emit", ("s", 1), 0.0),
               ("exit", None, 0.0)]
    _play(tl, clock, script + [("commit", None, 0.0)])
    tt = tl.summary()["token_time"]
    assert [round(w["gap_s"], 6) for w in tt["worst"]] == [
        round(0.001 * i, 6) for i in range(12, 4, -1)]
    assert tt["gaps"] == 12 and tt["gap_max_s"] == pytest.approx(0.012)
    assert tt["row_s"]["decode"] == pytest.approx(0.078)
    # reset() zeroes the account; a live sequence's next wait is still
    # whole (its marks are into totals that reset() does not touch)
    tl.reset()
    zero = tl.summary()["token_time"]
    assert zero == {"cause_s": dict.fromkeys(CAUSES, 0.0),
                    "row_s": dict.fromkeys(CAUSES, 0.0), "gaps": 0,
                    "gap_max_s": 0.0, "worst": []}
    _play(tl, clock, [("begin", None, 0.0)] + _dispatch("decode", 0.0)
          + _wait(None, 0.005) + [("enter", "detok", 0.0),
                                  ("emit", ("s", 2), 0.0),
                                  ("exit", None, 0.0), ("commit", None, 0.0)])
    tt = tl.summary()["token_time"]
    assert tt["gaps"] == 2 and tt["cause_s"]["decode"] == pytest.approx(0.005)
    assert tt["row_s"] == pytest.approx(
        {"decode": 0.005, "prompt": 0.0, "drained": 0.0})
    # a disabled timeline keeps no account and hands out no TokenWait
    off = StepTimeline(capacity=8, enabled=False)
    off.fold()
    assert off.token_start() is None
    assert off.summary()["token_time"]["gaps"] == 0


def test_merge_summaries_sums_token_time():
    def worker(scale, rid):
        return {"steps": 1, "wall_s": 1.0, "token_time": {
            "cause_s": {"decode": 3.0 * scale, "prompt": 1.0 * scale,
                        "drained": 0.5 * scale},
            "row_s": {"decode": 30.0 * scale, "prompt": 8.0 * scale,
                      "drained": 2.0 * scale},
            "gaps": 1000 * scale, "gap_max_s": 0.6 * scale,
            "worst": [{"gap_s": g * scale, "decode_s": 0.0,
                       "prompt_s": g * scale, "drained_s": 0.0,
                       "programs": 2, "t_unix_ns": 1, "request_id": rid}
                      for g in (0.6, 0.5, 0.4, 0.3, 0.25, 0.2, 0.15, 0.1)]}}

    old = {"steps": 1, "wall_s": 1.0}  # a worker from before the account
    tt = merge_summaries([worker(1, "a"), worker(2, "b"), old, {}])[
        "token_time"]
    assert tt["cause_s"] == {"decode": 9.0, "prompt": 3.0, "drained": 1.5}
    assert tt["row_s"] == {"decode": 90.0, "prompt": 24.0, "drained": 6.0}
    assert tt["gaps"] == 3000 and tt["gap_max_s"] == 1.2
    assert [(w["request_id"], w["gap_s"]) for w in tt["worst"]] == [
        ("b", 1.2), ("b", 1.0), ("b", 0.8), ("a", 0.6), ("b", 0.6),
        ("a", 0.5), ("b", 0.5), ("a", 0.4)]
    assert merge_summaries([old])["token_time"]["gaps"] == 0


def test_dynamo_top_prints_token_time_by_cause(clock):
    """scripts/dynamo_top.py: one `token` line a worker, from the summary
    that rides /worker/stats; none before a token ended a wait."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "dynamo_top", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "dynamo_top.py"))
    top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(top)
    tl = StepTimeline(capacity=8, enabled=True)
    _play(tl, clock, [("begin", None, 0.0)] + _dispatch("prompt")
          + _wait(None, 0.030) + [("first", "req-7", 0.0),
                                  ("commit", None, 0.0)])

    def lines():
        frame = {"ts": "00:00:00", "workers": [{
            "url": "http://w", "flight": None,
            "stats": {"model": "m", "timeline": tl.summary()}}]}
        return [ln for ln in top.render(frame, 0)
                if " token " in ln or " stepln " in ln]

    (line,) = lines()  # the bubble panel; no token ended a wait yet
    assert "device idle=6.2% (most in dispatch)" in line
    assert "longest gap=2.0ms before #1" in line
    _play(tl, clock, [("begin", None, 0.0)] + _dispatch("decode", 0.004)
          + _wait(None, 0.196) + [("enter", "detok", 0.0),
                                  ("emit", ("req-7", 16), 0.0),
                                  ("exit", None, 0.0), ("commit", None, 0.0)])
    panel, line = lines()
    assert "device idle=2.6% (most in dispatch)" in panel
    assert "longest gap=4.0ms before #2" in panel
    assert "decode=12.25ms" in line and "prompt=0.00ms" in line
    assert "idle=0.25ms" in line
    assert "drained=0.25ms" in line
    assert "longest gap=200ms (req-7)" in line


# ---------------------------------------------------------------------------
# the device account: busy and idle from the programs' own ends
# ---------------------------------------------------------------------------
def _device_conserves(summ):
    dev = summ["device"]
    assert dev["busy_s"] + dev["idle_s"] == pytest.approx(
        summ["loop_wall_s"], abs=2e-6)
    assert sum(dev["idle_by"].values()) == pytest.approx(dev["idle_s"],
                                                         abs=2e-6)
    assert sum(dev["busy_by"].values()) == pytest.approx(dev["busy_s"],
                                                         abs=2e-6)
    assert set(dev["idle_by"]) == set(IDLE_KEYS)
    assert dev["busy_enter_s"] >= dev["busy_s"] - 2e-6
    return dev


def _pipelined(stamp_at):
    """Two windows in the pipeline, then a third launched 3 ms after the
    second's end. Program 1 runs 0.002-0.010 (the host's wait on it
    returns then: the only stamp it gets), program 2 from 0.010 to 0.011,
    in the middle of the host's `detok`; the host reads it only after it
    has launched program 3 at 0.014, so no second of this is drained. The
    watcher's stamp for 2 is taken up either at the next boundary
    (`early`: the account finds nothing enqueued and charges segments as
    they close) or after program 3 is enqueued (`late`: the 3 ms are cut
    out of the tail)."""
    stamp = [("stamp", (2, 0.011), 0.0)]
    return ([("loop", "between_steps", 0.0), ("begin", None, 0.0)]
            + _dispatch("decode") + _dispatch("prompt")
            + _wait(1, 0.006)
            + [("enter", "detok", 0.002), ("exit", None, 0.0)]
            + (stamp if stamp_at == "early" else [])
            + [("enter", "bank", 0.0005), ("exit", None, 0.0),
               ("idle", None, 0.0005)]
            + _dispatch("decode", 0.001)
            + (stamp if stamp_at == "late" else [])
            + _wait(2, 0.0001) + _wait(3, 0.005)
            + [("commit", None, 0.0), ("loop", "no_work", 0.020),
               ("loop", "no_work", 0.0)])


@pytest.mark.parametrize("stamp_at", ["early", "late"])
def test_device_idle_behind_the_hosts_back(clock, stamp_at):
    tl = StepTimeline(capacity=8, enabled=True)
    _play(tl, clock, _pipelined(stamp_at))
    summ = tl.summary()
    dev = _device_conserves(summ)
    # the host's account saw none of it: a program was unread until the
    # last wait, and what is drained is the waiting for a request after it
    assert {k: v for k, v in summ["drained"]["by"].items() if v} == {
        "no_work": pytest.approx(0.020)}
    assert summ["drained"]["count"] == 0
    # the device's: the 2 ms of the first dispatch, the 3 ms between
    # program 2's end and program 3's launch cut by the four segments that
    # cover them, and the waiting once program 3 was read
    assert dev["idle_by"] == pytest.approx(
        {"admit": 0.0, "page_alloc": 0.0, "dispatch": 0.002 + 0.001,
         "device_wait": 0.0, "detok": 0.001, "bank": 0.0005,
         "untracked": 0.0005, "between_steps": 0.0, "no_work": 0.020},
        abs=1e-6)
    assert dev["programs"] == tl.dispatch_seq == 3 and dev["unstamped"] == 0
    assert dev["late_stamps"] == 0
    # 1: 0.002-0.010 (decode), 2: 0.010-0.011 (prompt), 3: 0.014-0.0191
    assert dev["busy_by"] == pytest.approx(
        {"decode": 0.008 + 0.0051, "prompt": 0.001}, abs=1e-6)
    # counted from each dispatch's ENTER: 2 ms more for program 1, 1 ms
    # for program 3; program 2 started at its predecessor's end either way
    assert dev["busy_enter_s"] == pytest.approx(dev["busy_s"] + 0.003,
                                                abs=1e-6)
    recs = tl.programs()
    assert [r["ticket"] for r in recs] == [1, 2, 3]
    # a successor launched before the stamp waited for the device, not
    # the device for it
    assert [r["idle_before_s"] for r in recs] == pytest.approx(
        [0.002, 0.0, 0.003], abs=1e-9)
    assert [r["busy_s"] for r in recs] == pytest.approx(
        [0.008, 0.001, 0.0051], abs=1e-9)
    assert [(r["kind"], r["steps"], r["rows"]) for r in recs] == [
        ("decode", 1, 0), ("prompt", 1, 0), ("decode", 1, 0)]
    # the longest gaps, ranked by what is not `no_work`
    worst = dev["idle_worst"]
    assert [w["before_ticket"] for w in worst] == [3, 1]
    assert worst[0]["idle_s"] == pytest.approx(0.003)
    assert worst[0]["by"] == pytest.approx(
        {"detok": 0.001, "bank": 0.0005, "untracked": 0.0005,
         "dispatch": 0.001})
    assert worst[0]["kind"] == "decode"
    assert worst[0]["t_unix_ns"] == int((T0 + 0.011) * 1e9)
    # the histogram behind dynamo_engine_device_idle_seconds: one sample a
    # program
    assert tl.idle_digest.count == 3
    assert tl.idle_digest.sum_s == pytest.approx(0.005)
    # a reading between stamps counts the oldest unstamped program busy
    _play(tl, clock, [("loop", "between_steps", 0.0), ("begin", None, 0.0)]
          + _dispatch("prompt") + [("enter", "detok", 0.004)])
    clock.t += 0.001
    tl.fold()
    mid = tl.summary()["device"]
    assert (mid["programs"], mid["unstamped"]) == (3, 1)
    assert mid["busy_by"]["prompt"] == pytest.approx(0.001 + 0.005, abs=1e-6)
    assert tl.device_totals() == {
        "busy_s": mid["busy_s"], "busy_enter_s": mid["busy_enter_s"],
        "idle_s": mid["idle_s"]}


def test_the_earlier_of_two_stamps_wins_and_the_skew_is_kept(clock):
    tl = StepTimeline(capacity=8, enabled=True)
    # 1: the watcher's stamp (0.0070) is there before the host's wait on it
    # returns (0.0072); 2: the wait returns at 0.0152 and the watcher's
    # stamp, taken at 0.0153, is posted behind it; 3 was long finished
    # when the host's wait on it began, and the wait then sat 2 ms on an
    # implicit program: no measure of the stamp
    _play(tl, clock, [("loop", "between_steps", 0.0), ("begin", None, 0.0)]
          + _dispatch("decode")
          + [("enter", ("device_wait", 1), 0.005), ("stamp", (1, 0.0070), 0.0002),
             ("exit", None, 0.0)]
          + _dispatch("decode", 0.001) + _wait(2, 0.007)
          + [("stamp", (2, 0.0153), 0.0)]
          + _dispatch("decode", 0.001)
          + [("stamp", (3, 0.0170), 0.003), ("enter", "detok", 0.002),
             ("exit", None, 0.0)]
          + _wait(3, 0.002) + [("commit", None, 0.0)])
    dev = _device_conserves(tl.summary())
    assert [r["t_done"] - T0 for r in tl.programs()] == pytest.approx(
        [0.0070, 0.0152, 0.0170], abs=1e-9)
    assert dev["stamp_skew_ms"] == {"p50": 0.1, "p95": 0.1, "count": 2}
    assert sorted(tl._dev.skews) == pytest.approx([-0.0002, 0.0001])


def test_a_stamp_older_than_the_tail(clock, monkeypatch):
    monkeypatch.setattr(timeline_mod, "TAIL_SEGMENTS", 3)
    tl = StepTimeline(capacity=8, enabled=True)
    # program 1 ends at 0.003, in `admit`; five segments close before its
    # stamp is seen, and the tail keeps three: [0.003, 0.005) is older
    _play(tl, clock, [("loop", "between_steps", 0.0), ("begin", None, 0.0)]
          + _dispatch("decode")
          + [("enter", "admit", 0.002), ("exit", None, 0.0),
             ("enter", "page_alloc", 0.001), ("exit", None, 0.0),
             ("enter", "detok", 0.001), ("exit", None, 0.0),
             ("enter", "bank", 0.001), ("exit", None, 0.0),
             ("stamp", (1, 0.003), 0.001), ("commit", None, 0.0)])
    dev = _device_conserves(tl.summary())
    assert dev["late_stamps"] == 1
    assert dev["busy_s"] == pytest.approx(0.001)
    assert dev["idle_by"] == pytest.approx(
        {"admit": 0.0, "page_alloc": 0.0, "dispatch": 0.002,
         "device_wait": 0.0, "detok": 0.001, "bank": 0.001,
         "untracked": 0.002 + 0.001, "between_steps": 0.0, "no_work": 0.0},
        abs=1e-9)


def test_device_reset_forgets_stamps_in_flight(clock):
    tl = StepTimeline(capacity=8, enabled=True)
    _play(tl, clock, [("loop", "between_steps", 0.0), ("begin", None, 0.0)]
          + _dispatch("decode") + [("commit", None, 0.001),
                                   ("stamp", (1, 0.0025), 0.0)])
    old = tl._dev.stamps
    tl.reset()
    assert old and not tl._dev.stamps and not tl._dev.flying
    # the program dispatched before the reset belongs to no account: its
    # wait settles nothing, and the next program starts the count at one
    _play(tl, clock, [("loop", "between_steps", 0.002), ("begin", None, 0.0)]
          + _wait(1, 0.001) + _dispatch("decode") + _wait(2, 0.004)
          + [("commit", None, 0.0), ("loop", "no_work", 0.0)])
    summ = tl.summary()
    dev = _device_conserves(summ)
    assert summ["loop_wall_s"] == pytest.approx(0.009)
    assert dev["programs"] == 1 and tl.programs()[0]["ticket"] == 2
    assert dev["busy_s"] == pytest.approx(0.004)
    assert tl.idle_digest.count == 1


def test_row_idle_goes_to_the_slots_live_across_the_gap(clock):
    tl = StepTimeline(capacity=8, enabled=True)
    # "a" has its first token before the 3 ms gap of _pipelined, "b" after
    script = _pipelined("early")
    at = script.index(("enter", "detok", 0.002))
    script[at:at] = [("first", "a", 0.0)]
    end = script.index(("commit", None, 0.0))
    script[end:end] = [("first", "b", 0.0), ("enter", "detok", 0.001),
                       ("emit", ("a", 1), 0.0), ("emit", ("b", 1), 0.0),
                       ("exit", None, 0.0)]
    _play(tl, clock, script)
    summ = tl.summary()
    dev = _device_conserves(summ)
    assert dev["row_idle_s"] == pytest.approx(0.003, abs=1e-9)
    # beside the causes, not among them: they still sum to the token time
    assert summ["token_time"]["gaps"] == 2
    assert sum(tl.waits["a"].sums) == pytest.approx(0.0091, abs=1e-9)
    # idle once the last program is read is the emitting `detok` here:
    # charged at the NEXT emission of whoever is live then
    _play(tl, clock, [("loop", "between_steps", 0.0), ("begin", None, 0.0),
                      ("enter", "detok", 0.0), ("emit", ("b", 1), 0.0),
                      ("exit", None, 0.0), ("commit", None, 0.0)])
    assert tl.summary()["device"]["row_idle_s"] == pytest.approx(
        0.003 + 0.001 + 0.020, abs=1e-9)
    tl.reset()
    assert tl.summary()["device"]["row_idle_s"] == 0.0


def _gaps(tl, clock, gaps_ms):
    """One synchronous program a gap: the host sits `g` ms in `admit` with
    the device empty, then dispatches and waits."""
    for g in gaps_ms:
        _play(tl, clock, [("begin", None, 0.0),
                          ("enter", "admit", g / 1e3), ("exit", None, 0.0)]
              + _dispatch("decode", 0.0005) + _wait(None, 0.002)
              + [("commit", None, 0.0)])


def test_the_eight_longest_idle_intervals_are_kept(clock):
    tl = StepTimeline(capacity=8, enabled=True)
    tl.loop_state("between_steps")
    _gaps(tl, clock, [3, 9, 1, 7, 5, 11, 2, 8, 6, 10, 4])
    worst = tl.summary()["device"]["idle_worst"]
    assert [round(w["idle_s"] * 1e3, 1) for w in worst] == [
        11.5, 10.5, 9.5, 8.5, 7.5, 6.5, 5.5, 4.5]
    assert worst[0]["by"] == pytest.approx({"admit": 0.011,
                                            "dispatch": 0.0005})
    assert worst[0]["before_ticket"] == 6
    # half a second without a request is long, and nothing a host change
    # could shrink: it is ranked by the rest of its interval
    _play(tl, clock, [("loop", "no_work", 0.5),
                      ("loop", "between_steps", 0.0)])
    _gaps(tl, clock, [5.2])
    worst = tl.summary()["device"]["idle_worst"]
    assert len(worst) == 8
    assert [w["before_ticket"] for w in worst][6:] == [12, 5]
    assert worst[6]["idle_s"] == pytest.approx(0.5057)
    assert worst[6]["by"]["no_work"] == pytest.approx(0.5)
    tl.reset()
    assert tl.summary()["device"]["idle_worst"] == []


def test_merge_summaries_folds_the_device_account(clock):
    a, b = (StepTimeline(capacity=8, enabled=True) for _ in range(2))
    _play(a, clock, _pipelined("early"))
    b.loop_state("between_steps")
    _gaps(b, clock, [4, 2])
    sa, sb = a.summary(), b.summary()
    sb["device"]["stamp_skew_ms"] = {"p50": -0.3, "p95": 0.2, "count": 4}
    old = {"steps": 1, "wall_s": 0.5}  # a worker from before the account
    merged = merge_summaries([sa, sb, old, {}])
    dev = merged["device"]
    for key in ("busy_s", "idle_s", "programs", "unstamped", "row_idle_s",
                "busy_enter_s", "late_stamps"):
        assert dev[key] == pytest.approx(
            sa["device"][key] + sb["device"][key], abs=2e-6), key
    assert dev["programs"] == 5
    for key in IDLE_KEYS:
        assert dev["idle_by"][key] == pytest.approx(
            sa["device"]["idle_by"][key] + sb["device"]["idle_by"][key],
            abs=2e-6)
    assert dev["busy_by"]["prompt"] == pytest.approx(0.001)
    assert [w["idle_s"] for w in dev["idle_worst"]] == pytest.approx(
        [0.0045, 0.003, 0.0025, 0.002])
    assert dev["stamp_skew_ms"] == {"p50": -0.3, "p95": 0.2, "count": 4}


def test_perfetto_draws_the_programs_the_timeline_kept(clock):
    tl = StepTimeline(capacity=8, enabled=True)
    _play(tl, clock, _pipelined("late"))
    events = perfetto_trace(tl)["traceEvents"]
    bars = [e for e in events if e["ph"] == "X" and e["cat"] == "device"]
    assert [b["args"]["ticket"] for b in bars] == [1, 2, 3]
    for before, after in zip(bars, bars[1:]):
        assert before["ts"] + before["dur"] <= after["ts"]
    assert [round(b["dur"]) for b in bars] == [8000, 1000, 5100]
    segs = [e for e in events if e["ph"] == "X" and e["name"] == "dispatch"]
    assert len(segs) == 3
    for bar, seg in zip(bars, segs):
        start, end = [e for e in events if e["ph"] in "sf"
                      and e["id"] == bar["args"]["ticket"]]
        assert seg["ts"] < start["ts"] < seg["ts"] + seg["dur"]
        assert (end["ts"], end["tid"]) == (bar["ts"], bar["tid"])
    payload = timeline_debug_payload(tl, {})
    assert [p["ticket"] for p in payload["programs"]] == [1, 2, 3]


def test_a_handle_that_never_ends_does_not_hold_the_watcher_for_good():
    """A hung program: the watcher sits on its handle. The dispatches
    behind it queue up to WATCH_BACKLOG handles, then get a watcher of
    their own; a handle that raises (a deleted buffer) is stamped as of
    then; close() does not wait for the one that hangs."""
    import threading
    import time

    release = threading.Event()

    class _Handle:
        def __init__(self, how):
            self.how = how

        def devices(self):
            return {0}

        def block_until_ready(self):
            if self.how == "hangs":
                release.wait(30.0)
            elif self.how == "deleted":
                raise RuntimeError("Array has been deleted.")

    tl = StepTimeline(capacity=8, enabled=True)
    tl.begin_step()

    def dispatch(how):
        with tl.phase("dispatch") as ph:
            ph.done_when(_Handle(how))

    def stamped(n, within=5.0):
        deadline = time.monotonic() + within
        while time.monotonic() < deadline:
            tl.fold()
            tl._mark(tl._cur_t, tl._cur_name)
            if tl.summary()["device"]["programs"] >= n:
                return True
            time.sleep(0.01)
        return False

    try:
        dispatch("ready")
        dispatch("deleted")
        assert stamped(2)
        first = tl._dev.thread
        dispatch("hangs")
        while tl._dev._inbox.qsize():  # until the watcher has taken it
            time.sleep(0.001)
        for _ in range(timeline_mod.WATCH_BACKLOG + 1):
            dispatch("ready")
        assert tl._dev.thread is first and first.is_alive()
        assert not stamped(3, within=0.2)
        dispatch("ready")  # one too many behind it: a watcher of its own
        second = tl._dev.thread
        assert second is not first
        # programs run in order: the newest one's stamp settles them all
        assert stamped(tl.dispatch_seq)
        t0 = time.monotonic()
        tl.close()
        assert time.monotonic() - t0 < 3.0 and not second.is_alive()
        assert first.is_alive()
    finally:
        release.set()
    first.join(5.0)
    assert not first.is_alive()


def _service_streams(monkeypatch, enabled):
    """Three greedy requests through an EngineService on the tiny model:
    (streams by request, the engine's timeline, the watcher thread)."""
    import threading

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import Engine
    from dynamo_tpu.engine.request import GenRequest
    from dynamo_tpu.serving.engine_service import EngineService

    monkeypatch.setenv("DYNAMO_TPU_TIMELINE", "1" if enabled else "0")
    eng = Engine(EngineConfig(**KW, num_scheduler_steps=4,
                              prefill_chunk_tokens=8, mixed_batch_tokens=8))
    assert eng.timeline.enabled is enabled
    svc = EngineService(eng)
    watcher = None
    try:
        reqs = [GenRequest(f"d{i}", list(range(1 + i, 4 + 9 * i)),
                           max_tokens=12, temperature=0.0, ignore_eos=True)
                for i in range(3)]
        queues = [svc.submit(r) for r in reqs]
        streams = {r.request_id: [ev.token_id for ev in svc.drain(r, q, 60.0)]
                   for r, q in zip(reqs, queues)}
        watcher = eng.timeline._dev.thread
        if enabled:
            assert watcher is not None and watcher.is_alive()
            assert watcher in threading.enumerate()
    finally:
        svc.close()
    return streams, eng.timeline, watcher


def test_engine_watcher_stamps_every_ticket_and_ends_with_the_loop(
        monkeypatch):
    on, tl, watcher = _service_streams(monkeypatch, True)
    # the loop is closed: the watcher has ended behind its last handle,
    # which it had stamped (close() joined it)
    assert not watcher.is_alive() and tl._dev.thread is None
    tl.loop_state("no_work")  # a boundary: the last stamps are taken up
    summ = tl.summary()
    dev = summ["device"]
    assert tl.dispatch_seq > 6
    assert dev["programs"] == tl.dispatch_seq and dev["unstamped"] == 0
    assert dev["late_stamps"] == 0
    assert dev["busy_s"] > 0.0 and dev["idle_by"]["no_work"] >= 0.0
    assert dev["busy_s"] + dev["idle_s"] == pytest.approx(
        summ["loop_wall_s"], abs=1e-4)
    assert sum(dev["idle_by"].values()) == pytest.approx(dev["idle_s"],
                                                         abs=1e-4)
    assert 0.0 < dev["row_idle_s"] <= 3 * dev["idle_s"]
    kinds = {p["kind"] for p in tl.programs()}
    assert kinds == {"decode", "prompt"}
    assert any(p["steps"] == 4 for p in tl.programs())
    # a disabled timeline starts no thread, and the streams are the same
    off, tl_off, none = _service_streams(monkeypatch, False)
    assert none is None and tl_off.summary()["device"]["programs"] == 0
    assert on == off and all(len(v) == 12 for v in on.values())


# ---------------------------------------------------------------------------
# profiler annotations
# ---------------------------------------------------------------------------
def test_annotations_are_made_only_during_a_capture(clock):
    log = []

    class _Ann:
        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        def __enter__(self):
            log.append(("enter", self.name, self.kw))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    tl = StepTimeline(capacity=8, enabled=True)
    step = _sync_step(0.01) + [("commit", None, 0.001)]
    _play(tl, clock, [("loop", "between_steps", 0.0)] + step)
    assert log == [], "no capture is open: no annotation may be made"
    tl.start_annotations(_Ann, _Ann)
    _play(tl, clock, [("loop", "between_steps", 0.0)] + step)
    names = [e[1] for e in log if e[0] == "enter"]
    assert names == ["stepline/between_steps", "stepline/step",
                     "stepline/untracked", "stepline/dispatch",
                     "stepline/untracked", "stepline/device_wait",
                     "stepline/untracked", "stepline/between_steps"]
    assert ("enter", "stepline/step", {"step_num": 1}) in log
    # a dispatch names the ticket its program gets, a wait the one it is
    # on: what joins the device's operations in the profile to the program
    assert ("enter", "stepline/dispatch",
            {"ticket": 2, "kind": "decode"}) in log
    assert ("enter", "stepline/device_wait",
            {"ticket": 2, "kind": "decode"}) in log
    assert ("enter", "stepline/untracked", {}) in log
    # segments nest inside the step's annotation and never overlap
    depth = 0
    for e in log:
        depth += 1 if e[0] == "enter" else -1
        assert 0 <= depth <= 2
    tl.stop_annotations()
    n = len(log)
    _play(tl, clock, [("loop", "between_steps", 0.0)] + step)
    # the engine thread closes what it had open, and makes no more
    assert log[n:] == [("exit", "stepline/between_steps")]
    assert tl._tracing is False


# ---------------------------------------------------------------------------
# overhead
# ---------------------------------------------------------------------------
def test_timeline_overhead_bounded():
    """The always-on path must stay cheap: a full 6-phase instrumented
    micro-step (no engine, pure bookkeeping) well under 1 ms average."""
    import time

    tl = StepTimeline(capacity=256, enabled=True)
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        tl.begin_step()
        with tl.phase("admit"):
            pass
        with tl.phase("page_alloc"):
            pass
        with tl.phase("dispatch"):
            pass
        with tl.phase("device_wait"):
            pass
        with tl.phase("detok"):
            pass
        with tl.phase("bank"):
            pass
        tl.commit_step(active=1)
    per_step = (time.perf_counter() - t0) / n
    assert tl.steps_total == n
    assert per_step < 1e-3, f"timeline overhead {per_step * 1e6:.1f}us/step"
