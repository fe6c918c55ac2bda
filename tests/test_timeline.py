"""Stepline suite (`make flight-check`, marker `flight`).

Covers observability/timeline.py and its engine + HTTP wiring:

- phase-stack mechanics: pause semantics (nested phases record exclusive
  self-time), disjoint segments, exception unwind, idle-step elision;
- conservation: on a REAL tiny-engine run, every record's phase
  self-times are disjoint, live inside [0, wall], and sum + gap equals
  the step wall time — the invariant the zero-bubble acceptance reads;
- host-gap sampling: every inter-dispatch gap sample is >= 0 (clamped:
  async scheduling dispatches N+1 before materializing N);
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
- profiler annotations exist only between start_ and stop_annotations;
- disabled mode + ring bounds + overhead budget of the on path.
"""

import json

import pytest

from dynamo_tpu.observability import timeline as timeline_mod
from dynamo_tpu.observability.timeline import (
    DRAINED_KEYS,
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
    for g in rec["host_gap"]:
        assert g >= 0.0


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


def test_host_gap_sampled_between_dispatches():
    tl = StepTimeline(capacity=8, enabled=True)
    for _ in range(3):
        tl.begin_step()
        with tl.phase("dispatch"):
            pass
        with tl.phase("device_wait"):
            pass
        tl.commit_step()
    recs = tl.records()
    # first dispatch has no prior device return: no sample; later ones do
    assert recs[0]["host_gap"] == []
    assert len(recs[1]["host_gap"]) == 1
    assert len(recs[2]["host_gap"]) == 1
    assert all(g >= 0.0 for r in recs for g in r["host_gap"])
    assert tl.gap_digest.count == 2
    assert tl.summary()["host_gap"]["count"] == 2


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


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------
class _StubTimeline:
    def __init__(self, recs):
        self._recs = recs

    def records(self, n=None):
        return self._recs[-n:] if n else list(self._recs)


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
            "host_gap": [],
        },
        {
            "seq": 1,
            "t0_unix_ns": _BASE_NS + 10_000_000,
            "wall_s": 0.008,
            "phases": {"dispatch": 0.006, "detok": 0.001},
            "segs": [("dispatch", 0.0, 0.006), ("detok", 0.006, 0.007)],
            "gap_s": 0.001,
            "host_gap": [0.0005],
        },
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
    trace = perfetto_trace(_StubTimeline(_stub_records()),
                           collector=_stub_collector(), steps=128)
    # deterministic, JSON-round-trippable
    blob = json.dumps(trace, sort_keys=True)
    assert json.loads(blob) == trace
    assert trace["displayTimeUnit"] == "ms"
    events = trace["traceEvents"]
    for ev in events:
        assert ev["ph"] in ("M", "i", "X")
        assert isinstance(ev["name"], str)
        if ev["ph"] != "M":
            assert isinstance(ev["ts"], float)
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0
        if ev["ph"] == "i":
            assert ev["s"] == "t"
    # every phase segment exports as a complete event on the engine track
    engine_x = [e for e in events if e["ph"] == "X" and e["pid"] == 1]
    assert [e["name"] for e in engine_x] == [
        "admit", "dispatch", "device_wait", "dispatch", "detok"]
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
    assert s["steps"] == 1 and "phases" in s and "host_gap" in s
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
            "host_gap": {"count": 5, "total_s": gap_s, "p50_ms": 1.0,
                         "p95_ms": p95, "share": gap_s / wall},
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
    hg = merged["host_gap"]
    assert hg["count"] == 10 and hg["p95_ms_max"] == 9.0
    assert abs(hg["total_s"] - 0.15) < 1e-9
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
class _Clock:
    """Stands in for the `time` module inside observability/timeline.py."""

    def __init__(self):
        self.t = 100.0

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
    (`enter` takes a name or (name, upto)), `idle` is time inside a step
    that no phase claims. The clock moves by `seconds` AFTER each op, so
    that is how long the segment the op opened lasts."""
    for op, arg, seconds in script:
        if op == "loop":
            tl.loop_state(arg)
        elif op == "begin":
            tl.begin_step()
        elif op == "commit":
            tl.commit_step()
        elif op == "enter":
            name, upto = arg if isinstance(arg, tuple) else (arg, None)
            tl._enter(name, upto)
        elif op == "exit":
            tl._exit()
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
