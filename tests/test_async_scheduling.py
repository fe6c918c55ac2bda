"""Async (pipelined) decode scheduling: output parity with synchronous mode
across stops, sampling, aborts, chunked admissions, and disagg imports."""

import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.engine.request import GenRequest


def _mk(async_sched, **kw):
    base = dict(model="tiny-debug", page_size=4, num_pages=128,
                max_num_seqs=4, max_seq_len=128, num_scheduler_steps=4,
                async_scheduling=async_sched)
    base.update(kw)
    return Engine(EngineConfig(**base))


def _run_all(eng, reqs):
    out = {r.request_id: [] for r in reqs}
    for r in reqs:
        eng.add_request(r)
    while eng.has_work:
        for ev in eng.step():
            if ev.token_id >= 0:
                out[ev.request_id].append(ev.token_id)
    return out


def _reqs():
    return [
        GenRequest("a", [1, 2, 3], max_tokens=17, temperature=0.0,
                   ignore_eos=True),
        GenRequest("b", [4, 5, 6, 7, 8, 9], max_tokens=5, temperature=0.0,
                   ignore_eos=True),
        GenRequest("c", [7, 8], max_tokens=11, temperature=0.9, seed=3,
                   ignore_eos=True),
    ]


def test_async_matches_sync_mixed_lengths():
    ref = _run_all(_mk(False), _reqs())
    out = _run_all(_mk(True), _reqs())
    assert out == ref


def test_async_matches_sync_eos_stops():
    # temperature sampling WITHOUT ignore_eos: stops at arbitrary steps
    reqs = [GenRequest(f"r{i}", [i + 1, i + 2], max_tokens=40,
                       temperature=1.2, seed=i) for i in range(4)]
    ref = _run_all(_mk(False), [GenRequest(f"r{i}", [i + 1, i + 2],
                                           max_tokens=40, temperature=1.2,
                                           seed=i) for i in range(4)])
    out = _run_all(_mk(True), reqs)
    assert out == ref


def test_async_abort_mid_pipeline():
    eng = _mk(True)
    eng.add_request(GenRequest("x", [1, 2, 3], max_tokens=64,
                               temperature=0.0, ignore_eos=True))
    for _ in range(3):
        eng.step()
    eng.abort_request("x")
    evs = []
    while eng.has_work:
        evs.extend(eng.step())
    assert any(e.request_id == "x" and e.finish_reason == "abort"
               for e in evs)
    assert eng.allocator.free_pages == eng.cfg.num_pages - 1


def test_async_with_chunked_admission_mid_decode():
    ref = None
    for mode in (False, True):
        eng = _mk(mode, prefill_chunk_tokens=8)
        eng.add_request(GenRequest("live", [1, 2, 3], max_tokens=30,
                                   temperature=0.0, ignore_eos=True))
        out = {"live": [], "long": []}

        def drain(evs):
            for ev in evs:
                if ev.token_id >= 0:
                    out[ev.request_id].append(ev.token_id)

        for _ in range(2):
            drain(eng.step())
        eng.add_request(GenRequest(
            "long", [(i * 5) % 200 + 1 for i in range(40)], max_tokens=6,
            temperature=0.0, ignore_eos=True))
        while eng.has_work:
            drain(eng.step())
        if ref is None:
            ref = out
        else:
            assert out == ref


def test_async_disagg_import_mid_pipeline():
    """import_kv from an HTTP thread between steps (the side-door membership
    change) must not corrupt the in-flight window's readback."""
    kw = dict(model="tiny-debug", page_size=4, num_pages=128, max_num_seqs=4,
              max_seq_len=128, num_scheduler_steps=4, seed=9)
    pre = Engine(EngineConfig(disaggregation_mode="prefill", **kw))
    ref_eng = Engine(EngineConfig(async_scheduling=False, **kw))
    dec = Engine(EngineConfig(disaggregation_mode="decode",
                              async_scheduling=True, **kw))

    live = GenRequest("live", [1, 2, 3], max_tokens=20, temperature=0.0,
                      ignore_eos=True)
    dec.add_request(GenRequest("live", [1, 2, 3], max_tokens=20,
                               temperature=0.0, ignore_eos=True))
    out = {"live": [], "imp": []}

    def drain(evs):
        for ev in evs:
            if ev.token_id >= 0:
                out[ev.request_id].append(ev.token_id)

    for _ in range(3):
        drain(dec.step())

    imp = GenRequest("imp", [5, 6, 7, 8], max_tokens=10, temperature=0.0,
                     ignore_eos=True)
    first, _, _ = pre.prefill_only(imp)
    k, v, _ = pre.export_kv_device(imp.request_id)
    finished, _ = dec.import_kv(imp, first, k, v)
    assert not finished
    out["imp"].append(first)
    while dec.has_work:
        drain(dec.step())

    ref = {}
    ref["live"] = ref_eng.generate(GenRequest(
        "live", [1, 2, 3], max_tokens=20, temperature=0.0, ignore_eos=True))
    ref["imp"] = ref_eng.generate(GenRequest(
        "imp", [5, 6, 7, 8], max_tokens=10, temperature=0.0,
        ignore_eos=True))
    assert out == ref


# --- mixed steps ride the pipeline (dispatched behind the program in flight,
# read one program late): the synchronous order is the oracle ---

MIXED = dict(prefill_chunk_tokens=8, mixed_batch_tokens=8,
             enable_prefix_caching=False)
LONG = [(i * 5) % 200 + 1 for i in range(22)]  # three chunks of 8


@pytest.fixture(scope="module")
def pair():
    """(synchronous, pipelined) engines, shared: a test leaves both idle."""
    return _mk(False, **MIXED), _mk(True, **MIXED)


def _drive(eng, script, probe=None):
    """Step `eng` until idle; `script` maps a step number to a callable run
    before that step (arrivals). Per request: tokens, the finish reason and
    the chosen logprobs. `probe(eng)` runs after every step."""
    eng.reset_metrics()
    out = {}
    n = 0
    while eng.has_work or any(k >= n for k in script):
        if n in script:
            script[n](eng)
        for ev in eng.step():
            rec = out.setdefault(ev.request_id,
                                 {"tokens": [], "finish": None, "lp": []})
            if ev.token_id >= 0:
                rec["tokens"].append(ev.token_id)
                if ev.logprob is not None:
                    rec["lp"].append(ev.logprob)
            if ev.finished:
                rec["finish"] = ev.finish_reason
        if probe is not None:
            probe(eng)
        n += 1
    assert eng.allocator.free_pages == eng.cfg.num_pages - 1
    assert len(eng._free_slots) == eng.cfg.max_num_seqs
    return out


def _add(*reqs):
    return lambda eng: [eng.add_request(r) for r in reqs]


def _live(rid="live", n=40, **kw):
    kw.setdefault("temperature", 0.0)
    return GenRequest(rid, [1, 2, 3], max_tokens=n, ignore_eos=True, **kw)


def _long(n=6, **kw):
    kw.setdefault("temperature", 0.0)
    return GenRequest("long", LONG, max_tokens=n, ignore_eos=True, **kw)


def _same(out, ref):
    assert out.keys() == ref.keys()
    for rid in ref:
        assert out[rid]["tokens"] == ref[rid]["tokens"], rid
        assert out[rid]["finish"] == ref[rid]["finish"], rid
        assert out[rid]["lp"] == pytest.approx(ref[rid]["lp"], abs=1e-4), rid


def _three_chunks():
    return {0: _add(_live(logprobs=2)), 3: _add(_long(logprobs=2))}


def _one_chunk():
    return {0: _add(_live()),
            3: _add(GenRequest("short", LONG[:7], max_tokens=6,
                               temperature=0.0, ignore_eos=True))}


def _seeded_sampling():
    return {0: _add(_live(temperature=0.9, seed=5, logprobs=1),
                    _live("two", 30, temperature=1.1, seed=6)),
            3: _add(_long(9, temperature=0.8, seed=7, logprobs=1))}


def _two_live():
    return {0: _add(_live(), _live("two", 30)), 3: _add(_long())}


@pytest.mark.parametrize("case,usable", [
    (_three_chunks, None), (_one_chunk, None), (_seeded_sampling, None),
    # two live rows' pages and the prompt's 6 do not fit: a mixed step finds
    # no page behind the program in flight and drains; a row is preempted
    # and recomputed (15 usable pages) or ends kv_oom (13), in both orders
    (_two_live, 15), (_two_live, 13)],
    ids=["three_chunks", "one_chunk", "seeded_sampling", "page_shortage",
         "page_shortage_kv_oom"])
def test_mixed_steps_behind_the_pipeline_match_sync(pair, case, usable):
    ref_eng, eng = pair if usable is None else (
        _mk(a, **MIXED, num_pages=usable + 1) for a in (False, True))
    ref = _drive(ref_eng, case())
    refused = []
    grow = eng._grow_pages

    def watched(window, events, offset=0, allow_kill=True):
        got = grow(window, events, offset=offset, allow_kill=allow_kill)
        if got == 0 and eng._mixed_eligible():
            refused.append(offset)
        return got

    eng._grow_pages = watched
    try:
        _same(_drive(eng, case()), ref)
    finally:
        del eng._grow_pages
    m, ref_m = eng.metrics, ref_eng.metrics
    assert m.mixed_count == ref_m.mixed_count > 0
    assert (m.kv_oom, m.num_preempted) == (ref_m.kv_oom, ref_m.num_preempted)
    assert ref_m.mixed_behind == 0
    if usable is None:
        assert not refused and m.mixed_behind == m.mixed_count
    else:
        assert refused  # _grow_pages -> 0 behind a program: drained first
        assert m.kv_oom == (usable == 13) and m.num_preempted > 0


def test_an_eos_found_behind_a_mixed_step_drains_it(pair):
    """`stopper` stops on a token that the window in flight at a mixed
    step's dispatch holds: the step is read in the same step() (the freed
    pages are its to touch), and every stream is the synchronous order's."""
    ref_eng, eng = pair

    def script(stop):
        return {0: _add(_live(), GenRequest(
                    "stopper", [4, 5, 6], max_tokens=40, temperature=1.3,
                    seed=11, stop_token_ids=stop)),
                3: _add(_long())}

    free_run = _drive(ref_eng, script([]))["stopper"]["tokens"]
    read = eng._materialize_window
    seen, left = [], []

    def watched(pw):
        behind = eng._pending_win
        evs = read(pw)
        if (behind is not pw and behind is not None
                and behind.chunk is not None
                and any(e.finished for e in evs)):
            seen.append(behind)
        return evs

    eng._materialize_window = watched
    try:
        for k in range(8, 24):  # the stream's k-th token as the stop token
            stop = [free_run[k]]
            if seen or free_run.index(stop[0]) != k:
                continue
            out = _drive(eng, script(stop),
                         probe=lambda e: seen and left.append(e._pending_win))
            _same(out, _drive(ref_eng, script(stop)))
            assert out["stopper"]["finish"] == "stop"
    finally:
        del eng._materialize_window
    assert seen, "no stop token fell in the program behind a mixed step"
    assert left[0] is None  # the mixed step was read before step() returned


@pytest.mark.parametrize("victim", ["long", "live"])
def test_abort_while_a_mixed_step_is_in_flight(pair, victim):
    ref_eng, eng = pair
    ref = _drive(ref_eng, _two_live())
    aborted = []

    def probe(e):
        pw = e._pending_win
        if not aborted and pw is not None and pw.chunk is not None:
            e.abort_request(victim)  # its chunk / its row is on the device
            aborted.append(pw)

    out = _drive(eng, _two_live(), probe=probe)
    assert aborted and out[victim]["finish"] == "abort"
    n = len(out[victim]["tokens"])
    assert out[victim]["tokens"] == ref[victim]["tokens"][:n]
    for rid in ref:
        if rid != victim:
            assert out[rid]["tokens"] == ref[rid]["tokens"], rid


def _no_mixed_step_in_flight(eng):
    pw = eng._pending_win
    assert pw is None or pw.chunk is None


def test_what_keeps_the_drained_order_is_a_property_of_the_step(pair):
    """async_scheduling off, and a verify (its drafts need the newest
    tokens on the host), read every mixed step at once."""
    spec = _mk(True, **MIXED, speculative_mode="ngram",
               num_speculative_tokens=2)
    for eng in (pair[0], spec):
        _drive(eng, _three_chunks(), probe=_no_mixed_step_in_flight)
        assert eng.metrics.mixed_count == 3
        assert eng.metrics.mixed_behind == 0
