"""Async (pipelined) decode scheduling: output parity with synchronous mode
across stops, sampling, aborts, chunked admissions, and disagg imports."""

import itertools

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.engine.kv_cache import SeqState
from dynamo_tpu.engine.request import GenRequest

from dynamo_tpu.robustness import faults

from pipelined_common import (
    assert_finish_rides_pipeline, assert_first_token_rides_pipeline,
    assert_windows_as_long_as_the_shortest_headroom, drive, same_streams)


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
    """pipelined_common.drive on an engine without a prefix cache: every
    page and every slot is free again when it is idle."""
    out = drive(eng, script, probe)
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


_same = same_streams


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

    def watched(window, events, offset=0, **kw):
        got = grow(window, events, offset=offset, **kw)
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


def test_an_eos_found_behind_a_mixed_step_leaves_it_in_flight(pair):
    """`stopper` stops on a token that the window in flight at a mixed
    step's dispatch holds. The finish is found when that window is read,
    with the mixed step already on the device over the stopper's row: the
    step STAYS in flight past the end of its step() (unless it carries
    the prompt's final chunk), the stopper's pages and its slot wait for
    it, and every stream is the synchronous order's."""
    ref_eng, eng = pair

    def script(stop, at=3):
        return {0: _add(_live(), GenRequest(
                    "stopper", [4, 5, 6], max_tokens=40, temperature=1.3,
                    seed=11, stop_token_ids=stop)),
                at: _add(_long())}

    free_run = _drive(ref_eng, script([]))["stopper"]["tokens"]
    read = eng._materialize_window
    seen, left = [], []

    def watched(pw):
        behind = eng._pending_win
        slot = next((s for s, q in eng.seqs.items()
                     if q.request_id == "stopper"), None)
        evs = read(pw)
        if (behind is not pw and behind is not None
                and behind.chunk is not None
                and sum(behind.chunk) < len(LONG)
                and any(e.finished and e.request_id == "stopper"
                        for e in evs)):
            seen.append((behind, slot))
        return evs

    def probe(e):
        if seen and not left:
            left.append((e._pending_win, list(e._held),
                         list(e._free_slots), e.allocator.free_pages))

    eng._materialize_window = watched
    try:
        # the stream's k-th token as the stop token, the prompt arriving
        # before step `at`: some pair puts the stop in the one-token
        # program that a non-final mixed step is dispatched behind
        for at, k in itertools.product((3, 4, 5, 6), range(8, 24)):
            stop = [free_run[k]]
            if seen or free_run.index(stop[0]) != k:
                continue
            free = eng.allocator.free_pages
            out = _drive(eng, script(stop, at), probe=probe)
            _same(out, _drive(ref_eng, script(stop, at)))
            assert out["stopper"]["finish"] == "stop"
    finally:
        del eng._materialize_window
    assert seen, "no stop token fell in the program behind a mixed step"
    (step, slot), (pending, held, free_slots, free_then) = seen[0], left[0]
    assert pending is step  # still unread when step() returned
    (ticket, pages, rid, held_slot), = held
    assert (ticket, rid, held_slot) == (step.ticket, "stopper", slot)
    assert slot in step.slots and slot not in free_slots
    # live's pages, the prompt's six and the stopper's are all still out
    assert free_then <= free - len(pages) - 6
    assert eng.metrics.held_pages_peak == len(pages) > 0
    assert eng.metrics.finishes_behind >= 1


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
    """async_scheduling off, a verify (its drafts need the newest tokens
    on the host) and enforce_eager read every mixed step at once and
    apply every finish with nothing in flight."""
    spec = _mk(True, **MIXED, speculative_mode="ngram",
               num_speculative_tokens=2)
    eager = _mk(True, **MIXED, enforce_eager=True)
    for eng in (pair[0], spec, eager):
        _drive(eng, _three_chunks(), probe=_no_mixed_step_in_flight)
        assert eng.metrics.mixed_count == 3
        assert eng.metrics.mixed_behind == 0
        assert (eng.metrics.num_finished, eng.metrics.finishes_behind,
                eng.metrics.held_pages_peak) == (2, 0, 0)


@pytest.mark.parametrize("exit_", ["abort", "preempt", "kv_oom",
                                   "integrity_fault"])
def test_an_exit_that_is_no_plain_finish_keeps_the_drained_order(pair, exit_):
    """What the exit IS decides: an abort, a preemption, kv_oom and an
    integrity fault are applied with nothing left in flight and nothing
    held back, and count in no `finishes_behind`; the streams are the
    synchronous order's."""
    kw = dict(MIXED)
    if exit_ in ("preempt", "kv_oom"):
        kw["num_pages"] = {"preempt": 15, "kv_oom": 13}[exit_] + 1
    ref_eng, eng = pair if exit_ not in ("preempt", "kv_oom") else (
        _mk(a, **kw) for a in (False, True))
    script = _two_live()
    state = {}

    def probe(e):
        if exit_ == "abort" and not state and e._pending_win is not None \
                and len(e.seqs) == 2:
            e.abort_request("two")
            state["at"] = e.metrics.finishes_behind
        if exit_ == "integrity_fault" and not state and len(e.seqs) == 2 \
                and e._pending_win is not None:
            # the next readback carries a token id outside the vocabulary
            pw = e._pending_win
            bad = pw.ys[0].at[0, pw.slots[-1]].set(-7)
            e._pending_win = pw._replace(ys=(bad, *pw.ys[1:]))
            state["at"] = e.metrics.finishes_behind
        assert not e._held and not e._leaving

    was = eng.integrity
    if exit_ == "integrity_fault":
        eng.integrity = "on"
    try:
        out = _drive(eng, script, probe=probe)
    finally:
        eng.integrity = was
    m = eng.metrics
    if exit_ in ("abort", "integrity_fault"):
        victim = next(r for r, rec in out.items() if rec["finish"] == exit_)
        ref = _drive(ref_eng, script)
        for rid in ref:
            n = len(out[rid]["tokens"])
            assert out[rid]["tokens"] == ref[rid]["tokens"][:n], rid
            assert rid == victim or n == len(ref[rid]["tokens"])
    else:
        _same(out, _drive(ref_eng, script))
    ref_m = ref_eng.metrics
    if exit_ == "preempt":
        assert m.num_preempted == ref_m.num_preempted > 0
    if exit_ == "kv_oom":
        assert m.kv_oom == ref_m.kv_oom == 1
    # every plain finish beside the exit still rode; the exit itself never
    plain = sum(rec["finish"] in ("stop", "length") for rec in out.values())
    assert m.finishes_behind <= plain
    assert m.held_pages_peak == 0


# --- a finish rides the pipeline: retired in the device carry behind the
# program in flight, pages and slot held back where that program still
# touches them ---

@pytest.mark.parametrize("model,kw", [
    ("tiny-debug", {}),
    ("tiny-moe-debug", {}),
    ("tiny-mla-debug", {}),
    ("tiny-kimi-ep4-debug", dict(dtype="float32")),
    ("tiny-debug", dict(lora_slots=2)),
], ids=["dense", "moe", "mla", "mla_grouped_experts", "lora_on"])
def test_a_finish_rides_the_pipeline(pair, model, kw):
    """Dense, every-expert MoE, MLA, and MLA under grouped expert matmuls
    (whose layers mask the rows of an empty slot), also with the LoRA
    operand riding: assert_finish_rides_pipeline's whole contract."""
    sync, eng = pair if (model, kw) == ("tiny-debug", {}) else (
        _mk(a, **MIXED, model=model, **kw) for a in (False, True))
    assert_finish_rides_pipeline(
        sync, eng, lambda i: [(7 * j + 11 * i) % 90 + 1 for j in range(5 + i)])


def _stop_at(ref_eng, lo=8):
    """`stopper`'s request, stopping on the first token from its `lo`-th
    on that its free-running stream has not shown before."""
    mk = lambda stop: GenRequest("stopper", [4, 5, 6], max_tokens=40,  # noqa: E731
                                 temperature=1.3, seed=11, ignore_eos=True,
                                 stop_token_ids=stop)
    free_run = ref_eng.generate(mk([]))
    k = next(k for k in range(lo, 40) if free_run.index(free_run[k]) == k)
    return mk([free_run[k]]), free_run[:k + 1]


@pytest.mark.parametrize("short", ["slots", "pages"])
def test_what_is_held_is_not_handed_out_before_its_program_is_read(short):
    """A stop token found at the read leaves the stopper's pages and its
    decode slot held for the program in flight. A request that arrives at
    that moment and needs exactly what is held (every slot taken; a pool
    one page short of its prompt) waits out the step that reads that
    program, is admitted by the next, and `PageAllocator.alloc` never
    returns a held page."""
    kw = dict(MIXED, num_pages=24, max_num_seqs=2 if short == "slots" else 4)
    ref_eng, eng = _mk(False, **kw), _mk(True, **kw)
    stopper, want = _stop_at(ref_eng)
    alloc = eng.allocator.alloc
    handed = []

    def watched(n):
        pages = alloc(n)
        handed.append((set(pages), {p for _, ps, _, _ in eng._held
                                    for p in ps}))
        return pages

    eng.allocator.alloc = watched
    seen = {}

    def probe(e):
        if e._held and not seen:
            (ticket, pages, rid, slot), = e._held
            assert rid == "stopper" and e._pending_win.ticket == ticket
            assert slot not in e._free_slots and slot not in e.seqs
            free = e.allocator.free_pages
            # the whole free list, handed out and given back: no held page
            e.allocator.free(e.allocator.alloc(free))
            if short == "slots":
                assert not e._free_slots
                n_prompt = 5
            else:
                assert e._free_slots
                n_prompt = 4 * (free + 1)  # one page more than is free
                assert len(pages) >= 1
            late = GenRequest("late", [(3 * i) % 50 + 1
                                       for i in range(n_prompt)],
                              max_tokens=4, temperature=0.0, ignore_eos=True)
            e.add_request(late)
            seen.update(late=late, ticket=ticket, step=0)
        elif seen and seen["step"] == 0:
            # the step that READ the held ticket: it admitted nothing
            seen["step"] = 1
            assert [r.request_id for r in e.pending] == ["late"]
            assert e._inflight is None and not e._held
            assert e.timeline.dispatch_seq > seen["ticket"]
        elif seen and seen["step"] == 1:
            seen["step"] = 2
            assert not e.pending  # given the slot / the pages now

    out = _drive(eng, {0: _add(_live(n=30), stopper)}, probe=probe)
    assert seen.get("step") == 2
    assert out["stopper"]["tokens"] == want
    assert out["stopper"]["finish"] == "stop"
    assert out["live"]["tokens"] == ref_eng.generate(_live(n=30))
    assert out["late"]["tokens"] == ref_eng.generate(seen["late"])
    assert all(not got & held for got, held in handed)
    assert any(held for _, held in handed)
    assert eng.metrics.finishes_behind >= 1


def test_six_finishes_counted_ahead_open_no_drained_interval():
    """An anchor decodes 100 tokens while six sequences of 20, 29, ... 65
    tokens end beside it. PR 48's tree counted `timeline.drained.count`
    17 over this script: every finish read the program in flight early,
    then ran one synchronous step, so two dispatches found the device
    empty; the anchor alone counts 1. With the six leaving behind the
    program in flight it was 17 - 2 x 6 = 5 (the first dispatch, and
    those after a first token was installed); now the three prompts that
    end beside decoding rows join behind their final chunk's program
    too, and what is left is the busy spell's first dispatch and the one
    after the grouped prefill of the first four."""
    eng = _mk(True, **MIXED, max_num_seqs=8)

    def run(n):
        eng.reset_metrics()
        eng.add_request(_live("anchor", 100))
        for i in range(n):
            eng.add_request(GenRequest(f"s{i}", [4 + i, 5, 6],
                                       max_tokens=20 + 9 * i,
                                       temperature=0.0, ignore_eos=True))
        while eng.has_work:
            eng.step()
        m = eng.metrics
        return (eng.timeline.drained_count, m.num_finished,
                m.finishes_behind, m.first_tokens_behind)

    assert run(0) == (1, 1, 0, 0)
    assert run(6) == (2, 7, 6, 3)
    assert eng.timeline.summary()["drained"]["count"] == 2


def test_a_finished_top_k_and_logit_bias_row_closes_the_samplers_gates():
    """The tiered sampler's gates read the whole [B] arrays
    (engine/sampling.py: `needs_mask` = any top_k > 0 or top_p < 1,
    `any_bias` = any bias id >= 0). A sampled request with top-k, top-p
    and a logit bias ends beside a greedy one: the arrays the NEXT program
    is handed, on the device, are all defaults again, and the active mask
    holds the greedy row alone."""
    eng = _mk(True, **MIXED)
    eng.add_request(_live(n=40))
    eng.add_request(GenRequest("picky", [9, 8, 7], max_tokens=9,
                               temperature=0.8, top_k=5, top_p=0.9, seed=4,
                               logit_bias={17: 4.0}, ignore_eos=True))
    open_, closed = [], []
    while eng.has_work:
        eng.step()
        if eng._dev_sampling is None or eng._dev_state is None:
            continue
        temp, top_p, top_k, *_, bias_ids, _, _ = (
            np.asarray(a) for a in eng._dev_sampling)
        gates = (bool(((top_k > 0) | (top_p < 1.0)).any()),
                 bool((bias_ids >= 0).any()), bool((temp > 0).any()))
        if len(eng.seqs) == 2:
            open_.append(gates)
        elif eng.metrics.finishes_behind == 1 and eng._pending_win:
            mask = np.asarray(eng._dev_state[3]).tolist()
            closed.append((gates, mask == [s in eng.seqs for s in range(4)]))
    assert open_ and all(g == (True, True, True) for g in open_)
    assert closed and all(g == (False, False, False) and mask_ok
                          for g, mask_ok in closed)
    assert eng.metrics.num_finished == 2 and eng.metrics.finishes_behind == 1


def test_a_guided_batchs_finishes_ride_the_pipeline(pair):
    """Guided sequences end beside a plain one, by `max_tokens` and on the
    EOS their grammar allows once the object is closed (found at the
    read): the grammar carry of those that stay lives on the device
    through both, because a retirement uploads the active mask and the
    guided window masks its automaton with `gactive & active`. Streams
    and finish reasons are the synchronous order's."""
    ref_eng, eng = pair

    def script():
        return {0: _add(
            _live("plain", 60),
            GenRequest("g1", [9, 8, 7], max_tokens=12, temperature=0.0,
                       guided_json=True),
            GenRequest("g2", [5, 8, 7, 1], max_tokens=40, temperature=1.1,
                       seed=4, guided_json=True),
            GenRequest("g3", [5, 8, 2, 1], max_tokens=25, temperature=1.3,
                       seed=5, guided_json=True))}

    held = []
    out = _drive(eng, script(), probe=lambda e: held.append(len(e._held)))
    _same(out, _drive(ref_eng, script()))
    reasons = {rid: rec["finish"] for rid, rec in out.items()}
    assert reasons["g1"] == "length" and "stop" in reasons.values()
    m = eng.metrics
    assert (m.num_finished, m.finishes_behind) == (4, 3)
    assert any(held) and m.held_pages_peak > 0  # the EOS: one program late


# --- a window as long as the shortest headroom: the fused program takes
# its trip count as an operand, so a sequence's last tokens cost one short
# window; num_scheduler_steps=1 (a classic program a step) is the oracle ---

@pytest.mark.parametrize("model,kw,short", [
    ("tiny-debug", dict(num_scheduler_steps=16), None),
    ("tiny-debug", dict(num_scheduler_steps=16), 16),
    ("tiny-kimi-ep4-debug", dict(dtype="float32"), None),
    ("tiny-debug", dict(lora_slots=2), None),
], ids=["dense_16_steps", "dense_16_steps_every_trip_count",
        "mla_grouped_experts", "lora_on"])
def test_windows_of_every_length_give_the_single_steps_tokens(model, kw,
                                                              short):
    """Dense at the cells' 16 steps (rows end at every offset 1 .. 17 of a
    window: windows of 1 .. 4 and 16 steps as the scheduler hands them
    out, and once with the short window's bound lifted, so that the
    program runs at EVERY trip count 1 .. 16), MLA under grouped expert
    matmuls (whose counts the loop sums in its carry), and the LoRA
    operand riding beside the trip count; the state-slot, ring and DSA
    models in their own engine tests."""
    kw = dict(MIXED, model=model, **kw)
    single = _mk(False, **dict(kw, num_scheduler_steps=1))
    engines = [_mk(False, **kw), _mk(True, **kw)]
    for eng in engines:
        if short is not None:
            eng.SHORT_WINDOW_STEPS = short
    assert_windows_as_long_as_the_shortest_headroom(
        single, engines, lambda i: _prompt(i, 5 + i % 3))


def _rows(eng, monkeypatch, headrooms, prompt_len=6):
    """`eng.seqs` set to one sequence a slot with `headrooms[slot]` tokens
    left by max_tokens (far from max_seq_len and the table's end)."""
    seqs = {}
    for slot, room in enumerate(headrooms):
        seq = SeqState(f"r{slot}", slot, [], prompt_len, max_tokens=room + 1)
        seq.output_tokens = [1]
        seq.num_tokens = prompt_len
        seqs[slot] = seq
    monkeypatch.setattr(eng, "seqs", seqs)


@pytest.mark.parametrize("headrooms,extra,skip,want", [
    ((40, 30, 9), 0, (), 4),  # everybody has a full window left
    ((40, 3, 9), 0, (), 3),  # the shortest headroom
    ((40, 3, 2), 0, (), 2),
    ((40, 1, 9), 0, (), 1),  # one token left: the classic program
    ((40, 7, 9), 4, (), 3),  # behind a program in flight: headroom - lag
    ((40, 5, 9), 4, (), 1),
    ((40, 4, 9), 4, (), 0),  # no step fits on top of the program in flight
    ((40, 4, 9), 4, (1,), 4),  # a leaver is priced out: min over the rest
    ((40, 4, 6), 4, (1,), 2),
    ((3, 4, 2), 4, (0, 1, 2), 0),  # nobody stays to run it
    ((), 0, (), 1),  # nobody live
], ids=["full", "shortest_3", "shortest_2", "one_left", "behind_lag",
        "behind_lag_one", "behind_lag_none", "skip_leaver",
        "skip_leaver_short", "all_leave", "nobody_live"])
def test_window_steps_is_the_shortest_headroom_of_the_rows_that_stay(
        pair, monkeypatch, headrooms, extra, skip, want):
    """`_window_steps` = min(num_scheduler_steps, shortest headroom - extra)
    over the rows that stay, 0 where not even one step fits."""
    eng = pair[1]
    assert eng.cfg.num_scheduler_steps == 4 and not eng.has_work
    _rows(eng, monkeypatch, headrooms)
    assert eng._window_steps(extra=extra, skip=skip) == want


@pytest.mark.parametrize("headrooms,extra,skip,want", [
    ((40, 30, 16), 0, (), 16),  # a full window fits
    ((40, 30, 15), 0, (), 4),  # it no longer does: the short window
    ((40, 5, 15), 0, (), 4),
    ((40, 3, 15), 0, (), 3),  # the shortest headroom under it
    ((40, 36, 32), 16, (), 16),  # behind a full window in flight
    ((40, 36, 31), 16, (), 4),
    ((40, 18, 31), 16, (), 2),
    ((40, 16, 31), 16, (1,), 4),  # the leaver priced out
    ((40, 16, 32), 16, (1,), 16),
], ids=["full", "just_under", "under", "shortest_3", "behind_full",
        "behind_just_under", "behind_shortest_2", "skip_leaver_short",
        "skip_leaver_full"])
def test_a_window_under_a_full_ones_headroom_is_a_short_one(
        pair, monkeypatch, headrooms, extra, skip, want):
    """At the cells' 16 steps: a full window where every row that stays
    has 16 tokens of headroom on top of the program in flight, else the
    shortest headroom, SHORT_WINDOW_STEPS (4) at most: an arrival waits
    out two programs, and they are 8 steps where the headroom's own
    length would make them up to 30."""
    eng = pair[1]
    assert eng.SHORT_WINDOW_STEPS == 4 and not eng.has_work
    monkeypatch.setattr(eng.cfg, "num_scheduler_steps", 16)
    _rows(eng, monkeypatch, headrooms)
    assert eng._window_steps(extra=extra, skip=skip) == want


@pytest.mark.parametrize("small", ["pending", "chunk_rides_next_step",
                                   "one_scheduler_step"])
def test_window_steps_stays_one_step_in_every_small_case(pair, monkeypatch,
                                                         small):
    """Something pending, a prompt whose chunk rides the next mixed step,
    num_scheduler_steps <= 1: one step exactly as before, whatever the
    headrooms; 0 still where no step fits."""
    eng = pair[1]
    _rows(eng, monkeypatch, (40, 3, 9))
    assert eng._window_steps() == 3
    if small == "pending":
        monkeypatch.setattr(eng, "pending", [_live()])
    elif small == "chunk_rides_next_step":
        monkeypatch.setattr(eng, "_inflight", object())
        assert eng._mixed_eligible()
    else:
        monkeypatch.setattr(eng.cfg, "num_scheduler_steps", 1)
    assert eng._window_steps() == 1
    assert eng._window_steps(extra=2) == 1
    assert eng._window_steps(extra=3) == 0


def test_a_row_that_ends_on_a_short_windows_last_step_is_a_leaver(pair):
    """`short` has 3 decode steps left when the batch is whole: the window
    is 3 steps and ends ON its end. While that window is in flight the row
    is a leaver (retired in the carry, no page grown for it), the next
    window is priced over `live` alone (a full 4 steps) and dispatched
    before the short one is read: a finish behind the program in flight,
    as PR 50's full windows had it."""
    sync, eng = pair
    script = {0: _add(_live(n=30),
                      GenRequest("short", [4, 5, 6, 7], max_tokens=4,
                                 temperature=0.8, seed=3, ignore_eos=True))}
    seen = []

    def probe(e):
        pw = e._pending_win
        if pw is not None:
            seen.append((pw.lag, len(pw.slots), len(e.seqs),
                         sorted(e._leaving)))

    ref = _drive(sync, script)
    out = _drive(eng, script, probe)
    _same(out, ref)
    # the 3-step window over both rows, then 4 steps over `live` alone,
    # dispatched before `short` was read (finishes_behind)
    assert seen[:2] == [(3, 2, 2, []), (4, 1, 1, [])]
    m = eng.metrics
    assert m.finishes_behind == 1 and m.held_pages_peak == 0
    assert m.windows["short"] >= 1
    assert m.windows["steps"] == m.decode_steps - m.mixed_count
    assert not eng._leaving


def test_a_stop_token_inside_a_short_window_holds_its_pages(pair):
    """A stop token found where a SHORT window is read: the program behind
    it computes the stopper's row, so its pages and slot wait in `_held`
    until that program is read, as behind a full window."""
    sync, eng = pair
    stopper, want = _stop_at(sync, lo=9)
    k = len(want)  # tokens the stopper gives, the stop included
    # `ender` ends two steps behind the stop: the window that holds the
    # stop token is cut to the ender's headroom
    script = {0: _add(_live(n=40), stopper,
                      GenRequest("ender", [9, 8, 7], max_tokens=k + 2,
                                 temperature=0.0, ignore_eos=True))}
    read = []
    materialize = eng._materialize_window

    def watched(pw):
        events = materialize(pw)
        if any(ev.request_id == "stopper" and ev.finished for ev in events):
            read.append((pw.lag, pw.chunk, len(eng._held)))
        return events

    eng._materialize_window = watched
    try:
        out = _drive(eng, script)
    finally:
        del eng._materialize_window
    _same(out, _drive(sync, script))
    assert out["stopper"]["tokens"] == want
    assert out["stopper"]["finish"] == "stop"
    (lag, chunk, held), = read
    assert chunk is None and 1 < lag < eng.cfg.num_scheduler_steps
    assert held == 1 and eng.metrics.held_pages_peak > 0
    assert eng.metrics.windows["short"] >= 1


# --- a first token rides the pipeline: the final chunk's program samples
# it and installs the row in the device carry; the host reads it one
# program late ---

def _prompt(i, n):
    return [(7 * j + 11 * i) % 90 + 1 for j in range(n)]


def test_a_first_token_rides_the_pipeline(pair):
    """Greedy and sampled prompts of one, two and three chunks, with
    logprobs, top-k / min-p / logit-bias rows and penalties, on the dense
    tiny model (tests/test_nemotron_h_engine.py and test_falcon_h1_engine
    .py hold the state-slot models to the same contract)."""
    assert_first_token_rides_pipeline(*pair, _prompt)


def test_eight_admissions_beside_an_anchor_open_no_drained_interval():
    """An anchor decodes while eight prompts arrive one after another and
    end beside it: every one of them is sampled by its final chunk's own
    program (`first_tokens_behind` 8 of 8), and from the anchor's second
    program on no dispatch finds the device empty."""
    eng = _mk(True, **MIXED, max_num_seqs=4)
    eng.add_request(_live("anchor", 120))
    for _ in range(3):
        eng.step()
    assert eng._pending_win is not None
    drained = eng.timeline.drained_count
    base = eng.metrics.num_admitted
    n_steps, late, ended = 0, 0, set()
    while len(ended) < 8:  # then the anchor is alone again, still decoding
        if late < 8 and n_steps % 3 == 0:
            eng.add_request(GenRequest(
                f"n{late}", _prompt(late, 5 + 3 * late), max_tokens=5,
                temperature=0.7, seed=late, ignore_eos=True))
            late += 1
        ended.update(ev.request_id for ev in eng.step() if ev.finished)
        n_steps += 1
    assert "anchor" not in ended and eng._pending_win is not None
    m = eng.metrics
    assert (m.first_tokens_behind, m.num_admitted - base) == (8, 8)
    assert eng.timeline.drained_count == drained
    assert eng.timeline.summary()["drained"]["count"] == drained
    eng.abort_request("anchor")
    while eng.has_work:
        eng.step()


def _first_of(ref_eng, req):
    """The first token the synchronous order gives `req` beside `live`."""
    out = _drive(ref_eng, {0: _add(_live()), 3: _add(req)})
    return out[req.request_id]["tokens"][0]


def test_an_eos_as_first_token_leaves_the_program_behind_it_in_flight(pair):
    """The prompt's first token is its stop token. It is found when the
    final chunk's program is read, with the next program already on the
    device over the newcomer's row: that program STAYS in flight, the
    newcomer's pages and its slot wait in `_held` under its ticket, and
    `PageAllocator.alloc` hands none of them out before it is read."""
    ref_eng, eng = pair
    mk = lambda stop: GenRequest(  # noqa: E731
        "long", LONG, max_tokens=9, temperature=0.9, seed=3,
        stop_token_ids=stop, ignore_eos=True)
    stop = [_first_of(ref_eng, mk([]))]
    script = lambda: {0: _add(_live()), 3: _add(mk(stop))}  # noqa: E731
    ref = _drive(ref_eng, script())
    assert ref["long"]["tokens"] == stop and ref["long"]["finish"] == "stop"
    alloc = eng.allocator.alloc
    handed, seen = [], []

    def watched(n):
        pages = alloc(n)
        handed.append((set(pages), {p for _, ps, _, _ in eng._held
                                    for p in ps}))
        return pages

    def probe(e):
        if e._held and not seen:
            (ticket, pages, rid, slot), = e._held
            pw = e._pending_win
            assert pw is not None and pw.ticket == ticket  # still unread
            assert rid == "long" and slot in pw.slots
            assert slot not in e._free_slots and slot not in e.seqs
            assert len(pages) >= 6
            # the whole free list, handed out and given back: none held
            e.allocator.free(e.allocator.alloc(e.allocator.free_pages))
            seen.append(ticket)

    eng.allocator.alloc = watched
    try:
        out = _drive(eng, script(), probe=probe)
    finally:
        del eng.allocator.alloc
    _same(out, ref)
    assert seen and any(held for _, held in handed)
    assert all(not got & held for got, held in handed)
    m = eng.metrics
    assert (m.first_tokens_behind, m.finishes_behind) == (1, 1)
    assert m.held_pages_peak >= 6


def test_a_first_token_that_is_the_last_is_never_activated(pair):
    """`max_tokens` 1 is known ahead: the final chunk's program samples
    the token and installs no row, the next program's mask never holds
    the newcomer's bit, and its pages and its slot go back when the token
    is read."""
    ref_eng, eng = pair
    script = lambda: {0: _add(_live()), 3: _add(_long(1, logprobs=2))}  # noqa: E731
    masks = []

    def probe(e):
        pw = e._pending_win
        if pw is not None and pw.joiner is not None:
            masks.append((pw.joiner.slot, None))
        elif masks and masks[-1][1] is None and e._dev_state[3] is not None:
            # the program dispatched behind the final chunk's
            masks[-1] = (masks[-1][0], np.asarray(e._dev_state[3]).tolist())

    out = _drive(eng, script(), probe=probe)
    _same(out, _drive(ref_eng, script()))
    assert out["long"]["finish"] == "length" and len(out["long"]["lp"]) == 1
    (slot, mask), = masks
    assert mask is not None and not mask[slot] and sum(mask) == 1
    m = eng.metrics
    assert (m.first_tokens_behind, m.finishes_behind) == (1, 1)
    assert m.held_pages_peak == 0


def test_a_poisoned_final_chunk_ends_that_stream_alone(pair):
    """The integrity sentinel beside the first token reads false (the
    program's result, poisoned before it is read): the newcomer's stream
    ends `integrity_fault`, the row is retired behind the program that
    decodes it, and the anchor's stream is the synchronous order's."""
    ref_eng, eng = pair
    script = lambda: {0: _add(_live()), 3: _add(_long(9))}  # noqa: E731
    ref = _drive(ref_eng, script())
    done = []

    def probe(e):
        pw = e._pending_win
        if not done and pw is not None and pw.joiner is not None:
            e._pending_win = pw._replace(
                first=(*pw.first[:4], np.bool_(False)))
            done.append(pw.joiner.slot)

    was, eng.integrity = eng.integrity, "on"
    try:
        out = _drive(eng, script(), probe=probe)
    finally:
        eng.integrity = was
    assert done
    assert out["long"] == {"tokens": [], "finish": "integrity_fault",
                           "lp": []}
    assert out["live"] == ref["live"]
    assert eng.watchdog.integrity_faults_total.get("logits", 0) >= 1
    assert eng.metrics.first_tokens_behind == 0  # it was given none


def test_abort_while_a_final_chunks_program_is_in_flight(pair):
    """The newcomer is aborted after its final chunk's dispatch and
    before its first token is read: the abort drains the pipeline (the
    token is emitted), then ends the stream; the anchor's is whole."""
    ref_eng, eng = pair
    ref = _drive(ref_eng, _three_chunks())
    aborted = []

    def probe(e):
        pw = e._pending_win
        if not aborted and pw is not None and pw.joiner is not None:
            e.abort_request("long")
            aborted.append(pw)

    out = _drive(eng, _three_chunks(), probe=probe)
    assert aborted and out["long"]["finish"] == "abort"
    assert out["long"]["tokens"] == ref["long"]["tokens"][:1]
    assert out["live"]["tokens"] == ref["live"]["tokens"]


@pytest.mark.parametrize("what", ["guided", "penalized_continuation",
                                  "nan_drill_armed"])
def test_what_keeps_the_read_at_once_order_is_a_property_of_the_request(
        pair, what):
    """A guided request (its first token is masked on the host), a
    preempted continuation whose penalties count its earlier output (the
    host re-seeds its count row), and any request while the corrupted-
    forward drill is armed (it poisons the logits the host reads): their
    final chunk's program is read at once and no first token rides;
    streams are the synchronous order's. The plain request beside them
    in the same run rides."""
    ref_eng, eng = pair
    kw = {"guided": dict(guided_json=True, temperature=1.1, seed=4),
          "penalized_continuation": dict(
              prior_output_token_ids=[5, 9, 9, 14], presence_penalty=0.6,
              frequency_penalty=0.4, temperature=0.9, seed=8, logprobs=1),
          "nan_drill_armed": dict(temperature=0.9, seed=8)}[what]
    odd = GenRequest("long", LONG, max_tokens=9, **kw)
    plain = GenRequest("plain", LONG[:11], max_tokens=5, temperature=0.0,
                       ignore_eos=True)
    script = lambda: {0: _add(_live()), 3: _add(odd),  # noqa: E731
                      12: _add(plain)}
    rode = []

    def probe(e):
        pw = e._pending_win
        if pw is not None and pw.joiner is not None:
            rode.append(pw.joiner.req.request_id)

    if what == "nan_drill_armed":
        # armed, and never firing: the path is chosen by what is armed
        faults.get_plane().arm("engine.device_nan", after=1 << 30)
        script = lambda: {0: _add(_live()), 3: _add(odd)}  # noqa: E731
    try:
        ref = _drive(ref_eng, script())
        out = _drive(eng, script(), probe=probe)
    finally:
        faults.reset_plane()
    _same(out, ref)
    if what == "nan_drill_armed":
        assert not rode and eng.metrics.first_tokens_behind == 0
    else:
        assert set(rode) == {"plain"}
        assert eng.metrics.first_tokens_behind == 1
    assert eng.metrics.num_admitted == len(out)


def test_warmup_compiles_what_a_first_token_behind_the_pipeline_runs():
    """The mixed program is warmed with its first-token operands: the
    count of compiled programs is the parent's for this configuration
    (30, PR 50's tree), and a run whose prompts join behind their final
    chunks compiles nothing more."""
    eng = _mk(True, **MIXED, max_seq_len=64)
    assert eng.warmup()["programs"] == 30
    n = eng.compiled_program_count()
    out = _drive(eng, _seeded_sampling())
    assert len(out) == 3 and eng.metrics.first_tokens_behind == 1
    assert eng.compiled_program_count() == n
