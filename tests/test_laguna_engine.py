"""`tiny-laguna-debug` through `Engine` on the CPU: the served path (chunked
prefill riding mixed steps, fused decode windows, warm-up) over a KV pool
and a page table for each attention kind, held to the float32 reference's
greedy tokens; both pools' page counts conserved across preemption, resume
and sequences of very different lengths; a prefix hit served only where it
is exact (never, with rings of a sequence's own: counted, served a miss);
`metrics.attn_kinds` and the memory snapshot by kind; what is refused."""

import functools

import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.engine.request import GenRequest
from dynamo_tpu.models.reference import laguna_s as ref
from dynamo_tpu.observability.memory import MemoryAccountant

from pipelined_common import (
    assert_finish_rides_pipeline, assert_pipelined_matches_sync,
    assert_windows_as_long_as_the_shortest_headroom, drain, engine_pair,
    greedy_of, prompt, warm_then_serve)
from test_laguna import hf_dict, tiny

CFG = dict(model="tiny-laguna-debug", page_size=4, num_pages=128,
           max_num_seqs=4, max_seq_len=128, prefill_chunk_tokens=8,
           mixed_batch_tokens=8, num_scheduler_steps=4, dtype="float32")

reference_greedy = functools.partial(greedy_of, ref, hf_dict)
engine, sync_engine = engine_pair(CFG)


def test_two_sequences_of_very_different_lengths_match_the_reference(engine):
    """A 70-token prompt (17 full pages; its ring of 6 pages is written
    over 12 times) beside a 9-token one that arrives while it decodes: the
    short one's chunks ride mixed steps. Greedy tokens are the reference's;
    afterwards both pools are whole again."""
    eng = engine
    free = (eng.allocator.free_pages, eng.win_rings.allocator.free_pages)
    long_p, short_p = prompt(1, 70), prompt(2, 9)
    eng.add_request(GenRequest("long", long_p, max_tokens=24,
                               temperature=0.0, ignore_eos=True))
    got, sent = {}, False
    while eng.has_work:
        for ev in eng.step():
            if ev.token_id >= 0:
                got.setdefault(ev.request_id, []).append(ev.token_id)
        if not sent and len(got.get("long", ())) >= 3:
            eng.add_request(GenRequest("short", short_p, max_tokens=12,
                                       temperature=0.0, ignore_eos=True))
            sent = True
    assert eng.metrics.mixed_count > 0  # the short prompt rode mixed steps
    for name, p in (("long", long_p), ("short", short_p)):
        toks = got[name]
        assert toks == reference_greedy(eng, p + toks, len(toks)), name
    ring = eng.kv_spec.ring_pages
    assert eng.win_rings.handed_back >= -(-(70 + 24) // 4) - ring
    held = eng.prefix_cache.stats()["entries"]  # full pages it published
    assert (eng.allocator.free_pages + held,
            eng.win_rings.allocator.free_pages) == free
    assert eng.win_rings.pages_held() == 0
    kinds = eng.metrics.attn_kinds
    assert kinds["full"]["decode_q_rows"] == kinds["window"]["decode_q_rows"] > 0
    # a sliding layer's query reads at most its window
    assert (kinds["window"]["decode_kv_rows"]
            <= 8 * kinds["window"]["decode_q_rows"]
            < kinds["full"]["decode_kv_rows"])
    assert 0 < kinds["window"]["mixed_chunk_kv_pairs"] <= kinds["full"][
        "mixed_chunk_kv_pairs"]


def test_mixed_steps_behind_the_pipeline_match_the_synchronous_order(
        sync_engine, engine):
    """A 30-token prompt's four chunks, each dispatched on the device
    outputs of the program before it, while the decoding row's ring (6
    pages, written over) keeps turning: tokens and `metrics.attn_kinds` of
    both kinds are the synchronous order's."""
    got = assert_pipelined_matches_sync(
        sync_engine, engine,
        GenRequest("live", prompt(11, 29), max_tokens=28, temperature=0.0,
                   ignore_eos=True),
        GenRequest("late", prompt(12, 30), max_tokens=9, temperature=0.0,
                   ignore_eos=True))
    late = prompt(12, 30) + got["late"]
    assert got["late"] == reference_greedy(engine, late, 9)
    assert engine.win_rings.pages_held() == 0


def test_a_prefix_hit_is_served_only_where_exact(engine):
    """The same prompt again: its full pages are in the prefix cache, its
    sliding layers' rows are not (a ring is its sequence's own), so the
    hit is turned into a miss, counted, and the tokens are the first
    run's."""
    eng = engine
    p = prompt(3, 40)
    runs = []
    for name in ("first", "again"):
        eng.add_request(GenRequest(name, p, max_tokens=8, temperature=0.0,
                                   ignore_eos=True))
        runs.append(drain(eng)[name])
    assert runs[0] == runs[1] == reference_greedy(eng, p + runs[0], 8)
    assert eng.metrics.prefix_hits_inexact == 1
    assert eng.prefix_cache.stats()["cached_tokens_served"] == 0
    # a ring is its sequence's own and nothing of it is kept: no snapshot pool
    assert eng._state_snaps is None and eng._prefix_recomputed
    assert "snapshots" not in eng.prefix_cache.stats()


def test_memory_snapshot_by_kind(engine):
    eng = engine
    eng.add_request(GenRequest("m", prompt(4, 50), max_tokens=30,
                               temperature=0.0, ignore_eos=True))
    for _ in range(12):
        eng.step()
    snap = MemoryAccountant(eng).snapshot()
    by = snap["rows_by_kind"]
    assert snap["bytes_per_token_by_kind"] == {
        "full": 2 * 2 * 64 * 4, "window": 3 * 2 * 64 * 4}
    assert by["window"]["rows_held"] == eng.kv_spec.ring_pages * 4
    assert by["window"]["rows_if_kept_in_full"] == by["full"]["rows_held"] > \
        by["window"]["rows_held"]
    drain(eng)
    assert MemoryAccountant(eng).snapshot()["rows_by_kind"]["window"][
        "rows_held"] == 0


def test_preemption_and_resume_conserve_both_pools():
    """A pool too small for three sequences' contexts: the engine preempts
    by recompute and resumes; every request completes with the tokens it
    gets alone, and both pools end whole."""
    small = EngineConfig(**{**CFG, "num_pages": 40,
                            "enable_prefix_caching": False})
    eng = Engine(small)
    prompts = {f"r{i}": prompt(10 + i, 30) for i in range(3)}
    alone = {}
    for name, p in prompts.items():
        eng.add_request(GenRequest(name, p, max_tokens=40, temperature=0.0,
                                   ignore_eos=True))
        alone[name] = drain(eng)[name]
    free = (eng.allocator.free_pages, eng.win_rings.allocator.free_pages)
    for name, p in prompts.items():
        eng.add_request(GenRequest(name, p, max_tokens=40, temperature=0.0,
                                   ignore_eos=True))
    together = drain(eng)
    assert eng.metrics.num_preempted > 0
    assert together == alone
    assert (eng.allocator.free_pages,
            eng.win_rings.allocator.free_pages) == free
    assert eng.win_rings.pages_held() == 0


def test_warmup_compiles_what_the_window_runs(engine):
    """After warmup() no request compiles a program: not a short prompt
    whose decoders leave before it is done (the chunk program at a table
    width the prefix-cached second pass would have compiled for a one-kind
    model), nor a long one."""
    warm_then_serve(engine)


@pytest.mark.parametrize("change,word", [
    (dict(speculative_mode="ngram", num_speculative_tokens=2), "speculation"),
    (dict(lora_slots=2), "LoRA"),
    (dict(kvbm_host_blocks=8), "KVBM"),
    (dict(disaggregation_mode="prefill"), "disaggregated"),
    (dict(prefill_chunk_tokens=0, mixed_batch_tokens=0), "whole-prompt"),
    (dict(kv_cache_dtype="int8"), "int8"),
], ids=["speculation", "lora", "kvbm", "disagg", "no_chunking", "int8_kv"])
def test_what_pools_by_kind_do_not_serve_is_refused(change, word):
    with pytest.raises(ValueError, match=word):
        Engine(EngineConfig(**{**CFG, **change}), model_cfg=tiny())


def test_windows_of_every_length_give_the_single_steps_tokens(
        sync_engine, engine):
    """Rows end at every offset of a window, so the fused program runs at
    every trip count 1 .. 4: the sliding layers' rings are grown for the
    window's own length and written as far.
    Tokens, logprobs and the counters are those of a classic program a
    step (num_scheduler_steps=1), in both orders."""
    single = Engine(EngineConfig(**{**CFG, "num_scheduler_steps": 1,
                                    "async_scheduling": False}))
    assert_windows_as_long_as_the_shortest_headroom(
        single, [sync_engine, engine],
        lambda i: prompt(100 + i, 5 + i % 3))


def test_a_finish_rides_the_pipeline(sync_engine, engine):
    """Sequences leave a running batch by `max_tokens` and on stop tokens
    with no program read early; a leaver's ring on the sliding layers is
    held back with its pages where the program in flight still writes
    there, and both pools end as they began."""
    assert_finish_rides_pipeline(sync_engine, engine,
                                 lambda i: prompt(40 + i, 5 + i))

