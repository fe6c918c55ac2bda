"""`tiny-mimo-v2-debug` through `Engine` on the CPU: the served path (whole
prompts inside the window, chunked prefill riding mixed steps behind the
async pipeline, fused decode windows, warm-up) over a KV pool for each
attention kind whose rows differ in KV heads and in K and V lanes, held to
the float32 reference's greedy tokens past several turns of a sliding
layer's ring; ring pages and full pages back at finish, abort and
preemption, a preempted sequence's tokens the unbroken run's; a prefix hit
served as a miss, counted; the counters by kind with the sink's rows and the
memory snapshot's KV heads and lanes by kind; what is refused."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.engine.request import GenRequest
from dynamo_tpu.models.reference import mimo_v2 as ref
from dynamo_tpu.observability.memory import MemoryAccountant

from mimo_v2_common import hf_dict, tiny
from pipelined_common import (
    assert_finish_rides_pipeline, assert_pipelined_matches_sync, drain,
    prompt, warm_then_serve)

CFG = dict(model="tiny-mimo-v2-debug", page_size=4, num_pages=128,
           max_num_seqs=4, max_seq_len=128, prefill_chunk_tokens=8,
           mixed_batch_tokens=8, num_scheduler_steps=4, dtype="float32")


def pools_free(eng: Engine):
    """Free pages of both pools, the prefix cache's own counted as free."""
    return (eng.allocator.free_pages + eng.prefix_cache.stats()["entries"],
            eng.win_rings.allocator.free_pages)


def reference_greedy(eng: Engine, tokens, n_new: int):
    """The reference's argmax at every generated position, teacher forced
    on `tokens` (prompt + what the engine gave)."""
    cfg = dataclasses.replace(eng.model_cfg, dtype="float32")
    share = (ref.Share(cfg.local_expert_offset, cfg.held_experts)
             if cfg.num_local_experts else None)
    logits = ref.forward(ref.Config.from_hf(hf_dict(cfg)),
                         ref.dequantize(eng.params), jnp.asarray(tokens),
                         share=share)
    first = len(tokens) - n_new
    return [int(t) for t in np.argmax(logits[first - 1:-1], axis=-1)]


@pytest.fixture(scope="module")
def engine():
    eng = Engine(EngineConfig(**CFG))
    # a selection bias that changes the pick (the seeded one is zero)
    bias = eng.params["router_bias"]
    eng.params["router_bias"] = jnp.asarray(
        np.random.default_rng(1).normal(0.0, 0.3, bias.shape), bias.dtype)
    return eng


@pytest.fixture(scope="module")
def sync_engine(engine):
    """The oracle of the pipelined orders: async_scheduling off, the
    same router bias."""
    sync = Engine(EngineConfig(**CFG, async_scheduling=False))
    sync.params = engine.params
    return sync


def test_two_sequences_of_very_different_lengths_match_the_reference(engine):
    """A 70-token prompt (its ring of 6 pages is written over many times)
    beside a 6-token one (inside the window: one whole-prompt prefill) that
    arrives while it decodes. Greedy tokens are the reference's through
    mixed steps and fused windows; afterwards both pools are whole again,
    and the counters by kind counted three kinds of row."""
    eng = engine
    free = pools_free(eng)
    long_p, short_p = prompt(1, 70), prompt(2, 6)
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
    assert eng.metrics.mixed_count > 0  # the long prompt rode mixed steps
    for name, p in (("long", long_p), ("short", short_p)):
        toks = got[name]
        assert toks == reference_greedy(eng, p + toks, len(toks)), name
    ring = eng.kv_spec.ring_pages
    assert eng.win_rings.handed_back >= -(-(70 + 24) // 4) - ring
    assert pools_free(eng) == free and eng.win_rings.pages_held() == 0
    kinds = eng.metrics.attn_kinds
    full, window = kinds["full"], kinds["window"]
    assert full["decode_q_rows"] == window["decode_q_rows"] > 0
    # a sliding layer's query reads at most its window
    assert (window["decode_kv_rows"] <= 8 * window["decode_q_rows"]
            < full["decode_kv_rows"])
    assert 0 < window["mixed_chunk_kv_pairs"] <= full["mixed_chunk_kv_pairs"]
    # every query row of a sliding layer carried a sink; no full one did
    assert window["sink_rows"] == (
        window["decode_q_rows"] + window["mixed_decode_q_rows"]
        + window["mixed_chunk_q_rows"])
    assert "sink_rows" not in full


def test_a_share_of_the_experts_serves_the_reference_given_that_share():
    """`tiny-mimo-v2-ep4-debug` (experts 4-7 of 16 held) through the same
    path: greedy tokens are the reference's when it routes over all 16 and
    adds the held four's part alone."""
    eng = Engine(EngineConfig(**{**CFG, "model": "tiny-mimo-v2-ep4-debug"}))
    p = prompt(21, 26)
    eng.add_request(GenRequest("s", p, max_tokens=10, temperature=0.0,
                               ignore_eos=True))
    toks = drain(eng)["s"]
    assert toks == reference_greedy(eng, p + toks, 10)
    moe = eng.metrics.kernel_counters()["moe"]
    assert 0 < moe["assignments_held"] < moe["assignments"]


def test_mixed_steps_behind_the_pipeline_match_the_synchronous_order(
        sync_engine, engine):
    """A 30-token prompt's four chunks, each dispatched on the device
    outputs of the program before it, while the decoding row's ring keeps
    turning: tokens and `metrics.attn_kinds` (the sink's rows too) are the
    synchronous order's."""
    got = assert_pipelined_matches_sync(
        sync_engine, engine,
        GenRequest("live", prompt(11, 29), max_tokens=28, temperature=0.0,
                   ignore_eos=True),
        GenRequest("late", prompt(12, 30), max_tokens=9, temperature=0.0,
                   ignore_eos=True))
    late = prompt(12, 30) + got["late"]
    assert got["late"] == reference_greedy(engine, late, 9)
    assert engine.win_rings.pages_held() == 0


def test_a_prefix_hit_is_served_as_a_miss_and_counted(engine):
    """The same prompt again: its full pages are in the prefix cache, its
    sliding layers' rows are not (a ring is its sequence's own), so the hit
    is turned into a miss, counted, and the tokens are the first run's."""
    eng = engine
    p = prompt(3, 40)
    before = eng.metrics.prefix_hits_inexact
    runs = []
    for name in ("first", "again"):
        eng.add_request(GenRequest(name, p, max_tokens=8, temperature=0.0,
                                   ignore_eos=True))
        runs.append(drain(eng)[name])
    assert runs[0] == runs[1] == reference_greedy(eng, p + runs[0], 8)
    assert eng.metrics.prefix_hits_inexact == before + 1
    assert eng.prefix_cache.stats()["cached_tokens_served"] == 0
    # a ring is its sequence's own and nothing of it is kept: no snapshot pool
    assert eng._state_snaps is None and eng._prefix_recomputed
    assert "snapshots" not in eng.prefix_cache.stats()


def test_memory_snapshot_says_what_a_row_of_each_kind_holds(engine):
    eng = engine
    eng.add_request(GenRequest("m", prompt(4, 50), max_tokens=30,
                               temperature=0.0, ignore_eos=True))
    for _ in range(12):
        eng.step()
    snap = MemoryAccountant(eng).snapshot()
    assert snap["kv_heads_by_kind"] == {"full": 1, "window": 2}
    assert snap["kv_lanes_by_kind"] == {"full": {"k": 24, "v": 16},
                                        "window": {"k": 48, "v": 32}}
    # layers of the kind x (K lanes + V lanes) x 4 bytes (float32 here)
    assert snap["bytes_per_token_by_kind"] == {
        "full": 2 * (24 + 16) * 4, "window": 5 * (48 + 32) * 4}
    by = snap["rows_by_kind"]
    assert by["window"]["rows_held"] == eng.kv_spec.ring_pages * 4
    assert by["window"]["rows_if_kept_in_full"] == by["full"]["rows_held"] > \
        by["window"]["rows_held"]
    assert [tuple(p.shape[2:]) for p in eng.k_pages] == [(4, 24), (4, 48)]
    assert [tuple(p.shape[2:]) for p in eng.v_pages] == [(4, 16), (4, 32)]
    eng.abort_request("m")
    drain(eng)
    assert MemoryAccountant(eng).snapshot()["rows_by_kind"]["window"][
        "rows_held"] == 0
    assert eng.win_rings.pages_held() == 0


def test_preemption_and_resume_conserve_both_pools():
    """A pool too small for three sequences' contexts: the engine preempts
    by recompute and resumes; every request completes with the tokens it
    gets alone (a preempted sequence's recomputed logits are the unbroken
    run's), and both pools end whole."""
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
    """After warmup() no request compiles a program: not a prompt inside
    the window, nor one past it whose chunks ride mixed steps."""
    warm_then_serve(engine, short=7)


@pytest.mark.parametrize("change,word", [
    (dict(speculative_mode="ngram", num_speculative_tokens=2), "speculation"),
    (dict(lora_slots=2), "LoRA"),
    (dict(kvbm_host_blocks=8), "KVBM"),
    (dict(disaggregation_mode="prefill"), "disaggregated"),
    (dict(prefill_chunk_tokens=0, mixed_batch_tokens=0), "whole-prompt"),
    (dict(kv_cache_dtype="int8"), "int8"),
    (dict(tensor_parallel=2), "one chip a replica"),
], ids=["speculation", "lora", "kvbm", "disagg", "no_chunking", "int8_kv",
        "tensor_parallel"])
def test_what_pools_by_kind_do_not_serve_is_refused(change, word):
    with pytest.raises(ValueError, match=word):
        Engine(EngineConfig(**{**CFG, **change}), model_cfg=tiny())


def test_a_finish_rides_the_pipeline(sync_engine, engine):
    """Sequences leave a running batch by `max_tokens` and on stop tokens
    with no program read early; a leaver's ring (rows of another width
    than the full layers') is held back with its pages where the program
    in flight still writes there, and both pools end as they began."""
    assert_finish_rides_pipeline(sync_engine, engine,
                                 lambda i: prompt(40 + i, 5 + i))

