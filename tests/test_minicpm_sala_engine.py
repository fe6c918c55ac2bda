"""`tiny-minicpm-sala-debug` through `Engine` on the CPU: the served path
(chunked prompts riding mixed steps, fused decode windows, warm-up) over a
state slot of five Lightning states, three sparse layers' pages and their
pooled-key sums, held to the float32 reference's greedy tokens; two
sequences of which one stays under `dense_len` in one batch; a reused slot
starts from zero; preemption and resume; a prefix hit counted inexact;
`metrics.sparse`, `metrics.ssm` and the memory snapshot; what is refused."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.engine.request import GenRequest
from dynamo_tpu.models.reference import minicpm_sala as ref
from dynamo_tpu.observability.memory import MemoryAccountant

from minicpm_sala_common import hf_dict, tiny

from pipelined_common import (
    assert_finish_rides_pipeline, assert_first_token_rides_pipeline, drain,
    engine_pair, greedy_of, prompt, slots_held, warm_then_serve)

CFG = dict(model="tiny-minicpm-sala-debug", page_size=4, num_pages=256,
           max_num_seqs=4, max_seq_len=256, prefill_chunk_tokens=16,
           mixed_batch_tokens=16, num_scheduler_steps=4, dtype="float32")

reference_greedy = functools.partial(greedy_of, ref, hf_dict)
engine, sync_engine = engine_pair(CFG)


def test_a_long_and_a_short_sequence_in_one_batch_match_the_reference(engine):
    """A 150-token prompt (ten chunks: chunks 1-4 dense, the rest select)
    decoding past dense_len beside a 30-token one that arrives while it
    decodes and never leaves dense_len's reach for 20 of its tokens: one
    program, the predicate a row. Greedy tokens are the reference's; every
    live sequence holds ONE slot; afterwards pages and slots are whole."""
    eng = engine
    free = eng.allocator.free_pages
    long_p, short_p = prompt(1, 150), prompt(2, 30)
    eng.add_request(GenRequest("long", long_p, max_tokens=40,
                               temperature=0.0, ignore_eos=True))
    got, sent, held = {}, False, set()
    while eng.has_work:
        for ev in eng.step():
            if ev.token_id >= 0:
                got.setdefault(ev.request_id, []).append(ev.token_id)
        held.add((len(eng.seqs) + (eng._inflight is not None),
                  slots_held(eng)))
        if not sent and len(got.get("long", ())) >= 3:
            eng.add_request(GenRequest("short", short_p, max_tokens=50,
                                       temperature=0.0, ignore_eos=True))
            sent = True
    assert eng.metrics.mixed_count > 0  # the short prompt rode mixed steps
    assert all(live == slots for live, slots in held) and (2, 2) in held
    for name, p in (("long", long_p), ("short", short_p)):
        toks = got[name]
        assert toks == reference_greedy(eng, p + toks, len(toks)), name
    cached = eng.prefix_cache.stats()["entries"]
    assert eng.allocator.free_pages + cached == free
    assert slots_held(eng) == 0 and len(eng._free_slots) == 4
    counters = eng.metrics.kernel_counters()
    sp, ssm = counters["sparse"], counters["ssm"]
    assert sp["decode_rows"] == (40 - 1) + (50 - 1) == ssm["decode_rows"]
    # the short one's rows up to context 64 are dense (a row seated behind
    # its prompt's final chunk is counted one token behind: PR 51)
    assert sp["dense_rows"] in (64 - 30, 64 - 30 + 1)
    far = sp["decode_rows"] - sp["dense_rows"]
    assert sp["blocks_selected"] == 5 * far and sp["blocks_forced"] == 3 * far
    assert 0 < sp["rows_attended"] < sp["rows_in_context"]
    assert sp["keys_scored"] > 0
    # chunks whose last query stood past dense_len: 150 tokens' 5th .. 10th
    assert sp["chunk_calls"] == 6
    # one tile a sparse layer a chunk: under a table that can hold a context
    # past dense_len EVERY chunk takes the masked attention (12 chunks)
    assert sp["chunk_blocks_visited"] == 3 * 12
    assert sp["chunk_blocks_skipped"] == 0
    assert ssm["chunk_tokens"] == 180 and ssm["chunk_calls"] == 10 + 2
    assert ssm["layer_steps"] >= ssm["chunk_calls"]
    assert not any(eng.metrics.conv.values())
    assert not any(eng.metrics.dsa.values())


def test_a_prefix_hit_is_counted_inexact_and_served_by_recompute(engine):
    eng = engine
    p = prompt(3, 90)
    runs = []
    for name in ("first", "again"):
        eng.add_request(GenRequest(name, p, max_tokens=8, temperature=0.0,
                                   ignore_eos=True))
        runs.append(drain(eng)[name])
    assert runs[0] == runs[1] == reference_greedy(eng, p + runs[0], 8)
    assert eng.metrics.prefix_hits_inexact == 1
    assert eng.prefix_cache.stats()["cached_tokens_served"] == 0
    assert eng._state_snaps is None and eng._prefix_recomputed


def test_a_slot_reused_after_a_finish_or_an_abort_starts_from_zero(engine):
    eng = engine
    eng.add_request(GenRequest("a", prompt(20, 80), max_tokens=10,
                               temperature=0.0, ignore_eos=True))
    drain(eng)
    slot_a = eng._free_slots[-1]
    assert float(jnp.abs(eng.k_pages.state[0][:, slot_a]).max()) > 0
    eng.add_request(GenRequest("b", prompt(21, 70), max_tokens=40,
                               temperature=0.0, ignore_eos=True))
    for _ in range(8):
        eng.step()
    eng.abort_request("b")
    drain(eng)
    assert slots_held(eng) == 0
    p = prompt(22, 75)
    eng.add_request(GenRequest("c", p, max_tokens=10, temperature=0.0,
                               ignore_eos=True))
    eng.step()
    assert (eng._inflight.slot if eng._inflight else list(eng.seqs)[0]
            ) == slot_a
    toks = drain(eng)["c"]
    assert toks == reference_greedy(eng, p + toks, 10)


def test_an_empty_slots_state_stays_bit_for_bit_through_others_steps(engine):
    eng = engine
    eng.add_request(GenRequest("x", prompt(70, 21), max_tokens=6,
                               temperature=0.0, ignore_eos=True))
    eng.add_request(GenRequest("y", prompt(71, 17), max_tokens=30,
                               temperature=0.0, ignore_eos=True))
    done = {}
    while "x" not in done:
        for ev in eng.step():
            if ev.finished:
                done[ev.request_id] = True
    eng.step()
    eng.step()
    (slot_y,) = list(eng.seqs)
    dead = [s for s in range(4) if s != slot_y]
    before = np.asarray(eng.k_pages.state[0])[:, dead].copy()
    drain(eng)
    after = np.asarray(eng.k_pages.state[0])[:, dead]
    assert before.any() and np.array_equal(before, after)


def test_memory_snapshot_counts_slots_and_pooled_keys(engine):
    eng = engine
    eng.add_request(GenRequest("m", prompt(4, 50), max_tokens=30,
                               temperature=0.0, ignore_eos=True))
    eng.step()
    snap = MemoryAccountant(eng).snapshot()
    per_slot = 5 * 8 * 32 * 32 * 4  # five Lightning layers' float32 states
    assert snap["bytes_per_slot"] == per_slot == eng.kv_spec.bytes_per_slot()
    assert snap["state_slots"] == {"held": 1, "total": 4, "bytes": per_slot}
    # a row a page of the widest table a sequence is handed: 64 + 3 + 1
    assert snap["pooled_key_bytes"] == 3 * 4 * 68 * 64 * 4
    assert snap["bytes_per_token"] == 3 * 2 * 2 * 32 * 4  # the sparse layers'
    assert eng.v_pages.state == () and len(eng.k_pages.state) == 1
    assert eng.k_pages.state[0].shape == (5, 4, 8, 32, 32)
    assert eng.k_pages.pooled[0].shape == (3, 4, 68, 64)
    drain(eng)
    assert slots_held(eng) == 0


def test_preemption_and_resume_reproduce_the_tokens():
    """A pool too small for three sequences' contexts: the engine preempts
    by recompute (pages, their sums and the state prefilled again from
    zeros) and resumes; every request completes with the tokens it gets
    alone, and pages and slots end whole."""
    small = EngineConfig(**{**CFG, "num_pages": 70,
                            "enable_prefix_caching": False})
    eng = Engine(small)
    prompts = {f"r{i}": prompt(10 + i, 70) for i in range(3)}
    alone = {}
    for name, p in prompts.items():
        eng.add_request(GenRequest(name, p, max_tokens=40, temperature=0.0,
                                   ignore_eos=True))
        alone[name] = drain(eng)[name]
    free = eng.allocator.free_pages
    for name, p in prompts.items():
        eng.add_request(GenRequest(name, p, max_tokens=40, temperature=0.0,
                                   ignore_eos=True))
    together = drain(eng)
    assert eng.metrics.num_preempted > 0
    assert together == alone
    assert eng.allocator.free_pages == free
    assert slots_held(eng) == 0 and sorted(eng._free_slots) == [0, 1, 2, 3]


def test_warmup_compiles_what_the_window_runs(engine):
    p, toks = warm_then_serve(engine, long=120)
    assert toks == reference_greedy(engine, p + toks, 6)


@pytest.mark.parametrize("change,word", [
    (dict(speculative_mode="ngram", num_speculative_tokens=2), "speculation"),
    (dict(lora_slots=2), "LoRA"),
    (dict(kvbm_host_blocks=8), "KVBM"),
    (dict(disaggregation_mode="prefill"), "disaggregated"),
    (dict(kv_cache_dtype="int8"), "int8"),
    (dict(tensor_parallel=2), "tensor parallelism"),
    (dict(page_size=8), "page_size=8"),
], ids=["speculation", "lora", "kvbm", "disagg", "int8_kv", "tp",
        "page_size"])
def test_what_the_model_is_not_served_with_is_refused(change, word):
    with pytest.raises(ValueError, match=word):
        Engine(EngineConfig(**{**CFG, **change}), model_cfg=tiny())


def test_a_finish_rides_the_pipeline(sync_engine, engine):
    assert_finish_rides_pipeline(sync_engine, engine,
                                 lambda i: prompt(40 + i, 60 + i))


def test_a_first_token_rides_the_pipeline(sync_engine, engine):
    assert_first_token_rides_pipeline(sync_engine, engine,
                                      lambda i, n: prompt(60 + i, n))
