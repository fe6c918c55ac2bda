"""`tiny-falcon-h1-debug` through `Engine` on the CPU: the served path
(chunked prompts riding mixed steps, fused decode windows, warm-up) with
pages AND a state slot in every layer, held to the float32 reference's
greedy tokens; a live sequence holds exactly one slot from its first chunk
to its last token; the slot and the pages of every layer come back at
finish, abort and preemption, and a preempted sequence's tokens are the
unbroken run's; a prefix hit counted inexact and served by recompute;
`metrics.ssm`, `metrics.attn_kinds.full`, `metrics.admit_blocked` by the
store that lacked room, and the memory snapshot; what is refused."""

import functools

import jax.numpy as jnp
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.engine.request import GenRequest
from dynamo_tpu.models.reference import falcon_h1 as ref
from dynamo_tpu.observability.memory import MemoryAccountant

from falcon_h1_common import hf_dict, tiny
from pipelined_common import (
    assert_finish_rides_pipeline, assert_first_token_rides_pipeline,
    assert_pipelined_matches_sync, drain, engine_pair, greedy_of, prompt,
    slots_held, warm_then_serve)

CFG = dict(model="tiny-falcon-h1-debug", page_size=4, num_pages=128,
           max_num_seqs=4, max_seq_len=128, prefill_chunk_tokens=8,
           mixed_batch_tokens=8, num_scheduler_steps=4, dtype="float32")

reference_greedy = functools.partial(greedy_of, ref, hf_dict)
engine, sync_engine = engine_pair(CFG)


def test_two_sequences_of_very_different_lengths_match_the_reference(engine):
    """A 70-token prompt (nine chunks: its state rides its slot of every
    layer from step to step) beside a 9-token one that arrives while it
    decodes: the short one's chunks ride mixed steps. Greedy tokens are the
    reference's; every live sequence holds ONE slot whatever its length;
    afterwards pages and slots are whole again; the counters count a
    layer's worth a step."""
    eng = engine
    free = eng.allocator.free_pages
    long_p, short_p = prompt(1, 70), prompt(2, 9)
    eng.add_request(GenRequest("long", long_p, max_tokens=24,
                               temperature=0.0, ignore_eos=True))
    got, sent, held = {}, False, set()
    while eng.has_work:
        for ev in eng.step():
            if ev.token_id >= 0:
                got.setdefault(ev.request_id, []).append(ev.token_id)
        held.add((len(eng.seqs) + (eng._inflight is not None),
                  slots_held(eng)))
        if not sent and len(got.get("long", ())) >= 3:
            eng.add_request(GenRequest("short", short_p, max_tokens=12,
                                       temperature=0.0, ignore_eos=True))
            sent = True
    assert eng.metrics.mixed_count > 0  # the short prompt rode mixed steps
    assert all(live == slots for live, slots in held) and (2, 2) in held
    for name, p in (("long", long_p), ("short", short_p)):
        toks = got[name]
        assert toks == reference_greedy(eng, p + toks, len(toks)), name
    cached = eng.prefix_cache.stats()["entries"]  # full pages it published
    assert eng.allocator.free_pages + cached == free
    assert slots_held(eng) == 0 and len(eng._free_slots) == 4
    ssm = eng.metrics.ssm
    assert ssm["chunk_tokens"] == 79 and ssm["chunk_calls"] == 9 + 2
    assert ssm["decode_rows"] == (24 - 1) + (12 - 1)
    kinds = eng.metrics.attn_kinds
    assert kinds["full"]["decode_q_rows"] > 0
    assert kinds["full"]["mixed_chunk_q_rows"] == 79
    assert not any(kinds["window"].values())
    counters = eng.metrics.kernel_counters()
    assert counters["ssm"] == ssm
    assert counters["admit_blocked"] == {"state_slots": 0, "pages": 0}
    assert eng.metrics.snapshot()["admit_blocked"] == counters[
        "admit_blocked"]


def test_mixed_steps_behind_the_pipeline_match_the_synchronous_order(
        sync_engine, engine):
    """A 30-token prompt's four chunks, each dispatched on the device
    outputs of the program before it (pages AND every layer's state slots
    are that program's results): tokens, `metrics.ssm` and
    `metrics.attn_kinds` are the synchronous order's."""
    got = assert_pipelined_matches_sync(
        sync_engine, engine,
        GenRequest("live", prompt(11, 13), max_tokens=28, temperature=0.0,
                   ignore_eos=True),
        GenRequest("late", prompt(12, 30), max_tokens=9, temperature=0.0,
                   ignore_eos=True))
    assert engine.metrics.ssm["chunk_calls"] == 4 + 2  # late's, live's
    late = prompt(12, 30) + got["late"]
    assert got["late"] == reference_greedy(engine, late, 9)
    assert slots_held(engine) == 0


def test_a_prefix_hit_is_counted_inexact_and_served_by_recompute(engine):
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
    # a Mamba-2 state larger than a block's KV is kept at no block boundary: no pool
    assert eng._state_snaps is None and eng._prefix_recomputed
    assert "snapshots" not in eng.prefix_cache.stats()


@pytest.mark.parametrize("backend", ["auto", "pallas_interpret"])
def test_a_slot_reused_after_a_finish_or_an_abort_starts_from_zero(backend):
    """Slots are handed out last-freed first, so each request here decodes
    in the slot its predecessor left its state in, in every layer (under
    the kernel a dead slot keeps its last state until a prompt's first
    chunk zeroes it): after a finish and after an abort the pages and the
    slot are back, and the next tenant's tokens are the reference's.
    `metrics.ssm.slots_touched` says which path ran."""
    eng = Engine(EngineConfig(**{**CFG, "attention_backend": backend,
                                 "enable_prefix_caching": False}))
    free = eng.allocator.free_pages
    eng.add_request(GenRequest("a", prompt(20, 30), max_tokens=10,
                               temperature=0.0, ignore_eos=True))
    drain(eng)
    assert eng.allocator.free_pages == free
    slot_a = eng._free_slots[-1]
    eng.add_request(GenRequest("b", prompt(21, 26), max_tokens=40,
                               temperature=0.0, ignore_eos=True))
    for _ in range(8):
        eng.step()
    assert list(eng.seqs) == [slot_a] and eng.allocator.free_pages < free
    eng.abort_request("b")
    drain(eng)
    assert slots_held(eng) == 0 and eng.allocator.free_pages == free
    p = prompt(22, 19)
    eng.add_request(GenRequest("c", p, max_tokens=10, temperature=0.0,
                               ignore_eos=True))
    eng.step()
    assert (eng._inflight.slot if eng._inflight else list(eng.seqs)[0]
            ) == slot_a
    toks = drain(eng)["c"]
    assert toks == reference_greedy(eng, p + toks, 10)
    ssm = eng.metrics.ssm
    assert ssm["decode_rows"] > 0
    if backend == "pallas_interpret":
        assert ssm["slots_touched"] == ssm["decode_rows"]
    else:
        assert ssm["slots_touched"] == 4 * eng.metrics.decode_steps


def test_memory_snapshot_counts_a_slot_and_pages_in_every_layer(engine):
    eng = engine
    eng.add_request(GenRequest("m", prompt(4, 50), max_tokens=30,
                               temperature=0.0, ignore_eos=True))
    eng.step()  # admitted: the first chunk has run, the slot is held
    snap = MemoryAccountant(eng).snapshot()
    per_slot = 3 * (4 * 8 * 8 * 4 + 3 * 64 * 4)  # S float32 + conv rows
    assert snap["bytes_per_slot"] == per_slot
    assert snap["state_slots"] == {"held": 1, "total": 4, "bytes": per_slot}
    # ALL three layers own pages: K and V, 2 heads of 16 lanes, float32
    assert snap["bytes_per_token"] == 3 * 2 * 2 * 16 * 4
    assert eng.k_pages.pages.shape[0] == 3
    (state,), (conv,) = eng.k_pages.state, eng.v_pages.state
    assert state.shape == (3, 4, 4, 8, 8) and state.dtype == jnp.float32
    assert conv.shape == (3, 4, 3, 64)
    drain(eng)
    assert slots_held(eng) == 0


def _together_and_alone(cfg: EngineConfig, n: int, plen: int, new: int):
    """`n` requests run one after the other, then all at once."""
    eng = Engine(cfg)
    prompts = {f"r{i}": prompt(10 + i, plen) for i in range(n)}
    alone = {}
    for name, p in prompts.items():
        eng.add_request(GenRequest(name, p, max_tokens=new, temperature=0.0,
                                   ignore_eos=True))
        alone[name] = drain(eng)[name]
    free = eng.allocator.free_pages
    assert eng.metrics.admit_blocked == {"state_slots": 0, "pages": 0}
    for name, p in prompts.items():
        eng.add_request(GenRequest(name, p, max_tokens=new, temperature=0.0,
                                   ignore_eos=True))
    together = drain(eng)
    assert together == alone
    assert eng.allocator.free_pages == free
    assert slots_held(eng) == 0
    return eng


def test_preemption_and_resume_conserve_pages_and_slots():
    """A pool too small for three sequences' contexts: the engine preempts
    by recompute (the state and the pages of every layer are dropped, the
    prompt and what was decoded prefilled again from zero) and resumes;
    every request completes with the tokens of its unbroken run, and pages
    and slots end whole."""
    eng = _together_and_alone(
        EngineConfig(**{**CFG, "num_pages": 40,
                        "enable_prefix_caching": False}), 3, 30, 40)
    assert eng.metrics.num_preempted > 0
    assert sorted(eng._free_slots) == [0, 1, 2, 3]


def test_admit_blocked_counts_state_slots_where_slots_ran_out():
    """Four requests over TWO slots and pages to spare: the third waits for
    a slot, never for pages."""
    eng = _together_and_alone(
        EngineConfig(**{**CFG, "max_num_seqs": 2,
                        "enable_prefix_caching": False}), 4, 12, 16)
    blocked = eng.metrics.admit_blocked
    assert blocked["state_slots"] > 0 and blocked["pages"] == 0


def test_admit_blocked_counts_pages_where_pages_ran_out():
    """Four slots and pages for two sequences of 52 tokens (13 pages each
    of 31): the third prompt's 10 pages are not there while the two
    decode, with a slot free."""
    eng = _together_and_alone(
        EngineConfig(**{**CFG, "num_pages": 32,
                        "enable_prefix_caching": False}), 3, 40, 12)
    blocked = eng.metrics.admit_blocked
    assert blocked["pages"] > 0 and blocked["state_slots"] == 0


def test_a_model_without_state_slots_counts_no_blocked_admission():
    eng = Engine(EngineConfig(model="tiny-debug", page_size=4, num_pages=20,
                              max_num_seqs=2, max_seq_len=64,
                              dtype="float32",
                              enable_prefix_caching=False))
    for i in range(4):
        eng.add_request(GenRequest(f"r{i}", prompt(i, 30), max_tokens=6,
                                   temperature=0.0, ignore_eos=True))
    drain(eng)
    assert eng.metrics.admit_blocked == {"state_slots": 0, "pages": 0}


def test_warmup_compiles_what_the_window_runs(engine):
    p, toks = warm_then_serve(engine)
    assert toks == reference_greedy(engine, p + toks, 6)


@pytest.mark.parametrize("change,word", [
    (dict(speculative_mode="ngram", num_speculative_tokens=2), "speculation"),
    (dict(lora_slots=2), "LoRA"),
    (dict(kvbm_host_blocks=8), "KVBM"),
    (dict(disaggregation_mode="prefill"), "disaggregated"),
    (dict(kv_cache_dtype="int8"), "int8"),
    (dict(tensor_parallel=2), "tensor parallelism"),
], ids=["speculation", "lora", "kvbm", "disagg", "int8_kv", "tp"])
def test_what_a_state_slot_does_not_serve_is_refused(change, word):
    with pytest.raises(ValueError, match=word):
        Engine(EngineConfig(**{**CFG, **change}), model_cfg=tiny())


def test_a_finish_rides_the_pipeline(sync_engine, engine):
    """Sequences leave a running batch by `max_tokens` and on stop tokens
    with no program read early. A retired row's state slot in ALL layers
    is neither advanced by the next program nor handed to a prompt while
    the program in flight still updates it (the decode slot IS the state
    slot): tokens, `metrics.ssm` and `metrics.attn_kinds` are the
    synchronous order's."""
    assert_finish_rides_pipeline(sync_engine, engine,
                                 lambda i: prompt(40 + i, 5 + i))


def test_a_first_token_rides_the_pipeline(sync_engine, engine):
    """Prompts end beside a sequence that keeps decoding: the final
    chunk's program samples the first token and installs the row; the
    newcomer's state in ALL layers is where its chunks left it when the
    next program decodes its row. Tokens, logprobs, `metrics.ssm` and
    `metrics.attn_kinds` are the synchronous order's."""
    assert_first_token_rides_pipeline(sync_engine, engine,
                                      lambda i, n: prompt(60 + i, n))

