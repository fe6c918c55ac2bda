"""`tiny-lfm2-moe-debug` through `Engine` on the CPU: the served path
(chunked prompts riding mixed steps, fused decode windows, warm-up) over a
state slot of conv rows alone beside the two attention layers' pages, held
to the float32 reference's greedy tokens; a live sequence holds exactly one
slot from its first chunk to its last token; pages and slots conserved
across finish (PR 50's held finish included), abort, preemption and resume;
a reused slot starts from zeros; a first token that rides (PR 51) joins with
the state its final chunk left; a prefix hit counted inexact and served by
recompute; `metrics.conv`, `metrics.attn_kinds.full` and the memory
snapshot; what is refused."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.engine.request import GenRequest
from dynamo_tpu.models.reference import lfm2_moe as ref
from dynamo_tpu.observability.memory import MemoryAccountant

from lfm2_moe_common import hf_dict, tiny

from pipelined_common import (
    assert_finish_rides_pipeline, assert_first_token_rides_pipeline,
    assert_windows_as_long_as_the_shortest_headroom, drain, engine_pair,
    greedy_of, prompt, slots_held, warm_then_serve)

CFG = dict(model="tiny-lfm2-moe-debug", page_size=4, num_pages=128,
           max_num_seqs=4, max_seq_len=128, prefill_chunk_tokens=8,
           mixed_batch_tokens=8, num_scheduler_steps=4, dtype="float32")

reference_greedy = functools.partial(greedy_of, ref, hf_dict)
engine, sync_engine = engine_pair(CFG)


def test_two_sequences_of_very_different_lengths_match_the_reference(engine):
    """A 70-token prompt (nine chunks: its two conv rows ride its slot from
    step to step in seven layers) beside a 9-token one that arrives while
    it decodes: the short one's chunks ride mixed steps. Greedy tokens are
    the reference's; every live sequence holds ONE slot whatever its
    length; afterwards pages and slots are whole again."""
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
    conv = eng.metrics.conv
    assert conv["chunk_tokens"] == 79 and conv["chunk_calls"] == 9 + 2
    # a token a live row a step, the prompts' first tokens apart
    assert conv["decode_rows"] == (24 - 1) + (12 - 1)
    assert conv["layer_steps"] >= conv["chunk_calls"]
    # the layer's slots are read and written as one block a decode step
    assert conv["slots_touched"] % 4 == 0 and conv["slots_touched"] > 0
    assert not any(eng.metrics.ssm.values())  # no recurrence is counted
    kinds = eng.metrics.attn_kinds
    assert kinds["full"]["decode_q_rows"] > 0
    assert kinds["full"]["mixed_chunk_q_rows"] == 79
    assert not any(kinds["window"].values())  # it has no such layer
    counters = eng.metrics.kernel_counters()
    assert counters["conv"] == conv
    assert counters["moe"]["layer_steps"] > 0  # seven expert layers a step


def test_a_prefix_hit_is_counted_inexact_and_served_by_recompute(engine):
    """The same prompt again: its full pages are in the prefix cache, the
    two conv rows at their end are not (at this page size they cost more
    than a block's KV, and such a state is kept nowhere), so the hit is
    turned into a miss, counted, and the tokens are the first run's."""
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
    # at 4-token pages the two rows cost more than a block's KV (3,584 B
    # against 2,048): kept at no boundary (tests/test_prefix_state.py: at 8)
    assert eng._state_snaps is None and eng._prefix_recomputed
    assert "snapshots" not in eng.prefix_cache.stats()


def test_a_slot_reused_after_a_finish_or_an_abort_starts_from_zero(engine):
    """Slots are handed out last-freed first, so each request here decodes
    in the slot its predecessor left its rows in: after a finish and after
    an abort the next tenant's tokens are the reference's (a prompt's first
    chunk starts from zeros whatever the slot held)."""
    eng = engine
    eng.add_request(GenRequest("a", prompt(20, 30), max_tokens=10,
                               temperature=0.0, ignore_eos=True))
    drain(eng)
    slot_a = eng._free_slots[-1]
    assert float(jnp.abs(eng.v_pages.state[0][:, slot_a]).max()) > 0
    eng.add_request(GenRequest("b", prompt(21, 26), max_tokens=40,
                               temperature=0.0, ignore_eos=True))
    for _ in range(8):
        eng.step()
    assert list(eng.seqs) == [slot_a]
    eng.abort_request("b")
    drain(eng)
    assert slots_held(eng) == 0
    p = prompt(22, 19)
    eng.add_request(GenRequest("c", p, max_tokens=10, temperature=0.0,
                               ignore_eos=True))
    eng.step()
    assert (eng._inflight.slot if eng._inflight else list(eng.seqs)[0]
            ) == slot_a
    toks = drain(eng)["c"]
    assert toks == reference_greedy(eng, p + toks, 10)


def test_an_empty_slots_rows_stay_bit_for_bit_through_others_steps(engine):
    """What a finished sequence left in its slot is neither read nor
    written while another sequence prefills and decodes in another slot."""
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
    assert "y" not in done
    eng.step()  # whatever was in flight behind x's finish has been read
    eng.step()
    (slot_y,) = list(eng.seqs)
    dead = [s for s in range(4) if s != slot_y]
    before = np.asarray(eng.v_pages.state[0])[:, dead].copy()
    drain(eng)
    after = np.asarray(eng.v_pages.state[0])[:, dead]
    assert before.any() and np.array_equal(before, after)


def test_memory_snapshot_counts_slots_beside_pages(engine):
    eng = engine
    eng.add_request(GenRequest("m", prompt(4, 50), max_tokens=30,
                               temperature=0.0, ignore_eos=True))
    eng.step()  # admitted: the first chunk has run, the slot is held
    snap = MemoryAccountant(eng).snapshot()
    per_slot = 7 * 2 * 64 * 4  # seven conv layers, two rows of 64 float32
    assert snap["bytes_per_slot"] == per_slot == eng.kv_spec.bytes_per_slot()
    assert snap["state_slots"] == {"held": 1, "total": 4, "bytes": per_slot}
    # the page pool is the TWO attention layers'
    assert snap["bytes_per_token"] == 2 * 2 * 2 * 16 * 4
    assert eng.k_pages.state == () and len(eng.v_pages.state) == 1
    assert eng.v_pages.state[0].shape == (7, 4, 2, 64)
    for _ in range(12):
        eng.step()
    assert slots_held(eng) == 1
    drain(eng)
    assert MemoryAccountant(eng).snapshot()["state_slots"]["held"] == 0


def test_bytes_per_slot_at_the_published_depth():
    """147,456 B: 18 conv layers x 2 rows x 2,048 lanes x 2 B, no ssm part."""
    import os

    from dynamo_tpu.engine.kv_cache import KVCacheSpec
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_model_name(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks/chip/configs/lfm2-8b-a1b-w8a8-1chip"))
    spec = KVCacheSpec.from_model(cfg, 8192, 16, state_slots=64)
    assert spec.bytes_per_slot() == 147456 and spec.ssm_shape == ()
    assert (spec.num_layers, spec.state_layers) == (6, 18)
    assert spec.shape == (6, 8192, 16, 512)  # 8 KV heads of 64 lanes a row


def test_preemption_and_resume_conserve_pages_and_slots():
    """A pool too small for three sequences' contexts: the engine preempts
    by recompute (the rows are dropped, the prompt and what was decoded
    prefilled again from zeros) and resumes; every request completes with
    the tokens it gets alone (a preempted sequence's recomputed logits pick
    the unbroken run's tokens), and pages and slots end whole."""
    small = EngineConfig(**{**CFG, "num_pages": 40,
                            "enable_prefix_caching": False})
    eng = Engine(small)
    prompts = {f"r{i}": prompt(10 + i, 30) for i in range(3)}
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
    """After warmup() no request compiles a program: not a short prompt
    whose decoders leave before it is done, nor a long one; the conv rows
    ride every step program as a donated buffer and come back."""
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


def test_windows_of_every_length_give_the_single_steps_tokens(
        sync_engine, engine):
    """Rows end at every offset of a window, so the fused program runs at
    every trip count 1 .. 4: a slot's two conv rows are those of the
    last step run, and the experts' counts are summed in the loop's carry.
    Tokens, logprobs and the counters are those of a classic program a
    step (num_scheduler_steps=1), in both orders."""
    single = Engine(EngineConfig(**{**CFG, "num_scheduler_steps": 1,
                                    "async_scheduling": False}))
    assert_windows_as_long_as_the_shortest_headroom(
        single, [sync_engine, engine],
        lambda i: prompt(100 + i, 5 + i % 3))


def test_a_finish_rides_the_pipeline(sync_engine, engine):
    """Sequences leave a running batch by `max_tokens` and on stop tokens
    with no program read early; the leaver's slot of conv rows waits for
    the program in flight where that program still updates it: tokens and
    the counters are the synchronous order's."""
    assert_finish_rides_pipeline(sync_engine, engine,
                                 lambda i: prompt(40 + i, 5 + i))


def test_a_first_token_rides_the_pipeline(sync_engine, engine):
    """Prompts end beside a sequence that keeps decoding: the final chunk's
    program samples the first token and installs the row; the chunks have
    written the prompt's two conv rows into its reserved slot already, and
    the join only flips the slot's table row and mask bit. Tokens and
    logprobs are the synchronous order's."""
    assert_first_token_rides_pipeline(sync_engine, engine,
                                      lambda i, n: prompt(60 + i, n))
