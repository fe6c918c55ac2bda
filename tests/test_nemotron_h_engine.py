"""`tiny-nemotron-h-debug` through `Engine` on the CPU: the served path
(chunked prompts riding mixed steps, fused decode windows, warm-up) over a
state slot a sequence beside the ONE attention layer's pages, held to the
float32 reference's greedy tokens; a live sequence holds exactly one slot
from its first chunk to its last token; pages and slots conserved across
finish, abort, preemption and resume; a reused slot starts from zero; a
prefix hit counted inexact and served by recompute; `metrics.ssm`,
`metrics.attn_kinds.full` and the memory snapshot; what is refused."""

import functools

import jax.numpy as jnp
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine
from dynamo_tpu.engine.request import GenRequest
from dynamo_tpu.models.reference import nemotron_h as ref
from dynamo_tpu.observability.memory import MemoryAccountant

from nemotron_h_common import hf_dict, tiny

from pipelined_common import (
    assert_finish_rides_pipeline, assert_first_token_rides_pipeline,
    assert_windows_as_long_as_the_shortest_headroom, drain, engine_pair,
    greedy_of, prompt, slots_held, warm_then_serve)

CFG = dict(model="tiny-nemotron-h-debug", page_size=4, num_pages=128,
           max_num_seqs=4, max_seq_len=128, prefill_chunk_tokens=8,
           mixed_batch_tokens=8, num_scheduler_steps=4, dtype="float32")

reference_greedy = functools.partial(greedy_of, ref, hf_dict)
engine, sync_engine = engine_pair(CFG)


# the state update's kernel (interpret mode: ops/ssm.update_live walks the
# live slots only and leaves a dead slot's state where it lies); attention
# takes its XLA path at these head sizes, which is counted and no matter here
KERNEL = dict(CFG, attention_backend="pallas_interpret")
kernel_engine, kernel_sync_engine = engine_pair(KERNEL)


def test_two_sequences_of_very_different_lengths_match_the_reference(engine):
    """A 70-token prompt (nine chunks: its state rides its slot from step
    to step) beside a 9-token one that arrives while it decodes: the short
    one's chunks ride mixed steps. Greedy tokens are the reference's; every
    live sequence holds ONE slot whatever its length; afterwards pages and
    slots are whole again."""
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
    # a token a live row a step, the prompts' first tokens apart
    assert ssm["decode_rows"] == (24 - 1) + (12 - 1)
    assert ssm["layer_steps"] >= ssm["chunk_calls"]
    kinds = eng.metrics.attn_kinds
    assert kinds["full"]["decode_q_rows"] > 0
    assert kinds["full"]["mixed_chunk_q_rows"] == 79
    assert not any(kinds["window"].values())  # it has no such layer
    counters = eng.metrics.kernel_counters()
    assert counters["ssm"] == ssm
    # four expert layers a step beside four Mamba-2 ones
    assert counters["moe"]["layer_steps"] > 0


def test_a_prefix_hit_is_counted_inexact_and_served_by_recompute(engine):
    """The same prompt again: its full pages are in the prefix cache, the
    state at their end is not (one larger than a block's KV is kept nowhere), so the hit is turned
    into a miss, counted, and the tokens are the first run's."""
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


@pytest.mark.parametrize("which", ["engine", "kernel_engine"])
def test_a_slot_reused_after_a_finish_or_an_abort_starts_from_zero(request,
                                                                   which):
    """Slots are handed out last-freed first, so each request here decodes
    in the slot its predecessor left its state in (under the kernel a dead
    slot keeps its last state until a prompt's first chunk zeroes it):
    after a finish and after an abort the next tenant's tokens are the
    reference's. `metrics.ssm.slots_touched` says which path ran: the live
    rows under the kernel, every slot a step under the XLA twin."""
    eng = request.getfixturevalue(which)
    before, steps_before = dict(eng.metrics.ssm), eng.metrics.decode_steps
    eng.add_request(GenRequest("a", prompt(20, 30), max_tokens=10,
                               temperature=0.0, ignore_eos=True))
    drain(eng)
    slot_a = eng._free_slots[-1]
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
    grew = {k: v - before[k] for k, v in eng.metrics.ssm.items()}
    assert grew["decode_rows"] > 0
    if which == "kernel_engine":
        assert grew["slots_touched"] == grew["decode_rows"]
    else:  # every step over the decode batch; prompt rows alone: no update
        assert grew["slots_touched"] == 4 * (
            eng.metrics.decode_steps - steps_before)


@pytest.fixture(scope="module")
def seeded_streams():
    """Three sampled requests with seeds of their own, the first done after
    5 tokens (its slot is dead for the rest of the run, between and inside
    the others' windows), through an engine on the state update's kernel
    path: (num_scheduler_steps, async_scheduling) -> {request: tokens},
    each engine built once."""
    done = {}

    def run(steps: int, async_sched: bool) -> dict:
        key = (steps, async_sched)
        if key not in done:
            eng = Engine(EngineConfig(**{
                **KERNEL, "num_scheduler_steps": steps,
                "async_scheduling": async_sched}))
            for i, n_new in enumerate((5, 37, 50)):
                eng.add_request(GenRequest(
                    f"r{i}", prompt(30 + i, 11 + 6 * i), max_tokens=n_new,
                    temperature=1.0, seed=100 + i, ignore_eos=True))
            done[key] = drain(eng)
            ssm = eng.metrics.ssm
            assert ssm["slots_touched"] == ssm["decode_rows"] > 0
        return done[key]
    return run


@pytest.mark.parametrize("steps,async_sched", [(1, True), (16, False),
                                               (16, True)])
def test_seeded_streams_are_the_same_bytes_whatever_the_window_and_schedule(
        seeded_streams, steps, async_sched):
    """The 16-step window, the 1-step window and the mixed step's decode
    rows run ONE arithmetic (the same kernel over the same live list), so a
    seeded stream is byte-identical across window lengths and async
    scheduling on / off, with a slot going dead mid-run."""
    want = seeded_streams(1, False)
    assert [len(want[f"r{i}"]) for i in range(3)] == [5, 37, 50]
    assert seeded_streams(steps, async_sched) == want


def test_memory_snapshot_counts_slots_beside_pages(engine):
    eng = engine
    eng.add_request(GenRequest("m", prompt(4, 50), max_tokens=30,
                               temperature=0.0, ignore_eos=True))
    eng.step()  # admitted: the first chunk has run, the slot is held
    snap = MemoryAccountant(eng).snapshot()
    per_slot = 4 * (4 * 8 * 8 * 4 + 3 * 64 * 4)  # S float32 + conv rows
    assert snap["bytes_per_slot"] == per_slot
    assert snap["state_slots"] == {"held": 1, "total": 4, "bytes": per_slot}
    # the page pool is the ONE attention layer's
    assert snap["bytes_per_token"] == 2 * 2 * 16 * 4
    for _ in range(12):
        eng.step()
    assert slots_held(eng) == 1
    drain(eng)
    assert MemoryAccountant(eng).snapshot()["state_slots"]["held"] == 0


def test_preemption_and_resume_conserve_pages_and_slots():
    """A pool too small for three sequences' contexts: the engine preempts
    by recompute (the state is dropped, the prompt and what was decoded
    prefilled again from zero) and resumes; every request completes with
    the tokens it gets alone, and pages and slots end whole."""
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
    whose decoders leave before it is done, nor a long one; the state
    arrays ride every step program as donated buffers and come back."""
    p, toks = warm_then_serve(engine)
    assert toks == reference_greedy(engine, p + toks, 6)
    assert len(engine.k_pages.state) == len(engine.v_pages.state) == 4
    assert engine.k_pages.state[0].dtype == jnp.float32


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
    with no program read early; the leaver's state slot waits for the
    program in flight where that program still updates it: tokens and
    `metrics.ssm` are the synchronous order's."""
    assert_finish_rides_pipeline(sync_engine, engine,
                                 lambda i: prompt(40 + i, 5 + i))

def test_windows_of_every_length_give_the_single_steps_tokens(
        sync_engine, engine):
    """Rows end at every offset of a window, so the fused program runs at
    every trip count 1 .. 4: the Mamba-2 state slots' recurrence
    stops where the loop stops, the live slots' list is built once a window.
    Tokens, logprobs and the counters are those of a classic program a
    step (num_scheduler_steps=1), in both orders."""
    single = Engine(EngineConfig(**{**CFG, "num_scheduler_steps": 1,
                                    "async_scheduling": False}))
    assert_windows_as_long_as_the_shortest_headroom(
        single, [sync_engine, engine],
        lambda i: prompt(100 + i, 5 + i % 3))


def test_a_finish_rides_the_pipeline_over_the_live_slots_kernel(
        kernel_sync_engine, kernel_engine):
    """The same under the state update's kernel, which walks the live
    slots' list: a retired slot is off that list in the next program, so
    its state is neither read nor written while it waits."""
    assert_finish_rides_pipeline(kernel_sync_engine, kernel_engine,
                                 lambda i: prompt(50 + i, 5 + i))


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["xla_twin", "live_slots_kernel"])
def test_a_first_token_rides_the_pipeline(request, kernel):
    """Prompts end beside a sequence that keeps decoding: the final
    chunk's program samples the first token and installs the row, the
    chunks have written the prompt's state into its reserved slot already
    and the join only flips the slot's table row and mask bit (under the
    kernel: puts it on the live slots' list of the NEXT program). Tokens,
    logprobs and `metrics.ssm` are the synchronous order's."""
    sync, eng = (request.getfixturevalue(name) for name in (
        ("kernel_sync_engine", "kernel_engine") if kernel
        else ("sync_engine", "engine")))
    assert_first_token_rides_pipeline(sync, eng,
                                      lambda i, n: prompt(60 + i, n))
