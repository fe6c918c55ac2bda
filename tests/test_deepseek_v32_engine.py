"""DeepSeek-V3.2's block through the Engine (tests/test_deepseek_v32.py holds
the model's functions to the float32 reference): mixed steps, the fused decode
window, the prefix cache, metrics.dsa, and the indexer's key rows following
their pages through a prefix hit, preemption and the host tier."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import Engine, EngineMetrics
from dynamo_tpu.engine.kv_cache import KVCacheSpec
from dynamo_tpu.engine.request import GenRequest
from dynamo_tpu.models import llama
from dynamo_tpu.models.reference import deepseek_v32 as ref

from tests.deepseek_v32_common import PS, TOPK, ref_config, tapped, tiny
from tests.pipelined_common import (
    assert_finish_rides_pipeline, assert_pipelined_matches_sync,
    assert_windows_as_long_as_the_shortest_headroom)


def _engine(**kw):
    base = dict(model="tiny-dsv32-ep4-debug", dtype="float32", page_size=4,
                num_pages=128, max_num_seqs=4, max_seq_len=128,
                mixed_batch_tokens=16, num_scheduler_steps=4)
    base.update(kw)
    return Engine(EngineConfig(**base))


SHARED = [(i * 7) % 290 + 5 for i in range(40)]


def _req(rid, tail, n=12, **kw):
    return GenRequest(rid, SHARED + tail, max_tokens=n, temperature=0.0,
                      ignore_eos=True, **kw)


def _drive(eng, first, then=(), on_token=None):
    """Serve `first`; once it decodes, add `then` (they prefill beside it
    by mixed steps). {request id: [(token, logprob)]}."""
    outs, later = {}, list(then)
    eng.add_request(first)
    while eng.has_work:
        for ev in eng.step():
            if ev.token_id >= 0:
                outs.setdefault(ev.request_id, []).append(
                    (ev.token_id, ev.logprob))
                if on_token is not None:
                    on_token(ev, outs)
            if later:
                eng.add_request(later.pop(0))
    return outs


def test_engine_matches_reference_and_counts():
    """Through the Engine (float32): a prompt's 16-token chunks riding
    mixed steps beside a decoding sequence, its prefix served from cached
    pages, the fused decode window; the chosen tokens' log-probabilities
    against the reference's full forward, and metrics.dsa's arithmetic."""
    eng = _engine()
    outs = _drive(eng, _req("a", [50, 51, 52], logprobs=1),
                  [_req("b", [60, 61, 62, 63, 64], logprobs=1)])
    assert [len(v) for v in outs.values()] == [12, 12]
    assert eng.prefix_cache.cached_tokens_served >= 36  # b found a's pages
    assert eng.metrics.mixed_count > 0
    mcfg = dataclasses.replace(eng.model_cfg, dtype="float32")
    fp = ref.dequantize(jax.device_get(eng.params))
    rshare = ref.Share(mcfg.local_expert_offset, mcfg.held_experts)
    for rid, tail in (("a", [50, 51, 52]), ("b", [60, 61, 62, 63, 64])):
        prompt = SHARED + tail
        toks = [t for t, _ in outs[rid]]
        want, _, _ = ref.forward(ref_config(mcfg), fp,
                                 jnp.asarray(prompt + toks[:-1]), rshare)
        lp = jax.nn.log_softmax(want, -1)
        for i, (tok, got) in enumerate(outs[rid]):
            # float32 engine, but its programs differ in the order of sums
            # from the reference's: 1e-3 on log-probabilities
            assert abs(float(lp[len(prompt) - 1 + i, tok]) - got) < 1e-3
    d = eng.metrics.snapshot()["dsa"]
    assert d["decode_queries"] > 0 and d["chunk_queries"] > 0
    # every context here is past index_topk = 16 but for a prompt's first
    # 16 tokens: a's, and nothing of b's (its first 40 came from the cache)
    assert d["queries_unselected"] == TOPK
    assert d["chunk_queries"] == (len(SHARED) + 3) + (5 + 40 - 40 // PS * PS)
    q = d["decode_queries"] + d["chunk_queries"]
    assert d["rows_selected"] == (q - TOPK) * TOPK + TOPK * (TOPK + 1) // 2
    assert d["decode_keys_scored"] >= 43 * d["decode_queries"]
    assert d["chunk_keys_scored"] > d["rows_selected"] - d["decode_queries"] * TOPK


@pytest.fixture(scope="module")
def pair():
    """(synchronous, pipelined) engines; a test leaves both idle."""
    return (_engine(async_scheduling=False, enable_prefix_caching=False),
            _engine(enable_prefix_caching=False))


def test_mixed_steps_behind_the_pipeline_match_the_synchronous_order(pair):
    """A 43-token prompt's three chunks, each dispatched on the device
    outputs of the program before it; the decode rows select over the live
    slots' rung at contexts the host has not read yet: tokens and
    `metrics.dsa` / `metrics.attn` are the synchronous order's."""
    assert_pipelined_matches_sync(
        *pair, _req("live", [50, 51, 52], n=28),
        _req("late", [60, 61, 62], n=9))


def test_a_finish_rides_the_pipeline(pair):
    """Sequences leave a running batch by `max_tokens` and on stop tokens
    with no program read early: the rows that stay select over the live
    slots' rung without the retired row (its table row is trash), at
    contexts past `index_topk`: tokens, `metrics.dsa` and `metrics.attn`
    are the synchronous order's."""
    assert_finish_rides_pipeline(
        *pair, lambda i: SHARED[:18 + i] + [70 + i, 71, 72])


def test_windows_of_every_length_give_the_single_steps_tokens(pair):
    """Rows end at every offset of a window, so the fused program runs at
    every trip count 1 .. 4, its rows selecting at contexts past
    `index_topk`: tokens, logprobs, `metrics.dsa` and `metrics.attn` are
    those of a classic program a step (num_scheduler_steps=1), in both
    orders."""
    single = _engine(async_scheduling=False, enable_prefix_caching=False,
                     num_scheduler_steps=1)
    assert_windows_as_long_as_the_shortest_headroom(
        single, list(pair),
        lambda i: SHARED[:18 + i % 3] + [70 + i, 71, 72])


def test_metrics_dsa_arithmetic():
    m = EngineMetrics()
    m.observe_dsa(16, True, contexts=[10, 30], steps=4, chunk=(8, 12))
    d = m.dsa
    assert d["decode_queries"] == 8 and d["chunk_queries"] == 12
    assert d["decode_keys_scored"] == (10 + 11 + 12 + 13) + (30 + 31 + 32 + 33)
    assert d["chunk_keys_scored"] == sum(range(9, 21))
    # contexts 10..13 and chunk contexts 9..16 are at most 16
    assert d["queries_unselected"] == 4 + 8
    assert d["rows_selected"] == (10 + 11 + 12 + 13) + 4 * 16 + sum(
        min(c, 16) for c in range(9, 21))
    m.observe_dsa(16, False, contexts=[5], steps=2)  # a program that keeps
    assert m.dsa["decode_queries"] == 10             # today's kernels
    assert m.dsa["decode_keys_scored"] == d["decode_keys_scored"]
    assert m.dsa["queries_unselected"] == 14


def _selection_of(eng, req, position_floor, interrupt=None):
    """Tokens of `req` and its decode rows' selected sets at positions >=
    position_floor (per position, per layer), with an optional interruption
    once it has produced 3 tokens."""
    state = {"done": False}

    def on_token(ev, outs):
        if (interrupt and not state["done"] and ev.request_id == req.request_id
                and len(outs[req.request_id]) == 3):
            state["done"] = True
            interrupt(eng)

    outs, calls = tapped(lambda: _drive(eng, req, on_token=on_token))
    sets = {}
    for kind, qpos, sel, valid in calls:
        if kind != "decode":
            continue
        for q, s, v in zip(qpos, sel, valid):
            if int(q) >= position_floor:
                sets.setdefault(int(q), []).append(frozenset(s[v].tolist()))
    return [t for t, _ in outs[req.request_id]], sets


def test_index_rows_follow_their_pages():
    """The indexer's key rows live under the same page ids as the latent
    rows, so whatever moves pages moves both: after a prefix-cache hit,
    after preemption and resume, and after a kvbm demote / onboard round
    trip the tokens AND the decode rows' selected sets are those of an
    uninterrupted run."""
    tail = [50, 51, 52]
    floor = len(SHARED) + len(tail) + 4  # decode rows after any interruption
    want_toks, want_sets = _selection_of(_engine(), _req("r", tail), floor)
    assert len(want_sets) >= 6
    assert all(len(v) == 3 and all(len(s) == TOPK for s in v)
               for v in want_sets.values())

    def check(toks, sets):
        assert toks == want_toks
        for pos, per_layer in want_sets.items():
            # a position decoded once gives one set a layer; preemption
            # recomputes some by a chunk, the rest decode as before
            assert sets.get(pos, per_layer)[-3:] == per_layer

    # a prefix-cache hit: the prompt's pages come from an earlier request
    eng = _engine()
    eng.generate(_req("warm", [70, 71], n=2))
    toks, sets = _selection_of(eng, _req("r", tail), floor)
    assert eng.prefix_cache.cached_tokens_served >= 36
    check(toks, sets)
    # preemption by recompute, then resume
    eng = _engine()
    toks, sets = _selection_of(
        eng, _req("r", tail), floor,
        interrupt=lambda e: e._preempt_slot(next(iter(e.seqs))))
    assert eng.metrics.num_preempted == 1
    check(toks, sets)
    # kvbm: the prefix is demoted to the host tier by an unrelated prompt,
    # then onboarded for the request
    eng = _engine(num_pages=26, max_num_seqs=2, kvbm_host_blocks=64)
    eng.generate(_req("warm", [70, 71], n=2))
    other = [(i * 11) % 290 + 3 for i in range(60)]
    eng.generate(GenRequest("fill", other, max_tokens=4, temperature=0.0,
                            ignore_eos=True))
    toks, sets = _selection_of(eng, _req("r", tail), floor)
    st = eng.kvbm.stats()
    assert st["demoted_blocks_total"] > 0 and st["onboarded_blocks_total"] > 0
    assert eng.kvbm.pool.v_block_shape[-1] == 32
    check(toks, sets)


def test_what_is_not_served_refuses_loudly():
    with pytest.raises(ValueError, match="int8"):
        KVCacheSpec.from_model(tiny(), 8, PS, kv_dtype="int8")
    with pytest.raises(ValueError, match="speculation"):
        _engine(speculative_mode="ngram", num_speculative_tokens=2)
    cfg = tiny()
    with pytest.raises(NotImplementedError, match="selection"):
        llama.decode_verify(cfg, {}, jnp.zeros((1, 2), jnp.int32), None,
                            None, None, None, None, page_size=PS)


def test_memory_snapshot_names_both_row_kinds():
    from dynamo_tpu.observability.memory import MemoryAccountant

    snap = MemoryAccountant(_engine()).snapshot()
    assert snap["row_lanes"] == {"k_pool": 40, "v_pool": 32,
                                 "v_pool_holds": "indexer_key"}
    assert snap["bytes_per_token"] == 3 * (40 + 32) * 4
    assert snap["page_bytes"] == snap["bytes_per_token"] * 4
