"""DeepSeek-V3.2's block on the serving path, against the plain float32
reference (dynamo_tpu/models/reference/deepseek_v32.py): MLA under the
lightning indexer's learned sparse selection (a second kind of per-token
state in the paged pools, a per-query row list chosen on the device), and
group-limited sigmoid routing, as one chip's share and uncut."""

import dataclasses
import importlib.util
import os
import re
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.kv_cache import KVCacheSpec, alloc_kv_pages
from dynamo_tpu.models import llama, quant
from dynamo_tpu.models.config import ModelConfig, PRESETS
from dynamo_tpu.models.reference import deepseek_v32 as ref
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import moe as moe_ops

from tests.deepseek_v32_common import PS, TOPK, ref_config, tapped, tiny

SHARES = 4      # 16 experts over 4 chips

# float32 program against the float32 reference on the same weights: what
# is left is the order of sums (absorbed against expanded MLA, grouped
# against looped experts): a few 1e-6 on logits of spread ~0.5 (measured
# 1.3e-6). A wrong selection moves them by ~1 (the controls below).
TOL = dict(rtol=2e-4, atol=2e-4)


def seeded(cfg, key=3):
    """Random weights with a selection bias that moves picks and an
    indexer LayerNorm whose weight and bias are not the identity."""
    p = llama.init_params(cfg, jax.random.PRNGKey(key))
    ks = jax.random.split(jax.random.PRNGKey(key + 1), 5)
    p["router_bias"] = 0.3 * jax.random.normal(
        ks[0], p["router_bias"].shape, jnp.float32)
    for i, pre in enumerate(("", "dense.")):
        p[pre + "idx_k_bias"] = 0.2 * jax.random.normal(
            ks[1 + i], p[pre + "idx_k_bias"].shape, jnp.float32)
        p[pre + "idx_k_norm"] = 1.0 + 0.2 * jax.random.normal(
            ks[3 + i], p[pre + "idx_k_norm"].shape, jnp.float32)
    return p


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, seeded(cfg)


def cut(cfg, p, r: int, held: int = 16 // SHARES):
    """Share r of the uncut model: its config and its slice of the weights."""
    lo = r * held
    scfg = dataclasses.replace(cfg, num_local_experts=held,
                               local_expert_offset=lo)
    sp = dict(p)
    for name in ("moe_w_gate", "moe_w_up", "moe_w_down"):
        sp[name] = p[name][:, lo:lo + held]
    return scfg, sp, ref.Share(lo, held)


N = 40  # tokens: contexts both under and over index_topk = 16
TOKENS = [int(t) for t in np.random.default_rng(0).integers(1, 500, N)]


@pytest.fixture(scope="module")
def reference(model):
    cfg, p = model
    return ref.forward(ref_config(cfg), ref.dequantize(p),
                       jnp.asarray(TOKENS))


# ------------------------------------------------------------- the config --

# the catalog row's `config` (model-configs guide, architectures.jsonl,
# DeepSeek-V3.2-Exp), verbatim
V32 = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 129280,
}


def test_published_config_is_read():
    """The catalog row with num_nextn_predict_layers 0 and nothing else
    changed gives the published sizes."""
    cfg = ModelConfig.from_hf_config({**V32, "num_nextn_predict_layers": 0})
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_layers) == (7168, 128, 61)
    assert (cfg.first_k_dense, cfg.dense_intermediate_size,
            cfg.intermediate_size) == (3, 18432, 2048)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.n_group,
            cfg.topk_group) == (256, 8, 8, 4)
    assert cfg.moe_scoring == "sigmoid" and cfg.router_bias
    assert cfg.routed_scaling_factor == 2.5 and cfg.num_shared_experts == 1
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        64, 128, 2048)
    assert cfg.is_dsa and cfg.cache_head_dim == 640
    assert cfg.cache_index_dim == 128
    assert cfg.rope_yarn_scaling == (40.0, 32.0, 1.0, 4096, 1.0, 1.0, -1.0)
    specs = llama.param_specs(cfg)
    assert specs["idx_wq_b"][0] == (58, 1536, 64, 128)
    assert specs["dense.idx_wk"][0] == (3, 7168, 128)
    assert specs["idx_w"][0] == (58, 7168, 64)


def test_multi_token_prediction_still_refuses():
    with pytest.raises(ValueError, match="multi-token-prediction"):
        ModelConfig.from_hf_config(V32)
    with pytest.raises(ValueError, match="multi-token-prediction"):
        ref.Config.from_hf(V32)


@pytest.mark.parametrize("change, match", [
    (dict(n_group=3), "group-limited"),             # 16 % 3
    (dict(n_group=4, topk_group=5), "group-limited"),
    (dict(n_group=8, topk_group=1), "group-limited"),  # 2 experts < k = 4
    (dict(moe_scoring="softmax"), "group-limited"),
    (dict(index_head_dim=4), "indexer"),            # < qk_rope_head_dim
    (dict(q_lora_rank=0), "indexer"),
    (dict(index_topk=0), "without"),
])
def test_config_checks_are_loud(change, match):
    with pytest.raises(ValueError, match=match):
        tiny(**change)


def test_the_cells_share_is_read():
    """The benchmark configuration's own config.json: widths as published,
    the counting keys giving what is held."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = ModelConfig.from_model_name(os.path.join(
        here, "benchmarks", "chip", "configs",
        "deepseek-v32-w8a8-ep16-1chip"))
    assert (cfg.num_experts, cfg.held_experts, cfg.local_expert_offset) == (
        256, 16, 0)
    assert (cfg.num_layers, cfg.first_k_dense, cfg.vocab_size) == (9, 1, 16160)
    assert (cfg.n_group, cfg.topk_group, cfg.index_topk) == (8, 4, 2048)
    spec = KVCacheSpec.from_model(cfg, num_pages=8192, page_size=16)
    assert spec.bytes_per_token() == (640 + 128) * 2 * 9 == 13824
    assert spec.shape[-1] == 640 and spec.v_shape[-1] == 128


# ------------------------------------------------------------- the router --

def test_group_limited_routing_matches_reference_on_10000_rows():
    """ids and weights, the published widths' structure (256 experts in 8
    groups, 4 kept, 8 picked) on random score rows with a bias."""
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.normal(size=(10000, 256)), jnp.float32)
    bias = jnp.asarray(0.2 * rng.normal(size=(256,)), jnp.float32)
    topi, w = moe_ops.route_topk(logits, 8, renormalize=True,
                                 scaling_factor=2.5, scoring="sigmoid",
                                 select_bias=bias, n_group=8, topk_group=4)
    rc = dataclasses.replace(
        ref_config(tiny()), n_routed_experts=256, num_experts_per_tok=8,
        n_group=8, topk_group=4)
    # the reference routes x @ W_r: hand it the logits through an identity
    picked, rw = ref.route(rc, {"router": jnp.eye(256, dtype=jnp.float32),
                                "router_bias": bias}, logits)
    np.testing.assert_array_equal(np.sort(topi, -1), np.sort(picked, -1))
    order, rorder = np.argsort(topi, -1), np.argsort(picked, -1)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w), order, -1),
                               np.take_along_axis(np.asarray(rw), rorder, -1),
                               rtol=1e-6)
    # the picks really are limited: at most 4 groups a row, and an
    # unlimited pick differs on a good part of the rows
    assert int(np.max([len(set(r // 32)) for r in np.asarray(topi)])) <= 4
    free, _ = moe_ops.route_topk(logits, 8, scoring="sigmoid",
                                 select_bias=bias)
    assert np.mean(np.sort(free, -1) != np.sort(topi, -1)) > 0.2


def test_one_group_is_todays_router():
    logits = jnp.asarray(np.random.default_rng(6).normal(size=(64, 16)),
                         jnp.float32)
    kw = dict(scaling_factor=2.5, scoring="sigmoid")
    a = moe_ops.route_topk(logits, 4, **kw)
    b = moe_ops.route_topk(logits, 4, n_group=1, topk_group=1, **kw)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    text = [jax.jit(lambda x, kw2=kw2: moe_ops.route_topk(x, 4, **kw, **kw2)
                    ).lower(logits).as_text()
            for kw2 in ({}, dict(n_group=1, topk_group=1))]
    assert text[0] == text[1]
    with pytest.raises(ValueError, match="sigmoid"):
        moe_ops.route_topk(logits, 4, n_group=4, topk_group=2)


# ------------------------------------------------- program vs reference --

def _chunk_table(spec, pages, chunk_tokens):
    """A chunked prompt's page table as the engine hands it to a chunk
    program: the bucket's pages, then the chunk's trash tail."""
    width = spec.page_table_width(len(pages) * spec.page_size, chunk_tokens)
    return jnp.concatenate(
        [pages, jnp.zeros((width - len(pages),), pages.dtype)])


def _run_program(cfg, p, tokens, n_prefill=24, n_chunk=8):
    """The serving path's forward functions through the paged cache: a
    bucket-sized prefill (24 tokens: past index_topk, so it selects), one
    chunk over the cached prefix, then decode steps in a batch of two slots
    of which one is empty. Returns {position: logits}."""
    n = len(tokens)
    spec = KVCacheSpec.from_model(cfg, num_pages=32, page_size=PS)
    kp, vp = alloc_kv_pages(spec)
    assert kp.shape[-1] == 40 and vp.shape[-1] == 32  # latent row, index key
    pages = jnp.arange(1, 13, dtype=jnp.int32)  # 48 positions
    toks = jnp.asarray(tokens, jnp.int32)
    got = {}
    out = llama.prefill(cfg, p, toks[:n_prefill], jnp.int32(n_prefill), kp,
                        vp, pages[:n_prefill // PS], page_size=PS)
    got[n_prefill - 1] = out.last_logits
    end = n_prefill + n_chunk
    out = llama.prefill_chunk(
        cfg, p, toks[n_prefill:end], jnp.int32(n_prefill), jnp.int32(n_chunk),
        out.k_pages, out.v_pages, _chunk_table(spec, pages, n_chunk),
        page_size=PS)
    got[end - 1] = out.last_logits
    kp, vp = out.k_pages, out.v_pages
    tables = jnp.stack([pages, jnp.zeros_like(pages)])
    for pos in range(end, n):
        out = llama.decode_step(
            cfg, p, jnp.asarray([tokens[pos], 0], jnp.int32),
            jnp.asarray([pos, 0], jnp.int32), tables,
            jnp.asarray([pos + 1, 1], jnp.int32), kp, vp, page_size=PS)
        kp, vp = out.k_pages, out.v_pages
        got[pos] = out.logits[0]
    return got


def _sets_by_layer(calls, cfg, positions):
    """{position: [per layer: frozenset of selected positions]} from a
    tap's calls (each program runs its layers in order)."""
    per = {pos: [] for pos in positions}
    for _, qpos, sel, valid in calls:
        for q, s, v in zip(qpos.reshape(-1), sel.reshape(-1, sel.shape[-1]),
                           valid.reshape(-1, sel.shape[-1])):
            if int(q) in per:
                per[int(q)].append(frozenset(s[v].tolist()))
    assert all(len(v) == cfg.num_layers for v in per.values()), per
    return per


@pytest.fixture(scope="module")
def program(model):
    """The uncut tiny model through `_run_program`, once, with the selection
    of every call recorded: ({position: logits}, tap calls)."""
    return tapped(lambda: _run_program(*model, TOKENS))


@pytest.mark.parametrize("share", [None, 0, 3])
def test_prefill_then_cached_decode_matches_reference(model, program, share):
    """Logits of the whole tiny model and of a share: the program's
    prefill, chunk-over-cached-prefix and decode steps against the
    reference's full forward, all at contexts past index_topk."""
    cfg, p = model
    rshare = None
    if share is not None:
        cfg, p, rshare = cut(cfg, p, share)
    want, _, _ = ref.forward(ref_config(cfg), ref.dequantize(p),
                             jnp.asarray(TOKENS), rshare)
    got = program[0] if share is None else _run_program(cfg, p, TOKENS)
    assert sorted(got) == [23, 31] + list(range(32, 40))
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], **TOL)


def test_contexts_under_index_topk_take_todays_kernels(model, reference):
    """A bucket of at most index_topk tokens selects nothing (today's
    prefill, chunk and decode ops, the V pool kept away from them) and is
    causal attention in the reference too."""
    cfg, p = model
    spec = KVCacheSpec.from_model(cfg, num_pages=8, page_size=PS)
    kp, vp = alloc_kv_pages(spec)
    pages = jnp.arange(1, 5, dtype=jnp.int32)  # 16 positions = index_topk
    toks = jnp.asarray(TOKENS, jnp.int32)
    calls = []
    att.DSA_TAP = lambda *a: calls.append(a)
    try:
        out = llama.prefill(cfg, p, toks[:8], jnp.int32(8), kp, vp, pages[:2],
                            page_size=PS)
        np.testing.assert_allclose(out.last_logits, reference[0][7], **TOL)
        out = llama.prefill_chunk(cfg, p, toks[8:12], jnp.int32(8),
                                  jnp.int32(4), out.k_pages, out.v_pages,
                                  pages, page_size=PS)
        np.testing.assert_allclose(out.last_logits, reference[0][11], **TOL)
        out = llama.decode_step(
            cfg, p, toks[12:13], jnp.asarray([12]), pages[None],
            jnp.asarray([13]), out.k_pages, out.v_pages, page_size=PS)
        np.testing.assert_allclose(out.logits[0], reference[0][12], **TOL)
        jax.effects_barrier()
    finally:
        att.DSA_TAP = None
    assert calls == []  # no program ran a selection
    # ... and the index keys were cached all the same (a later, longer
    # context needs them): layer 0's rows of the 13 positions
    assert float(jnp.abs(out.v_pages[0, 1:4]).min()) > 0


@pytest.mark.parametrize("live", [(3, 9), tuple(range(1, 11))])
def test_live_slots_alone_are_selected_for(model, reference, live):
    """A 12-slot decode batch: the selection runs over the smallest rung of
    slots that holds the live ones (8 for two live, the whole batch for
    ten), every live slot's logits are the reference's, and an empty
    slot's are whatever zeros give (the engine discards them)."""
    cfg, p = model
    spec = KVCacheSpec.from_model(cfg, num_pages=16, page_size=PS)
    kp, vp = alloc_kv_pages(spec)
    pages = jnp.arange(1, 9, dtype=jnp.int32)
    out = llama.prefill(cfg, p, jnp.asarray(TOKENS[:24]), jnp.int32(24), kp,
                        vp, pages[:6], page_size=PS)
    on = np.zeros((12,), bool)
    on[list(live)] = True
    plan = llama._dsa_live_plan(jnp.where(on[:, None], pages[None], 0))
    assert int(plan[0]) == (0 if len(live) <= 8 else 1)
    assert sorted(np.asarray(plan[1])[:len(live)].tolist()) == list(live)
    out = llama.decode_step(
        cfg, p, jnp.where(on, TOKENS[24], 0), jnp.where(on, 24, 0),
        jnp.where(on[:, None], pages[None], 0), jnp.where(on, 25, 1),
        out.k_pages, out.v_pages, page_size=PS)
    for slot in live:
        np.testing.assert_allclose(out.logits[slot], reference[0][24], **TOL)
    assert bool(jnp.all(jnp.isfinite(out.logits)))


def test_mixed_step_matches_reference(model):
    """One ragged step of a share: a decode row at a context past
    index_topk, an empty slot, and a chunk over a cached prefix."""
    cfg, p, rshare = cut(*model, 1)
    spec = KVCacheSpec.from_model(cfg, num_pages=32, page_size=PS)
    kp, vp = alloc_kv_pages(spec)
    a, b = TOKENS[:25], TOKENS[3:39]  # a decodes its 25th; b prefills
    rc, fp = ref_config(cfg), ref.dequantize(p)
    pa = jnp.arange(1, 11, dtype=jnp.int32)
    pb = jnp.arange(11, 21, dtype=jnp.int32)
    out = llama.prefill(cfg, p, jnp.asarray(a[:24]), jnp.int32(24), kp, vp,
                        pa[:6], page_size=PS)
    out = llama.prefill(cfg, p, jnp.asarray(b[:24]), jnp.int32(24),
                        out.k_pages, out.v_pages, pb[:6], page_size=PS)
    out = llama.mixed_step(
        cfg, p, jnp.asarray([a[24], 0]), jnp.asarray([24, 0]),
        jnp.stack([pa, jnp.zeros_like(pa)]), jnp.asarray([25, 1]),
        jnp.asarray(b[24:36]), jnp.int32(24), jnp.int32(12),
        _chunk_table(spec, pb, 12), out.k_pages, out.v_pages, page_size=PS)
    np.testing.assert_allclose(
        out.logits[0], ref.forward(rc, fp, jnp.asarray(a), rshare)[0][24],
        **TOL)
    np.testing.assert_allclose(
        out.chunk_logits,
        ref.forward(rc, fp, jnp.asarray(b), rshare)[0][35], **TOL)


def test_selected_sets_match_reference(model, reference, program):
    """The program's selected sets are the reference's (float32 both: no
    pick flips; the bound is >= 99% with the rest within 1e-5 of the
    threshold score), and with the reference handed the program's sets
    the logits agree again (the attention arithmetic apart from a pick)."""
    cfg, p = model
    want, ref_sets, thresholds = reference
    got, calls = program
    positions = sorted(got)
    per = _sets_by_layer(calls, cfg, positions)
    given = [np.zeros((N, N), bool) for _ in range(cfg.num_layers)]
    same = total = 0
    for pos in positions:
        for layer, mine in enumerate(per[pos]):
            theirs = frozenset(np.flatnonzero(ref_sets[layer][pos]).tolist())
            assert len(mine) == min(TOPK, pos + 1) == len(theirs)
            same += len(mine & theirs)
            total += len(theirs)
            given[layer][pos, sorted(mine)] = True
    assert same / total >= 0.99, (same, total)
    # rows the tap did not cover keep the reference's own sets
    rc, fp = ref_config(cfg), ref.dequantize(p)
    for layer in range(cfg.num_layers):
        rows = [i for i in range(N) if i not in positions]
        given[layer][rows] = np.asarray(ref_sets[layer])[rows]
    again, _, _ = ref.forward(rc, fp, jnp.asarray(TOKENS),
                              given=[jnp.asarray(g) for g in given])
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, again[pos], **TOL)


@pytest.mark.parametrize("control", ["recency", "no_relu", "no_weights"])
def test_a_wrong_selection_must_fail(model, reference, program, control):
    """MUST FAIL: the program against a reference that selects by recency,
    whose indexer has no ReLU, or no head weights. Margin: the logits
    differ by over 0.25 (measured 1.0-1.1; the tolerance is 2e-4)."""
    cfg, p = model
    wrong, _, _ = ref.forward(ref_config(cfg), ref.dequantize(p),
                              jnp.asarray(TOKENS), select=control)
    got = program[0]
    err = max(float(jnp.abs(v - wrong[k]).max()) for k, v in got.items())
    assert err > 0.25, err
    for pos, logits in got.items():  # and the program is the model's
        np.testing.assert_allclose(logits, reference[0][pos], **TOL)


def test_the_shares_add_up(model):
    """What the 4 shares of an expert layer give, with the shared expert
    counted once, is what the uncut reference gives for the layer (the
    router limited to its groups) — and each share's part is what the
    program's layer computes for it."""
    cfg, p = model
    rc, fp = ref_config(cfg), ref.dequantize(p)
    x = jax.random.normal(jax.random.PRNGKey(7), (10, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        for layer in (1, 2):
            lp = ref.layer_params(rc, fp, layer)
            whole = ref.experts(rc, lp, x)
            shared = ref.swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
            parts = 0
            for r in range(SHARES):
                scfg, sp, rshare = cut(cfg, p, r)
                slp = ref.layer_params(rc, ref.dequantize(sp), layer)
                part = ref.experts(rc, slp, x, rshare, with_shared=False)
                prog, _ = llama._mlp(
                    scfg, {k: v[layer - 1] for k, v in sp.items()
                           if not k.startswith("dense.") and v.ndim > 1
                           and k not in ("embed", "lm_head")}, x)
                np.testing.assert_allclose(prog, part + shared, **TOL)
                parts = parts + part
            np.testing.assert_allclose(parts + shared, whole, **TOL)
            assert float(jnp.abs(parts).max()) > 1e-3


def test_w8a8_share_stays_near_reference(model):
    """int8 weights and activations (the indexer's two larger projections
    among them): near the reference on the same dequantized weights, the
    reference handed the program's selected sets (at 128-wide rows int8
    rounding flips a pick among 16 of ~30 scores, which is another
    question than the arithmetic's; the sets still overlap by 90%)."""
    cfg, p, rshare = cut(*model, 0)
    qp = quant.quantize_params(p, "w8a8")
    assert isinstance(qp["idx_wq_b"], quant.QTensorA8)
    assert isinstance(qp["dense.idx_wk"], quant.QTensorA8)
    assert not isinstance(qp["idx_w"], quant.QTensor)
    rc, fp = ref_config(cfg), ref.dequantize(qp)
    toks = TOKENS[:34]  # two decode steps: eager int8 programs are slow
    _, ref_sets, _ = ref.forward(rc, fp, jnp.asarray(toks), rshare)
    got, calls = tapped(lambda: _run_program(cfg, qp, toks))
    per = _sets_by_layer(calls, cfg, sorted(got))
    given = [np.array(m) for m in ref_sets]
    same = total = 0
    for pos, layers in per.items():
        for layer, mine in enumerate(layers):
            same += int(given[layer][pos, sorted(mine)].sum())
            total += len(mine)
            given[layer][pos] = False
            given[layer][pos, sorted(mine)] = True
    assert same / total > 0.9, (same, total)
    want, _, _ = ref.forward(rc, fp, jnp.asarray(toks), rshare,
                             given=[jnp.asarray(g) for g in given])
    err = max(float(jnp.abs(v - want[k]).max()) for k, v in got.items())
    # 128-wide rows: int8 rounding is a larger share than at real widths,
    # and it still flips picks of the group-limited router (0.12 measured)
    assert err < 0.75 * float(jnp.std(want)), err


# ------------------------------------------------- roofline and benchmark --

def _bench_file(*parts):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip", *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod, path


def test_benchmark_kernel_costs_agree_with_the_analytic_roofline():
    from dynamo_tpu.profiler import roofline

    cfg = ModelConfig.from_hf_config({**V32, "num_nextn_predict_layers": 0})
    idx_cost, _ = _bench_file("kernel_costs", "dsa_indexer.py")
    att_cost, _ = _bench_file("kernel_costs", "dsa_sparse_attention.py")
    assert idx_cost.cost(29000.0, 64, 128) == roofline.dsa_indexer_cost(
        cfg, 29000.0)
    assert att_cost.cost(2048.0, 128, 512, 64, 640) == \
        roofline.dsa_sparse_attention_cost(cfg, 2048.0)
    c = idx_cost.cost(1.0, 64, 128)
    assert c == {"ops": 2 * 64 * 128, "bytes": 256}
    c = att_cost.cost(1.0, 128, 512, 64, 640)
    assert c == {"ops": 128 * (576 + 512) * 2, "bytes": 1280}
    # a decode row scoring 29k keys is compute-bound on a v5e (64 FLOP a
    # byte against 240); its attention over 2,048 rows too (218)
    assert 2 * 64 * 128 / 197e12 < 256 / 819e9
    assert 128 * 1088 * 2 / 197e12 < 1280 / 819e9

    def grew(path):
        return {"metrics.dsa.decode_keys_scored": 100.0,
                "metrics.dsa.chunk_keys_scored": 50.0,
                "metrics.dsa.rows_selected": 7.0}.get(path, 0.0)

    args = dict(layers=9, index_n_heads=64, index_head_dim=128, heads=128,
                kv_lora_rank=512, qk_rope_head_dim=64, row_lanes=640)
    assert idx_cost.from_counters(grew, args)["ops"] == 150 * 9 * 16384
    assert att_cost.from_counters(grew, args)["bytes"] == 7 * 9 * 1280


def test_roofline_counts_the_indexer(model):
    from dynamo_tpu.profiler import roofline

    for cfg in (tiny(), PRESETS["tiny-dsv32-ep4-debug"]):
        real = sum(int(np.prod(shape))
                   for shape, _, _ in llama.param_specs(cfg).values())
        assert roofline.param_count(cfg) == real
    assert roofline.kv_bytes_per_token(tiny()) == 3 * (40 + 32) * 2


def test_the_benchmarks_reference_is_this_reference():
    _, path = _bench_file("reference", "deepseek_v32.py")
    with open(path) as a, open(os.path.abspath(ref.__file__)) as b:
        assert a.read() == b.read()


def test_random_int8_path_and_sharding_know_the_new_names():
    from dynamo_tpu.models import loader
    from dynamo_tpu.parallel import sharding

    cfg = PRESETS["tiny-dsv32-ep4-debug"]
    p = loader.random_quantized_params(cfg, seed=1, mode="w8a8")
    assert isinstance(p["idx_wq_b"], quant.QTensorA8)
    assert p["idx_wq_b"].q.shape == (2, 48, 4, 32)
    assert p["idx_k_norm"].shape == (2, 32) and p["dense.idx_w"].shape == (
        1, 128, 4)
    specs = sharding.param_specs(p)
    assert all(a is None for a in specs["idx_wk"].q)  # replicated


# ---- PR 35: the selecting sort carries each position's physical row ----

SEL_PS, SEL_WP, SEL_K, SEL_LP, SEL_D = 4, 16, 16, 32, 8
SEL_S, SEL_OFF = SEL_PS * SEL_WP, 2 * SEL_LP  # layer 2 of a 3-layer pool


def _oracle(scores, tables, pool):
    """The plain form PR 35 replaced: jax.lax.top_k, each selected
    position's page looked up in the table, a (page, slot) gather."""
    vals, sel = jax.lax.top_k(scores, min(SEL_K, scores.shape[-1]))
    page = jnp.take_along_axis(tables, sel // SEL_PS, axis=1)
    return sel, vals > -jnp.inf, pool[page, sel % SEL_PS]


def _selection_case(case: str, kind: str):
    """(scores [N, S] masked, tables [N, Wp] flat ids) of one case."""
    rng = np.random.default_rng(zlib.crc32(f"{case}/{kind}".encode()))
    n = 6
    sc = rng.normal(size=(n, SEL_S)).astype(np.float32)
    if case == "ties":  # runs of equal scores across the threshold
        sc = np.round(sc * 2) / 2
    elif case == "zeros":  # the threshold falls among +0.0 and -0.0
        sc = rng.choice(np.asarray([0.0, -0.0, 0.0, -0.0, 1.0, -1.0],
                                   np.float32), size=(n, SEL_S))
    # a sequence's pages: distinct, never the trash page, in no order
    if kind == "chunk":
        row = rng.permutation(np.arange(1, SEL_LP))[:SEL_WP]
        tables = np.broadcast_to(row, (n, SEL_WP)).copy()
        pos = 41 + np.arange(n)  # consecutive queries of one sequence
        if case == "short":
            pos = 3 + np.arange(n)
        seen = np.arange(SEL_S)[None, :] <= pos[:, None]
    else:
        tables = np.stack([rng.permutation(np.arange(1, SEL_LP))[:SEL_WP]
                           for _ in range(n)])
        ctx = rng.integers(SEL_K + 1, SEL_S + 1, n)
        if case == "short":
            ctx = rng.integers(1, SEL_K, n)
        if case == "empty":  # slot 2 is empty: context 1 on the trash page
            tables[2], ctx[2] = 0, 1
        seen = np.arange(SEL_S)[None, :] < ctx[:, None]
    if case == "ascending":
        tables = np.sort(tables, axis=1)
    sc = np.where(seen, sc, -np.inf).astype(np.float32)
    return jnp.asarray(sc), jnp.asarray(tables + SEL_OFF, jnp.int32)


@pytest.mark.parametrize("layer_pages", [SEL_LP, 1 << 28],
                         ids=["one_word", "two_words"])
@pytest.mark.parametrize("case, kind", [
    (c, k) for c in ("random", "ties", "zeros", "short", "empty", "ascending")
    for k in ("decode", "chunk") if (c, k) != ("empty", "chunk")])
def test_the_sort_carries_top_ks_rows_in_top_ks_order(case, kind,
                                                      layer_pages):
    """The selection + flat gather returns, row for row and in order, what
    jax.lax.top_k + the page-table lookup returned: equal scores at the
    threshold go to the lower position, +0.0 before -0.0, masked keys last
    (the first of them where a context is shorter than K), an empty slot
    its trash row (a chunk has no empty slot), page tables in any order;
    the tap still sees positions.
    Both layouts of the sort's payload (a pool too large for one word is
    pretended by `layer_pages` alone)."""
    scores, tables = _selection_case(case, kind)
    pool = jnp.arange(3 * SEL_LP * SEL_PS * SEL_D, dtype=jnp.float32
                      ).reshape(3 * SEL_LP, SEL_PS, SEL_D)
    want_sel, want_valid, want_rows = _oracle(scores, tables, pool)
    if case in ("ties", "zeros"):  # the case is what it says it is
        v = np.asarray(jnp.take_along_axis(scores, want_sel, axis=1))
        nxt = np.sort(np.asarray(scores), axis=1)[:, -SEL_K - 1]
        assert (v[:, -1] == nxt).any()

    def run():
        words, unpack = att._dsa_row_words(
            tables[0] if kind == "chunk" else tables, SEL_OFF, SEL_PS,
            layer_pages)
        assert len(words) == (1 if layer_pages == SEL_LP else 2)
        rows, valid = jax.jit(
            lambda s, w: att._dsa_select(s, w, unpack, SEL_K, kind,
                                         jnp.zeros((), jnp.int32))
        )(scores, words)
        return att._gather_rows(pool, rows), valid

    (got_rows, got_valid), calls = tapped(run)
    np.testing.assert_array_equal(got_valid, want_valid)
    np.testing.assert_array_equal(got_rows, want_rows)
    (_, _, sel, valid), = calls
    np.testing.assert_array_equal(sel, want_sel)
    np.testing.assert_array_equal(valid, want_valid)


@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_the_tap_sees_positions_of_the_rows_gathered(kind):
    """DSA_TAP's contract is PR 32's, (kind, qpos, sel, valid) with `sel`
    as positions, and the rows the op attended over are the physical rows
    of those positions: its output is the attention over them."""
    rng = np.random.default_rng(5)
    h, hi, di, n = 2, 2, 8, 4
    pool = jnp.asarray(rng.normal(size=(3 * SEL_LP, SEL_PS, SEL_D)),
                       jnp.float32)
    idx = jnp.asarray(rng.normal(size=(3 * SEL_LP, SEL_PS, di)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(n, h, SEL_D)), jnp.float32)
    qi = jnp.asarray(rng.normal(size=(n, hi, di)), jnp.float32)
    wi = jnp.asarray(rng.normal(size=(n, hi)), jnp.float32)
    tables = jnp.asarray(
        np.stack([rng.permutation(np.arange(1, SEL_LP))[:SEL_WP]
                  for _ in range(n)]) + SEL_OFF, jnp.int32)
    kw = dict(page_size=SEL_PS, topk=SEL_K, page_off=SEL_OFF,
              layer_pages=SEL_LP)
    if kind == "decode":
        ctx = jnp.asarray([9, 30, 64, 17], jnp.int32)
        out, calls = tapped(lambda: att.dsa_decode_attention(
            q, qi, wi, pool, idx, tables, ctx, **kw))
        want_qpos = np.asarray(ctx) - 1
    else:
        tables = jnp.broadcast_to(tables[0], tables.shape)
        out, calls = tapped(lambda: att.dsa_chunk_attention(
            q, qi, wi, pool, idx, tables[0], 37, block_q=n, **kw))
        want_qpos = 37 + np.arange(n)
    (got_kind, qpos, sel, valid), = calls
    assert got_kind == kind
    np.testing.assert_array_equal(qpos, want_qpos)
    assert sel.shape == valid.shape == (n, SEL_K)
    for r in range(n):  # positions: in sight of the query, each once
        seen = sel[r][valid[r]]
        assert len(set(seen.tolist())) == len(seen) == min(
            SEL_K, want_qpos[r] + 1)
        assert seen.max() <= want_qpos[r]
    page = np.take_along_axis(np.asarray(tables), sel // SEL_PS, axis=1)
    rows = pool[page, sel % SEL_PS]
    np.testing.assert_array_equal(
        out, att._dsa_attend(q, rows, jnp.asarray(valid)))


# ---- PR 38: a chunk's selection is built over the bucket's pages, not ----
# ---- over the table's (the bucket's + the chunk's trash tail)         ----

CH_C, CH_LEN = 12, 62  # a 12-token chunk; a 62-token prompt in SEL_S = 64
CH_TAIL = att.chunk_table_tail(CH_C, SEL_PS)  # 2 trailing trash slots


def _whole_table_chunk(q, qi, wi, pool, idx, pages, start):
    """The form PR 38 replaced, kept plainly: every position of the WHOLE
    table (the trash tail's too) scored and masked, jax.lax.top_k, each
    selected position's page looked up in the table, a (page, slot) gather
    -> (output, selected positions, valid)."""
    s = pages.shape[0] * SEL_PS
    keys = idx[pages].reshape(s, idx.shape[-1])
    pos = start + jnp.arange(q.shape[0])
    scores = att._dsa_scores(qi, wi, keys, "qhd,sd->qhs")
    scores = jnp.where(jnp.arange(s)[None, :] <= pos[:, None], scores,
                       -jnp.inf)
    sel, valid, rows = _oracle(
        scores, jnp.broadcast_to(pages, (q.shape[0],) + pages.shape), pool)
    return att._dsa_attend(q, rows, valid), sel, valid


def _chunk_case(seed: int = 7, trash=None):
    """One sequence's chunk operands: a 62-token prompt on 16 pages of a
    64-token bucket, a table of those and two trash slots behind them; the
    trash page's rows (latent and index key) are `trash`, or whatever was
    written there last (random rows)."""
    rng = np.random.default_rng(seed)
    h, hi, di = 2, 2, 8
    pool = rng.normal(size=(3 * SEL_LP, SEL_PS, SEL_D)).astype(np.float32)
    idx = rng.normal(size=(3 * SEL_LP, SEL_PS, di)).astype(np.float32)
    if trash is not None:
        pool[SEL_OFF], idx[SEL_OFF] = trash, trash
    pages = np.concatenate([rng.permutation(np.arange(1, SEL_LP))[:SEL_WP],
                            np.zeros(CH_TAIL, np.int64)]) + SEL_OFF
    q = rng.normal(size=(CH_C, h, SEL_D)).astype(np.float32)
    qi = rng.normal(size=(CH_C, hi, di)).astype(np.float32)
    wi = rng.normal(size=(CH_C, hi)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q, qi, wi, pool, idx)) + (
        jnp.asarray(pages, jnp.int32),)


def _trimmed_chunk(ops, start, layer_pages=SEL_LP):
    """The shipped op over the bucket's pages -> (output, tap's calls)."""
    return tapped(lambda: att.dsa_chunk_attention(
        *ops, start, block_q=CH_C, page_size=SEL_PS, topk=SEL_K,
        page_off=SEL_OFF, layer_pages=layer_pages, key_pages=SEL_WP))


@pytest.mark.parametrize("layer_pages", [SEL_LP, 1 << 28],
                         ids=["one_word", "two_words"])
@pytest.mark.parametrize("start", [0, 24, 56],
                         ids=["first_chunk", "mid_prompt",
                              "last_chunk_crosses_the_bucket"])
def test_a_chunk_selects_over_its_bucket_as_over_its_whole_table(
        start, layer_pages):
    """The selection built over the bucket's 16 pages hands every REAL
    query (position < the prompt's 62 tokens) the rows, in the order, and
    the output bit for bit that the selection over the whole 18-entry
    table handed it: at the prompt's start (fewer keys in sight than K), in
    its middle, and in a last chunk whose padded window (56..67) crosses
    the bucket's end at 64. Excluded by name: the padded queries past the
    prompt's end (62..67 there), whose rows land on the trash page and
    whose outputs the engine discards; past the bucket (64..67) they see
    other keys than they did."""
    ops = _chunk_case()
    want, want_sel, want_valid = _whole_table_chunk(*ops, start)
    got, ((kind, qpos, sel, valid),) = _trimmed_chunk(ops, start,
                                                      layer_pages)
    real = np.flatnonzero(start + np.arange(CH_C) < CH_LEN)
    assert len(real) == (6 if start == 56 else CH_C) and kind == "chunk"
    np.testing.assert_array_equal(qpos, start + np.arange(CH_C))
    np.testing.assert_array_equal(valid[real], np.asarray(want_valid)[real])
    np.testing.assert_array_equal(sel[real], np.asarray(want_sel)[real])
    np.testing.assert_array_equal(np.asarray(got)[real],
                                  np.asarray(want)[real])
    if start == 56:  # the case is what it says: padded queries do differ
        assert (sel[len(real):] != np.asarray(want_sel)[len(real):]).any()


def test_no_real_query_reads_the_trash_page():
    """With the trash page's index keys and latent rows NaN, the last
    chunk's real queries give the bits they gave with a clean trash page:
    nothing of the tail is gathered, scored or sorted, and no real query
    selects a row there."""
    clean, _ = _trimmed_chunk(_chunk_case(), 56)
    dirty, _ = _trimmed_chunk(_chunk_case(trash=np.nan), 56)
    real = CH_LEN - 56
    assert not np.isnan(np.asarray(dirty)[:real]).any()
    np.testing.assert_array_equal(np.asarray(dirty)[:real],
                                  np.asarray(clean)[:real])


@pytest.mark.parametrize("program", ["prefill_chunk", "mixed_step"])
def test_chunk_programs_select_over_the_bucket(model, program):
    """Both chunk programs hand the op the bucket's pages by shape (the
    table's width less the tail of their own chunk length): an 8-token
    chunk over a 13-entry table (a 48-token bucket + 1 trash slot) lowers
    to sorts over 48 positions, and nothing 52 wide is left."""
    cfg, p = model
    spec = KVCacheSpec.from_model(cfg, num_pages=32, page_size=PS)
    kp, vp = alloc_kv_pages(spec)
    table = _chunk_table(spec, jnp.arange(1, 13, dtype=jnp.int32), 8)
    assert table.shape == (13,)
    chunk = (jnp.zeros((8,), jnp.int32), jnp.int32(24), jnp.int32(8))
    if program == "prefill_chunk":
        text = jax.jit(lambda *a: llama.prefill_chunk(
            cfg, p, *a, page_size=PS)).lower(
                *chunk, kp, vp, table).as_text()
    else:
        two = jnp.zeros((2,), jnp.int32)
        text = jax.jit(lambda *a: llama.mixed_step(
            cfg, p, *a, page_size=PS)).lower(
                two, two, jnp.zeros((2, 12), jnp.int32), two + 1, *chunk,
                table, kp, vp).as_text()
    sorts = re.findall(r'"stablehlo.sort".*?\}\) : \(tensor<\d+x(\d+)xf32>',
                       text, re.S)
    assert sorts and set(sorts) == {"48"}, sorts
    assert "x52x" not in text
