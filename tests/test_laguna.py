"""Laguna's structure at a toy size (`tiny-laguna-debug`) against its float32
reference (dynamo_tpu/models/reference/laguna_s.py): window and full
attention layers mixed (head counts and rotaries by kind, a per-head output
gate), a KV pool and a page table for each kind with a sliding layer's pages
in a ring, softmax-routed experts beside a shared expert, one leading dense
layer; the layer scan over periods; the windowed kernels; the refusals of
`from_hf_config`.

Tolerance of the logit comparisons (RTOL / ATOL, float32 on both sides, the
reference's matmuls at "highest"): the program sums in another order (paged
blocks, grouped expert matmuls, a scan) and nothing else; the tiny model's
logits are O(1) and agree to ~1e-5, so 2e-4 is Kimi-K2's tests' tolerance
and a wrong mechanism reads 1e-2 and more: ignoring the window, dropping the
gate and turning a full layer with the sliding rotary must each FAIL it.
"""

import dataclasses
import filecmp
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.kv_cache import (KVCacheSpec, WindowRings,
                                        alloc_kv_pages, window_ring_pages)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import FULL, PRESETS, SLIDING, ModelConfig
from dynamo_tpu.models.reference import laguna_s as ref
from dynamo_tpu.ops import attention as att

PS = 4       # page size: the window's edges (8 back) fall inside pages
CHUNK = 8
RTOL = ATOL = 2e-4
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny(**kw) -> ModelConfig:
    return dataclasses.replace(PRESETS["tiny-laguna-debug"], dtype="float32",
                               **kw)


def hf_dict(cfg: ModelConfig) -> dict:
    """The tiny preset as the published config.json spells it."""
    def rope(r):
        _, theta, share, yarn = r
        out = {"rope_theta": theta, "partial_rotary_factor": share,
               "rope_type": "default"}
        if yarn:
            f, bf, bs, orig, _, _, af = yarn
            out.update(rope_type="yarn", factor=f, beta_fast=bf, beta_slow=bs,
                       original_max_position_embeddings=orig,
                       attention_factor=af)
        return out

    n, k = cfg.num_layers, cfg.first_k_dense
    return {
        "model_type": "laguna", "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.dense_intermediate_size,
        "num_hidden_layers": n, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rms_norm_eps": cfg.rms_norm_eps, "num_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "moe_intermediate_size": cfg.intermediate_size,
        "shared_expert_intermediate_size": cfg.shared_expert_width,
        "norm_topk_prob": True, "decoder_sparse_step": 1,
        "mlp_only_layers": list(range(k)), "tie_word_embeddings": False,
        "gating": "per-head", "sliding_window": cfg.sliding_window,
        "rope_parameters": {r[0]: rope(r) for r in cfg.rope_by_kind},
        "layer_types": list(cfg.layer_types),
        "num_attention_heads_per_layer": list(cfg.heads_per_layer),
        "mlp_layer_types": ["dense"] * k + ["sparse"] * (n - k),
        "gating_types": ["per_head"] * n,
        "moe_apply_router_weight_on_input": False,
        "moe_routed_scaling_factor": cfg.routed_scaling_factor,
        "moe_router_logit_softcapping": 0,
    }


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    p = llama.init_params(cfg, jax.random.PRNGKey(3))
    # a gate that matters: its default sigma leaves sigmoid near 0.5
    for name in ("wg", "win.wg", "dense.wg"):
        p[name] = p[name] * 8.0
    return cfg, p


TOKENS = [int(t) for t in np.random.default_rng(0).integers(1, 500, 45)]


def _run_program(cfg, p, tokens, n_chunked=40, ahead=CHUNK):
    """The serving path's forward functions through both pools: the prompt
    in 8-token chunks (5 of them: the ring of 5 pages = 20 rows is written
    over twice), then decode steps in a batch of two slots of which one is
    empty. Returns {position: logits}."""
    spec = KVCacheSpec.from_model(cfg, num_pages=32, page_size=PS,
                                  window_slots=2, window_ahead=ahead)
    assert spec.ring_pages == window_ring_pages(8, ahead, PS)
    kp, vp = alloc_kv_pages(spec)
    assert kp.full.shape[:2] == (cfg.kind_layers(FULL), 32)
    assert kp.window.shape[0] == cfg.kind_layers(SLIDING)
    n_pages = -(-len(tokens) // PS)
    pages = llama.ByKind(
        jnp.arange(1, 1 + n_pages + 1, dtype=jnp.int32).at[-1].set(0),
        jnp.arange(1, 1 + spec.ring_pages, dtype=jnp.int32))
    toks = jnp.asarray(tokens, jnp.int32)
    got = {}
    for start in range(0, n_chunked, CHUNK):
        out = llama.prefill_chunk(
            cfg, p, toks[start:start + CHUNK], jnp.int32(start),
            jnp.int32(CHUNK), kp, vp, pages, page_size=PS)
        kp, vp = out.k_pages, out.v_pages
        got[start + CHUNK - 1] = out.last_logits
    tables = llama.ByKind(
        jnp.stack([pages.full, jnp.zeros_like(pages.full)]),
        jnp.stack([pages.window, jnp.zeros_like(pages.window)]))
    for pos in range(n_chunked, len(tokens)):
        out = llama.decode_step(
            cfg, p, jnp.asarray([tokens[pos], 0], jnp.int32),
            jnp.asarray([pos, 0], jnp.int32), tables,
            jnp.asarray([pos + 1, 1], jnp.int32), kp, vp, page_size=PS)
        kp, vp = out.k_pages, out.v_pages
        got[pos] = out.logits[0]
    return got


def _worst(got, want):
    return max(float(np.max(np.abs(np.asarray(v) - np.asarray(want[pos]))))
               for pos, v in got.items())


def test_chunked_prefill_then_decode_matches_reference(model):
    """Logits at contexts past window + chunk (pages handed back and
    reused) against the reference's full forward; and the three wrong
    models each fail the same tolerance."""
    cfg, p = model
    rc = ref.Config.from_hf(hf_dict(cfg))
    rp = ref.dequantize(p)
    want = ref.forward(rc, rp, jnp.asarray(TOKENS))
    got = _run_program(cfg, p, TOKENS)
    assert sorted(got) == [7, 15, 23, 31, 39, 40, 41, 42, 43, 44]
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], rtol=RTOL, atol=ATOL)
    for variant in ("no_window", "no_gate", "one_rotary"):
        wrong = ref.forward(rc, rp, jnp.asarray(TOKENS), variant=variant)
        assert _worst(got, wrong) > 50 * ATOL, variant


def test_reference_in_blocks_is_the_reference(model):
    cfg, p = model
    rc = ref.Config.from_hf(hf_dict(cfg))
    rp = ref.dequantize(p)
    toks = jnp.asarray(TOKENS)
    np.testing.assert_allclose(ref.forward(rc, rp, toks, q_block=16),
                               ref.forward(rc, rp, toks), rtol=1e-5,
                               atol=1e-5)


def test_mixed_step_matches_reference(model):
    """A decode row at context 41 and another sequence's chunk at 24-31 in
    ONE mixed step, each over its own table and ring."""
    cfg, p = model
    rc = ref.Config.from_hf(hf_dict(cfg))
    rp = ref.dequantize(p)
    other = [int(t) for t in np.random.default_rng(1).integers(1, 500, 32)]
    want_a = ref.forward(rc, rp, jnp.asarray(TOKENS[:41]))
    want_b = ref.forward(rc, rp, jnp.asarray(other))
    spec = KVCacheSpec.from_model(cfg, num_pages=64, page_size=PS,
                                  window_slots=2, window_ahead=CHUNK)
    kp, vp = alloc_kv_pages(spec)
    w = spec.ring_pages

    def pages_of(first, ring_first, n_tokens):
        n = n_tokens // PS + 2
        return llama.ByKind(
            jnp.arange(first, first + n, dtype=jnp.int32).at[-2:].set(0),
            jnp.arange(ring_first, ring_first + w, dtype=jnp.int32))

    pa, pb = pages_of(1, 1, 44), pages_of(20, 1 + w, 32)
    for toks, pg, upto in ((TOKENS, pa, 40), (other, pb, 24)):
        for start in range(0, upto, CHUNK):
            out = llama.prefill_chunk(
                cfg, p, jnp.asarray(toks[start:start + CHUNK], jnp.int32),
                jnp.int32(start), jnp.int32(CHUNK), kp, vp, pg, page_size=PS)
            kp, vp = out.k_pages, out.v_pages
    pad = pa.full.shape[0]
    tables = llama.ByKind(
        jnp.stack([pa.full, jnp.zeros((pad,), jnp.int32)]),
        jnp.stack([pa.window, jnp.zeros((w,), jnp.int32)]))
    out = llama.mixed_step(
        cfg, p, jnp.asarray([TOKENS[40], 0], jnp.int32),
        jnp.asarray([40, 0], jnp.int32), tables,
        jnp.asarray([41, 1], jnp.int32),
        jnp.asarray(other[24:32], jnp.int32), jnp.int32(24), jnp.int32(8),
        pb, kp, vp, page_size=PS)
    np.testing.assert_allclose(out.logits[0], want_a[40], rtol=RTOL,
                               atol=ATOL)
    np.testing.assert_allclose(out.chunk_logits, want_b[31], rtol=RTOL,
                               atol=ATOL)
    st = dict(zip(llama.moe_ops.MOE_STATS, np.asarray(out.moe_stats)))
    assert st["layer_steps"] == 4  # the dense layer counts nothing
    assert st["assignments"] == 4 * 9 * cfg.num_experts_per_tok


def test_period_scan_is_the_layers_unrolled():
    """1 dense + two whole periods + a period cut short (11 layers): the
    scan over periods and its unrolled tail against the reference, which
    runs the layers one after another."""
    kinds = (FULL,) + (SLIDING, SLIDING, SLIDING, FULL) * 2 + (SLIDING,) * 2
    cfg = tiny(num_layers=11, layer_types=kinds,
               heads_per_layer=tuple(4 if k == FULL else 6 for k in kinds))
    assert cfg.kind_period == 4
    p = llama.init_params(cfg, jax.random.PRNGKey(5))
    assert p["wq"].shape[0] == 2 and p["win.wq"].shape[0] == 8
    want = ref.forward(ref.Config.from_hf(hf_dict(cfg)), ref.dequantize(p),
                       jnp.asarray(TOKENS[:32]))
    got = _run_program(cfg, p, TOKENS[:32], n_chunked=24)
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], rtol=RTOL, atol=ATOL)


# ------------------------------------------------------- windowed kernels --

def _pool(rng, pages, ps, n_kv, d):
    return (jnp.asarray(rng.standard_normal((pages, ps, n_kv * d)),
                        jnp.float32),
            jnp.asarray(rng.standard_normal((pages, ps, n_kv * d)),
                        jnp.float32))


@pytest.mark.parametrize("group", [6, 9])
def test_windowed_decode_kernel_is_the_masked_composition(group):
    """The decode kernel (interpret mode) under a static window of 40 over
    16-row pages (its lower edge falls inside a page, and for the longest
    context below whole superblocks, which it skips) against the XLA
    composition, at 6 and 9 query heads a KV head."""
    rng = np.random.default_rng(group)
    n_kv, d, ps, window = 2, 128, 16, 40
    kp, vp = _pool(rng, 64, ps, n_kv, d)
    q = jnp.asarray(rng.standard_normal((4, n_kv * group, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, 61)).reshape(4, 15),
                         jnp.int32)
    ctx = jnp.asarray([1, 37, 41, 230], jnp.int32)
    with att.attention_context("xla", None, 1):
        want = att.paged_attention_decode(
            q, kp, vp, tables, ctx, page_size=ps, num_kv_heads=n_kv,
            window=window)
        full = att.paged_attention_decode(
            q, kp, vp, tables, ctx, page_size=ps, num_kv_heads=n_kv)
    with att.attention_context("pallas_interpret", None, 1):
        got = att.paged_attention_decode(
            q, kp, vp, tables, ctx, page_size=ps, num_kv_heads=n_kv,
            window=window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the window bites on the two long contexts only
    assert np.allclose(want[:2], full[:2], atol=1e-6)
    assert not np.allclose(want[2:], full[2:], atol=1e-3)


@pytest.mark.parametrize("group,decode", [(6, 3), (9, 3), (9, 0)])
def test_windowed_ragged_kernel_is_the_masked_composition(group, decode):
    """The ragged kernel under a static window: decode rows and a chunk
    (or the chunk alone: chunk_attention's route), the chunk starting
    where its first rows' reach begins inside a page."""
    rng = np.random.default_rng(10 + group + decode)
    n_kv, d, ps, window, c = 2, 128, 16, 40, 32
    kp, vp = _pool(rng, 96, ps, n_kv, d)
    q = jnp.asarray(rng.standard_normal((decode + c, n_kv * group, d)),
                    jnp.float32)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, 46)).reshape(3, 15)[:decode], jnp.int32)
    ctx = jnp.asarray([5, 41, 200][:decode], jnp.int32)
    p_pages = jnp.asarray(rng.permutation(np.arange(46, 96))[:20], jnp.int32)
    start = 13 * ps  # 208: the first query's reach starts at 169, mid-page
    outs = {}
    # the counts are the process's own: another file's tests may have run
    # in this worker first, so the assertion below is on their growth
    demoted = att.pallas_fallback_counts().get(
        ("ragged attention", "window_softcap"), 0)
    for backend in ("xla", "pallas_interpret"):
        with att.attention_context(backend, None, 1):
            if decode:
                outs[backend] = att.ragged_mixed_attention(
                    q, kp, vp, tables, ctx, p_pages, start, page_size=ps,
                    num_kv_heads=n_kv, num_decode=decode, window=window)
            else:
                outs[backend] = att.chunk_attention(
                    q, kp, vp, p_pages, start, page_size=ps,
                    num_kv_heads=n_kv, window=window)
    np.testing.assert_allclose(outs["pallas_interpret"], outs["xla"],
                               rtol=2e-5, atol=2e-5)
    assert att.pallas_fallback_counts().get(
        ("ragged attention", "window_softcap"), 0) == demoted


# ------------------------------------------------------------ the two pools --

def test_a_30k_sequence_holds_a_ring_on_the_sliding_layers():
    """At the published sizes (window 512, 256-token chunks, 16-row pages)
    a sequence of 30 k tokens holds, on a sliding layer, no more than 512
    rows + one chunk + one page: 49 pages, whatever the context."""
    w = window_ring_pages(512, 256, 16)
    assert w * 16 == 512 + 256 + 16
    rings = WindowRings(64 * w + 1, w)
    for tokens in (100, 784, 785, 30720):
        rings.grow("a", -(-tokens // 16))
        assert rings.held_by("a") == min(-(-tokens // 16), w)
    assert rings.handed_back == 30720 // 16 - w
    assert rings.row("a").shape == (w,) and rings.row("a").min() > 0
    rings.release("a")
    assert rings.allocator.free_pages == 64 * w


# ---------------------------------------------------------- from_hf_config --

def _row(name="Laguna-S-2.1") -> dict:
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == name:
                return row["config"]
    raise AssertionError(name)


def test_from_hf_config_loads_the_published_row_and_the_cut():
    cfg = ModelConfig.from_hf_config(_row())
    assert cfg.num_layers == 48 and cfg.kind_period == 4
    assert cfg.kind_heads(FULL) == 48 and cfg.kind_heads(SLIDING) == 72
    assert cfg.kind_layers(FULL) == 12 and cfg.kind_layers(SLIDING) == 36
    assert cfg.first_k_dense == 1 and cfg.dense_intermediate_size == 12288
    assert cfg.intermediate_size == cfg.shared_expert_width == 1024
    assert cfg.moe_grouped and cfg.routed_scaling_factor == 2.5
    assert cfg.sliding_window == 512 and cfg.attn_gate == "per-head"
    full, sliding = cfg.rope_by_kind
    assert full[:3] == (FULL, 500000.0, 0.5) and full[3][0] == 128.0
    assert full[3][-1] == pytest.approx(1.4852030263919618)
    assert sliding == (SLIDING, 10000.0, 1.0, None)
    specs = llama.param_specs(cfg)
    assert specs["wq"][0] == (11, 3072, 48, 128)
    assert specs["win.wq"][0] == (36, 3072, 72, 128)
    assert specs["dense.wq"][0] == (1, 3072, 48, 128)
    assert specs["win.wg"][0] == (36, 3072, 72)
    assert specs["moe_w_gate"][0] == (47, 256, 3072, 1024)
    cut = ModelConfig.from_model_name(os.path.join(
        REPO, "benchmarks/chip/configs/laguna-s-2.1-w8a8-1chip"))
    assert cut.num_layers == 5 and cut.layer_types == cfg.layer_types[:5]
    assert cut.kind_layers(FULL) == 2 and cut.kind_layers(SLIDING) == 3
    # the tiny preset is the same mapping
    assert dataclasses.replace(
        ModelConfig.from_hf_config(hf_dict(tiny()), name="tiny-laguna-debug",
                                   dtype="float32"),
        max_position_embeddings=tiny().max_position_embeddings) == tiny()


@pytest.mark.parametrize("change,word", [
    ({"model_type": "somenet"}, "not mapped"),
    ({"gating": True}, "gating"),
    ({"gating": "per-channel"}, "gating"),
    ({"moe_router_logit_softcapping": 30.0}, "softcapping"),
    ({"moe_apply_router_weight_on_input": True}, "router_weight_on_input"),
    ({"layer_types": ["full_attention"] * 47}, "layer_types has 47"),
    ({"num_attention_heads_per_layer": [48] * 5},
     "num_attention_heads_per_layer has 5"),
    ({"mlp_only_layers": [3]}, "LEADING"),
], ids=["unmapped_type", "gating_true", "gating_other", "router_softcap",
        "weight_on_input", "short_layer_types", "short_heads",
        "interleaved_dense"])
def test_from_hf_config_refuses_what_it_would_serve_as_another_model(
        change, word):
    with pytest.raises(ValueError, match=word):
        ModelConfig.from_hf_config({**_row(), **change})


def test_an_unmapped_type_without_the_keys_is_still_a_llama():
    """The refusal is for the keys, not for the name."""
    cfg = {k: v for k, v in _row().items()
           if k not in ("layer_types", "num_attention_heads_per_layer",
                        "gating", "model_type")}
    assert ModelConfig.from_hf_config(cfg).layer_types == ()


def test_the_benchmark_keeps_a_copy_of_the_reference():
    assert filecmp.cmp(
        os.path.join(REPO, "dynamo_tpu/models/reference/laguna_s.py"),
        os.path.join(REPO, "benchmarks/chip/reference/laguna_s.py"),
        shallow=False)
