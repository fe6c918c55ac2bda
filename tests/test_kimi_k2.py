"""Kimi-K2 / DeepSeek-V3 structure on the serving path, against the plain
float32 reference (dynamo_tpu/models/reference/kimi_k2.py): a leading dense
layer, MLA with the query low-rank path, sigmoid routing with a selection
bias and a shared expert, one chip's share of an expert-parallel layer, the
grouped expert matmuls, and the latent row stored once."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.kv_cache import KVCacheSpec, alloc_kv_pages
from dynamo_tpu.models import llama, quant
from dynamo_tpu.models.config import ModelConfig, PRESETS
from dynamo_tpu.models.reference import kimi_k2 as ref
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import moe as moe_ops

PS = 4          # page size
SHARES = 4      # 16 experts over 4 chips


def tiny(**kw) -> ModelConfig:
    return dataclasses.replace(PRESETS["tiny-kimi-debug"], dtype="float32",
                               **kw)


def ref_config(cfg: ModelConfig) -> ref.Config:
    f, bf, bs, orig, ms, msad, _ = cfg.rope_yarn_scaling
    return ref.Config.from_hf({
        "hidden_size": cfg.hidden_size, "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads, "q_lora_rank": cfg.q_lora_rank,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "n_routed_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "n_shared_experts": cfg.num_shared_experts,
        "first_k_dense_replace": cfg.first_k_dense,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": cfg.norm_topk_prob, "scoring_func": "sigmoid",
        "n_group": 1, "topk_group": 1, "rms_norm_eps": cfg.rms_norm_eps,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": {"type": "yarn", "factor": f, "beta_fast": bf,
                         "beta_slow": bs, "mscale": ms,
                         "mscale_all_dim": msad,
                         "original_max_position_embeddings": orig}})


@pytest.fixture(scope="module")
def model():
    """(uncut config, its params with a selection bias that moves picks)."""
    cfg = tiny()
    p = llama.init_params(cfg, jax.random.PRNGKey(3))
    p["router_bias"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(4), p["router_bias"].shape, jnp.float32)
    return cfg, p


def cut(cfg, p, r: int, held: int = 16 // SHARES):
    """Share r of the uncut model: its config and its slice of the weights."""
    lo = r * held
    scfg = dataclasses.replace(cfg, num_local_experts=held,
                               local_expert_offset=lo)
    sp = dict(p)
    for name in ("moe_w_gate", "moe_w_up", "moe_w_down"):
        sp[name] = p[name][:, lo:lo + held]
    return scfg, sp, ref.Share(lo, held)


# ------------------------------------------------------------- the config --

KIMI = {
    "architectures": ["DeepseekV3ForCausalLM"], "model_type": "kimi_k2",
    "vocab_size": 163840, "hidden_size": 7168, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_hidden_layers": 61,
    "num_attention_heads": 64, "num_key_value_heads": 64,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "n_routed_experts": 384,
    "n_shared_experts": 1, "num_experts_per_tok": 8, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.827,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "rms_norm_eps": 1e-6,
    "rope_theta": 50000, "tie_word_embeddings": False,
    "num_nextn_predict_layers": 0, "max_position_embeddings": 131072,
    "rope_scaling": {"beta_fast": 1, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
}


def test_published_config_is_read():
    cfg = ModelConfig.from_hf_config(KIMI)
    assert (cfg.first_k_dense, cfg.dense_intermediate_size,
            cfg.intermediate_size) == (1, 18432, 2048)
    assert (cfg.num_experts, cfg.held_experts, cfg.num_experts_per_tok) == (
        384, 384, 8)
    assert cfg.moe_scoring == "sigmoid" and cfg.router_bias
    assert cfg.q_lora_rank == 1536 and cfg.is_mla
    assert cfg.cache_head_dim == 640 and cfg.moe_grouped
    assert cfg.rope_yarn_scaling == (32.0, 1.0, 1.0, 4096, 1.0, 1.0, -1.0)


def test_share_keys_are_read():
    cfg = ModelConfig.from_hf_config({
        **KIMI, "n_routed_experts": 24, "vocab_size": 20480,
        "num_hidden_layers": 9,
        "deployment_share": {"n_routed_experts_total": 384,
                             "first_routed_expert": 48,
                             "first_vocab_row": 20480}})
    assert (cfg.num_experts, cfg.held_experts, cfg.local_expert_offset) == (
        384, 24, 48)
    assert cfg.vocab_size == 20480 and cfg.vocab_offset == 20480
    specs = llama.param_specs(cfg)
    assert specs["router"][0] == (8, 7168, 384)
    assert specs["moe_w_gate"][0] == (8, 24, 7168, 2048)
    assert specs["dense.w_gate"][0] == (1, 7168, 18432)
    assert specs["wq_b"][0] == (8, 1536, 64, 192)


@pytest.mark.parametrize("key, value", [
    ("n_group", 7), ("topk_group", 4), ("num_nextn_predict_layers", 1),
    ("moe_layer_freq", 2), ("scoring_func", "tanh"),
    ("topk_method", "top_p")])
def test_unimplemented_keys_raise(key, value):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        ModelConfig.from_hf_config({**KIMI, key: value})


def test_held_experts_must_lie_inside_the_router():
    with pytest.raises(ValueError, match="held experts"):
        tiny(num_local_experts=8, local_expert_offset=12)


# ------------------------------------------------------------- the router --

def test_sigmoid_routing_bias_moves_the_pick_not_the_weights():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0, -2.0, 0.5]], jnp.float32)
    s = np.asarray(jax.nn.sigmoid(logits))[0]
    topi, w = moe_ops.route_topk(logits, 2, scoring="sigmoid",
                                 scaling_factor=2.5)
    assert sorted(np.asarray(topi)[0].tolist()) == [0, 1]
    np.testing.assert_allclose(
        np.sort(np.asarray(w)[0]), np.sort(2.5 * s[:2] / s[:2].sum()),
        rtol=1e-6)
    # a bias that lifts expert 4 over expert 1: picked, at the weight of s
    bias = jnp.zeros((6,), jnp.float32).at[4].set(1.0)
    topi, w = moe_ops.route_topk(logits, 2, scoring="sigmoid",
                                 scaling_factor=2.5, select_bias=bias)
    got = dict(zip(np.asarray(topi)[0].tolist(), np.asarray(w)[0].tolist()))
    assert set(got) == {0, 4}
    tot = s[0] + s[4]
    np.testing.assert_allclose([got[0], got[4]],
                               [2.5 * s[0] / tot, 2.5 * s[4] / tot],
                               rtol=1e-6)
    # unnormalised: the plain sigmoid scores
    _, w = moe_ops.route_topk(logits, 2, renormalize=False,
                              scoring="sigmoid")
    np.testing.assert_allclose(np.sort(np.asarray(w)[0]), np.sort(s[:2]),
                               rtol=1e-6)


def test_router_matches_reference(model):
    cfg, p = model
    x = jax.random.normal(jax.random.PRNGKey(1), (13, cfg.hidden_size))
    lp = {k: v[0] for k, v in p.items() if k in ("router", "router_bias")}
    picked, w = ref.route(ref_config(cfg), lp, x)
    logits = (x @ lp["router"]).astype(jnp.float32)
    topi, tw = moe_ops.route_topk(
        logits, cfg.num_experts_per_tok, cfg.norm_topk_prob,
        cfg.routed_scaling_factor, scoring="sigmoid",
        select_bias=lp["router_bias"])
    np.testing.assert_array_equal(np.sort(picked, -1), np.sort(topi, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(tw, -1), rtol=1e-6)
    # the bias of this fixture really moves picks
    plain, _ = moe_ops.route_topk(logits, cfg.num_experts_per_tok,
                                  scoring="sigmoid")
    assert not np.array_equal(np.sort(plain, -1), np.sort(topi, -1))


# ------------------------------------------------------ grouped expert layer --

def _experts(x_dim=16, f=24, n=6, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (n, x_dim, f)) / 4,
            jax.random.normal(k[1], (n, x_dim, f)) / 4,
            jax.random.normal(k[2], (n, f, x_dim)) / 4)


def _picks(kind: str, t: int, k: int, n: int):
    rng = np.random.default_rng(5)
    if kind == "random":
        return np.stack([rng.choice(n, k, replace=False) for _ in range(t)])
    if kind == "one_expert_gets_all":   # expert 2 in every token's picks
        rest = [e for e in range(n) if e != 2]
        return np.stack([np.r_[2, rng.choice(rest, k - 1, replace=False)]
                         for _ in range(t)])
    if kind == "one_expert_gets_none":  # expert 0 never picked
        return np.stack([1 + rng.choice(n - 1, k, replace=False)
                         for _ in range(t)])
    if kind == "two_experts_only":
        return np.tile(np.asarray([[1, 4]]), (t, 1))
    raise ValueError(kind)


@pytest.mark.parametrize("kind", ["random", "one_expert_gets_all",
                                  "one_expert_gets_none",
                                  "two_experts_only"])
@pytest.mark.parametrize("mode", ["float32", "int8", "w8a8"])
def test_grouped_equals_dense_at_every_imbalance(kind, mode):
    t, k, n, e = 11, 2, 6, 16
    wg, wu, wd = _experts(e, 24, n)
    if mode != "float32":
        cls = quant.qtensor_class(mode)
        wg, wu, wd = (quant.quantize(w, (1,), cls) for w in (wg, wu, wd))
    x = jax.random.normal(jax.random.PRNGKey(9), (t, e))
    topi = jnp.asarray(_picks(kind, t, k, n), jnp.int32)
    w = jax.random.uniform(jax.random.PRNGKey(2), (t, k)) + 0.1
    mask = jnp.arange(t) != 3  # one padding row
    combine = moe_ops.scatter_combine(topi, w, n, x.dtype) * mask[:, None]
    want = moe_ops.moe_mlp_dense(x, combine, wg, wu, wd)
    got, stats = moe_ops.moe_mlp_grouped(x, topi, w, wg, wu, wd,
                                         token_mask=mask)
    # w8a8: both paths round a token's input row alike, but the dense
    # einsum takes ONE scale for a token's hidden rows over all experts
    # where the grouped rows have one each: int8 rounding apart, no more
    tol = 0.1 if mode == "w8a8" else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    counts = np.bincount(np.asarray(topi)[np.asarray(mask)].ravel(),
                         minlength=n)
    st = dict(zip(moe_ops.MOE_STATS, np.asarray(stats).tolist()))
    assert st == {"assignments": (t - 1) * k, "assignments_held": (t - 1) * k,
                  "busiest_held_sum": int(counts.max()),
                  "experts_touched": int((counts > 0).sum()),
                  "layer_steps": 1, "rows_computed": t * k}
    assert not np.allclose(np.asarray(got)[4], 0)  # no token dropped
    np.testing.assert_array_equal(np.asarray(got)[3], 0)  # the padding row


def test_grouped_layer_computes_only_the_experts_it_holds():
    """Experts [2, 5) of 6 held: assignments to the others are left out,
    exactly the dense layer's result with their combine columns zeroed."""
    t, k, n, e = 9, 3, 6, 16
    wg, wu, wd = _experts(e, 24, n)
    x = jax.random.normal(jax.random.PRNGKey(9), (t, e))
    topi = jnp.asarray(_picks("random", t, k, n), jnp.int32)
    w = jax.random.uniform(jax.random.PRNGKey(2), (t, k)) + 0.1
    combine = moe_ops.scatter_combine(topi, w, n, x.dtype)
    held = (jnp.arange(n) >= 2) & (jnp.arange(n) < 5)
    want = moe_ops.moe_mlp_dense(x, combine * held, wg, wu, wd)
    got, stats = moe_ops.moe_mlp_grouped(
        x, topi, w, wg[2:5], wu[2:5], wd[2:5], expert_offset=2)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    n_held = int(((np.asarray(topi) >= 2) & (np.asarray(topi) < 5)).sum())
    assert np.asarray(stats).tolist()[:2] == [t * k, n_held]


def test_no_pick_held_here_gives_zero():
    wg, wu, wd = _experts(16, 24, 2)
    x = jnp.ones((3, 16))
    topi = jnp.asarray([[7, 9]] * 3, jnp.int32)
    got, stats = moe_ops.moe_mlp_grouped(x, topi, jnp.ones((3, 2)), wg, wu,
                                         wd, expert_offset=0)
    np.testing.assert_array_equal(got, 0)
    assert np.asarray(stats).tolist() == [6, 0, 0, 0, 1, 0]


# ------------------------------------------------ program against reference --

def _run_program(cfg, p, tokens, n_prefill=8, n_chunk=8):
    """The serving path's forward functions through the paged cache:
    prefill, then one chunk over the cached prefix, then decode steps in a
    batch of two slots of which one is empty. Returns {position: logits}
    and the summed expert-layer counts."""
    n = len(tokens)
    spec = KVCacheSpec.from_model(cfg, num_pages=32, page_size=PS)
    kp, vp = alloc_kv_pages(spec)
    assert vp.shape[-1] == 0  # the latent row lives once
    pages = jnp.arange(1, 1 + 8, dtype=jnp.int32)  # 32 positions
    toks = jnp.asarray(tokens, jnp.int32)
    got, stats = {}, []
    out = llama.prefill(cfg, p, toks[:n_prefill], jnp.int32(n_prefill), kp,
                        vp, pages[:n_prefill // PS], page_size=PS)
    got[n_prefill - 1] = out.last_logits
    stats.append(out.moe_stats)
    end = n_prefill + n_chunk
    out = llama.prefill_chunk(
        cfg, p, toks[n_prefill:end], jnp.int32(n_prefill), jnp.int32(n_chunk),
        out.k_pages, out.v_pages, pages, page_size=PS)
    got[end - 1] = out.last_logits
    stats.append(out.moe_stats)
    kp, vp = out.k_pages, out.v_pages
    tables = jnp.stack([pages, jnp.zeros_like(pages)])
    for pos in range(end, n):
        out = llama.decode_step(
            cfg, p, jnp.asarray([tokens[pos], 0], jnp.int32),
            jnp.asarray([pos, 0], jnp.int32), tables,
            jnp.asarray([pos + 1, 1], jnp.int32), kp, vp, page_size=PS)
        kp, vp = out.k_pages, out.v_pages
        got[pos] = out.logits[0]
        stats.append(out.moe_stats)
    return got, stats


TOKENS = [int(t) for t in np.random.default_rng(0).integers(1, 500, 20)]


@pytest.mark.parametrize("share", [None, 0, 1, 2, 3])
def test_prefill_then_cached_decode_matches_reference(model, share):
    """Logits of the whole tiny model and of each share: the program's
    prefill, chunk-over-cached-prefix and decode steps against the
    reference's full forward (expanded MLA, experts as a loop)."""
    cfg, p = model
    rshare = None
    if share is not None:
        cfg, p, rshare = cut(cfg, p, share)
    want = ref.forward(ref_config(cfg), ref.dequantize(p),
                       jnp.asarray(TOKENS), rshare)
    got, stats = _run_program(cfg, p, TOKENS)
    assert sorted(got) == [7, 15, 16, 17, 18, 19]
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], rtol=2e-4, atol=2e-4)
    if share is None:
        assert all(s is None for s in stats)  # 4 of 16: the dense layer
        return
    # the grouped layer counted: 2 expert layers a forward, k picks a live
    # token, and an empty decode slot counts for nothing
    rows = [8, 8, 1, 1, 1, 1]
    for st, n in zip(stats, rows):
        st = dict(zip(moe_ops.MOE_STATS, np.asarray(st).tolist()))
        assert st["layer_steps"] == 2
        assert st["assignments"] == 2 * n * cfg.num_experts_per_tok
        assert 0 <= st["assignments_held"] <= st["assignments"]
        assert st["experts_touched"] <= 2 * cfg.held_experts


def test_mixed_step_matches_reference(model):
    """One ragged step (a decode row, an empty slot and a chunk) of a
    share."""
    cfg, p, rshare = cut(*model, 1)
    spec = KVCacheSpec.from_model(cfg, num_pages=32, page_size=PS)
    kp, vp = alloc_kv_pages(spec)
    a, b = TOKENS[:9], TOKENS[9:]  # a decodes its 9th, b prefills 8 of 11
    rc = ref_config(cfg)
    fp = ref.dequantize(p)
    pa = jnp.arange(1, 9, dtype=jnp.int32)
    pb = jnp.arange(9, 17, dtype=jnp.int32)
    out = llama.prefill(cfg, p, jnp.asarray(a[:8]), jnp.int32(8), kp, vp,
                        pa[:2], page_size=PS)
    out = llama.mixed_step(
        cfg, p, jnp.asarray([a[8], 0]), jnp.asarray([8, 0]),
        jnp.stack([pa, jnp.zeros_like(pa)]), jnp.asarray([9, 1]),
        jnp.asarray(b[:8]), jnp.int32(0), jnp.int32(8), pb,
        out.k_pages, out.v_pages, page_size=PS)
    np.testing.assert_allclose(
        out.logits[0], ref.forward(rc, fp, jnp.asarray(a), rshare)[8],
        rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        out.chunk_logits, ref.forward(rc, fp, jnp.asarray(b[:8]), rshare)[7],
        rtol=2e-4, atol=2e-4)
    st = dict(zip(moe_ops.MOE_STATS, np.asarray(out.moe_stats).tolist()))
    assert st["assignments"] == 2 * 9 * cfg.num_experts_per_tok


def test_the_shares_add_up(model):
    """What the 4 shares of an expert layer give, with the shared expert
    counted once, is what the uncut reference gives for the layer — and
    each share's part is what the program's layer computes for it."""
    cfg, p = model
    rc = ref_config(cfg)
    fp = ref.dequantize(p)
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
                np.testing.assert_allclose(prog, part + shared, rtol=2e-4,
                                           atol=2e-4)
                parts = parts + part
            np.testing.assert_allclose(parts + shared, whole, rtol=2e-4,
                                       atol=2e-4)
            assert float(jnp.abs(parts).max()) > 1e-3


def test_w8a8_share_stays_near_reference(model):
    """int8 weights and activations through the grouped matmuls: near the
    reference on the same dequantized weights (int8 rounding only)."""
    cfg, p, rshare = cut(*model, 2)
    qp = quant.quantize_params(p, "w8a8")
    assert isinstance(qp["dense.wq_a"], quant.QTensorA8)
    assert isinstance(qp["wq_b"], quant.QTensorA8)
    assert not isinstance(qp["w_uk"], quant.QTensor)
    want = ref.forward(ref_config(cfg), ref.dequantize(qp),
                       jnp.asarray(TOKENS), rshare)
    got, _ = _run_program(cfg, qp, TOKENS)
    err = max(float(jnp.abs(v - want[k]).max()) for k, v in got.items())
    # 128-wide rows: int8 rounding is a larger share than at real widths
    assert err < 0.25 * float(jnp.std(want)), err


# ----------------------------------------------------- the latent row, once --

def _latent_pools(rng, pages=12, lanes=40):
    k = jnp.asarray(rng.normal(size=(pages, PS, lanes)), jnp.float32)
    return k, jnp.zeros((pages, PS, 0), jnp.float32)


def test_one_latent_pool_equals_two_pools_bit_for_bit():
    """tiny-mla-debug's geometry (4 heads on one 40-lane row): every paged
    attention op with V read from the K rows gives the bits the old second
    pool gave."""
    rng = np.random.default_rng(1)
    k, v0 = _latent_pools(rng)
    two = (k, k + 0)  # the old layout: the same rows in a second pool
    q = jnp.asarray(rng.normal(size=(3, 4, 40)), jnp.float32)
    bt = jnp.asarray([[1, 2, 3], [4, 5, 0], [0, 0, 0]], jnp.int32)
    cl = jnp.asarray([11, 6, 1], jnp.int32)
    kw = dict(page_size=PS, num_kv_heads=1)
    for name, call in {
        "decode": lambda kp, vp: att.paged_attention_decode_xla(
            q, kp, vp, bt, cl, **kw),
        "chunk": lambda kp, vp: att.chunk_attention_xla(
            jnp.tile(q, (4, 1, 1))[:8], kp, vp,
            jnp.asarray([6, 7, 8, 0], jnp.int32), 4, **kw),
        "verify": lambda kp, vp: att.verify_attention(
            jnp.stack([q, q], 1), kp, vp, bt, cl - 2, **kw),
    }.items():
        np.testing.assert_array_equal(call(k, v0), call(*two), err_msg=name)
    # and nothing is written to a pool without lanes
    new = jnp.ones((3, 1, 40), jnp.float32)
    k2, v2 = att.write_kv_token(k, v0, new, new, bt, cl - 1, page_size=PS)
    k3, _ = att.write_kv_token(*two, new, new, bt, cl - 1, page_size=PS)
    np.testing.assert_array_equal(k2, k3)
    assert v2.shape == v0.shape
    rows = jnp.ones((8, 1, 40), jnp.float32)
    k2, v2 = att.write_kv_prefill(k, v0, rows, rows,
                                  jnp.asarray([9, 10]), page_size=PS)
    k3, _ = att.write_kv_prefill(*two, rows, rows, jnp.asarray([9, 10]),
                                 page_size=PS)
    np.testing.assert_array_equal(k2, k3)
    assert v2.shape == v0.shape


@pytest.mark.parametrize("op", ["decode", "chunk", "ragged"])
def test_kernels_read_v_from_the_k_rows(op):
    """The paged Pallas kernels (interpret mode) at a 128-lane latent row:
    K alone is copied, and the result is the two-pool kernel's, bit for
    bit."""
    from dynamo_tpu.ops import pallas_attention as pa
    from dynamo_tpu.ops import ragged_attention as ra

    rng = np.random.default_rng(2)
    k, v0 = _latent_pools(rng, pages=12, lanes=128)
    q = jnp.asarray(rng.normal(size=(10, 4, 128)), jnp.float32)
    bt = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    cl = jnp.asarray([11, 6], jnp.int32)
    kw = dict(page_size=PS, num_kv_heads=1, interpret=True)
    if op == "decode":
        def call(kp, vp):
            return pa.paged_attention_decode(q[:2], kp, vp, bt, cl, **kw)
    elif op == "chunk":
        def call(kp, vp):
            return pa.chunk_prefill_attention(
                q[2:], kp, vp, jnp.asarray([6, 7, 8, 0], jnp.int32),
                jnp.int32(4), **kw)
    else:
        tabs = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 0]],
                           jnp.int32)

        def call(kp, vp):
            return ra.ragged_paged_attention(
                q, kp, vp, tabs, jnp.asarray([11, 6, 12], jnp.int32),
                jnp.asarray([10, 5, 4], jnp.int32), num_decode=2, **kw)
    np.testing.assert_array_equal(call(k, v0), call(k, k + 0))


def test_engine_holds_the_latent_row_once():
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import Engine

    eng = Engine(EngineConfig(model="tiny-mla-debug", page_size=4,
                              num_pages=64, max_num_seqs=2, max_seq_len=64))
    assert eng.k_pages.shape == (2, 64, 4, 40)
    assert eng.v_pages.shape == (2, 64, 4, 0) and eng.v_pages.nbytes == 0
    assert eng.kv_spec.bytes_per_token() == 2 * 40 * eng.k_pages.dtype.itemsize
    dense = KVCacheSpec.from_model(PRESETS["tiny-debug"], 64, 4)
    assert dense.v_shape == dense.shape  # every other model keeps two pools


# ------------------------------------------------------- the checkpoint's names --

def _kimi_checkpoint(cfg, vocab_total: int):
    """An HF-layout checkpoint of the UNCUT tiny model under the published
    names (modeling_deepseek.py)."""
    rng = np.random.default_rng(11)
    e, h = cfg.hidden_size, cfg.num_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    lora, vd, qr = cfg.kv_lora_rank, cfg.v_head_dim, cfg.q_lora_rank

    def w(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    t = {"model.embed_tokens.weight": w(vocab_total, e),
         "model.norm.weight": w(e), "lm_head.weight": w(vocab_total, e)}
    for i in range(cfg.num_layers):
        a = f"model.layers.{i}.self_attn"
        t[f"model.layers.{i}.input_layernorm.weight"] = w(e)
        t[f"model.layers.{i}.post_attention_layernorm.weight"] = w(e)
        t[f"{a}.q_a_proj.weight"] = w(qr, e)
        t[f"{a}.q_a_layernorm.weight"] = w(qr)
        t[f"{a}.q_b_proj.weight"] = w(h * (nope + rope), qr)
        t[f"{a}.kv_a_proj_with_mqa.weight"] = w(lora + rope, e)
        t[f"{a}.kv_a_layernorm.weight"] = w(lora)
        t[f"{a}.kv_b_proj.weight"] = w(h * (nope + vd), lora)
        t[f"{a}.o_proj.weight"] = w(e, h * vd)
        m = f"model.layers.{i}.mlp"
        if i < cfg.first_k_dense:
            f = cfg.dense_intermediate_size
            t[f"{m}.gate_proj.weight"] = w(f, e)
            t[f"{m}.up_proj.weight"] = w(f, e)
            t[f"{m}.down_proj.weight"] = w(e, f)
            continue
        f = cfg.intermediate_size
        t[f"{m}.gate.weight"] = w(cfg.num_experts, e)
        t[f"{m}.gate.e_score_correction_bias"] = w(cfg.num_experts)
        for j in range(cfg.num_experts):
            t[f"{m}.experts.{j}.gate_proj.weight"] = w(f, e)
            t[f"{m}.experts.{j}.up_proj.weight"] = w(f, e)
            t[f"{m}.experts.{j}.down_proj.weight"] = w(e, f)
        fs = f * cfg.num_shared_experts
        t[f"{m}.shared_experts.gate_proj.weight"] = w(fs, e)
        t[f"{m}.shared_experts.up_proj.weight"] = w(fs, e)
        t[f"{m}.shared_experts.down_proj.weight"] = w(e, fs)
    return t


def test_loader_reads_the_published_names_and_the_shares_slice(tmp_path):
    from safetensors.numpy import save_file

    from dynamo_tpu.models.loader import load_hf_safetensors

    whole = tiny()
    t = _kimi_checkpoint(whole, vocab_total=2 * whole.vocab_size)
    path = tmp_path / "m.safetensors"
    save_file(t, str(path))
    cfg = dataclasses.replace(whole, num_local_experts=4,
                              local_expert_offset=8,
                              vocab_offset=whole.vocab_size)
    p = load_hf_safetensors(cfg, [str(path)])
    assert {k: tuple(v.shape) for k, v in p.items()} == {
        k: shape for k, (shape, _, _) in llama.param_specs(cfg).items()}
    v0 = whole.vocab_size
    np.testing.assert_array_equal(
        p["embed"], t["model.embed_tokens.weight"][v0:2 * v0])
    np.testing.assert_array_equal(p["lm_head"],
                                  t["lm_head.weight"][v0:2 * v0].T)
    # experts 8..11 of checkpoint layer 2 are the scanned stack's layer 1
    np.testing.assert_array_equal(
        p["moe_w_down"][1, 2],
        t["model.layers.2.mlp.experts.10.down_proj.weight"].T)
    # the router and its selection bias keep their whole width, in float32
    assert p["router"].shape == (2, whole.hidden_size, 16)
    assert p["router_bias"].dtype == jnp.float32
    np.testing.assert_array_equal(
        p["router_bias"][0],
        t["model.layers.1.mlp.gate.e_score_correction_bias"])
    np.testing.assert_array_equal(
        p["dense.w_up"][0], t["model.layers.0.mlp.up_proj.weight"].T)
    np.testing.assert_array_equal(
        p["q_a_norm"][1], t["model.layers.2.self_attn.q_a_layernorm.weight"])
    np.testing.assert_array_equal(
        p["dense.wq_a"][0], t["model.layers.0.self_attn.q_a_proj.weight"].T)
    # q_b_proj: per head [nope | rope], the rope lanes de-interleaved
    qb = t["model.layers.1.self_attn.q_b_proj.weight"].T.reshape(
        whole.q_lora_rank, whole.num_heads, -1)
    nope = whole.qk_nope_head_dim
    np.testing.assert_array_equal(p["wq_b"][0][..., :nope], qb[..., :nope])
    np.testing.assert_array_equal(p["wq_b"][0][..., nope + 1],
                                  qb[..., nope + 2])
    # kv_b_proj splits into W_UK [h, nope, lora] and W_UV [h, lora, vd]
    kvb = t["model.layers.1.self_attn.kv_b_proj.weight"].reshape(
        whole.num_heads, nope + whole.v_head_dim, whole.kv_lora_rank)
    np.testing.assert_array_equal(p["w_uk"][0], kvb[:, :nope])
    np.testing.assert_array_equal(p["w_uv"][0],
                                  np.swapaxes(kvb[:, nope:], 1, 2))
    # and the loaded share runs: logits against the reference
    want = ref.forward(ref_config(cfg), ref.dequantize(p),
                       jnp.asarray(TOKENS), ref.Share(8, 4))
    got, _ = _run_program(cfg, p, TOKENS)
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], rtol=5e-4, atol=5e-4)


def test_random_int8_path_and_sharding_know_the_new_names():
    from dynamo_tpu.models.loader import random_quantized_params
    from dynamo_tpu.parallel import sharding as shd

    cfg = PRESETS["tiny-kimi-ep4-debug"]
    p = random_quantized_params(cfg, seed=3, mode="w8a8")
    assert {k: tuple((v.q if isinstance(v, quant.QTensor) else v).shape)
            for k, v in p.items()} == {
        k: shape for k, (shape, _, _) in llama.param_specs(cfg).items()}
    quantized = {k for k, v in p.items() if isinstance(v, quant.QTensorA8)}
    assert {"wq_a", "wq_b", "dense.wq_a", "dense.w_gate", "moe_w_up",
            "w_kv_a", "lm_head"} <= quantized
    assert not quantized & {"w_uk", "w_uv", "router", "router_bias",
                            "q_a_norm", "dense.kv_a_norm"}
    assert p["router_bias"].dtype == np.float32
    specs = shd.param_specs(p)
    P = shd.P
    assert specs["moe_w_gate"].q == P(None, "expert", None, "model")
    assert specs["dense.w_gate"].q == specs["w_gate"].q == P(
        None, None, "model")
    assert specs["wq_b"].q == P(None, None, "model", None)
    assert specs["dense.wq_a"].q == P(None, None, None)
    assert specs["router_bias"] == P(None, None)


# ----------------------------------------------------- counts and rooflines --

def test_engine_serves_a_share_and_counts():
    """tiny-kimi-ep4-debug through the Engine: mixed steps, decode windows,
    a prefix-cache hit; the expert layers' counts and the attention rows
    land on the metrics snapshot."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import Engine
    from dynamo_tpu.engine.request import GenRequest

    eng = Engine(EngineConfig(
        model="tiny-kimi-ep4-debug", page_size=4, num_pages=128,
        max_num_seqs=4, max_seq_len=128, mixed_batch_tokens=16,
        num_scheduler_steps=4))
    shared = list(range(5, 45))
    outs = {}
    reqs = [GenRequest(rid, shared + tail, max_tokens=12, temperature=0.0,
                       ignore_eos=True)
            for rid, tail in (("a", [50, 51, 52]),
                              ("b", [60, 61, 62, 63, 64]))]
    eng.add_request(reqs.pop(0))
    while eng.has_work:
        for ev in eng.step():
            if ev.token_id >= 0:
                outs.setdefault(ev.request_id, []).append(ev.token_id)
            if reqs:  # a decodes: b's tail prefills beside it, prefix cached
                eng.add_request(reqs.pop(0))
    assert [len(v) for v in outs.values()] == [12, 12]
    snap = eng.metrics.snapshot()
    moe, attn = snap["moe"], snap["attn"]
    assert set(moe) == set(moe_ops.MOE_STATS)
    k = eng.model_cfg.num_experts_per_tok
    # every expert layer of every step counted its live rows' k picks
    assert moe["assignments"] % k == 0 and moe["layer_steps"] % 2 == 0
    assert moe["assignments"] >= 2 * k * (
        snap["prompt_tokens"] - eng.prefix_cache.cached_tokens_served)
    assert 0 < moe["assignments_held"] < moe["assignments"]
    assert moe["busiest_held_sum"] <= moe["assignments_held"]
    assert moe["experts_touched"] <= 4 * moe["layer_steps"]
    assert eng.prefix_cache.cached_tokens_served >= 36  # b found a's pages
    assert attn["decode_q_rows"] > 0 and attn["mixed_chunk_q_rows"] > 0
    assert attn["decode_kv_rows"] >= 40 * attn["decode_q_rows"]
    assert attn["mixed_chunk_kv_pairs"] <= attn["mixed_chunk_block_kv_rows"] * 8
    # the same request alone gives the same tokens: the share's programs
    # are deterministic across batch composition
    solo = Engine(EngineConfig(
        model="tiny-kimi-ep4-debug", page_size=4, num_pages=128,
        max_num_seqs=4, max_seq_len=128))
    assert solo.generate(GenRequest(
        "a", shared + [50, 51, 52], max_tokens=12, temperature=0.0,
        ignore_eos=True)) == outs["a"]


def _bench_file(*parts):
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip", *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    import sys
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod, path


def test_benchmark_kernel_costs_agree_with_the_analytic_roofline():
    from dynamo_tpu.profiler import roofline

    cfg = ModelConfig.from_hf_config(KIMI)
    moe_cost, _ = _bench_file("kernel_costs", "grouped_expert_matmul.py")
    att_cost, _ = _bench_file("kernel_costs", "mla_paged_attention.py")
    assert moe_cost.cost(160.0, 21.0, 7168, 2048) == roofline.moe_expert_cost(
        cfg, 160.0, 21.0)
    assert att_cost.cost(8600.0, 8600.0, 64, 512, 64) == \
        roofline.mla_attention_cost(cfg, 8600.0, 8600.0)
    # a decode row over 8.6k latent rows is memory-bound on a v5e
    c = att_cost.cost(8600.0, 8600.0, 64, 512, 64)
    assert c["bytes"] == 8600 * 576 * 2
    assert c["ops"] / 197e12 < c["bytes"] / 819e9
    assert roofline.attention_flops_per_pair(cfg) == 64 * 2 * (576 + 512)
    assert roofline.router_flops_per_token(cfg) == 2 * 7168 * 384


def test_roofline_reader_reads_the_counters_around_the_traced_slice():
    """run.py traces 3 s from (window - 3) / 2: the kernels' work is the
    counters' growth between the snapshots nearest outside that slice."""
    import os
    import sys

    chip = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip")
    sys.path.insert(0, chip)  # the reader imports the harness's lib
    try:
        reader, _ = _bench_file("readers", "kernel_roofline.py")
    finally:
        sys.path.remove(chip)
    snaps = [(t + 0.01, {"n": t}) for t in range(48)] + [(48.0, {"n": 48})]
    (t0, a), (t1, b) = reader.bracket(snaps, 48.0, 2.9)
    assert (a["n"], b["n"]) == (22, 26) and t1 - t0 == pytest.approx(4.0)
    # a window too short to hold a snapshot on each side: its two ends
    (_, a), (_, b) = reader.bracket(snaps[:3], 2.0, 1.5)
    assert (a["n"], b["n"]) == (0, 2)


def test_roofline_counts_the_share(model):
    """The analytic parameter count is the real tree's, for the uncut tiny
    model and for a share, and the cache holds the latent row once."""
    from dynamo_tpu.profiler import roofline

    for cfg in (tiny(), PRESETS["tiny-kimi-ep4-debug"]):
        real = sum(int(np.prod(shape))
                   for shape, _, _ in llama.param_specs(cfg).values())
        assert roofline.param_count(cfg) == real
    cfg = tiny()
    assert roofline.kv_bytes_per_token(cfg) == 3 * 40 * 2
    assert roofline.active_param_count(cfg) < roofline.param_count(cfg)


def test_the_benchmarks_reference_is_this_reference():
    import os

    _, path = _bench_file("reference", "kimi_k2.py")
    with open(path) as a, open(os.path.abspath(ref.__file__)) as b:
        assert a.read() == b.read()
