"""HF-checkpoint loading: safetensors streaming into the stacked layout.

Covers both upstream MoE tensor naming schemes (Mixtral's block_sparse_moe
w1/w3/w2, Qwen3-MoE's mlp.experts gate/up/down_proj) and the config.json
parse for Qwen3-MoE (num_experts + moe_intermediate_size keys).
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import param_specs as llama_param_specs
from dynamo_tpu.models.loader import load_hf_safetensors


def _tiny_moe_cfg():
    return dataclasses.replace(
        ModelConfig.from_model_name("tiny-moe-debug", dtype="float32"),
        qk_norm=True, tie_word_embeddings=False)


def _hf_tensors(cfg, scheme: str):
    """Synthesize an HF-layout checkpoint dict under the given naming."""
    rng = np.random.default_rng(0)
    e, h, kv, d, f = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim, cfg.intermediate_size)
    t = {}

    def w(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    t["model.embed_tokens.weight"] = w(cfg.vocab_size, e)
    t["model.norm.weight"] = w(e)
    t["lm_head.weight"] = w(cfg.vocab_size, e)
    for i in range(cfg.num_layers):
        L = f"model.layers.{i}"
        t[f"{L}.input_layernorm.weight"] = w(e)
        t[f"{L}.post_attention_layernorm.weight"] = w(e)
        t[f"{L}.self_attn.q_proj.weight"] = w(h * d, e)
        t[f"{L}.self_attn.k_proj.weight"] = w(kv * d, e)
        t[f"{L}.self_attn.v_proj.weight"] = w(kv * d, e)
        t[f"{L}.self_attn.o_proj.weight"] = w(e, h * d)
        t[f"{L}.self_attn.q_norm.weight"] = w(d)
        t[f"{L}.self_attn.k_norm.weight"] = w(d)
        if scheme == "mixtral":
            t[f"{L}.block_sparse_moe.gate.weight"] = w(cfg.num_experts, e)
            for j in range(cfg.num_experts):
                E = f"{L}.block_sparse_moe.experts.{j}"
                t[f"{E}.w1.weight"] = w(f, e)
                t[f"{E}.w3.weight"] = w(f, e)
                t[f"{E}.w2.weight"] = w(e, f)
        else:  # qwen3-moe naming
            t[f"{L}.mlp.gate.weight"] = w(cfg.num_experts, e)
            for j in range(cfg.num_experts):
                E = f"{L}.mlp.experts.{j}"
                t[f"{E}.gate_proj.weight"] = w(f, e)
                t[f"{E}.up_proj.weight"] = w(f, e)
                t[f"{E}.down_proj.weight"] = w(e, f)
    return t


@pytest.mark.parametrize("scheme", ["mixtral", "qwen3moe"])
def test_load_moe_checkpoint_schemes(tmp_path, scheme):
    from safetensors.numpy import save_file

    cfg = _tiny_moe_cfg()
    path = tmp_path / "model.safetensors"
    save_file(_hf_tensors(cfg, scheme), str(path))
    p = load_hf_safetensors(cfg, [str(path)])
    x, f, e, l = (cfg.num_experts, cfg.intermediate_size, cfg.hidden_size,
                  cfg.num_layers)
    assert p["moe_w_gate"].shape == (l, x, e, f)
    assert p["moe_w_up"].shape == (l, x, e, f)
    assert p["moe_w_down"].shape == (l, x, f, e)
    assert p["router"].shape == (l, e, x)
    assert p["lm_head"].shape == (e, cfg.vocab_size)  # untied head loads
    assert p["q_norm"].shape == (l, cfg.head_dim)


def test_both_schemes_load_identical_values(tmp_path):
    """Same weight values under either naming must produce identical
    params — the scheme is pure renaming."""
    from safetensors.numpy import save_file

    cfg = _tiny_moe_cfg()
    a, b = _hf_tensors(cfg, "mixtral"), _hf_tensors(cfg, "qwen3moe")
    # copy mixtral's values into the qwen3 names so contents match
    ren = {"w1": "gate_proj", "w3": "up_proj", "w2": "down_proj"}
    for k in list(b):
        if ".mlp.experts." in k:
            j = k.split(".experts.")[1].split(".")[0]
            L = k.split(".mlp.")[0]
            suf = k.rsplit(".", 2)[-2]
            src = next(mk for mk, qk in ren.items() if qk == suf)
            b[k] = a[f"{L}.block_sparse_moe.experts.{j}.{src}.weight"]
        elif ".mlp.gate.weight" in k:
            b[k] = a[k.replace(".mlp.", ".block_sparse_moe.")]
        else:
            b[k] = a[k]
    pa_path, pb_path = tmp_path / "a.safetensors", tmp_path / "b.safetensors"
    save_file(a, str(pa_path))
    save_file(b, str(pb_path))
    pa = load_hf_safetensors(cfg, [str(pa_path)])
    pb = load_hf_safetensors(cfg, [str(pb_path)])
    for k in pa:
        np.testing.assert_array_equal(np.asarray(pa[k]), np.asarray(pb[k]),
                                      err_msg=k)


def test_from_hf_config_qwen3_moe_keys():
    cfg = ModelConfig.from_hf_config({
        "architectures": ["Qwen3MoeForCausalLM"],
        "vocab_size": 151936,
        "hidden_size": 2048,
        "intermediate_size": 6144,       # dense-equivalent: must be IGNORED
        "moe_intermediate_size": 768,    # per-expert: the real one
        "num_hidden_layers": 48,
        "num_attention_heads": 32,
        "num_key_value_heads": 4,
        "head_dim": 128,
        "num_experts": 128,
        "num_experts_per_tok": 8,
        "rope_theta": 1000000.0,
        "tie_word_embeddings": False,
        "eos_token_id": 151645,
    }, name="qwen3-moe-test")
    assert cfg.num_experts == 128
    assert cfg.intermediate_size == 768
    assert cfg.qk_norm is True
    assert not cfg.tie_word_embeddings


def test_from_hf_config_dense_keeps_intermediate():
    cfg = ModelConfig.from_hf_config({
        "architectures": ["LlamaForCausalLM"],
        "vocab_size": 1000, "hidden_size": 64, "intermediate_size": 256,
        "num_hidden_layers": 2, "num_attention_heads": 4,
    }, name="dense-test")
    assert cfg.num_experts == 0 and cfg.intermediate_size == 256


def test_load_mla_checkpoint_names(tmp_path):
    """DeepSeek-V2-family tensor names load: kv_a_proj_with_mqa,
    kv_a_layernorm, and kv_b_proj split per head into W_UK / W_UV."""
    from safetensors.numpy import save_file

    cfg = dataclasses.replace(
        ModelConfig.from_model_name("tiny-mla-debug", dtype="float32"),
        tie_word_embeddings=False, num_experts=4, num_experts_per_tok=2,
        num_shared_experts=2)
    rng = np.random.default_rng(1)
    e, h = cfg.hidden_size, cfg.num_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    lora, vd, f = cfg.kv_lora_rank, cfg.v_head_dim, cfg.intermediate_size

    def w(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    t = {"model.embed_tokens.weight": w(cfg.vocab_size, e),
         "model.norm.weight": w(e), "lm_head.weight": w(cfg.vocab_size, e)}
    for i in range(cfg.num_layers):
        L = f"model.layers.{i}"
        t[f"{L}.input_layernorm.weight"] = w(e)
        t[f"{L}.post_attention_layernorm.weight"] = w(e)
        t[f"{L}.self_attn.q_proj.weight"] = w(h * (nope + rope), e)
        t[f"{L}.self_attn.kv_a_proj_with_mqa.weight"] = w(lora + rope, e)
        t[f"{L}.self_attn.kv_a_layernorm.weight"] = w(lora)
        t[f"{L}.self_attn.kv_b_proj.weight"] = w(h * (nope + vd), lora)
        t[f"{L}.self_attn.o_proj.weight"] = w(e, h * vd)
        t[f"{L}.mlp.gate.weight"] = w(cfg.num_experts, e)
        for j in range(cfg.num_experts):
            E = f"{L}.mlp.experts.{j}"
            t[f"{E}.gate_proj.weight"] = w(f, e)
            t[f"{E}.up_proj.weight"] = w(f, e)
            t[f"{E}.down_proj.weight"] = w(e, f)
        S = f"{L}.mlp.shared_experts"
        t[f"{S}.gate_proj.weight"] = w(2 * f, e)
        t[f"{S}.up_proj.weight"] = w(2 * f, e)
        t[f"{S}.down_proj.weight"] = w(e, 2 * f)
    path = tmp_path / "model.safetensors"
    save_file(t, str(path))
    p = load_hf_safetensors(cfg, [str(path)])
    l = cfg.num_layers
    assert p["wq_mla"].shape == (l, e, h, nope + rope)
    assert p["w_kv_a"].shape == (l, e, lora + rope)
    assert p["w_uk"].shape == (l, h, nope, lora)
    assert p["w_uv"].shape == (l, h, lora, vd)
    assert p["wo"].shape == (l, h, vd, e)
    assert p["w_gate"].shape == (l, e, 2 * f)  # shared experts
    # kv_b split round-trips: stitching W_UK/W_UV back rebuilds kv_b rows
    kv_b = t["model.layers.0.self_attn.kv_b_proj.weight"].reshape(
        h, nope + vd, lora)
    np.testing.assert_allclose(np.asarray(p["w_uk"][0]), kv_b[:, :nope, :],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(p["w_uv"][0]),
                               np.swapaxes(kv_b[:, nope:, :], 1, 2),
                               rtol=1e-6)


def test_from_hf_config_deepseek_mla_keys():
    cfg = ModelConfig.from_hf_config({
        "architectures": ["DeepseekV2ForCausalLM"],
        "vocab_size": 102400, "hidden_size": 2048,
        "intermediate_size": 10944, "moe_intermediate_size": 1408,
        "num_hidden_layers": 27, "num_attention_heads": 16,
        "n_routed_experts": 64, "num_experts_per_tok": 6,
        "n_shared_experts": 2, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    }, name="dsv2")
    assert cfg.is_mla and cfg.kv_lora_rank == 512
    assert cfg.num_shared_experts == 2
    assert cfg.intermediate_size == 1408
    assert cfg.cache_head_dim == 640 and cfg.cache_kv_heads == 1  # padded for Pallas


def test_from_hf_config_takes_dense_first_layers():
    """first_k_dense_replace: leading dense layers of their own width, then
    the expert layers (it used to be refused)."""
    cfg = ModelConfig.from_hf_config({
        "vocab_size": 100, "hidden_size": 64, "num_hidden_layers": 3,
        "num_attention_heads": 4, "first_k_dense_replace": 1,
        "n_routed_experts": 4, "intermediate_size": 256,
        "moe_intermediate_size": 32,
    }, name="dsv2-dense-first")
    assert cfg.first_k_dense == 1 and cfg.num_moe_layers == 2
    assert cfg.dense_intermediate_size == 256
    assert cfg.intermediate_size == 32  # the experts' width
    specs = llama_param_specs(cfg)
    assert specs["dense.w_gate"][0] == (1, 64, 256)
    assert specs["moe_w_gate"][0] == (2, 4, 64, 32)
    assert specs["attn_norm"][0] == (2, 64)
    assert specs["dense.attn_norm"][0] == (1, 64)


def test_loader_loads_dense_first_layer_checkpoint(tmp_path):
    """A checkpoint whose layer 0 is a dense FFN loads into the dense
    stack and the later layers into the scanned one (it used to be
    refused); the same checkpoint under a config that promises an expert
    layer there still fails with the reason."""
    from safetensors.numpy import save_file

    moe = dataclasses.replace(
        ModelConfig.from_model_name("tiny-moe-debug", dtype="float32"),
        num_layers=3)
    t = _hf_tensors(moe, "qwen3moe")
    # turn layer 0 into a dense FFN (DeepSeek first_k_dense_replace=1)
    for k in [k for k in t if k.startswith("model.layers.0.mlp.")]:
        del t[k]
    e, fd = moe.hidden_size, 96
    rng = np.random.default_rng(2)
    for name, shape in (("gate_proj", (fd, e)), ("up_proj", (fd, e)),
                        ("down_proj", (e, fd))):
        t[f"model.layers.0.mlp.{name}.weight"] = \
            rng.standard_normal(shape).astype(np.float32)
    path = tmp_path / "m.safetensors"
    save_file(t, str(path))
    with pytest.raises(ValueError, match="first_k_dense_replace"):
        load_hf_safetensors(moe, [str(path)])
    cfg = dataclasses.replace(moe, first_k_dense=1,
                              dense_intermediate_size=fd)
    p = load_hf_safetensors(cfg, [str(path)])
    assert p["dense.w_gate"].shape == (1, e, fd)
    np.testing.assert_array_equal(
        np.asarray(p["dense.w_gate"][0]),
        t["model.layers.0.mlp.gate_proj.weight"].T)
    assert p["moe_w_gate"].shape[:2] == (2, moe.num_experts)
    # checkpoint layer 1 is the scanned stack's first
    np.testing.assert_array_equal(
        np.asarray(p["router"][0]),
        t["model.layers.1.mlp.gate.weight"].T)
    np.testing.assert_array_equal(
        np.asarray(p["attn_norm"][1]),
        t["model.layers.2.input_layernorm.weight"])
    np.testing.assert_array_equal(
        np.asarray(p["dense.attn_norm"][0]),
        t["model.layers.0.input_layernorm.weight"])
    assert {k: v.shape for k, v in p.items()} == {
        k: shape for k, (shape, _, _) in llama_param_specs(cfg).items()}


def test_rope_deinterleave_matches_hf_reference():
    """Folding the de-interleave into the weights must reproduce HF's
    DeepSeek rope exactly: interleaved pairs de-interleaved at runtime
    then rotate_half == our half-split apply_rope on the permuted weights."""
    import jax.numpy as jnp

    from dynamo_tpu.ops.rope import apply_rope, rope_freqs

    rng = np.random.default_rng(7)
    e, rope, t, theta = 16, 8, 5, 10000.0
    W = rng.standard_normal((rope, e)).astype(np.float32)  # HF [out, in]
    x = rng.standard_normal((t, e)).astype(np.float32)
    positions = np.arange(t)

    # HF reference: project with the RAW (interleaved) weight, de-interleave
    # pairs, then half-split rotation
    y = x @ W.T  # [t, rope] interleaved lanes
    y_d = np.concatenate([y[:, 0::2], y[:, 1::2]], axis=1)
    inv = np.asarray(rope_freqs(rope, theta))
    ang = positions[:, None] * inv  # [t, rope/2]
    cos, sin = np.cos(ang), np.sin(ang)
    y1, y2 = y_d[:, :rope // 2], y_d[:, rope // 2:]
    ref = np.concatenate([y1 * cos - y2 * sin, y2 * cos + y1 * sin], axis=1)

    # our path: permute the weight ROWS once (what fix_q/fix_kv_a do to the
    # rope output columns), project, then the repo's half-split apply_rope
    deint = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    Wp = W[deint]  # fold the de-interleave into the weight
    out = apply_rope(jnp.asarray(x @ Wp.T)[:, None, :],
                     jnp.asarray(positions), theta)[:, 0]
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)
