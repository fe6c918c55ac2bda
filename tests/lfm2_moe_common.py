"""What the two LFM2-MoE test files share: the float32 tiny presets, the
published config.json's spelling of them (what the reference reads), the
drawn parameters, and the tolerance of the logit comparisons."""

import dataclasses

import jax
import jax.numpy as jnp

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ATTENTION, CONV, PRESETS, ModelConfig

# Tolerance of the logit comparisons (float32 on both sides, the reference's
# matmuls at "highest"): the program sums in another order (paged attention
# blocks, grouped expert matmuls, the taps accumulated left to right), adds
# 1e-20 where the reference adds 1e-6 to the picked scores' sum (under 1e-5
# relative on a weight, reference ASSUMED (h)), and nothing else. The tiny
# model's logits are O(1) and agree to ~2e-6; every mechanism left out
# (tests/test_lfm2_moe.py::test_each_mechanism_is_seen) reads 1e-2 and more.
RTOL = ATOL = 2e-4


def tiny(name="tiny-lfm2-moe-debug", **kw) -> ModelConfig:
    return dataclasses.replace(PRESETS[name], dtype="float32", **kw)


def hf_dict(cfg: ModelConfig) -> dict:
    """The tiny preset as the published config.json spells it."""
    word = {CONV: "conv", ATTENTION: "full_attention"}
    return {
        "model_type": "lfm2_moe", "architectures": ["Lfm2MoeForCausalLM"],
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "layer_types": [word[k] for k in cfg.mixer_types],
        "conv_L_cache": cfg.conv_kernel, "conv_bias": False,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "intermediate_size": cfg.dense_intermediate_size,
        "moe_intermediate_size": cfg.intermediate_size,
        "num_dense_layers": cfg.first_k_dense,
        "num_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": True, "use_expert_bias": True,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "max_position_embeddings": 8192, "tie_word_embeddings": True,
    }


def drawn(cfg: ModelConfig, seed: int = 3) -> dict:
    """init_params with what it draws at the identity drawn away from it:
    every norm's weights (q / k norms included) about 1 +- 0.3, a selection
    bias as large as the scores' spread (so that it moves picks), and the
    branches loud enough that the logits are O(1): a mechanism left out
    then moves them far past ATOL."""
    p = llama.init_params(cfg, jax.random.PRNGKey(seed))
    for i, name in enumerate(("operator_norm", "ffn_norm", "q_norm",
                              "k_norm", "final_norm")):
        p[name] = 1.0 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(100 + i), p[name].shape, jnp.float32)
    p["router"] = p["router"] * 40.0  # scores spread over (0, 1)
    p["router_bias"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(11), p["router_bias"].shape, jnp.float32)
    for name in ("conv_out", "wo", "dense.w_down", "moe_w_down"):
        p[name] = p[name] * 3.0
    p["embed"] = p["embed"] * 10.0
    return p
