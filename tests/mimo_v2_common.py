"""What the two MiMo-V2 test files share: the float32 tiny presets, the
published config.json's spelling of them (what the reference reads and what
`from_hf_config` maps back), seeded weights whose every mechanism matters,
and the tolerance of the logit comparisons."""

import dataclasses

import jax
import numpy as np

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import FULL, PRESETS, SLIDING, ModelConfig

# Tolerance of the logit comparisons (float32 on both sides, the reference's
# matmuls at "highest"): the program sums in another order (paged blocks, an
# online softmax that starts from the sink, grouped expert matmuls, a scan
# inside a scan) and scales the heads' outputs where the reference scales
# the average; nothing else. The tiny model's logits are O(1) and agree to
# ~1e-5, so 2e-4 is the tolerance Laguna's and Kimi-K2's tests hold; the
# mildest wrong model the tests know must FAIL at 50x it.
RTOL = ATOL = 2e-4


def tiny(name: str = "tiny-mimo-v2-debug", **kw) -> ModelConfig:
    return dataclasses.replace(PRESETS[name], dtype="float32", **kw)


def hf_dict(cfg: ModelConfig) -> dict:
    """The tiny preset as the published config.json spells it (a share's
    held experts under `deployment_share`, as the benchmark's cut has it)."""
    n, k = cfg.num_layers, cfg.first_k_dense
    (_, theta, share, _), (_, swa_theta, _, _) = cfg.rope_by_kind
    out = {
        "model_type": "mimo_v2", "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.dense_intermediate_size,
        "moe_intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": n, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "swa_num_key_value_heads": cfg.kind_kv_heads(SLIDING),
        "swa_num_attention_heads": cfg.num_heads,
        "head_dim": cfg.head_dim, "swa_head_dim": cfg.head_dim,
        "v_head_dim": cfg.v_head_dim, "swa_v_head_dim": cfg.v_head_dim,
        # the published 0.334 of 192 is int(64.128) = 64 lanes; here a
        # factor a hair over the share, floored the same way
        "partial_rotary_factor": share + 1e-3,
        "rope_theta": theta, "swa_rope_theta": swa_theta,
        "rope_scaling": {"rope_type": "default", "type": "default"},
        "hybrid_layer_pattern": [int(t == SLIDING) for t in cfg.layer_types],
        "hybrid_block_size": None,
        "moe_layer_freq": [0] * k + [1] * (n - k),
        "sliding_window": cfg.sliding_window,
        "sliding_window_size": cfg.sliding_window,
        "attention_chunk_size": cfg.sliding_window,
        "attention_value_scale": cfg.attn_value_scale,
        "attention_bias": False, "attention_projection_layout": "fused_qkv",
        "add_full_attention_sink_bias": False,
        "add_swa_attention_sink_bias": SLIDING in cfg.attn_sink_kinds,
        "layernorm_epsilon": cfg.rms_norm_eps, "hidden_act": "silu",
        "n_routed_experts": cfg.held_experts, "n_shared_experts": None,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob, "routed_scaling_factor": None,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "n_group": 1, "topk_group": 1, "tie_word_embeddings": False,
        "max_position_embeddings": cfg.max_position_embeddings,
    }
    if cfg.num_local_experts:
        out["deployment_share"] = {
            "n_routed_experts_total": cfg.num_experts,
            "first_routed_expert": cfg.local_expert_offset}
    return out


def seeded_params(cfg: ModelConfig, seed: int = 3):
    """init_params (its sinks are drawn in [-2, 2)) with a selection bias
    that changes the pick: init leaves it zero."""
    p = llama.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    p["router_bias"] = jax.numpy.asarray(
        rng.normal(0.0, 0.3, p["router_bias"].shape), jax.numpy.float32)
    return p


def share_of(p: dict, first: int, held: int) -> dict:
    """The parameter tree of a chip that holds experts [first, first + held)
    of an uncut tree `p`."""
    return {k: (v[:, first:first + held] if k.startswith("moe_w_") else v)
            for k, v in p.items()}


__all__ = ["ATOL", "FULL", "RTOL", "SLIDING", "hf_dict", "seeded_params",
           "share_of", "tiny"]
