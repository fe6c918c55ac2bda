"""What the two Nemotron-H test files share: the float32 tiny preset, the
published config.json's spelling of it (what the reference reads), and the
tolerance of the logit comparisons."""

import dataclasses

from dynamo_tpu.models.config import MIXER_LETTERS, PRESETS, ModelConfig

# Tolerance of the logit comparisons (float32 on both sides, the reference's
# matmuls at "highest"): the program sums in another order (the chunked scan
# against the token-by-token recurrence, paged attention blocks, grouped
# expert matmuls) and nothing else; the tiny model's logits are O(1) and
# agree to ~5e-7, so 2e-5 leaves forty times of room. It is ten times
# tighter than the sibling models' 2e-4 because the mildest wrong mechanism
# here is mild: a state rounded to bfloat16 after every token reads ~3e-3
# on these logits (2^-9 a rounding, averaged over the state's lanes), where
# a wrong norm, gate, expert or rotary reads 0.2 and more. Each control
# must FAIL at 50x the tolerance.
RTOL = ATOL = 2e-5


def tiny(**kw) -> ModelConfig:
    return dataclasses.replace(PRESETS["tiny-nemotron-h-debug"],
                               dtype="float32", **kw)


def hf_dict(cfg: ModelConfig) -> dict:
    """The tiny preset as the published config.json spells it."""
    letter = {v: k for k, v in MIXER_LETTERS.items()}
    return {
        "model_type": "nemotron_h", "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size, "num_hidden_layers": cfg.num_layers,
        "hybrid_override_pattern": "".join(
            letter[k] for k in cfg.mixer_types),
        "mamba_num_heads": cfg.mamba_num_heads,
        "mamba_head_dim": cfg.mamba_head_dim, "n_groups": cfg.mamba_n_groups,
        "ssm_state_size": cfg.ssm_state_size, "conv_kernel": cfg.conv_kernel,
        "chunk_size": cfg.ssm_chunk_size, "expand": 2,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "n_routed_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "moe_intermediate_size": cfg.intermediate_size,
        "intermediate_size": cfg.intermediate_size,
        "moe_shared_expert_intermediate_size": cfg.shared_expert_width,
        "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
        "norm_eps": cfg.rms_norm_eps, "layer_norm_epsilon": cfg.rms_norm_eps,
        "use_conv_bias": True, "use_bias": False, "mlp_bias": False,
        "mamba_proj_bias": False, "attention_bias": False,
        "tie_word_embeddings": False, "rope_theta": 10000,
        "max_position_embeddings": 8192,
    }
