"""What the two Falcon-H1 test files share: the float32 tiny preset, the
published config.json's spelling of it (what the reference reads), and the
tolerance of the logit comparisons."""

import dataclasses

from dynamo_tpu.models.config import PARALLEL, PRESETS, ModelConfig

# Tolerance of the logit comparisons (float32 on both sides, the reference's
# matmuls at "highest"): the program sums in another order (the chunked scan
# against the token-by-token recurrence, paged attention blocks) and applies
# a multiplier to a projection's output where the reference scales its
# input; nothing else. The tiny model's logits are O(1) and agree to ~1e-6,
# so 2e-5 leaves room; the mildest wrong model the tests know (one
# multiplier taken as 1) must FAIL at 50x the tolerance.
RTOL = ATOL = 2e-5


def tiny(**kw) -> ModelConfig:
    return dataclasses.replace(PRESETS["tiny-falcon-h1-debug"],
                               dtype="float32", **kw)


def hf_dict(cfg: ModelConfig) -> dict:
    """The tiny preset as the published config.json spells it."""
    m = cfg.multipliers
    return {
        "model_type": "falcon_h1", "architectures": ["FalconH1ForCausalLM"],
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "layer_types": [PARALLEL] * cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "mamba_n_heads": cfg.mamba_num_heads,
        "mamba_d_head": cfg.mamba_head_dim,
        "mamba_d_ssm": cfg.mamba_d_inner, "mamba_expand": 2,
        "mamba_n_groups": cfg.mamba_n_groups,
        "mamba_d_state": cfg.ssm_state_size,
        "mamba_d_conv": cfg.conv_kernel,
        "mamba_chunk_size": cfg.ssm_chunk_size,
        "mamba_conv_bias": True, "mamba_proj_bias": False,
        "mamba_rms_norm": True, "mamba_norm_before_gate": False,
        "mamba_use_mlp": True, "attention_bias": False,
        "projectors_bias": False, "mlp_bias": False,
        "attn_layer_indices": None, "rope_scaling": None,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
        "hidden_act": "silu", "tie_word_embeddings": False,
        "max_position_embeddings": 8192,
        "embedding_multiplier": m.embedding,
        "lm_head_multiplier": m.lm_head,
        "attention_in_multiplier": m.attention_in,
        "attention_out_multiplier": m.attention_out,
        "key_multiplier": m.key, "ssm_in_multiplier": m.ssm_in,
        "ssm_out_multiplier": m.ssm_out, "ssm_multipliers": list(m.ssm),
        "mlp_multipliers": list(m.mlp),
    }
