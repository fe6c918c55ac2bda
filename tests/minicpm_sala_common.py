"""What the MiniCPM-SALA test files share: the float32 tiny preset, the
published config.json's spelling of it (what the reference reads), the drawn
parameters, and the tolerance of the logit comparisons."""

import dataclasses

import jax
import jax.numpy as jnp

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import LIGHTNING, PRESETS, SPARSE, ModelConfig

# Tolerance of the logit comparisons (float32 on both sides, the reference's
# matmuls at "highest"): the program sums in another order (paged attention
# blocks, the chunked scan against the one-token loop, a pooled key as two
# page sums) and nothing else. The tiny model's logits are O(1) and agree to
# ~1e-5; every control (tests/test_minicpm_sala.py) reads 1e-2 and more.
RTOL = ATOL = 5e-4


def tiny(**kw) -> ModelConfig:
    return dataclasses.replace(PRESETS["tiny-minicpm-sala-debug"],
                               dtype="float32", **kw)


def hf_dict(cfg: ModelConfig) -> dict:
    """The tiny preset as the published config.json spells it."""
    word = {SPARSE: "minicpm4", LIGHTNING: "lightning-attn"}
    return {
        "model_type": "minicpm_sala", "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size, "num_hidden_layers": cfg.num_layers,
        "intermediate_size": cfg.intermediate_size,
        "mixer_types": [word[k] for k in cfg.mixer_types],
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "lightning_nh": cfg.mamba_num_heads,
        "lightning_nkv": cfg.mamba_n_groups,
        "lightning_head_dim": cfg.mamba_head_dim,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "attn_use_rope": False, "qk_norm": True, "use_output_gate": True,
        "use_output_norm": True, "attn_use_output_gate": True,
        "attention_bias": False, "hidden_act": "silu",
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "scale_emb": cfg.scale_emb, "scale_depth": cfg.scale_depth,
        "dim_model_base": cfg.dim_model_base, "mup_denominator": 32,
        "max_position_embeddings": 8192, "tie_word_embeddings": False,
        "sparse_config": {
            "kernel_size": cfg.sparse_kernel_size,
            "kernel_stride": cfg.sparse_kernel_stride,
            "block_size": cfg.sparse_block_size, "topk": cfg.sparse_topk,
            "init_blocks": cfg.sparse_init_blocks,
            "window_size": cfg.sparse_window_size,
            "dense_len": cfg.sparse_dense_len},
    }


def drawn(cfg: ModelConfig, seed: int = 3) -> dict:
    """init_params with what it draws at the identity drawn away from it
    (every norm's weights about 1 +- 0.3) and the branches loud enough that
    the logits are O(1) and a mechanism left out moves them far past ATOL:
    the sparse layers' q / k norms x 2 (scores that spread: a selection that
    differs from the first blocks, dropped blocks that held mass), the
    output projections and the head louder."""
    p = llama.init_params(cfg, jax.random.PRNGKey(seed))
    norms = [k for k in p if k.endswith("_norm")]
    for i, name in enumerate(sorted(norms)):
        p[name] = 1.0 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(100 + i), p[name].shape, jnp.float32)
    for name in ("q_norm", "k_norm"):
        p[name] = p[name] * 2.0
    for name in ("wo", "lightning.wo", "w_down"):
        p[name] = p[name] * 4.0
    p["lm_head"] = p["lm_head"] * 40.0
    return p
