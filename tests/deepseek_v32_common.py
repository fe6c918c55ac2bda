"""What tests/test_deepseek_v32.py and tests/test_deepseek_v32_engine.py
share: the tiny preset in float32, the reference's configuration of it, and
the tap on the program's traced selections."""

import dataclasses

import jax
import numpy as np

from dynamo_tpu.models.config import ModelConfig, PRESETS
from dynamo_tpu.models.reference import deepseek_v32 as ref
from dynamo_tpu.ops import attention as att

PS = 4          # page size
TOPK = PRESETS["tiny-dsv32-debug"].index_topk  # 16


def tiny(**kw) -> ModelConfig:
    return dataclasses.replace(PRESETS["tiny-dsv32-debug"], dtype="float32",
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
        "n_group": cfg.n_group, "topk_group": cfg.topk_group,
        "first_k_dense_replace": cfg.first_k_dense,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": cfg.norm_topk_prob, "scoring_func": "sigmoid",
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "index_n_heads": cfg.index_n_heads,
        "index_head_dim": cfg.index_head_dim, "index_topk": cfg.index_topk,
        "rope_scaling": {"type": "yarn", "factor": f, "beta_fast": bf,
                         "beta_slow": bs, "mscale": ms,
                         "mscale_all_dim": msad,
                         "original_max_position_embeddings": orig}})


def tapped(fn):
    """Run fn() with the selection of every traced call recorded:
    [(kind, qpos, sel, valid)] in program order."""
    calls = []
    att.DSA_TAP = lambda kind, qpos, sel, valid: calls.append(
        (kind, np.asarray(qpos), np.asarray(sel), np.asarray(valid)))
    try:
        out = fn()
        jax.effects_barrier()
    finally:
        att.DSA_TAP = None
    return out, calls
