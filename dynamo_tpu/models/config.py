"""Model configuration for the llama-family decoder architectures served by the
engine workers.

The reference stack serves models by HF id via engine CLI flags
(`/root/reference/examples/deploy/vllm/agg.yaml:33-35` `--model
meta-llama/Llama-3.2-1B-Instruct`); here the analogous contract is
`ModelConfig.from_model_name`, which understands either a preset name, a local
HF checkpoint directory (config.json), or falls back to a tiny debug model.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import NamedTuple, Optional, Tuple


def _llama3_rope_scaling(cfg: dict):
    """HF rope_scaling with rope_type "llama3" (Llama-3.1+) ->
    (factor, low_freq_factor, high_freq_factor, original_max_pos).

    Other scaling kinds: "linear" is modeled for gemma-3 (per-layer),
    "yarn" by _yarn_rope_scaling below, "longrope" (Phi-3) by
    _longrope_rope_scaling; "dynamic" is NOT modeled — warn loudly rather
    than silently serving frequencies the checkpoint wasn't trained
    with."""
    rs = cfg.get("rope_scaling") or {}
    kind = rs.get("rope_type") or rs.get("type")
    if kind != "llama3":
        if kind in ("dynamic",):
            import logging

            logging.getLogger("dynamo_tpu.models").warning(
                "rope_scaling type %r is not modeled — serving with "
                "UNSCALED rope; outputs will diverge from the checkpoint's "
                "training distribution beyond its original context", kind)
        return None
    return (
        float(rs.get("factor", 8.0)),
        float(rs.get("low_freq_factor", 1.0)),
        float(rs.get("high_freq_factor", 4.0)),
        int(rs.get("original_max_position_embeddings", 8192)),
    )


def _yarn_rope_scaling(cfg: dict):
    """HF rope_scaling with type "yarn" (DeepSeek-V2's default) ->
    (factor, beta_fast, beta_slow, original_max_pos, mscale,
    mscale_all_dim, attention_factor).

    mscale_all_dim=0 flows through AS zero — yarn_get_mscale(f, 0) == 1,
    HF's softmax-neutral default. attention_factor=-1 means "derive from
    mscale"; an explicit value (generic HF yarn) overrides the rotary
    magnitude and suppresses the DeepSeek softmax mscale^2."""
    rs = cfg.get("rope_scaling") or {}
    if (rs.get("rope_type") or rs.get("type")) != "yarn":
        return None
    af = rs.get("attention_factor")
    return (
        float(rs.get("factor", 1.0)),
        float(rs.get("beta_fast", 32.0)),
        float(rs.get("beta_slow", 1.0)),
        int(rs.get("original_max_position_embeddings", 4096)),
        float(rs.get("mscale", 1.0)),
        float(rs.get("mscale_all_dim", 0.0)),
        float(af) if af is not None else -1.0,
    )


def _longrope_rope_scaling(cfg: dict):
    """HF rope_scaling with type "longrope" (Phi-3) ->
    (short_factors, long_factors, original_max_position_embeddings).

    Factor selection is PER POSITION at apply time (ops/rope.apply_rope):
    positions inside the original window rotate with short-factor
    frequencies, positions beyond with long-factor ones — vLLM's
    su-rope serving semantics, which keep short prompts on the
    frequencies the base model trained with. (HF torch instead switches
    the WHOLE forward to long factors once total length exceeds the
    window; the two agree on every request that fits the original
    window.) The attention magnitude sqrt(1 + ln(s)/ln(orig)) applies
    globally when the checkpoint extends the window, as in vLLM."""
    rs = cfg.get("rope_scaling") or {}
    if (rs.get("rope_type") or rs.get("type")) != "longrope":
        return None
    orig = int(rs.get("original_max_position_embeddings",
                      cfg.get("original_max_position_embeddings", 4096)))
    short = rs.get("short_factor")
    long = rs.get("long_factor")
    if not short or not long:
        import logging

        logging.getLogger("dynamo_tpu.models").warning(
            "rope_scaling type 'longrope' is missing short_factor/"
            "long_factor arrays — serving with UNSCALED rope; outputs "
            "will diverge from the checkpoint's training distribution")
        return None
    return (tuple(float(f) for f in short),
            tuple(float(f) for f in long), orig)


# the attention kinds of ModelConfig.layer_types, in HF's words
FULL, SLIDING = "full_attention", "sliding_attention"
ATTENTION_KINDS = (FULL, SLIDING)

# model_type values from_hf_config maps (with the architectures it reads
# them by): a config of another type that carries one of _KIND_KEYS would
# be served as a plain llama-family stack, which is another model
_MAPPED_MODEL_TYPES = frozenset((
    "llama", "mistral", "qwen2", "qwen3", "qwen3_moe", "mixtral", "phi3",
    "gemma", "gemma2", "gemma3", "gemma3_text", "deepseek_v2", "deepseek_v3",
    "kimi_k2", "deepseek_v32", "nemotron_h", "falcon_h1", "laguna",
    "mimo_v2", "lfm2_moe", "minicpm_sala"))
_MAPPED_ARCH_WORDS = ("Llama", "Mistral", "Qwen", "Mixtral", "Phi3", "Gemma",
                      "Deepseek", "Kimi")
_KIND_KEYS = ("layer_types", "num_attention_heads_per_layer", "gating")

# the mixer kinds of ModelConfig.mixer_types, and the letters of
# `hybrid_override_pattern` that name the three a nemotron_h layer is ONE of
MAMBA, EXPERTS, ATTENTION = "mamba", "moe", "attention"
MIXER_LETTERS = {"M": MAMBA, "E": EXPERTS, "*": ATTENTION}
# falcon_h1's layer: attention AND a Mamba-2 mixer side by side on one normed
# input, summed, then a gated MLP. Every layer of such a model is this one.
PARALLEL = "attention+mamba"
# lfm2_moe's operator kinds: a layer is an OPERATOR (a gated short
# convolution, `conv`, or GQA attention under q/k norms and a rotary) and
# then an FFN (dense in the leading layers, experts behind them). The conv
# keeps a state slot WITHOUT a recurrence: its last conv_kernel - 1 rows.
CONV = "conv"
# minicpm_sala's operator kinds (the same operator-then-FFN layer, every FFN
# dense): `sparse`, MiniCPM4's InfLLM-v2 attention (GQA under q/k norms, no
# rotary, an output gate; past `sparse_dense_len` tokens of context a query
# attends the blocks it selects over mean-pooled keys and nothing else), and
# `lightning`, Lightning linear attention (a decayed outer-product state
# [heads, head_dim, head_dim] float32 a sequence: ops/ssm.py's recurrence
# with x = v, B = k, C = q, dt = 1).
SPARSE, LIGHTNING = "sparse", "lightning"
MIXER_KINDS = (MAMBA, EXPERTS, ATTENTION, PARALLEL, CONV, SPARSE, LIGHTNING)
# the kinds whose layers page KV, and those that keep a state slot
PAGED_MIXERS = (ATTENTION, PARALLEL, SPARSE)
STATE_MIXERS = (MAMBA, PARALLEL, CONV, LIGHTNING)
# the operator kinds of an operator-then-FFN model, by family
_LFM2_OPERATORS, _SALA_OPERATORS = (CONV, ATTENTION), (SPARSE, LIGHTNING)
# two-matrix experts act(u W_up) W_down: the activations written down
TWO_MATRIX_ACTS = ("relu2", "silu")


class Multipliers(NamedTuple):
    """falcon_h1's fixed multipliers, under the published names less
    `_multiplier(s)`: `ssm` scales the five runs [z | x | B | C | dt] of the
    Mamba-2 input projection's output, `mlp` the gate's pre-activation and
    the down projection's output. Where each applies: models/reference/
    falcon_h1.py."""
    embedding: float = 1.0
    lm_head: float = 1.0
    attention_in: float = 1.0
    attention_out: float = 1.0
    key: float = 1.0
    ssm_in: float = 1.0
    ssm_out: float = 1.0
    ssm: Tuple[float, ...] = (1.0,) * 5
    mlp: Tuple[float, ...] = (1.0, 1.0)


def _falcon_h1_from_hf(cfg: dict) -> dict:
    """The ModelConfig fields of `model_type: falcon_h1` (every layer
    attention AND Mamba-2 on one normed input, summed, then a gated MLP,
    under fixed multipliers). Refuses, loudly and by key, what is not
    served."""
    n = int(cfg["num_hidden_layers"])
    for key, served in (("mamba_rms_norm", True),
                        ("mamba_norm_before_gate", False),
                        ("mamba_use_mlp", True), ("mamba_conv_bias", True),
                        ("attention_bias", False), ("projectors_bias", False),
                        ("mamba_proj_bias", False), ("mlp_bias", False)):
        if bool(cfg.get(key, served)) != served:
            raise ValueError(
                f"{key}={str(cfg[key]).lower()} is not implemented for "
                f"falcon_h1 ({str(served).lower()} is served)")
    for key in ("attn_layer_indices", "rope_scaling"):
        if cfg.get(key) is not None:
            raise ValueError(
                f"{key}={cfg[key]!r} is not implemented for falcon_h1 "
                "(null is served: every layer attends, under a plain "
                "rotary)")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act={cfg['hidden_act']!r} is not "
                         "implemented for falcon_h1 (silu is)")
    listed = cfg.get("layer_types")
    if listed is not None and tuple(listed) != (PARALLEL,) * n:
        raise ValueError(
            f"layer_types {list(listed)}: every falcon_h1 layer is "
            f"{PARALLEL!r}")
    heads, groups = int(cfg["mamba_n_heads"]), int(cfg["mamba_n_groups"])
    if heads % groups:
        raise ValueError(
            f"mamba_n_heads={heads} is no multiple of mamba_n_groups="
            f"{groups}: a head reads the B / C rows of ONE group")
    if int(cfg["mamba_d_ssm"]) != heads * int(cfg["mamba_d_head"]):
        raise ValueError(
            f"mamba_d_ssm={cfg['mamba_d_ssm']} is not mamba_n_heads x "
            f"mamba_d_head = {heads * int(cfg['mamba_d_head'])}: the "
            "mixer's width is served as heads x head size (mamba_expand "
            "is carried and unused)")
    ssm, mlp = cfg["ssm_multipliers"], cfg["mlp_multipliers"]
    if len(ssm) != 5 or len(mlp) != 2:
        raise ValueError("ssm_multipliers has five entries [z | x | B | C | "
                         "dt] and mlp_multipliers two [gate | down]")
    return dict(
        mixer_types=(PARALLEL,) * n,
        mamba_num_heads=heads, mamba_head_dim=int(cfg["mamba_d_head"]),
        mamba_n_groups=groups, ssm_state_size=int(cfg["mamba_d_state"]),
        conv_kernel=int(cfg["mamba_d_conv"]),
        ssm_chunk_size=int(cfg.get("mamba_chunk_size") or 128),
        multipliers=Multipliers(
            embedding=float(cfg["embedding_multiplier"]),
            lm_head=float(cfg["lm_head_multiplier"]),
            attention_in=float(cfg["attention_in_multiplier"]),
            attention_out=float(cfg["attention_out_multiplier"]),
            key=float(cfg["key_multiplier"]),
            ssm_in=float(cfg["ssm_in_multiplier"]),
            ssm_out=float(cfg["ssm_out_multiplier"]),
            ssm=tuple(float(m) for m in ssm),
            mlp=tuple(float(m) for m in mlp)),
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        attention_bias=False, sliding_window=0,
        rope_llama3_scaling=None, rope_yarn_scaling=None,
        rope_longrope_scaling=None,
    )


def _hybrid_from_hf(cfg: dict) -> dict:
    """The ModelConfig fields of a hybrid model: `model_type: nemotron_h`
    (every layer ONE mixer by `hybrid_override_pattern`: Mamba-2, experts of
    two matrices beside a shared one, or GQA attention without a rotary),
    `falcon_h1` (_falcon_h1_from_hf) or `lfm2_moe` (_lfm2_from_hf); {} for
    every other model. Refuses,
    loudly, what it would otherwise serve as another model."""
    if cfg.get("model_type") == "falcon_h1":
        return _falcon_h1_from_hf(cfg)
    if cfg.get("model_type") in ("lfm2_moe", "lfm2"):
        return _lfm2_from_hf(cfg)
    if cfg.get("model_type") == "minicpm_sala":
        return _minicpm_sala_from_hf(cfg)
    if cfg.get("model_type") != "nemotron_h":
        return {}
    n = int(cfg["num_hidden_layers"])
    pattern = cfg.get("hybrid_override_pattern") or ""
    unknown = sorted(set(pattern) - set(MIXER_LETTERS))
    if unknown:
        raise ValueError(
            f"hybrid_override_pattern {pattern!r} has letters {unknown}: "
            f"{sorted(MIXER_LETTERS)} are served ('-', a dense MLP alone, "
            "is not implemented)")
    if len(pattern) != n:
        raise ValueError(
            f"hybrid_override_pattern has {len(pattern)} letters for "
            f"num_hidden_layers={n}")
    mixers = tuple(MIXER_LETTERS[c] for c in pattern)
    listed = cfg.get("layer_types")
    if listed is not None and tuple(listed) != mixers:
        raise ValueError(
            f"layer_types {list(listed)} disagrees with "
            f"hybrid_override_pattern {pattern!r}")
    heads, groups = int(cfg["mamba_num_heads"]), int(cfg["n_groups"])
    if heads % groups:
        raise ValueError(
            f"mamba_num_heads={heads} is no multiple of n_groups={groups}: "
            "a head reads the B / C rows of ONE group")
    act = cfg.get("mlp_hidden_act")
    if act not in TWO_MATRIX_ACTS:
        raise ValueError(
            f"mlp_hidden_act={act!r} on a two-matrix expert is not "
            f"implemented: {TWO_MATRIX_ACTS} are")
    if int(cfg.get("n_group") or 1) > 1 or int(cfg.get("topk_group") or 1) > 1:
        raise ValueError(
            f"n_group={cfg.get('n_group')} / topk_group="
            f"{cfg.get('topk_group')} under model_type nemotron_h is not "
            "implemented: its router is served without groups")
    for key in ("mamba_proj_bias", "mlp_bias", "use_bias", "attention_bias"):
        if cfg.get(key):
            raise ValueError(f"{key}=true is not implemented for nemotron_h")
    if cfg.get("use_conv_bias") is False:
        raise ValueError("use_conv_bias=false is not implemented")
    if cfg.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError(f"mamba_hidden_act={cfg['mamba_hidden_act']!r}")
    shared = int(cfg.get("moe_shared_expert_intermediate_size") or 0)
    return dict(
        mixer_types=mixers,
        mamba_num_heads=heads, mamba_head_dim=int(cfg["mamba_head_dim"]),
        mamba_n_groups=groups, ssm_state_size=int(cfg["ssm_state_size"]),
        conv_kernel=int(cfg["conv_kernel"]),
        ssm_chunk_size=int(cfg.get("chunk_size") or 128),
        expert_act=act,
        rms_norm_eps=float(cfg.get("norm_eps")
                           or cfg.get("layer_norm_epsilon") or 1e-5),
        # ASSUMED (ISSUE 42 b): DeepSeek-V3's noaux_tc, the family whose
        # key names these are; the config names no scoring_func
        moe_scoring="sigmoid", router_bias=True, n_group=1, topk_group=1,
        num_shared_experts=1 if shared else 0,
        shared_expert_intermediate_size=shared,
        first_k_dense=0, dense_intermediate_size=0,
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        sliding_window=0,
        rope_llama3_scaling=None, rope_yarn_scaling=None,
        rope_longrope_scaling=None,
    )


def _lfm2_from_hf(cfg: dict) -> dict:
    """The ModelConfig fields of `model_type: lfm2_moe`: every layer an
    OPERATOR by `layer_types` (`conv`: C * conv_K(B * u) between two
    projections, depthwise, causal, no bias, no activation, its state the
    last K - 1 rows of B * u; `full_attention`: GQA under per-head q/k RMS
    norms and a rotary) and then an FFN (gated dense in the first
    `num_dense_layers`, sigmoid-routed gated experts picked under a
    selection bias behind them), a head tied to the embedding. Refuses, by
    the key's name, what the program would otherwise serve as another
    model."""
    def refuse(key, why):
        raise ValueError(f"{key}={cfg.get(key)!r} is not implemented for "
                         f"model_type {cfg.get('model_type')!r}: {why}")

    if cfg.get("model_type") == "lfm2":
        refuse("model_type", "the dense sibling (no experts) has not been "
               "run against a reference; 'lfm2_moe' is served")
    n = int(cfg["num_hidden_layers"])
    kinds = tuple(cfg.get("layer_types") or ())
    if len(kinds) != n:
        raise ValueError(f"layer_types has {len(kinds)} entries for "
                         f"num_hidden_layers={n}")
    names = {"conv": CONV, FULL: ATTENTION}
    if set(kinds) - set(names):
        refuse("layer_types", f"entries other than {sorted(names)}")
    if cfg.get("conv_bias"):
        refuse("conv_bias", "the projections and the conv carry no bias")
    if int(cfg.get("conv_L_cache") or 0) < 2:
        refuse("conv_L_cache", "a conv of fewer than 2 taps keeps no state")
    dense = int(cfg.get("num_dense_layers") or 0)
    if dense >= n:
        refuse("num_dense_layers", "at least one expert layer must follow "
               f"the dense ones (num_hidden_layers={n})")
    if not cfg.get("use_expert_bias", False):
        refuse("use_expert_bias", "the pick is served under the selection "
               "bias only")
    if not cfg.get("norm_topk_prob", False):
        refuse("norm_topk_prob", "the picked scores are served "
               "renormalised only")
    if int(cfg["num_experts_per_tok"]) > int(cfg["num_experts"]):
        refuse("num_experts_per_tok", f"over num_experts="
               f"{cfg['num_experts']}")
    if cfg.get("sliding_window") is not None:
        refuse("sliding_window", "the attention layers attend in full")
    for key in ("rope_scaling", "rope_parameters"):
        rp = cfg.get(key)
        if rp and (rp.get("rope_type") or rp.get("type")
                   or "default") != "default":
            refuse(key, "the rotary is served unscaled")
    return dict(
        mixer_types=tuple(names[k] for k in kinds),
        conv_kernel=int(cfg["conv_L_cache"]),
        qk_norm=True,
        rms_norm_eps=float(cfg.get("norm_eps") or 1e-5),
        rope_theta=float(cfg.get("rope_theta", 1000000.0)),
        rope_llama3_scaling=None, rope_yarn_scaling=None,
        rope_longrope_scaling=None,
        moe_scoring="sigmoid", router_bias=True, n_group=1, topk_group=1,
        norm_topk_prob=True,
        first_k_dense=dense,
        dense_intermediate_size=int(cfg["intermediate_size"]) if dense else 0,
        # ASSUMED (ISSUE 52): the family ties its head to the embedding
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", True)),
    )


# MiniCPM4-8B's published `sparse_config` (the InfLLM-v2 sizes the
# MiniCPM-SALA config does not repeat; ASSUMED, ISSUE 56): a 32-token mean
# pool every 16 tokens, blocks of 64, the 64 best beside 1 initial block and
# a 2,048-token local window, dense up to 8,192 tokens of context
SALA_SPARSE_DEFAULTS = dict(
    kernel_size=32, kernel_stride=16, block_size=64, topk=64, init_blocks=1,
    window_size=2048, dense_len=8192)


def _minicpm_sala_from_hf(cfg: dict) -> dict:
    """The ModelConfig fields of `model_type: minicpm_sala`: every layer an
    OPERATOR by `mixer_types` (`minicpm4`: InfLLM-v2 block-sparse GQA under
    per-head q/k norms, no rotary, a sigmoid output gate; `lightning-attn`:
    Lightning linear attention under q/k norms and a rotary, an output norm
    and gate) and then a dense gated silu FFN; the MiniCPM family's three
    muP scalars. Refuses, by the key's name, what the program would
    otherwise serve as another model."""
    def refuse(key, why):
        raise ValueError(f"{key}={cfg.get(key)!r} is not implemented for "
                         f"model_type 'minicpm_sala': {why}")

    n = int(cfg["num_hidden_layers"])
    kinds = tuple(cfg.get("mixer_types") or ())
    if len(kinds) != n:
        raise ValueError(f"mixer_types has {len(kinds)} entries for "
                         f"num_hidden_layers={n}")
    names = {"minicpm4": SPARSE, "lightning-attn": LIGHTNING}
    if set(kinds) - set(names):
        refuse("mixer_types", f"entries other than {sorted(names)}")
    if cfg.get("attn_use_rope", False):
        refuse("attn_use_rope", "the sparse layers are served without a "
               "rotary (a selected block has no position of its own in the "
               "page table a row attends)")
    if not cfg.get("lightning_use_rope", True):
        refuse("lightning_use_rope", "the Lightning layers are served "
               "under a rotary only")
    if not cfg.get("qk_norm", False):
        refuse("qk_norm", "both operators are served under per-head q/k "
               "norms only")
    for key in ("use_output_gate", "use_output_norm", "attn_use_output_gate"):
        if not cfg.get(key, False):
            refuse(key, "the operators are served with their output gate "
                   "and norm only")
    if cfg.get("attention_bias"):
        refuse("attention_bias", "the projections carry no bias")
    if cfg.get("lightning_scale", "1/sqrt(d)") != "1/sqrt(d)":
        refuse("lightning_scale", "q is scaled by 1 / sqrt(head_dim) only")
    heads, hd = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    lh = int(cfg.get("lightning_nh") or heads)
    lkv = int(cfg.get("lightning_nkv") or lh)
    lhd = int(cfg.get("lightning_head_dim") or hd)
    if (lh, lhd) != (heads, hd):
        refuse("lightning_nh", "the Lightning layers are served at the "
               "attention's heads and head_dim (their output gate and W_o "
               "are [hidden, heads * head_dim] alike)")
    if lkv != lh:
        refuse("lightning_nkv", "grouped Lightning keys are not served: a "
               "head's state is its own k (x) v")
    if cfg.get("rope_scaling"):
        refuse("rope_scaling", "the rotary is served unscaled")
    sparse = dict(SALA_SPARSE_DEFAULTS, **(cfg.get("sparse_config") or {}))
    unknown = sorted(set(sparse) - set(SALA_SPARSE_DEFAULTS) - {
        "use_nope", "dense_len_scale"})
    if unknown:
        refuse("sparse_config", f"keys {unknown} are not implemented")
    return dict(
        mixer_types=tuple(names[k] for k in kinds),
        qk_norm=True,
        rms_norm_eps=float(cfg.get("rms_norm_eps") or 1e-6),
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        rope_llama3_scaling=None, rope_yarn_scaling=None,
        rope_longrope_scaling=None,
        mamba_num_heads=lh, mamba_head_dim=lhd, mamba_n_groups=lkv,
        ssm_state_size=lhd,
        sparse_kernel_size=int(sparse["kernel_size"]),
        sparse_kernel_stride=int(sparse["kernel_stride"]),
        sparse_block_size=int(sparse["block_size"]),
        sparse_topk=int(sparse["topk"]),
        sparse_init_blocks=int(sparse["init_blocks"]),
        sparse_window_size=int(sparse["window_size"]),
        sparse_dense_len=int(sparse["dense_len"]),
        scale_emb=float(cfg.get("scale_emb") or 1.0),
        scale_depth=float(cfg.get("scale_depth") or 0.0),
        dim_model_base=int(cfg.get("dim_model_base") or 0),
        first_k_dense=0, dense_intermediate_size=0,
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
    )


def _rope_of_kind(kind: str, rp: dict):
    """One entry of HF rope_parameters -> ModelConfig.rope_by_kind's
    (kind, theta, rotary share, yarn tuple or None)."""
    rope_type = rp.get("rope_type") or rp.get("type") or "default"
    if rope_type not in ("default", "yarn"):
        raise ValueError(
            f"rope_parameters[{kind!r}]: rope_type {rope_type!r} is not "
            "implemented for a rotary a layer kind (default and yarn are)")
    yarn = (_yarn_rope_scaling({"rope_scaling": rp})
            if rope_type == "yarn" else None)
    return (kind, float(rp.get("rope_theta", 10000.0)),
            float(rp.get("partial_rotary_factor", 1.0)), yarn)


def _mimo_v2_from_hf(cfg: dict) -> dict:
    """`model_type: mimo_v2`: sliding layers (a window, KV heads of their
    own, a learned sink a query head in the softmax, a rotary base of their
    own) and full layers mixed by `hybrid_layer_pattern` (0 full, 1
    sliding), keys `head_dim` wide and values `v_head_dim`, a rotary over
    the first int(head_dim x partial_rotary_factor) lanes, the heads'
    outputs scaled by `attention_value_scale`, no output gate; leading
    dense layers by `moe_layer_freq`, then sigmoid-scored experts under a
    selection bias without groups or a shared expert. What the program
    cannot serve refuses here, by the key's name."""
    n = int(cfg["num_hidden_layers"])

    def refuse(key, why):
        raise ValueError(f"mimo_v2: {key}={cfg.get(key)!r} is not "
                         f"implemented: {why}")

    if cfg.get("add_full_attention_sink_bias"):
        refuse("add_full_attention_sink_bias",
               "a sink is served on the sliding layers' softmax only")
    if cfg.get("attention_bias"):
        refuse("attention_bias", "the projections are served without a bias")
    for key in ("n_group", "topk_group"):
        if int(cfg.get(key) or 1) != 1:
            refuse(key, "the router is served without groups for this "
                   "model type")
    if int(cfg.get("n_shared_experts") or 0) > 0:
        refuse("n_shared_experts", "no shared expert is served beside the "
               "routed ones for this model type")
    if cfg.get("scoring_func") != "sigmoid":
        refuse("scoring_func", "the router's scores are sigmoid")
    rs = cfg.get("rope_scaling") or {}
    if (rs.get("rope_type") or rs.get("type") or "default") != "default":
        refuse("rope_scaling", "both kinds take a plain rotary")
    if cfg.get("hybrid_block_size") is not None:
        refuse("hybrid_block_size", "the kinds follow hybrid_layer_pattern "
               "layer by layer")
    for swa, full in (("swa_head_dim", "head_dim"),
                      ("swa_v_head_dim", "v_head_dim"),
                      ("swa_num_attention_heads", "num_attention_heads")):
        if cfg.get(swa) is not None and cfg[swa] != cfg.get(full):
            refuse(swa, f"the sliding layers share {full}="
                   f"{cfg.get(full)!r} with the full ones (KV heads alone "
                   "differ by kind)")
    pattern = cfg.get("hybrid_layer_pattern")
    if pattern is None or len(pattern) != n or set(pattern) - {0, 1}:
        refuse("hybrid_layer_pattern", f"it needs {n} entries of 0 (full) "
               "or 1 (sliding)")
    kinds = tuple(SLIDING if p else FULL for p in pattern)
    listed = cfg.get("layer_types")
    if listed is not None and tuple(listed) != kinds:
        refuse("layer_types", "it disagrees with hybrid_layer_pattern")
    window = int(cfg.get("sliding_window") or 0)
    for key in ("attention_chunk_size", "sliding_window_size"):
        if cfg.get(key) is not None and int(cfg[key]) != window:
            refuse(key, f"it names the same span as sliding_window={window} "
                   "and adds no mechanism")
    freq = cfg.get("moe_layer_freq")
    freq = [1] * n if freq is None else (
        [int(f) for f in freq] if isinstance(freq, (list, tuple))
        else [int(i % int(freq) == 0) for i in range(n)])
    dense = n - sum(freq)
    if len(freq) != n or set(freq) - {0, 1} or any(freq[:dense]):
        refuse("moe_layer_freq", "dense layers are served as the LEADING "
               "layers only (a zero behind a one is not)")
    head_dim = int(cfg["head_dim"])
    lanes = int(head_dim * float(cfg.get("partial_rotary_factor", 1.0)))
    return dict(
        layer_types=kinds,
        heads_per_layer=(),
        rope_by_kind=tuple(
            (k, float(cfg.get(key, 10000.0)), lanes / head_dim, None)
            for k, key in ((FULL, "rope_theta"), (SLIDING, "swa_rope_theta"))
            if k in kinds),
        rope_yarn_scaling=None, rope_llama3_scaling=None,
        rope_longrope_scaling=None,
        sliding_window=window if SLIDING in kinds else 0,
        sliding_window_pattern=0,
        first_k_dense=dense,
        kv_heads_sliding=int(cfg.get("swa_num_key_value_heads")
                             or cfg["num_key_value_heads"]),
        v_head_dim=int(cfg.get("v_head_dim") or head_dim),
        attn_sink_kinds=((SLIDING,) if cfg.get("add_swa_attention_sink_bias")
                         and SLIDING in kinds else ()),
        attn_value_scale=float(cfg.get("attention_value_scale") or 1.0),
        rms_norm_eps=float(cfg.get("layernorm_epsilon")
                           or cfg.get("rms_norm_eps", 1e-5)),
    )


def _layer_kinds_from_hf(cfg: dict, arch: str) -> dict:
    """The ModelConfig fields of a model whose layers are of more than one
    kind (`model_type: laguna`: window and full attention mixed, head
    counts and rotaries by kind, a per-head output gate, softmax-routed
    experts beside a shared expert of a width of its own, leading dense
    layers named by mlp_only_layers; `model_type: mimo_v2`:
    _mimo_v2_from_hf); {} for every other model. Refuses, loudly, what it
    would otherwise serve as another model."""
    mt = cfg.get("model_type")
    carried = [k for k in _KIND_KEYS if cfg.get(k) is not None]
    if mt == "mimo_v2":
        return _mimo_v2_from_hf(cfg)
    if mt != "laguna":
        if carried and mt not in _MAPPED_MODEL_TYPES and not any(
                w in arch for w in _MAPPED_ARCH_WORDS):
            raise ValueError(
                f"model_type {mt!r} is not mapped and its config carries "
                f"{carried}: serving it as a llama-family stack would "
                "ignore them (per-layer kinds / head counts / an output "
                "gate are mapped for model_type 'laguna' and 'mimo_v2', "
                "operator kinds for 'lfm2_moe')")
        return {}
    n = int(cfg["num_hidden_layers"])
    gating = cfg.get("gating")
    if gating != "per-head":
        raise ValueError(
            f"gating={gating!r} is not implemented: 'per-head' (one "
            "sigmoid gate a head on the heads' outputs before W_o) is")
    if float(cfg.get("moe_router_logit_softcapping") or 0.0) != 0.0:
        raise ValueError(
            "moe_router_logit_softcapping="
            f"{cfg['moe_router_logit_softcapping']} is not implemented "
            "(the router's logits are served uncapped)")
    if cfg.get("moe_apply_router_weight_on_input"):
        raise ValueError(
            "moe_apply_router_weight_on_input=true is not implemented: "
            "the gate weights multiply the experts' outputs")
    lists = {k: cfg.get(k) for k in (
        "layer_types", "num_attention_heads_per_layer", "mlp_layer_types",
        "gating_types")}
    for k, v in lists.items():
        if v is not None and len(v) != n:
            raise ValueError(
                f"{k} has {len(v)} entries for num_hidden_layers={n}")
    kinds = tuple(lists["layer_types"] or (FULL,) * n)
    heads = tuple(int(h) for h in (lists["num_attention_heads_per_layer"]
                                   or (cfg["num_attention_heads"],) * n))
    if set(lists["gating_types"] or ["per_head"]) != {"per_head"}:
        raise ValueError("gating_types other than per_head on every layer "
                         "are not implemented")
    dense = sorted(int(i) for i in (cfg.get("mlp_only_layers") or []))
    if dense != list(range(len(dense))) or int(
            cfg.get("decoder_sparse_step") or 1) != 1:
        raise ValueError(
            f"mlp_only_layers={dense} / decoder_sparse_step="
            f"{cfg.get('decoder_sparse_step')}: dense layers are served "
            "as the LEADING layers only (interleaved ones are not "
            "implemented)")
    want = ["dense"] * len(dense) + ["sparse"] * (n - len(dense))
    if lists["mlp_layer_types"] is not None and list(
            lists["mlp_layer_types"]) != want:
        raise ValueError("mlp_layer_types disagrees with mlp_only_layers")
    rp = cfg.get("rope_parameters") or {}
    missing = set(kinds) - set(rp)
    if missing:
        raise ValueError(f"rope_parameters has no entry for {sorted(missing)}")
    shared = int(cfg.get("shared_expert_intermediate_size") or 0)
    return dict(
        layer_types=kinds,
        heads_per_layer=heads,
        num_heads=heads[kinds.index(FULL)] if FULL in kinds else heads[0],
        rope_by_kind=tuple(_rope_of_kind(k, rp[k]) for k in ATTENTION_KINDS
                           if k in kinds),
        rope_theta=float(rp.get(FULL, rp[kinds[0]]).get("rope_theta",
                                                        10000.0)),
        rope_yarn_scaling=None,
        attn_gate="per-head",
        sliding_window=(int(cfg.get("sliding_window") or 0)
                        if SLIDING in kinds else 0),
        sliding_window_pattern=0,
        first_k_dense=len(dense),
        num_shared_experts=1 if shared else 0,
        shared_expert_intermediate_size=shared,
        routed_scaling_factor=float(
            cfg.get("moe_routed_scaling_factor", 1.0)),
        moe_scoring="softmax",
        router_bias=False,
    )


# the shapes ops/grouped_matmul's kernel takes (it was measured at these
# and no others: PERF.md section 6, PR 52): a layer of at most 32 experts
# held here, of at most 4 MiB an int8 matrix, both extents lane multiples
GROUPED_KERNEL_MAX_EXPERTS, GROUPED_KERNEL_MAX_MATRIX_BYTES = 32, 4 << 20


def grouped_kernel_shapes(experts: int, k: int, n: int) -> bool:
    """Whether a layer of `experts` int8 matrices [k, n] (or [n, k]) has the
    shapes ops/grouped_matmul's kernel takes."""
    return (0 < experts <= GROUPED_KERNEL_MAX_EXPERTS and k % 128 == 0
            and n % 128 == 0 and k * n <= GROUPED_KERNEL_MAX_MATRIX_BYTES)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "tiny-debug"
    vocab_size: int = 512
    hidden_size: int = 128
    intermediate_size: int = 256
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 32
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 8192
    tie_word_embeddings: bool = True
    # MLP activation: "silu" (Llama/Qwen/Mixtral SwiGLU) or "gelu_tanh"
    # (Gemma GeGLU)
    hidden_act: str = "silu"
    # Gemma conventions: norms scale by (1 + w) instead of w, and the
    # embedding output is multiplied by sqrt(hidden_size)
    rms_norm_unit_offset: bool = False
    embed_scale: bool = False
    # Gemma-2 family:
    # sliding_window > 0 interleaves local-attention layers — layer i is
    # GLOBAL iff (i+1) % sliding_window_pattern == 0 (gemma-2: pattern 2 =
    # even layers local, matching HF's `not bool(layer_idx % 2)`), else
    # attends only to the last `sliding_window` positions. KV pages are
    # kept in full (masking enforces the window), and sliding models run
    # the XLA attention paths (the Pallas kernels don't window yet).
    sliding_window: int = 0
    sliding_window_pattern: int = 2
    # soft caps: cap * tanh(x / cap) on attention scores / final logits
    attn_logit_softcapping: float = 0.0
    final_logit_softcapping: float = 0.0
    # query scaling override: attention scales by query_pre_attn_scalar
    # ^-0.5 instead of head_dim^-0.5 when > 0 (gemma-2 uses 256 even
    # where head_dim is 128)
    query_pre_attn_scalar: float = 0.0
    # Gemma-3: per-layer rope bases — local (sliding) layers use
    # rope_local_theta, GLOBAL layers use rope_theta with positions
    # divided by rope_scaling_factor (HF linear rope scaling). 0 disables
    # (single rope_theta everywhere).
    rope_local_theta: float = 0.0
    rope_scaling_factor: float = 1.0
    # Llama-3.1+ frequency-dependent rope scaling (HF rope_type "llama3"):
    # (factor, low_freq_factor, high_freq_factor, original_max_position
    # _embeddings), or None. Applied to inv_freq once — affects every
    # position, so omitting it diverges from HF at ANY length.
    rope_llama3_scaling: Optional[Tuple[float, float, float, int]] = None
    # YaRN rope scaling (HF type "yarn"; DeepSeek-V2's default):
    # (factor, beta_fast, beta_slow, original_max_pos, mscale,
    # mscale_all_dim, attention_factor). Frequencies remap via the
    # correction-dim ramp; the attention softmax scale gains
    # yarn_get_mscale(factor, mscale_all_dim)^2 (applied as a q
    # pre-scale) unless an explicit attention_factor (>= 0) overrides
    # the rotary magnitude instead (generic HF yarn).
    rope_yarn_scaling: Optional[
        Tuple[float, float, float, int, float, float, float]] = None
    # Phi-3 longrope (HF type "longrope"): (short_factors, long_factors,
    # original_max_position_embeddings) — per-dim inv_freq divisors
    # selected PER POSITION at apply time (short inside the original
    # window, long beyond; vLLM su-rope semantics). cos/sin are
    # multiplied by sqrt(1 + ln(max/orig)/ln(orig)) when the checkpoint
    # extends the window.
    rope_longrope_scaling: Optional[
        Tuple[Tuple[float, ...], Tuple[float, ...], int]] = None
    # gemma-2/3 sandwich norms: extra RMSNorms on the attention and MLP
    # OUTPUTS (post_attention_layernorm / post_feedforward_layernorm in HF
    # naming — note HF llama's "post_attention_layernorm" is the PRE-MLP
    # norm; gemma-2's is genuinely post-attention)
    post_norms: bool = False
    # qwen3-style per-head q/k RMSNorm
    qk_norm: bool = False
    # qwen2-style attention bias on q/k/v projections
    attention_bias: bool = False
    # MoE (mixtral/deepseek-style). num_experts == 0 -> dense MLP.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # DeepSeek-style SHARED experts: always-active dense experts added to
    # the routed top-k output (each of width intermediate_size)
    num_shared_experts: int = 0
    # router gate convention: True (Mixtral/Qwen3) renormalizes the top-k
    # weights to sum 1; False (DeepSeek norm_topk_prob=false) keeps the
    # global-softmax probabilities, scaled by routed_scaling_factor
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # router scores: "softmax" (Mixtral / DeepSeek-V2) or "sigmoid"
    # (DeepSeek-V3 / Kimi-K2: s = sigmoid(logits), the k largest of
    # s + bias are picked, weights are s_i / sum_sel s, times
    # routed_scaling_factor). router_bias adds the per-expert selection
    # bias (HF e_score_correction_bias, topk_method "noaux_tc"): it moves
    # the PICK only, never the weights.
    moe_scoring: str = "softmax"
    router_bias: bool = False
    # group-limited selection (HF n_group / topk_group, DeepSeek-V3's
    # noaux_tc): the router's outputs form n_group groups of consecutive
    # experts, a group scores the sum of its 2 largest s + bias, and the
    # picks come from the topk_group best groups only. 1 / 1 = no groups.
    n_group: int = 1
    topk_group: int = 1
    # leading dense layers (HF first_k_dense_replace): the first
    # `first_k_dense` layers run one SwiGLU of dense_intermediate_size
    # instead of the expert layer; they keep their own parameter stack
    # ("dense." prefix) and run unrolled ahead of the layer scan.
    first_k_dense: int = 0
    dense_intermediate_size: int = 0
    # this chip's share of an expert-parallel deployment: the router keeps
    # num_experts outputs and num_experts_per_tok picks; the layer holds
    # experts [local_expert_offset, local_expert_offset + num_local_experts)
    # and computes only their part of the result (plus what every chip
    # computes alike: the shared expert). 0 = every expert is held.
    num_local_experts: int = 0
    local_expert_offset: int = 0
    # first vocabulary row held here when the vocabulary is sliced
    # (vocab_size is then the slice; only the checkpoint loader needs it)
    vocab_offset: int = 0
    # MLA (DeepSeek-V2-family multi-head latent attention). kv_lora_rank > 0
    # switches attention to the latent form: the paged cache stores ONE
    # shared [c_kv | k_rope] row per token (kv_lora_rank + qk_rope_head_dim
    # lanes) instead of per-head K/V — a 4x+ KV-cache compression — and
    # decode runs in the ABSORBED form (q_nope folded through W_UK so
    # queries attend directly over the latent rows).
    kv_lora_rank: int = 0
    # query low-rank path (DeepSeek-V2 236B / V3 / Kimi-K2):
    # x -> q_lora_rank -> RMSNorm -> H x (nope + rope); 0 = one full
    # query projection (DeepSeek-V2-Lite)
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0   # per-head no-rope query/key dim
    qk_rope_head_dim: int = 0   # shared rope dim appended to the latent row
    v_head_dim: int = 0         # per-head value dim out of W_UV
    # learned sparse selection in front of MLA (DeepSeek-V3.2's lightning
    # indexer): index_n_heads query heads of index_head_dim lanes score
    # every cached token against ONE index key a token (a second kind of
    # per-token state, kept in the V pool an MLA model leaves empty), and
    # a query attends over its index_topk best-scoring tokens only.
    # index_topk == 0: no indexer.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # attention layers of more than one KIND (HF layer_types): one entry a
    # layer, "full_attention" or "sliding_attention" (the last
    # `sliding_window` positions, the query's own included). Non-empty: the
    # kind of a layer is STATIC (read here by position, not computed from
    # a traced offset as the Gemma path above does), the layer scan runs
    # over periods of the list (models/llama._scan_periods_paged), every kind keeps
    # a KV pool and a page table of its own (engine/kv_cache.py) and the
    # paged kernels are specialised on the window. heads_per_layer: the
    # query heads of each layer (one count a kind; num_heads is the full
    # kind's). rope_by_kind: a rotary a kind, as (kind, theta,
    # rotary lanes' share of head_dim, yarn tuple as rope_yarn_scaling or
    # None). attn_gate "per-head": o <- o * sigmoid(h W_g) a head, before
    # W_o. shared_expert_intermediate_size: the shared expert's own width
    # (0: num_shared_experts * intermediate_size).
    # kv_heads_sliding: the sliding layers' KV heads where they differ
    # from num_kv_heads, the full layers' (0: the same). A model of kinds
    # may also store values narrower than keys: `v_head_dim` lanes a head
    # beside K's head_dim (0: head_dim; MLA reads the field its own way).
    # attn_sink_kinds: the kinds whose softmax carries a learned logit a
    # query head in its denominator. attn_value_scale: what the heads'
    # outputs are multiplied by before W_o.
    layer_types: Tuple[str, ...] = ()
    heads_per_layer: Tuple[int, ...] = ()
    rope_by_kind: Tuple[tuple, ...] = ()
    attn_gate: str = ""
    kv_heads_sliding: int = 0
    attn_sink_kinds: Tuple[str, ...] = ()
    attn_value_scale: float = 1.0
    shared_expert_intermediate_size: int = 0
    # a HYBRID model: one entry a layer. nemotron_h: "mamba" | "moe" |
    # "attention", and every layer is x + mixer(norm(x)) with that ONE
    # mixer: no attention + FFN pair; the layers run unrolled over a
    # parameter stack a kind (models/llama.py, "hybrid") and the attention
    # layers take NO rotary. falcon_h1: every entry "attention+mamba"
    # (PARALLEL): attention (with a rotary) and a Mamba-2 mixer on one
    # normed input, summed, then a gated MLP, under the fixed `multipliers`;
    # the layers are alike and run as ONE scan. Either way a layer that
    # attends owns KV pages and a layer with a Mamba-2 mixer owns a state
    # slot a sequence (engine/kv_cache.py): S [mamba_num_heads,
    # mamba_head_dim, ssm_state_size] float32 and the conv's last
    # conv_kernel - 1 input rows.
    # expert_act: "" = gate / up / down experts (silu(g) * u); "relu2" |
    # "silu" = two matrices an expert, act(u W_up) W_down, the shared one too.
    mixer_types: Tuple[str, ...] = ()
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 0
    ssm_state_size: int = 0
    conv_kernel: int = 0
    ssm_chunk_size: int = 128
    expert_act: str = ""
    multipliers: Optional[Multipliers] = None
    # minicpm_sala (SPARSE / LIGHTNING operators). The Lightning layers'
    # heads ride mamba_num_heads / mamba_head_dim / ssm_state_size (the
    # state is [heads, head_dim, head_dim] float32, a B / C row a head:
    # mamba_n_groups == mamba_num_heads). sparse_*: InfLLM-v2's sizes (a
    # mean pool of sparse_kernel_size tokens every sparse_kernel_stride;
    # blocks of sparse_block_size; the sparse_topk best beside
    # sparse_init_blocks leading blocks and the sparse_window_size tokens'
    # blocks that end with the query's; dense up to sparse_dense_len).
    # scale_emb / scale_depth / dim_model_base: the MiniCPM family's muP
    # scalars (embedding x scale_emb, a residual branch x scale_depth /
    # sqrt(layers), the final hidden / (hidden_size / dim_model_base));
    # 1 / 0 / 0 = none of them.
    sparse_kernel_size: int = 0
    sparse_kernel_stride: int = 0
    sparse_block_size: int = 0
    sparse_topk: int = 0
    sparse_init_blocks: int = 0
    sparse_window_size: int = 0
    sparse_dense_len: int = 0
    scale_emb: float = 1.0
    scale_depth: float = 0.0
    dim_model_base: int = 0
    # dtype for params/compute (bfloat16 on TPU; float32 for CPU tests)
    dtype: str = "bfloat16"
    eos_token_id: int = 2
    bos_token_id: int = 1
    # additional end-of-generation tokens (HF generation_config's eos
    # LIST): gemma-it models end chat turns with <end_of_turn>=107, which
    # they emit BEFORE <eos> — without it generations run to max_tokens
    extra_stop_token_ids: Tuple[int, ...] = ()

    def __post_init__(self):
        if self.is_moe and self.hidden_act != "silu":
            # the MoE dispatch kernels (ops/moe.py) contract with SwiGLU;
            # a GeGLU MoE config would silently serve the wrong activation
            raise ValueError(
                f"MoE models are SwiGLU-only (hidden_act={self.hidden_act!r}"
                " requested); ops/moe.py would need the activation plumbed")
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown moe_scoring {self.moe_scoring!r}")
        if self.first_k_dense and not (
                self.is_moe and 0 < self.first_k_dense < self.num_layers
                and self.dense_intermediate_size > 0):
            raise ValueError(
                "first_k_dense needs an MoE model with at least one expert "
                "layer after the dense ones and a dense_intermediate_size")
        if self.first_k_dense and not self.operator_ffn and (
                self.attention_bias or self.qk_norm or self.post_norms):
            # the dense stack (llama.param_specs) carries the attention
            # and FFN matrices and the two pre-norms, nothing else yet
            raise ValueError(
                "first_k_dense with attention_bias / qk_norm / post_norms "
                "is not implemented: the dense stack has no such leaves")
        if self.n_group > 1 or self.topk_group > 1:
            if not (self.is_moe and self.moe_scoring == "sigmoid"
                    and self.num_experts % self.n_group == 0
                    and 1 <= self.topk_group <= self.n_group
                    and self.num_experts // self.n_group >= 2
                    and self.num_experts_per_tok
                    <= self.topk_group * (self.num_experts // self.n_group)):
                raise ValueError(
                    f"group-limited routing (n_group={self.n_group}, "
                    f"topk_group={self.topk_group}) needs a sigmoid-scored "
                    f"router whose {self.num_experts} experts divide into "
                    "the groups (>= 2 a group), topk_group <= n_group, and "
                    "the kept groups must hold num_experts_per_tok experts")
        if self.index_topk:
            if not (self.is_mla and self.q_lora_rank > 0
                    and self.index_n_heads > 0
                    and self.index_head_dim >= self.qk_rope_head_dim
                    and self.index_head_dim % 2 == 0):
                raise ValueError(
                    "the sparse-attention indexer (index_topk="
                    f"{self.index_topk}) needs MLA with a query low-rank "
                    "path (its queries come from the q-LoRA latent), "
                    "index_n_heads > 0 and index_head_dim >= "
                    "qk_rope_head_dim (its leading lanes take the rotary)")
        elif self.index_n_heads or self.index_head_dim:
            raise ValueError("index_n_heads / index_head_dim without "
                             "index_topk: no indexer is built")
        if self.attn_gate not in ("", "per-head"):
            raise ValueError(f"unknown attn_gate {self.attn_gate!r}")
        if self.layer_types:
            self._check_kinds()
        elif (self.heads_per_layer or self.rope_by_kind or self.attn_gate
              or self.kv_heads_sliding or self.attn_sink_kinds
              or self.attn_value_scale != 1.0):
            raise ValueError(
                "heads_per_layer / rope_by_kind / attn_gate / "
                "kv_heads_sliding / attn_sink_kinds / attn_value_scale "
                "without layer_types: no layer would read them")
        if self.mixer_types:
            self._check_mixers()
        elif (self.mamba_num_heads or self.expert_act or self.multipliers
              or self.sparse_block_size or self.scale_depth
              or self.dim_model_base or self.scale_emb != 1.0):
            raise ValueError("mamba_* / expert_act / multipliers / sparse_* "
                             "/ scale_* without mixer_types: no layer would "
                             "read them")
        held = self.num_local_experts
        if held and not (
                self.is_moe and 0 <= self.local_expert_offset
                and self.local_expert_offset + held <= self.num_experts):
            raise ValueError(
                f"held experts [{self.local_expert_offset}, "
                f"{self.local_expert_offset + held}) lie outside the "
                f"router's {self.num_experts}")

    def _check_kinds(self) -> None:
        """layer_types and what hangs on it: loud, because a model served
        with one of these ignored is another model."""
        n, kinds = self.num_layers, self.layer_types
        if len(kinds) != n or (self.heads_per_layer
                               and len(self.heads_per_layer) != n):
            raise ValueError(
                f"layer_types ({len(kinds)}) / heads_per_layer "
                f"({len(self.heads_per_layer)}) need one entry for each of "
                f"the {n} layers")
        bad = set(kinds) - set(ATTENTION_KINDS)
        if bad:
            raise ValueError(f"unknown layer_types {sorted(bad)}")
        if SLIDING in kinds and self.sliding_window <= 0:
            raise ValueError("sliding_attention layers need sliding_window")
        if (self.is_mla or self.attention_bias or self.qk_norm
                or self.post_norms or self.attn_logit_softcapping
                or self.rope_local_theta or self.rope_llama3_scaling
                or self.rope_longrope_scaling or self.rope_yarn_scaling):
            raise ValueError(
                "layer_types is served for per-head K/V attention (query "
                "and KV heads a kind, values as wide as keys or narrower, "
                "a learned sink in a kind's softmax) with a rotary a kind "
                "(rope_by_kind) and none of: MLA, attention bias, q/k "
                "norms, sandwich norms, score capping, the model-wide "
                "rope scalings")
        if set(self.attn_sink_kinds) - set(kinds):
            raise ValueError(f"attn_sink_kinds {self.attn_sink_kinds} names "
                             "a kind no layer is of")
        if not 0 <= self.v_head_dim <= self.head_dim:
            raise ValueError(
                f"v_head_dim {self.v_head_dim} over head_dim "
                f"{self.head_dim}: values are as wide as keys or narrower")
        for kind in set(kinds):
            heads = {h for h, k in zip(self.heads_per_layer or
                                       (self.num_heads,) * n, kinds)
                     if k == kind}
            if len(heads) != 1:
                raise ValueError(
                    f"{kind} layers have head counts {sorted(heads)}: one "
                    "parameter stack a kind needs one count a kind")
            (h,) = heads
            if h % self.kind_kv_heads(kind):
                raise ValueError(f"{h} query heads over "
                                 f"{self.kind_kv_heads(kind)} KV heads")
        if set(k for k, *_ in self.rope_by_kind) - set(kinds) or (
                self.rope_by_kind
                and set(kinds) - set(k for k, *_ in self.rope_by_kind)):
            raise ValueError("rope_by_kind needs one entry for each kind of "
                             f"layer_types, got {self.rope_by_kind}")
        for _, _, share, _ in self.rope_by_kind:
            # a share is lanes / head_dim: 64 / 192 is no exact binary
            # fraction, so the product is held to its nearest whole
            lanes = self.head_dim * share
            if (abs(lanes - round(lanes)) > 1e-6 or round(lanes) % 2
                    or not 0 < share <= 1):
                raise ValueError(
                    f"partial_rotary_factor {share} of head_dim "
                    f"{self.head_dim} is not an even lane count")
        lead = set(kinds[:self.first_k_dense])
        if len(lead) > 1:
            raise ValueError("the leading dense layers are of more than one "
                             "kind: their stack has one shape")

    def _check_mixers(self) -> None:
        """mixer_types and what hangs on it, as loud as _check_kinds."""
        kinds = self.mixer_types
        if len(kinds) != self.num_layers:
            raise ValueError(f"mixer_types has {len(kinds)} entries for "
                             f"{self.num_layers} layers")
        bad = set(kinds) - set(MIXER_KINDS)
        if bad:
            raise ValueError(f"unknown mixer_types {sorted(bad)}")
        if self.state_layers and not self.operator_ffn and not (
                self.mamba_num_heads > 0 and self.mamba_head_dim > 0
                and self.mamba_n_groups > 0 and self.ssm_state_size > 0
                and self.conv_kernel > 1 and self.ssm_chunk_size > 0
                and self.mamba_num_heads % self.mamba_n_groups == 0):
            raise ValueError(
                "mamba layers need mamba_num_heads (a multiple of "
                "mamba_n_groups), mamba_head_dim, ssm_state_size, "
                "conv_kernel > 1 and ssm_chunk_size")
        if EXPERTS in kinds and not (
                self.is_moe and self.expert_act in TWO_MATRIX_ACTS):
            raise ValueError(
                "moe mixers need num_experts and a two-matrix expert_act "
                f"of {TWO_MATRIX_ACTS} (got {self.expert_act!r})")
        if PARALLEL in kinds:
            if set(kinds) != {PARALLEL}:
                raise ValueError(
                    f"a model with a {PARALLEL!r} layer has no layer of "
                    "another kind: its layers run as one scan")
            if (self.is_moe or self.expert_act or self.multipliers is None
                    or self.hidden_act != "silu"):
                raise ValueError(
                    f"{PARALLEL!r} layers are served with a dense gated "
                    "silu MLP and their `multipliers`, without experts")
        elif self.multipliers is not None:
            raise ValueError(f"multipliers without {PARALLEL!r} layers: no "
                             "layer would read them")
        if self.operator_ffn:
            self._check_operators()
        if (self.layer_types or self.is_mla or self.sliding_window
                or self.attention_bias or self.post_norms
                or self.attn_logit_softcapping or self.n_group > 1
                or self.rms_norm_unit_offset or self.embed_scale
                or (not self.operator_ffn and (
                    self.first_k_dense or self.qk_norm
                    or self.num_local_experts
                    or self.tie_word_embeddings))):
            raise ValueError(
                "mixer_types is served in three forms: every layer ONE mixer "
                "(Mamba-2 | two-matrix experts, all held | plain GQA "
                "attention without a rotary); every layer attention under a "
                "rotary AND Mamba-2 side by side, then a gated MLP; or every "
                "layer an operator (a gated short convolution | GQA "
                "attention under q/k norms and a rotary) and then an FFN "
                "(leading dense layers, then gated experts, all held or a "
                "share), its head tied or not. The first two with an untied "
                "head, every expert held and no leading dense layers or q/k "
                "norms; all three with none of: layer_types, MLA, a window, "
                "biases, sandwich norms, score capping, router groups, a "
                "capacity factor")

    def _check_sala(self) -> None:
        """The operator-then-FFN form of minicpm_sala and what hangs on
        it."""
        kinds = set(self.mixer_types)
        if kinds - set(_SALA_OPERATORS):
            raise ValueError(
                f"a model with a {SPARSE!r} or {LIGHTNING!r} layer has "
                f"layers of these two kinds only, got {sorted(kinds)}")
        if not (self.qk_norm and self.hidden_act == "silu"
                and not self.is_moe and not self.expert_act
                and self.multipliers is None and not self.conv_kernel):
            raise ValueError(
                f"{SPARSE!r} / {LIGHTNING!r} layers are served under q/k "
                "norms with dense gated silu FFNs, without experts, "
                "multipliers or a conv")
        if LIGHTNING in kinds and not (
                self.mamba_num_heads == self.mamba_n_groups == self.num_heads
                and self.mamba_head_dim == self.ssm_state_size
                == self.head_dim):
            raise ValueError(
                f"{LIGHTNING!r} layers are served at the attention's heads "
                "and head_dim, a key row a head (mamba_num_heads == "
                "mamba_n_groups == num_heads, mamba_head_dim == "
                "ssm_state_size == head_dim)")
        if SPARSE in kinds:
            k, s, b = (self.sparse_kernel_size, self.sparse_kernel_stride,
                       self.sparse_block_size)
            picked = (self.sparse_init_blocks + self.sparse_topk
                      + -(-self.sparse_window_size // max(b, 1)))
            if not (s > 0 and k == 2 * s and b > 0 and b % s == 0
                    and self.sparse_topk > 0 and self.sparse_init_blocks >= 0
                    and self.sparse_window_size > 0
                    and self.sparse_window_size % b == 0
                    and self.sparse_dense_len % b == 0
                    and self.sparse_dense_len >= picked * b - b):
                raise ValueError(
                    f"{SPARSE!r} layers are served with sparse_kernel_size "
                    "== 2 x sparse_kernel_stride (a pooled key is two "
                    "pages' sums), blocks and a window that are multiples "
                    "of the stride and of a block, and a sparse_dense_len "
                    "(a multiple of a block) past which every query has "
                    "its init + window + top-k blocks to select")

    def _check_operators(self) -> None:
        """The operator-then-FFN form (lfm2_moe) and what hangs on it."""
        kinds = set(self.mixer_types)
        if kinds & set(_SALA_OPERATORS):
            return self._check_sala()
        if kinds - {CONV, ATTENTION}:
            raise ValueError(
                f"a model with a {CONV!r} layer has layers of {CONV!r} and "
                f"{ATTENTION!r} only, got {sorted(kinds)}: its layers are an "
                "operator and then an FFN")
        if self.conv_kernel < 2:
            raise ValueError(f"conv_kernel {self.conv_kernel}: a short conv "
                             "of fewer than 2 taps keeps no state")
        if not (self.qk_norm and self.hidden_act == "silu"
                and not self.expert_act and self.multipliers is None
                and not self.mamba_num_heads):
            raise ValueError(
                f"{CONV!r} layers are served beside attention under q/k "
                "norms, with gated silu FFNs (expert_act '') and without "
                "mamba_* / multipliers")
        if self.is_moe and not (
                self.moe_scoring == "sigmoid" and self.num_shared_experts == 0
                and self.moe_grouped):
            raise ValueError(
                f"{CONV!r} layers are served with sigmoid-routed experts "
                "through the grouped matmuls and no shared expert")

    def mixer_layers(self, kind: str) -> int:
        return sum(1 for k in self.mixer_types if k == kind)

    @property
    def parallel_mixers(self) -> bool:
        """Every layer attention AND Mamba-2 side by side (falcon_h1)."""
        return PARALLEL in self.mixer_types

    @property
    def operator_ffn(self) -> bool:
        """Every layer an operator and then an FFN: a gated short conv |
        attention (lfm2_moe), or block-sparse attention | Lightning linear
        attention (minicpm_sala)."""
        return self.conv_state or self.is_sala

    @property
    def conv_state(self) -> bool:
        """The state layers are gated short convolutions (lfm2_moe): a
        slot holds the conv's last rows and no recurrent state."""
        return CONV in self.mixer_types

    @property
    def is_sala(self) -> bool:
        """minicpm_sala's operators: block-sparse attention | Lightning
        linear attention (a recurrent state and no conv rows a slot; the
        sparse layers keep a row of pooled-key sums a page)."""
        return bool(set(self.mixer_types) & set(_SALA_OPERATORS))

    @property
    def sparse_picked_blocks(self) -> int:
        """Blocks a query past sparse_dense_len attends: the initial ones,
        the window's (the query's own last) and the top-k."""
        return (self.sparse_init_blocks + self.sparse_topk
                + self.sparse_window_size // self.sparse_block_size)

    @property
    def state_stacked(self) -> bool:
        """A hybrid model whose layers run as scans: every state layer's
        slots ride ONE array over (layer, slot), not an array a layer."""
        return self.parallel_mixers or self.operator_ffn

    @property
    def paged_layers(self) -> int:
        """Layers of a hybrid model that own KV pages."""
        return sum(1 for k in self.mixer_types if k in PAGED_MIXERS)

    @property
    def state_layers(self) -> int:
        """Layers of a hybrid model that own a state slot a sequence."""
        return sum(1 for k in self.mixer_types if k in STATE_MIXERS)

    @property
    def expert_dims_stored(self) -> Tuple[int, int]:
        """(hidden rows, width lanes) a routed expert's W_up [rows, lanes]
        and W_down [lanes, rows] are STORED with for a hybrid model: each
        rounded up to a multiple of 1,024 (of 128 under 1,024; a tiny test
        config under 128 stays as it is), everything past the model's own
        extent zero (act(0) = 0 and a zero row meets a zero input lane:
        they add nothing). Why (PR 42, measured on a v5e,
        benchmarks/chip/records/pr42-ragged-dot-bench*.json): the grouped
        matmul is XLA's `ragged_dot`, a custom call whose tiling follows
        its operands' extents. At Nemotron-H's own 2,688 x 1,856 the TPU
        lays the int8 stack out transposed (a minor dimension that is no
        multiple of 128) and the call copied the whole 2.5 GB stack in every
        layer of every step; at 2,688 x 1,920 (a 128-multiple) it takes
        12-18 ms for 384 rows over 128 experts; at 3,072 x 2,048 it takes
        1.9 ms, and W_down at 2,048 x 3,072 likewise (15.5 -> 1.9 ms). The
        configurations the benchmark had before are 1,024-multiples
        already. The price: 26% more expert bytes than the model has.

        NOT padded: a layer whose shapes ops/grouped_matmul's kernel takes
        (`grouped_kernel_shapes`: LFM2-8B-A1B's 32 experts of 2,048 x
        1,792): it streams whole matrices at whatever lane multiple they
        have, so the model's own extents are the cheapest (PR 52)."""
        def up(n: int) -> int:
            if n >= 1024:
                return -(-n // 1024) * 1024
            return -(-n // 128) * 128 if n >= 128 else n
        if grouped_kernel_shapes(self.held_experts, self.hidden_size,
                                 self.intermediate_size):
            return self.hidden_size, self.intermediate_size
        return up(self.hidden_size), up(self.intermediate_size)

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Lanes the conv runs over: [x | B | C]."""
        return (self.mamba_d_inner
                + 2 * self.mamba_n_groups * self.ssm_state_size)

    @property
    def kind_period(self) -> int:
        """Layers of the smallest period the kinds behind the leading dense
        layers repeat with; the last period may be cut short (Laguna's 47
        layers behind the dense one are 11 periods of 4 and 3 more)."""
        rest = self.layer_types[self.first_k_dense:]
        n = len(rest)
        for p in range(1, n + 1):
            if all(rest[i] == rest[i % p] for i in range(n)):
                return p
        return max(n, 1)

    def kind_heads(self, kind: str) -> int:
        """Query heads of the layers of `kind`."""
        if not self.heads_per_layer:
            return self.num_heads
        return self.heads_per_layer[self.layer_types.index(kind)]

    def kind_layers(self, kind: str) -> int:
        return sum(1 for k in self.layer_types if k == kind)

    def kind_kv_heads(self, kind: str) -> int:
        """KV heads of the layers of `kind` (what a row of its pool holds)."""
        if kind == SLIDING and self.kv_heads_sliding:
            return self.kv_heads_sliding
        return self.cache_kv_heads

    @property
    def kv_by_kind(self) -> bool:
        """The kinds' K/V projections have shapes of their own (KV heads
        differ by kind): wk / wv stack by kind, as wq / wo do."""
        return bool(self.kv_heads_sliding
                    and self.kv_heads_sliding != self.num_kv_heads)

    @property
    def value_head_dim(self) -> int:
        """Lanes a head of a cached V row: head_dim, or a model of kinds'
        narrower v_head_dim."""
        if self.layer_types and self.v_head_dim and not self.is_mla:
            return self.v_head_dim
        return self.cache_head_dim

    @property
    def shared_expert_width(self) -> int:
        return (self.shared_expert_intermediate_size
                or self.num_shared_experts * self.intermediate_size)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def held_experts(self) -> int:
        """Experts whose weights live here (the whole router's unless the
        config states a share)."""
        return self.num_local_experts or self.num_experts

    @property
    def num_moe_layers(self) -> int:
        if self.mixer_types and not self.operator_ffn:
            return self.mixer_layers(EXPERTS)
        return self.num_layers - self.first_k_dense if self.is_moe else 0

    @property
    def moe_grouped(self) -> bool:
        """Which expert layer serves this shape (ops/moe.py): grouped
        matmuls over the tokens each expert was picked by, or every expert
        on every token. Dense wins while a token picks a large part of the
        experts (Mixtral: 2 of 8, weights streamed once either way, no
        sort/gather); at k/X <= 1/8 it would do >= 8x the model's
        arithmetic. A share of a wider router always groups."""
        return self.is_moe and (
            self.held_experts != self.num_experts
            or 8 * self.num_experts_per_tok <= self.num_experts)

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def is_dsa(self) -> bool:
        """MLA under the learned sparse selection (index_topk > 0)."""
        return self.index_topk > 0

    @property
    def cache_index_dim(self) -> int:
        """Lanes of the second per-token row an indexed model caches (the
        indexer's key); 0 for every other model."""
        return self.index_head_dim if self.is_dsa else 0

    # --- KV-cache geometry (what the paged pools actually store): MLA keeps
    # one shared latent row per token; classic attention keeps per-head K/V.
    @property
    def cache_kv_heads(self) -> int:
        return 1 if self.is_mla else self.num_kv_heads

    @property
    def cache_head_dim(self) -> int:
        if self.is_mla:
            w = self.kv_lora_rank + self.qk_rope_head_dim
            if w >= 128:
                # pad real-size latent rows to a 128-lane multiple so the
                # Pallas decode kernel's DMA tiling is eligible (e.g.
                # DeepSeek-V2's 576 -> 640, +11% cache for kernel access);
                # tiny test configs stay unpadded
                return -(-w // 128) * 128
            return w
        return self.head_dim

    @staticmethod
    def from_hf_config(cfg: dict, name: str = "hf-model", dtype: str = "bfloat16") -> "ModelConfig":
        """Map a HuggingFace config.json dict onto ModelConfig.

        Maps the config keys of: Llama 3.x, Mistral, Qwen2 / Qwen2.5,
        Qwen3 and Qwen3-MoE, Mixtral, Phi-3, Gemma 1 / 2 / 3 (text),
        DeepSeek-V2 (MLA, softmax-scored experts), DeepSeek-V3 / Kimi-K2
        (q-LoRA MLA, sigmoid scores with a selection bias, n_group /
        topk_group, leading dense layers, `deployment_share`) and
        DeepSeek-V3.2 (`deepseek_v32`: the same block under the lightning
        indexer's index_n_heads / index_head_dim / index_topk) and Laguna
        (`laguna`: full and sliding-window layers mixed by `layer_types`,
        head counts and rotaries by kind, a per-head output gate, leading
        dense layers by `mlp_only_layers`: _layer_kinds_from_hf) and
        MiMo-V2 (`mimo_v2`: the kinds by `hybrid_layer_pattern`, KV heads a
        kind, keys wider than values, a sink in the sliding softmax:
        _mimo_v2_from_hf). Keys whose
        mechanism is not implemented refuse loudly (multi-token-prediction
        layers, interleaved dense layers, other scoring functions).
        """
        arch = (cfg.get("architectures") or [""])[0]
        if arch.startswith("Gemma3n"):
            # Gemma-3n's altup/laurel/per-layer-embedding structure is a
            # different architecture, not a config variation of Gemma-3
            raise ValueError(
                f"{arch} (MatFormer/altup) is not supported; Gemma v1/2/3 "
                "dense text models are")
        if arch == "Gemma3ForConditionalGeneration":
            # multimodal wrapper: serve the nested TEXT config (this is
            # what the released gemma-3-4b+ checkpoints' config.json is;
            # vision towers are out of scope)
            text = cfg.get("text_config")
            if not text:
                raise ValueError(
                    "Gemma3ForConditionalGeneration config has no "
                    "text_config to serve")
            return ModelConfig.from_hf_config(
                {**text, "architectures": ["Gemma3ForCausalLM"]},
                name=name, dtype=dtype)
        is_gemma = arch.startswith("Gemma")
        is_gemma2 = arch.startswith("Gemma2")
        is_gemma3 = arch.startswith("Gemma3")
        num_heads = cfg["num_attention_heads"]
        hidden = cfg["hidden_size"]
        head_dim = cfg.get("head_dim") or hidden // num_heads
        eos = cfg.get("eos_token_id", 2)
        if isinstance(eos, list):
            eos = eos[0]
        # keys whose mechanism is not implemented refuse loudly: serving
        # a checkpoint while ignoring one of them serves another model
        if (cfg.get("num_nextn_predict_layers") or 0) > 0:
            raise ValueError(
                "multi-token-prediction layers (num_nextn_predict_layers="
                f"{cfg['num_nextn_predict_layers']}) are not implemented: "
                "the module is a layer past num_hidden_layers that drafts "
                "the next token; set the key to 0 to serve the model "
                "without it")
        if (cfg.get("moe_layer_freq") or 1) != 1 and cfg.get(
                "model_type") != "mimo_v2":  # a list there: _mimo_v2_from_hf
            raise ValueError(
                f"moe_layer_freq={cfg['moe_layer_freq']} (dense layers "
                "interleaved after the leading ones) is not implemented")
        scoring = cfg.get("scoring_func", "softmax")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring_func {scoring!r} is not implemented")
        topk_method = cfg.get("topk_method") or "greedy"
        if topk_method not in ("greedy", "noaux_tc", "group_limited_greedy"):
            raise ValueError(f"topk_method {topk_method!r} is not implemented")
        n_group = int(cfg.get("n_group") or 1)
        topk_group = int(cfg.get("topk_group") or 1)
        if topk_method == "greedy":
            n_group = topk_group = 1  # HF ignores the groups there
        elif (n_group > 1 or topk_group > 1) and topk_method != "noaux_tc":
            # DeepSeek-V2's group_limited_greedy scores a group by its
            # LARGEST softmax probability; only noaux_tc's rule (sum of the
            # 2 largest sigmoid scores + bias) is written down in
            # ops/moe.route_topk
            raise ValueError(
                f"topk_method {topk_method!r} with n_group={n_group} is not "
                "implemented (groups are served for noaux_tc only)")
        index_topk = int(cfg.get("index_topk") or 0)
        kinds_kw = _layer_kinds_from_hf(cfg, arch)
        # expert count: Mixtral uses num_local_experts, DeepSeek
        # n_routed_experts, Qwen3-MoE plain num_experts
        n_experts = (cfg.get("num_local_experts")
                     or cfg.get("n_routed_experts")
                     or cfg.get("num_experts") or 0)
        # this chip's share of a wider deployment (benchmark cuts, see the
        # model-configs guide, section 4): the counting keys give what is
        # HELD here and `deployment_share` gives the router's and the
        # vocabulary's whole extent and where the held part starts
        share = cfg.get("deployment_share") or {}
        held = 0
        if share.get("n_routed_experts_total"):
            held, n_experts = n_experts, int(share["n_routed_experts_total"])
        first_dense = int(cfg.get("first_k_dense_replace") or 0)
        if not n_experts:
            first_dense = 0
        if n_experts:
            # MoE configs carry BOTH intermediate_size (dense-equivalent,
            # unused) and moe_intermediate_size (per-expert, the real one)
            inter = (cfg.get("moe_intermediate_size")
                     or cfg.get("intermediate_size") or 4 * hidden)
        else:
            inter = cfg.get("intermediate_size") or 4 * hidden
        kw = dict(
            name=name,
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            intermediate_size=inter,
            num_layers=cfg["num_hidden_layers"],
            num_heads=num_heads,
            num_kv_heads=cfg.get("num_key_value_heads", num_heads),
            head_dim=head_dim,
            rope_theta=cfg.get("rope_theta", 10000.0),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=cfg.get("tie_word_embeddings", is_gemma),
            hidden_act="gelu_tanh" if (cfg.get("hidden_activation")
                                       or cfg.get("hidden_act", "silu")
                                       ).startswith("gelu") else "silu",
            rms_norm_unit_offset=is_gemma,
            embed_scale=is_gemma,
            sliding_window=(int(cfg.get("sliding_window") or 0)
                            if (is_gemma2 or is_gemma3
                                or "Mistral" in arch
                                or "Phi3" in arch) else 0),
            # Mistral and Phi-3 apply their window on EVERY layer
            # (pattern 0 = no global layers); gemma-2/3 interleave
            sliding_window_pattern=(
                0 if ("Mistral" in arch or "Phi3" in arch) else int(
                    cfg.get("sliding_window_pattern")
                    or (6 if is_gemma3 else 2))),
            attn_logit_softcapping=float(
                cfg.get("attn_logit_softcapping") or 0.0),
            final_logit_softcapping=float(
                cfg.get("final_logit_softcapping") or 0.0),
            query_pre_attn_scalar=float(
                cfg.get("query_pre_attn_scalar") or 0.0),
            post_norms=is_gemma2 or is_gemma3,
            rope_local_theta=float(
                cfg.get("rope_local_base_freq") or 0.0),
            rope_scaling_factor=float(
                ((cfg.get("rope_scaling") or {}).get("factor"))
                or 1.0) if is_gemma3 else 1.0,
            rope_llama3_scaling=_llama3_rope_scaling(cfg),
            rope_yarn_scaling=_yarn_rope_scaling(cfg),
            rope_longrope_scaling=_longrope_rope_scaling(cfg),
            qk_norm="Qwen3" in arch or is_gemma3,
            attention_bias=cfg.get("attention_bias", "Qwen2" in arch),
            num_experts=n_experts,
            num_experts_per_tok=cfg.get("num_experts_per_tok", 2),
            num_shared_experts=cfg.get("n_shared_experts", 0) or 0,
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            routed_scaling_factor=float(
                cfg.get("routed_scaling_factor", 1.0) or 1.0),  # null: 1
            moe_scoring=scoring if n_experts else "softmax",
            router_bias=bool(n_experts) and topk_method == "noaux_tc",
            n_group=n_group if n_experts else 1,
            topk_group=topk_group if n_experts else 1,
            first_k_dense=first_dense,
            dense_intermediate_size=(
                int(cfg.get("intermediate_size") or 0) if first_dense else 0),
            num_local_experts=held,
            local_expert_offset=int(share.get("first_routed_expert", 0)),
            vocab_offset=int(share.get("first_vocab_row", 0)),
            kv_lora_rank=cfg.get("kv_lora_rank", 0) or 0,
            q_lora_rank=cfg.get("q_lora_rank", 0) or 0,
            qk_nope_head_dim=cfg.get("qk_nope_head_dim", 0) or 0,
            qk_rope_head_dim=cfg.get("qk_rope_head_dim", 0) or 0,
            v_head_dim=cfg.get("v_head_dim", 0) or 0,
            index_n_heads=int(cfg.get("index_n_heads") or 0) if index_topk
            else 0,
            index_head_dim=int(cfg.get("index_head_dim") or 0) if index_topk
            else 0,
            index_topk=index_topk,
            dtype=dtype,
            eos_token_id=eos,
            bos_token_id=cfg.get("bos_token_id", 1),
        )
        kw.update(_hybrid_from_hf(cfg))
        if kinds_kw:
            k_dense = kinds_kw["first_k_dense"] if n_experts else 0
            kinds_kw.update(
                first_k_dense=k_dense,
                dense_intermediate_size=(
                    int(cfg.get("intermediate_size") or 0) if k_dense else 0))
            kw.update(kinds_kw)
        return ModelConfig(**kw)

    @staticmethod
    def from_model_name(model: str, dtype: Optional[str] = None) -> "ModelConfig":
        """Resolve a model identifier the way the reference's engine flags do.

        Accepts: a preset key (see PRESETS), a local directory containing an HF
        config.json, or an HF-style id whose basename matches a preset.
        """
        if model in PRESETS:
            cfg = PRESETS[model]
        else:
            cfg_path = os.path.join(model, "config.json")
            if os.path.isdir(model) and os.path.exists(cfg_path):
                with open(cfg_path) as f:
                    cfg = ModelConfig.from_hf_config(json.load(f), name=model)
            else:
                base = model.rstrip("/").split("/")[-1].lower()
                if base not in PRESETS:
                    raise ValueError(
                        f"unknown model {model!r}: not a preset "
                        f"({sorted(PRESETS)}), and not a local checkpoint dir "
                        f"with a config.json"
                    )
                cfg = dataclasses.replace(PRESETS[base], name=model)
        if dtype is not None:
            cfg = dataclasses.replace(cfg, dtype=dtype)
        return cfg


# Architecture presets for the model families named in BASELINE.json configs.
# Sizes match the public HF configs for each model.
PRESETS = {
    "tiny-debug": ModelConfig(),
    "tiny-moe-debug": ModelConfig(
        name="tiny-moe-debug", num_experts=4, num_experts_per_tok=2
    ),
    "tiny-mla-debug": ModelConfig(
        name="tiny-mla-debug",
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16,
    ),
    # Kimi-K2 / DeepSeek-V3 structure at a toy size: one leading dense
    # layer, then expert layers with sigmoid routing, a selection bias, a
    # shared expert, MLA with the query low-rank path, YaRN. No share:
    # tests cut shares from it with dataclasses.replace.
    "tiny-kimi-debug": ModelConfig(
        name="tiny-kimi-debug",
        intermediate_size=64, num_layers=3,
        num_experts=16, num_experts_per_tok=4, num_shared_experts=1,
        norm_topk_prob=True, routed_scaling_factor=2.5,
        moe_scoring="sigmoid", router_bias=True,
        first_k_dense=1, dense_intermediate_size=256,
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16,
        rope_theta=50000.0, rms_norm_eps=1e-6, tie_word_embeddings=False,
        rope_yarn_scaling=(32.0, 1.0, 1.0, 64, 1.0, 1.0, -1.0),
    ),
    "llama-3.2-1b-instruct": ModelConfig(
        name="llama-3.2-1b-instruct",
        vocab_size=128256,
        hidden_size=2048,
        intermediate_size=8192,
        num_layers=16,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        rope_theta=500000.0,
        max_position_embeddings=131072,
        # Llama-3.2 ships rope_type "llama3" scaling — part of the model,
        # not a long-context add-on (it reshapes inv_freq at every length)
        rope_llama3_scaling=(32.0, 1.0, 4.0, 8192),
        tie_word_embeddings=True,
        eos_token_id=128009,
        bos_token_id=128000,
    ),
    "meta-llama-3-8b-instruct": ModelConfig(
        name="meta-llama-3-8b-instruct",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        tie_word_embeddings=False,
        eos_token_id=128009,
        bos_token_id=128000,
    ),
    # Llama-3.1: same architecture as 3.0-8B plus llama3 rope scaling and
    # the 128k window (public HF config)
    "llama-3.1-8b-instruct": ModelConfig(
        name="llama-3.1-8b-instruct",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        max_position_embeddings=131072,
        rope_llama3_scaling=(8.0, 1.0, 4.0, 8192),
        tie_word_embeddings=False,
        eos_token_id=128009,
        bos_token_id=128000,
    ),
    # Phi-3-mini 4k (public HF config): llama-family decoder with FUSED
    # qkv_proj / gate_up_proj checkpoints (split by the loader), MHA
    # (kv_heads == heads), head_dim 96. The 128k variants add longrope
    # rope_scaling, parsed exactly from a local checkpoint's config.json
    # (from_model_name on the checkpoint dir) — the per-dim factor arrays
    # are checkpoint data, not preset constants.
    "phi-3-mini-4k-instruct": ModelConfig(
        name="phi-3-mini-4k-instruct",
        vocab_size=32064,
        hidden_size=3072,
        intermediate_size=8192,
        num_layers=32,
        num_heads=32,
        num_kv_heads=32,
        head_dim=96,
        rope_theta=10000.0,
        max_position_embeddings=4096,
        # Phi-3 trains with a 2047-token window on EVERY layer (HF
        # config.sliding_window; pattern 0 = no global layers)
        sliding_window=2047,
        sliding_window_pattern=0,
        tie_word_embeddings=False,
        eos_token_id=32000,
        extra_stop_token_ids=(32007,),  # <|end|>
        bos_token_id=1,
    ),
    # Qwen2.5: Qwen2 architecture (attention bias, no qk-norm)
    "qwen2.5-7b-instruct": ModelConfig(
        name="qwen2.5-7b-instruct",
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        head_dim=128,
        rope_theta=1000000.0,
        max_position_embeddings=32768,
        tie_word_embeddings=False,
        attention_bias=True,
        eos_token_id=151645,
        bos_token_id=151643,
    ),
    "meta-llama-3-70b-instruct": ModelConfig(
        name="meta-llama-3-70b-instruct",
        vocab_size=128256,
        hidden_size=8192,
        intermediate_size=28672,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        tie_word_embeddings=False,
        eos_token_id=128009,
        bos_token_id=128000,
    ),
    "qwen3-0.6b": ModelConfig(
        name="qwen3-0.6b",
        vocab_size=151936,
        hidden_size=1024,
        intermediate_size=3072,
        num_layers=28,
        num_heads=16,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=1000000.0,
        tie_word_embeddings=True,
        qk_norm=True,
        eos_token_id=151645,
        bos_token_id=151643,
    ),
    "mixtral-8x7b-instruct-v0.1": ModelConfig(
        name="mixtral-8x7b-instruct-v0.1",
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=1000000.0,
        num_experts=8,
        num_experts_per_tok=2,
        eos_token_id=2,
        bos_token_id=1,
    ),
    # fine-grained MoE + per-head q/k RMSNorm (the qwen3 combination) —
    # 30.5B total / ~3.3B active; the modern expert-parallel serving target
    # beyond Mixtral's 8-expert layout
    "qwen3-30b-a3b": ModelConfig(
        name="qwen3-30b-a3b",
        vocab_size=151936,
        hidden_size=2048,
        intermediate_size=768,  # PER-EXPERT width (hf moe_intermediate_size)
        num_layers=48,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        rope_theta=1000000.0,
        qk_norm=True,
        tie_word_embeddings=False,
        num_experts=128,
        num_experts_per_tok=8,
        eos_token_id=151645,
        bos_token_id=151643,
    ),
    # DeepSeek-V2-Lite dims: MLA latent attention — the paged cache stores
    # one shared [c_kv | k_rope] row per token (576 lanes, padded to 640
    # for Pallas DMA tiling) in each of the K/V pools: 1280 lanes total vs
    # 4096 for the equivalent per-head MHA = 3.2x KV compression (the
    # symmetric-pool duplication keeps the whole engine/transfer/donation
    # machinery unchanged) + 64 routed top-6 / 2
    # shared experts. DEVIATION from the checkpoint: the real model's FIRST
    # layer is a dense FFN (first_k_dense_replace=1), which the uniform
    # layer scan doesn't support yet — here every layer is MoE, so param
    # count runs ~0.5B over the published 15.7B.
    "deepseek-v2-lite": ModelConfig(
        name="deepseek-v2-lite",
        vocab_size=102400,
        hidden_size=2048,
        intermediate_size=1408,  # per-expert (hf moe_intermediate_size)
        num_layers=27,
        num_heads=16,
        num_kv_heads=16,
        head_dim=128,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        num_experts=64,
        num_experts_per_tok=6,
        num_shared_experts=2,
        norm_topk_prob=False,  # DeepSeek gate convention
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        # DeepSeek-V2 ships with YaRN on by default (32k over a 4k
        # original context, mscale 0.707 both — rotary ratio 1, softmax
        # scale x yarn_get_mscale(40, .707)^2)
        rope_yarn_scaling=(40.0, 32.0, 1.0, 4096, 0.707, 0.707, -1.0),
        max_position_embeddings=163840,
        eos_token_id=100001,
        bos_token_id=100000,
    ),
    # Gemma (v1) family: GeGLU activation, (1+w) norms, sqrt(E)-scaled
    # embeddings, tied head, head_dim 256 (public HF configs). The 2B is
    # MQA (one KV head) — the smallest-KV serving point in the zoo.
    "gemma-7b-it": ModelConfig(
        name="gemma-7b-it",
        vocab_size=256000,
        hidden_size=3072,
        intermediate_size=24576,
        num_layers=28,
        num_heads=16,
        num_kv_heads=16,
        head_dim=256,
        rms_norm_eps=1e-6,
        tie_word_embeddings=True,
        hidden_act="gelu_tanh",
        rms_norm_unit_offset=True,
        embed_scale=True,
        eos_token_id=1,
        extra_stop_token_ids=(107,),  # <end_of_turn>
        bos_token_id=2,
    ),
    "gemma-2b-it": ModelConfig(
        name="gemma-2b-it",
        vocab_size=256000,
        hidden_size=2048,
        intermediate_size=16384,
        num_layers=18,
        num_heads=8,
        num_kv_heads=1,
        head_dim=256,
        rms_norm_eps=1e-6,
        tie_word_embeddings=True,
        hidden_act="gelu_tanh",
        rms_norm_unit_offset=True,
        embed_scale=True,
        eos_token_id=1,
        extra_stop_token_ids=(107,),  # <end_of_turn>
        bos_token_id=2,
    ),
    "tiny-gemma-debug": ModelConfig(
        name="tiny-gemma-debug",
        num_kv_heads=1,  # exercise the MQA path in every engine test
        hidden_act="gelu_tanh",
        rms_norm_unit_offset=True,
        embed_scale=True,
    ),
    # Gemma-2 family: sandwich norms, interleaved sliding-window layers,
    # attn/final logit soft-caps, query_pre_attn_scalar (public HF configs)
    "gemma-2-9b-it": ModelConfig(
        name="gemma-2-9b-it",
        vocab_size=256000,
        hidden_size=3584,
        intermediate_size=14336,
        num_layers=42,
        num_heads=16,
        num_kv_heads=8,
        head_dim=256,
        rms_norm_eps=1e-6,
        tie_word_embeddings=True,
        hidden_act="gelu_tanh",
        rms_norm_unit_offset=True,
        embed_scale=True,
        sliding_window=4096,
        attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0,
        query_pre_attn_scalar=256.0,
        post_norms=True,
        eos_token_id=1,
        extra_stop_token_ids=(107,),  # <end_of_turn>
        bos_token_id=2,
    ),
    "gemma-2-2b-it": ModelConfig(
        name="gemma-2-2b-it",
        vocab_size=256000,
        hidden_size=2304,
        intermediate_size=9216,
        num_layers=26,
        num_heads=8,
        num_kv_heads=4,
        head_dim=256,
        rms_norm_eps=1e-6,
        tie_word_embeddings=True,
        hidden_act="gelu_tanh",
        rms_norm_unit_offset=True,
        embed_scale=True,
        sliding_window=4096,
        attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0,
        query_pre_attn_scalar=256.0,
        post_norms=True,
        eos_token_id=1,
        extra_stop_token_ids=(107,),  # <end_of_turn>
        bos_token_id=2,
    ),
    # Gemma-3 (text): 5-local:1-global sliding pattern, per-layer rope
    # bases (local 10k / global 1M, linear position scaling on global
    # layers), gemma-style qk-norm, no soft-caps (public HF text configs;
    # from_hf_config stays authoritative for real checkpoints)
    "gemma-3-4b-it": ModelConfig(
        name="gemma-3-4b-it",
        vocab_size=262208,
        hidden_size=2560,
        intermediate_size=10240,
        num_layers=34,
        num_heads=8,
        num_kv_heads=4,
        head_dim=256,
        rms_norm_eps=1e-6,
        max_position_embeddings=131072,
        tie_word_embeddings=True,
        hidden_act="gelu_tanh",
        rms_norm_unit_offset=True,
        embed_scale=True,
        qk_norm=True,
        sliding_window=1024,
        sliding_window_pattern=6,
        query_pre_attn_scalar=256.0,
        post_norms=True,
        rope_theta=1_000_000.0,
        rope_local_theta=10_000.0,
        rope_scaling_factor=8.0,
        eos_token_id=1,
        extra_stop_token_ids=(107,),  # <end_of_turn>
        bos_token_id=2,
    ),
    "gemma-3-1b-it": ModelConfig(
        name="gemma-3-1b-it",
        vocab_size=262144,
        hidden_size=1152,
        intermediate_size=6912,
        num_layers=26,
        num_heads=4,
        num_kv_heads=1,
        head_dim=256,
        rms_norm_eps=1e-6,
        max_position_embeddings=32768,
        tie_word_embeddings=True,
        hidden_act="gelu_tanh",
        rms_norm_unit_offset=True,
        embed_scale=True,
        qk_norm=True,
        sliding_window=512,
        sliding_window_pattern=6,
        query_pre_attn_scalar=256.0,
        post_norms=True,
        rope_theta=1_000_000.0,
        rope_local_theta=10_000.0,
        eos_token_id=1,
        extra_stop_token_ids=(107,),  # <end_of_turn>
        bos_token_id=2,
    ),
    "tiny-gemma3-debug": ModelConfig(
        name="tiny-gemma3-debug",
        num_layers=3,  # pattern 3: layers 0,1 local, layer 2 GLOBAL
        hidden_act="gelu_tanh",
        rms_norm_unit_offset=True,
        embed_scale=True,
        qk_norm=True,
        sliding_window=8,
        sliding_window_pattern=3,
        query_pre_attn_scalar=64.0,
        post_norms=True,
        rope_theta=1_000_000.0,
        rope_local_theta=10_000.0,
        rope_scaling_factor=8.0,
    ),
    "tiny-gemma2-debug": ModelConfig(
        name="tiny-gemma2-debug",
        hidden_act="gelu_tanh",
        rms_norm_unit_offset=True,
        embed_scale=True,
        sliding_window=8,  # tiny: windows engage within test prompts
        attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0,
        query_pre_attn_scalar=64.0,  # != head_dim 32: scaling exercised
        post_norms=True,
    ),
}
# Aliases matching the ids used in the reference manifests
# (/root/reference/examples/deploy/vllm/agg.yaml:33, .../dgdr/trtllm/disagg.yaml).
PRESETS["meta-llama/Llama-3.2-1B-Instruct".lower().split("/")[-1]] = PRESETS[
    "llama-3.2-1b-instruct"
]
PRESETS["qwen/qwen3-0.6b".split("/")[-1]] = PRESETS["qwen3-0.6b"]
PRESETS["deepseek-v2-lite-chat"] = PRESETS["deepseek-v2-lite"]
# DeepSeek-V3.2 structure at a toy size: tiny-kimi-debug's block with the
# experts in 4 groups of which 2 are kept, under an indexer of 4 heads x 32
# lanes that keeps 16 rows a query (so a 17-token context already selects)
PRESETS["tiny-dsv32-debug"] = dataclasses.replace(
    PRESETS["tiny-kimi-debug"], name="tiny-dsv32-debug",
    n_group=4, topk_group=2, rope_theta=10000.0,
    rope_yarn_scaling=(40.0, 32.0, 1.0, 64, 1.0, 1.0, -1.0),
    index_n_heads=4, index_head_dim=32, index_topk=16)
# one chip's share of it: experts 0-3 of 16 held (group 0), as the chip
# benchmark's cell holds experts 0-15 of 256
PRESETS["tiny-dsv32-ep4-debug"] = dataclasses.replace(
    PRESETS["tiny-dsv32-debug"], name="tiny-dsv32-ep4-debug",
    num_local_experts=4, local_expert_offset=0)
# one chip's share of tiny-kimi-debug: experts 4-7 of 16 held (the chip
# benchmark's CPU rehearsal of the Kimi-K2 cell serves this)
PRESETS["tiny-kimi-ep4-debug"] = dataclasses.replace(
    PRESETS["tiny-kimi-debug"], name="tiny-kimi-ep4-debug",
    num_local_experts=4, local_expert_offset=4)
# Laguna's structure at a toy size: 1 dense + 4 expert layers whose kinds
# run full | sliding, sliding, sliding, full (a period of 4 behind the dense
# layer), 4 query heads on full layers and 6 on sliding ones over 2 KV heads,
# window 8 (page 4 in the tests: edges fall inside pages), a rotary a kind
# (full: yarn on half the lanes with an explicit attention factor; sliding:
# plain, all lanes), the per-head output gate, 32 softmax-routed experts
# top-4 (32, not 16: 8 * 4 <= 32 keeps the grouped expert layer, the one
# the published shape takes, see moe_grouped) + a shared expert of its own
# width
PRESETS["tiny-laguna-debug"] = ModelConfig(
    name="tiny-laguna-debug",
    intermediate_size=64, num_layers=5, num_heads=4, num_kv_heads=2,
    head_dim=32, rms_norm_eps=1e-6, tie_word_embeddings=False,
    rope_theta=500000.0, num_experts=32, num_experts_per_tok=4, num_shared_experts=1,
    shared_expert_intermediate_size=48, norm_topk_prob=True,
    routed_scaling_factor=2.5, first_k_dense=1, dense_intermediate_size=256,
    sliding_window=8, sliding_window_pattern=0,
    layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
    heads_per_layer=(4, 6, 6, 6, 4),
    rope_by_kind=((FULL, 500000.0, 0.5,
                   (16.0, 32.0, 1.0, 16, 1.0, 0.0, 1.25)),
                  (SLIDING, 10000.0, 1.0, None)),
    attn_gate="per-head",
)

# MiMo-V2's structure at a toy size: 1 dense layer of the full kind, then
# one whole period [sliding x 5, full]; 8 query heads on both kinds over 2 KV
# heads on the sliding layers and 1 on the full ones; keys 24 lanes a head
# and values 16; a rotary over the first 8 of the 24 (a base a kind); window
# 8; a sink a query head on the sliding layers; the heads' outputs x 0.707;
# 16 sigmoid-routed experts top-2 under a selection bias, no shared one (8 *
# 2 <= 16 keeps the grouped expert layer, the one the published 256 / top-8
# takes)
PRESETS["tiny-mimo-v2-debug"] = ModelConfig(
    name="tiny-mimo-v2-debug",
    hidden_size=64, intermediate_size=32, num_layers=7, num_heads=8,
    num_kv_heads=1, kv_heads_sliding=2, head_dim=24, v_head_dim=16,
    rms_norm_eps=1e-5, tie_word_embeddings=False, rope_theta=1e7,
    num_experts=16, num_experts_per_tok=2, norm_topk_prob=True,
    moe_scoring="sigmoid", router_bias=True,
    first_k_dense=1, dense_intermediate_size=128,
    sliding_window=8, sliding_window_pattern=0,
    layer_types=(FULL,) + (SLIDING,) * 5 + (FULL,),
    rope_by_kind=((FULL, 1e7, 8 / 24, None), (SLIDING, 1e4, 8 / 24, None)),
    attn_sink_kinds=(SLIDING,), attn_value_scale=0.707,
)
# one chip's share of it: experts 4-7 of 16 held
PRESETS["tiny-mimo-v2-ep4-debug"] = dataclasses.replace(
    PRESETS["tiny-mimo-v2-debug"], name="tiny-mimo-v2-ep4-debug",
    num_local_experts=4, local_expert_offset=4)

# NVIDIA-Nemotron-3-Nano's structure at a toy size: the first nine letters
# of its pattern (MEMEM*EME: every layer ONE mixer), Mamba-2 with 4 heads of
# 8 lanes in 2 groups (more heads than groups, more than one group), state
# 8, conv 4, a scan chunk of 4 (the tests' prompts are no multiple of it),
# 4 query heads over 2 KV heads without a rotary, 16 sigmoid-routed
# two-matrix relu^2 experts top-2 (8 * 2 <= 16 keeps the grouped expert
# layer, the one the published 128 / top-6 takes) + a shared one of its
# own width, an untied head
PRESETS["tiny-nemotron-h-debug"] = ModelConfig(
    name="tiny-nemotron-h-debug",
    hidden_size=64, intermediate_size=32, num_layers=9, num_heads=4,
    num_kv_heads=2, head_dim=16, tie_word_embeddings=False,
    num_experts=16, num_experts_per_tok=2, num_shared_experts=1,
    shared_expert_intermediate_size=48, norm_topk_prob=True,
    routed_scaling_factor=2.5, moe_scoring="sigmoid", router_bias=True,
    mixer_types=tuple(MIXER_LETTERS[c] for c in "MEMEM*EME"),
    mamba_num_heads=4, mamba_head_dim=8, mamba_n_groups=2, ssm_state_size=8,
    conv_kernel=4, ssm_chunk_size=4, expert_act="relu2",
)

# Falcon-H1's structure at a toy size: three layers, each attention (4 query
# heads over 2 KV heads of 16 lanes, a rotary) AND Mamba-2 (4 heads of 8
# lanes in 2 groups, state 8, conv 4, a scan chunk of 4) on one normed
# input, then a gated MLP; every multiplier set, none 1, no two alike
PRESETS["tiny-falcon-h1-debug"] = ModelConfig(
    name="tiny-falcon-h1-debug",
    hidden_size=64, intermediate_size=128, num_layers=3, num_heads=4,
    num_kv_heads=2, head_dim=16, tie_word_embeddings=False,
    rope_theta=1e11,
    mixer_types=(PARALLEL,) * 3,
    mamba_num_heads=4, mamba_head_dim=8, mamba_n_groups=2, ssm_state_size=8,
    conv_kernel=4, ssm_chunk_size=4,
    multipliers=Multipliers(
        embedding=2.5, lm_head=0.6, attention_in=1.3, attention_out=0.7,
        key=0.45, ssm_in=0.8, ssm_out=0.9,
        ssm=(0.55, 1.2, 0.65, 1.4, 0.85), mlp=(0.75, 1.1)),
)

# LFM2-MoE's structure at a toy size: nine layers, each an operator and then
# an FFN. The operators `c c a c c c a c c`: a gated short convolution
# (kernel 3, a 2-row state a sequence) or GQA attention (8 query heads over
# 2 KV heads of 16 lanes, four a KV head as published, q/k norms, a rotary);
# the FFNs: two leading dense layers of width 128, then 16 sigmoid-routed
# gated experts of width 32 taking 2 a token (8 x 2 <= 16: the grouped
# matmuls, as the published 4 of 32 take them) under a selection bias; a
# tied head. The second preset is one chip's share of four: experts 4-7 held.
PRESETS["tiny-lfm2-moe-debug"] = ModelConfig(
    name="tiny-lfm2-moe-debug",
    hidden_size=64, intermediate_size=32, num_layers=9, num_heads=8,
    num_kv_heads=2, head_dim=16, tie_word_embeddings=True, qk_norm=True,
    rope_theta=1e6,
    num_experts=16, num_experts_per_tok=2, norm_topk_prob=True,
    moe_scoring="sigmoid", router_bias=True,
    first_k_dense=2, dense_intermediate_size=128,
    mixer_types=tuple({"c": CONV, "a": ATTENTION}[c] for c in "ccacccacc"),
    conv_kernel=3,
)
PRESETS["tiny-lfm2-moe-ep4-debug"] = dataclasses.replace(
    PRESETS["tiny-lfm2-moe-debug"], name="tiny-lfm2-moe-ep4-debug",
    num_local_experts=4, local_expert_offset=4)

# MiniCPM-SALA's structure at a toy size: eight layers `S L L L S L L S`,
# each an operator and then a dense gated FFN. S: InfLLM-v2 block-sparse GQA
# (8 query heads over 2 KV heads of 32 lanes under q/k norms, no
# rotary, an output gate; a mean pool of 8 tokens every 4 (4-token pages),
# blocks of 16, top-2 beside 1 initial block and a 32-token window, dense up
# to 64 tokens), so that every branch runs in under 300 tokens. L: Lightning
# linear attention, 8 heads of 32 lanes under q/k norms and a rotary, a state
# of [8, 32, 32] float32 a sequence. The three muP scalars set and distinct.
PRESETS["tiny-minicpm-sala-debug"] = ModelConfig(
    name="tiny-minicpm-sala-debug",
    hidden_size=128, intermediate_size=256, num_layers=8, num_heads=8,
    num_kv_heads=2, head_dim=32, qk_norm=True, rope_theta=10000.0,
    rms_norm_eps=1e-6, tie_word_embeddings=False,
    mixer_types=tuple({"S": SPARSE, "L": LIGHTNING}[c] for c in "SLLLSLLS"),
    mamba_num_heads=8, mamba_head_dim=32, mamba_n_groups=8,
    ssm_state_size=32, ssm_chunk_size=16,
    sparse_kernel_size=8, sparse_kernel_stride=4, sparse_block_size=16,
    sparse_topk=2, sparse_init_blocks=1, sparse_window_size=32,
    sparse_dense_len=64,
    scale_emb=12.0, scale_depth=1.4, dim_model_base=32,
)
