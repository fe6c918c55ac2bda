"""Checkpoint loading: HF safetensors -> the engine's stacked param layout.

Replaces the reference engines' HF-hub weight loading (the manifests mount a
HF cache PVC at /home/dynamo/.cache/huggingface,
/root/reference/examples/dgdr/trtllm/disagg_cache.yaml:29-34). This
environment has zero egress, so loading is strictly local-dir; absent weights
fall back to seeded random init (tests, smoke benches, fake-engine mode).
"""

from __future__ import annotations

import glob
import logging
import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models import llama

log = logging.getLogger("dynamo_tpu.loader")


def load_or_init_params(
    cfg: ModelConfig,
    model_path: Optional[str],
    seed: int = 0,
    quantization: str = "none",
) -> Dict[str, jax.Array]:
    """Load (or randomly init) params; optionally int8-quantize them.

    Quantization runs pinned to the CPU backend so a model whose bf16 weights
    exceed the accelerator's HBM (the whole point of quantizing — Llama-3-8B
    on v5e) never materializes on-chip; the engine's shard_params moves the
    int8 tree across afterwards.
    """

    files = []
    if model_path and os.path.isdir(model_path):
        files = sorted(glob.glob(os.path.join(model_path, "*.safetensors")))
        if not files:
            log.warning("no safetensors under %s; using random init",
                        model_path)

    def _load():
        if files:
            return load_hf_safetensors(cfg, files)
        return llama.init_params(cfg, jax.random.PRNGKey(seed))

    if quantization in (None, "none", ""):
        return _load()
    if quantization not in ("int8", "w8a8"):
        raise ValueError(f"unknown quantization {quantization!r}")
    from dynamo_tpu.models import quant

    n_params = sum(
        int(np.prod(shape))
        for shape, _, _ in llama.param_specs(cfg).values()
    )
    if not files and n_params > 2_000_000_000:
        # No checkpoint to preserve and a multi-billion-param model: build
        # the int8 tree directly instead of materializing the bf16 model on
        # the host and quantizing it (an hour-scale detour for the 8B bench
        # model). Small models keep init+quantize so int8 stays
        # token-parity-testable against the fp engine.
        return random_quantized_params(cfg, seed, mode=quantization)
    with jax.default_device(_host_device()):
        params = _load()
        return quant.quantize_params(params, mode=quantization)


def _host_device():
    """JAX's CPU device, for staging full-precision weights on the host
    before they are quantized. It exists beside the TPU when JAX_PLATFORMS
    is unset or lists `cpu` (utils/platform.init_backend adds it when an
    entry point was told `tpu` alone); a library caller that pinned
    JAX_PLATFORMS=tpu itself gets told what to change."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "quantizing a checkpoint on the host needs JAX's CPU backend "
            "beside the accelerator: leave JAX_PLATFORMS unset or use "
            "JAX_PLATFORMS=tpu,cpu") from e


def random_quantized_params(cfg: ModelConfig, seed: int = 0,
                            mode: str = "int8") -> Dict[str, jax.Array]:
    """Seeded random int8 params, generated directly as QTensors.

    Statistically equivalent to init + quantize (int8 values uniform over the
    byte range with per-channel scales sized so dequantized weights match
    each spec's sigma at amax ~= 4.5 sigma) at a tiny fraction of the cost:
    raw RNG bytes instead of N billion f32 normals + a second f32 pass.

    The leaves are host (numpy) arrays, so no JAX backend is touched here
    at all: the tree crosses to the accelerator once, shard by shard, in
    the engine's shard_params."""
    from dynamo_tpu.models import quant

    dt = jnp.dtype(cfg.dtype)
    cls = quant.qtensor_class(mode)
    rng = np.random.Generator(np.random.PCG64(seed))
    p: Dict[str, np.ndarray] = {}
    # the entropy's period. A hybrid model stores an expert's matrices 3,072
    # x 2,048 = 3 x 2^21 values (ModelConfig.expert_dims_stored): tiled from
    # 2^24 values, every eighth expert would be the SAME matrix, and a row
    # routed to the wrong one of them would show nowhere. An odd period
    # keeps all of them apart. The other models keep the draw they have
    # always had (their numbers on the chip are recorded against it).
    period = (1 << 24) - 57 if cfg.mixer_types else 1 << 24
    for name, (shape, kind, sigma) in llama.param_specs(cfg).items():
        axes = quant.quant_axes(name)
        if name == "router_bias":
            p[name] = np.zeros(shape, np.float32)
        elif kind in llama.SSM_INITS:
            p[name] = np.asarray(llama.SSM_INITS[kind](
                rng.random(shape, dtype=np.float32)), np.float32)
        elif kind == "ones":
            p[name] = np.ones(shape, dt)
        elif kind == "zeros":
            p[name] = np.zeros(shape, dt)
        elif axes:
            n = int(np.prod(shape))
            # 16 MiB of entropy tiled to size: weight VALUES are
            # irrelevant here (no checkpoint to reproduce; serving
            # timing is value-independent) — only shape/dtype/scale
            # matter, and multi-GiB PCG64 streams cost minutes
            ent = np.frombuffer(rng.bytes(min(n, period)), dtype=np.int8)
            q = np.tile(ent, -(-n // ent.size))[:n].reshape(shape)
            sshape = tuple(1 if i in axes else s
                           for i, s in enumerate(shape))
            scale = np.full(sshape, sigma * 4.5 / 127.0, dtype=np.float32)
            p[name] = cls(q, scale)
        else:
            # unquantized weight (router etc.): small enough for normals
            p[name] = (rng.standard_normal(shape, dtype=np.float32)
                       * sigma).astype(dt)
    for name, cuts in llama.zero_lanes(cfg).items():
        # what a hybrid model stores past the model's own extents: zero
        q = p[name].q.copy()
        for axis, extent in cuts:
            q[(slice(None),) * axis + (slice(extent, None),)] = 0
        p[name] = cls(q, p[name].scale)
    return p


def load_hf_safetensors(cfg: ModelConfig, files) -> Dict[str, jax.Array]:
    """Stream HF-layout tensors into the stacked [num_layers, ...] layout."""
    from safetensors import safe_open

    if cfg.layer_types:
        raise NotImplementedError(
            "loading a checkpoint of a model whose layers are of more than "
            "one kind (layer_types) is not implemented: the head-shaped "
            "leaves stack by kind (models/llama.KIND_PREFIX) and no weight "
            "file has been read against that yet; such a model is served "
            "on seeded random weights")
    if cfg.is_sala:
        raise NotImplementedError(
            "loading a checkpoint of model_type minicpm_sala (an operator-"
            "then-FFN model: models/llama._operator_param_specs) is not "
            "implemented: no weight file has been read against the "
            "published key names (ASSUMED, benchmarks/chip/configs/minicpm-"
            "sala-w8a8-1chip.json); such a model is served on seeded random "
            "weights")
    if cfg.operator_ffn:
        raise NotImplementedError(
            "loading a checkpoint of model_type lfm2_moe (an operator-then-"
            "FFN model: models/llama._operator_param_specs) is not "
            "implemented: no weight file has been read against the "
            "published key names (model.layers.N.conv.{in_proj,conv,"
            "out_proj}, self_attn.{q,k,v,out}_proj with q_layernorm / "
            "k_layernorm, operator_norm, ffn_norm, feed_forward.{w1,w2,w3} "
            "| gate | experts.M.{w1,w2,w3} | expert_bias, model."
            "embedding_norm: ASSUMED, benchmarks/chip/configs/lfm2-8b-a1b-"
            "w8a8-1chip.json); such a model is served on seeded random "
            "weights")
    dt = jnp.dtype(cfg.dtype)
    e, h, kv, d, f = (
        cfg.hidden_size,
        cfg.num_heads,
        cfg.num_kv_heads,
        cfg.head_dim,
        cfg.intermediate_size,
    )

    raw: Dict[str, jax.Array] = {}

    def want(name: str) -> bool:
        return name.startswith(("model.", "lm_head."))

    # framework="flax" hands back jnp arrays and handles bfloat16 natively
    for path in files:
        with safe_open(path, framework="flax") as fh:
            for name in fh.keys():
                if want(name):
                    raw[name] = fh.get_tensor(name)

    def g(name: str) -> jax.Array:
        return raw.pop(name)

    def has(name: str) -> bool:
        return name in raw

    def to_dt(x) -> jax.Array:
        return jnp.asarray(x).astype(dt)

    p: Dict[str, jax.Array] = {}
    # a sliced vocabulary (this chip's share): rows [vocab_offset,
    # vocab_offset + vocab_size) of the checkpoint's table and head
    v0, v1 = cfg.vocab_offset, cfg.vocab_offset + cfg.vocab_size
    p["embed"] = to_dt(g("model.embed_tokens.weight"))[v0:v1]
    p["final_norm"] = to_dt(g("model.norm.weight"))
    if not cfg.tie_word_embeddings:
        p["lm_head"] = to_dt(g("lm_head.weight"))[v0:v1].T
    first = cfg.first_k_dense
    # leading dense layers into their own stack, the rest into the scanned
    # one; checkpoint layer numbers run through both
    _load_layers(cfg, p, g, has, to_dt, range(first, cfg.num_layers), "",
                 is_moe=cfg.is_moe, f=f)
    if first:
        _load_layers(cfg, p, g, has, to_dt, range(first), llama.DENSE_PREFIX,
                     is_moe=False, f=cfg.dense_intermediate_size)
    return p


def _load_layers(cfg: ModelConfig, out: Dict[str, jax.Array], g, has, to_dt,
                 layers, pre: str, *, is_moe: bool, f: int) -> None:
    """Stack checkpoint layers `layers` into out[pre + name], leading axis
    in that order. `f` is the stack's dense / shared-expert FFN width."""
    e, h, kv, d = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                   cfg.head_dim)
    l0 = layers[0]
    p: Dict[str, jax.Array] = {}

    def stack(fmt: str, transform) -> jax.Array:
        return jnp.stack([transform(g(fmt.format(i=i))) for i in layers])

    p["attn_norm"] = stack(
        "model.layers.{i}.input_layernorm.weight", lambda w: to_dt(w)
    )
    if cfg.post_norms:
        # gemma-2 sandwich norms: HF's post_attention_layernorm here is
        # genuinely post-attention (llama's same-named key is the PRE-MLP
        # norm), the pre-MLP norm is pre_feedforward_layernorm
        p["mlp_norm"] = stack(
            "model.layers.{i}.pre_feedforward_layernorm.weight", to_dt
        )
        p["post_attn_norm"] = stack(
            "model.layers.{i}.post_attention_layernorm.weight", to_dt
        )
        p["post_mlp_norm"] = stack(
            "model.layers.{i}.post_feedforward_layernorm.weight", to_dt
        )
    else:
        p["mlp_norm"] = stack(
            "model.layers.{i}.post_attention_layernorm.weight",
            lambda w: to_dt(w)
        )
    if cfg.is_mla:
        # DeepSeek-V2-family MLA names: q_proj, kv_a_proj_with_mqa (latent
        # down-projection + shared rope key), kv_a_layernorm, and
        # kv_b_proj whose rows interleave per head as [W_UK^T | W_UV^T]
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        lora, vd = cfg.kv_lora_rank, cfg.v_head_dim
        # DeepSeek checkpoints store the rope lanes INTERLEAVED (pair
        # [2i, 2i+1] rotates together; HF de-interleaves at runtime before
        # rotate_half). Our apply_rope is half-split (neox), so fold the
        # de-interleave permutation into the rope output columns once at
        # load: deint[c] = 2c for the first half, 2(c - rope/2)+1 after.
        deint = np.concatenate([np.arange(0, rope, 2),
                                np.arange(1, rope, 2)])

        def fix_q(w):
            w = to_dt(w).T.reshape(e, h, nope + rope)
            return jnp.concatenate(
                [w[..., :nope], w[..., nope + deint]], axis=-1)

        def fix_kv_a(w):
            w = to_dt(w).T  # [E, lora + rope]
            return jnp.concatenate(
                [w[..., :lora], w[..., lora + deint]], axis=-1)

        if cfg.q_lora_rank > 0:
            # query low-rank path: q_a_proj [qr, E], its norm, and
            # q_b_proj [H*(nope+rope), qr] with the same per-head rope
            # de-interleave as the one-matrix form
            qr = cfg.q_lora_rank
            p["wq_a"] = stack(
                "model.layers.{i}.self_attn.q_a_proj.weight",
                lambda w: to_dt(w).T)
            p["q_a_norm"] = stack(
                "model.layers.{i}.self_attn.q_a_layernorm.weight", to_dt)

            def fix_q_b(w):
                w = to_dt(w).T.reshape(qr, h, nope + rope)
                return jnp.concatenate(
                    [w[..., :nope], w[..., nope + deint]], axis=-1)

            p["wq_b"] = stack(
                "model.layers.{i}.self_attn.q_b_proj.weight", fix_q_b)
        else:
            p["wq_mla"] = stack(
                "model.layers.{i}.self_attn.q_proj.weight", fix_q)
        p["w_kv_a"] = stack(
            "model.layers.{i}.self_attn.kv_a_proj_with_mqa.weight",
            fix_kv_a,
        )
        p["kv_a_norm"] = stack(
            "model.layers.{i}.self_attn.kv_a_layernorm.weight", to_dt)

        def split_kv_b(w):
            # [h*(nope+vd), lora] -> W_UK [h, nope, lora], W_UV [h, lora, vd]
            b = to_dt(w).reshape(h, nope + vd, lora)
            return b[:, :nope, :], jnp.swapaxes(b[:, nope:, :], 1, 2)

        kv_b = [split_kv_b(g(f"model.layers.{i}.self_attn.kv_b_proj.weight"))
                for i in layers]
        p["w_uk"] = jnp.stack([b[0] for b in kv_b])
        p["w_uv"] = jnp.stack([b[1] for b in kv_b])
        p["wo"] = stack(
            "model.layers.{i}.self_attn.o_proj.weight",
            lambda w: to_dt(w).T.reshape(h, vd, e),
        )
        if cfg.is_dsa:
            # the lightning indexer (DeepSeek-V3.2): self_attn.indexer.*.
            # Its rotary lanes are the FIRST `rope` of each head; ASSUMED
            # interleaved in the checkpoint like the attention's, so the
            # same de-interleave is folded into the outputs that turn
            # (and into the LayerNorm's weight and bias, whose lanes they
            # are). No checkpoint was at hand to hold this against.
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            lanes = np.concatenate([deint, np.arange(rope, di)])
            base = "model.layers.{i}.self_attn.indexer."
            p["idx_wq_b"] = stack(
                base + "wq_b.weight",
                lambda w: to_dt(w).T.reshape(cfg.q_lora_rank, hi, di)[
                    ..., lanes])
            p["idx_wk"] = stack(base + "wk.weight",
                                lambda w: to_dt(w).T[..., lanes])
            p["idx_k_norm"] = stack(base + "k_norm.weight",
                                    lambda w: to_dt(w)[lanes])
            p["idx_k_bias"] = stack(base + "k_norm.bias",
                                    lambda w: to_dt(w)[lanes])
            p["idx_w"] = stack(base + "weights_proj.weight",
                               lambda w: to_dt(w).T)
    elif has(f"model.layers.{l0}.self_attn.qkv_proj.weight"):
        # Phi-3 fuses q/k/v rows into one projection: [(H+2KV)*D, E] with
        # q first, then k, then v (same split in HF's Phi3Attention);
        # each fused tensor is read ONCE per layer (stack() consumes)
        qkv = [to_dt(g(f"model.layers.{i}.self_attn.qkv_proj.weight"))
               for i in layers]
        p["wq"] = jnp.stack([w[: h * d].T.reshape(e, h, d) for w in qkv])
        p["wk"] = jnp.stack(
            [w[h * d: (h + kv) * d].T.reshape(e, kv, d) for w in qkv])
        p["wv"] = jnp.stack(
            [w[(h + kv) * d:].T.reshape(e, kv, d) for w in qkv])
        p["wo"] = stack(
            "model.layers.{i}.self_attn.o_proj.weight",
            lambda w: to_dt(w).T.reshape(h, d, e),
        )
    else:
        p["wq"] = stack(
            "model.layers.{i}.self_attn.q_proj.weight",
            lambda w: to_dt(w).T.reshape(e, h, d),
        )
        p["wk"] = stack(
            "model.layers.{i}.self_attn.k_proj.weight",
            lambda w: to_dt(w).T.reshape(e, kv, d),
        )
        p["wv"] = stack(
            "model.layers.{i}.self_attn.v_proj.weight",
            lambda w: to_dt(w).T.reshape(e, kv, d),
        )
        p["wo"] = stack(
            "model.layers.{i}.self_attn.o_proj.weight",
            lambda w: to_dt(w).T.reshape(h, d, e),
        )
    if cfg.attention_bias:
        p["bq"] = stack(
            "model.layers.{i}.self_attn.q_proj.bias", lambda w: to_dt(w).reshape(h, d)
        )
        p["bk"] = stack(
            "model.layers.{i}.self_attn.k_proj.bias", lambda w: to_dt(w).reshape(kv, d)
        )
        p["bv"] = stack(
            "model.layers.{i}.self_attn.v_proj.bias", lambda w: to_dt(w).reshape(kv, d)
        )
    if cfg.qk_norm:
        p["q_norm"] = stack("model.layers.{i}.self_attn.q_norm.weight", to_dt)
        p["k_norm"] = stack("model.layers.{i}.self_attn.k_norm.weight", to_dt)
    if is_moe:
        if (has(f"model.layers.{l0}.mlp.gate_proj.weight")
                and not has(f"model.layers.{l0}.mlp.gate.weight")):
            # a dense layer where the config promised an expert layer: fail
            # with the real reason instead of a KeyError deep in the
            # expert stacking
            raise ValueError(
                f"checkpoint layer {l0} is a dense FFN but the config "
                f"(first_k_dense_replace={cfg.first_k_dense}) makes it an "
                "expert layer")
        # two upstream MoE naming schemes: Mixtral's block_sparse_moe with
        # w1/w3/w2, Qwen3-MoE's / DeepSeek's mlp.experts with
        # gate/up/down_proj
        if has(f"model.layers.{l0}.block_sparse_moe.gate.weight"):
            moe_base = "block_sparse_moe"
            names = {"gate": "w1", "up": "w3", "down": "w2"}
        else:
            moe_base = "mlp"
            names = {"gate": "gate_proj", "up": "up_proj",
                     "down": "down_proj"}
        p["router"] = stack(
            f"model.layers.{{i}}.{moe_base}.gate.weight",
            lambda w: to_dt(w).T
        )
        if cfg.router_bias:
            # selection bias: float32 in the checkpoint, and kept so
            p["router_bias"] = stack(
                f"model.layers.{{i}}.{moe_base}.gate.e_score_correction_bias",
                lambda w: jnp.asarray(w).astype(jnp.float32))
        # the experts held here: the share's slice of the checkpoint's
        # (the router above keeps its whole width)
        x0 = cfg.local_expert_offset
        held = range(x0, x0 + cfg.held_experts)

        def experts(i: int, which: str) -> jnp.ndarray:
            ws = [
                to_dt(g(f"model.layers.{i}.{moe_base}.experts.{j}"
                        f".{names[which]}.weight")).T
                for j in held
            ]
            return jnp.stack(ws)  # [X held, in, out]

        p["moe_w_gate"] = jnp.stack([experts(i, "gate") for i in layers])
        p["moe_w_up"] = jnp.stack([experts(i, "up") for i in layers])
        p["moe_w_down"] = jnp.stack([experts(i, "down") for i in layers])
        if cfg.num_shared_experts > 0:
            # DeepSeek shared experts load into the dense-MLP param slots
            p["w_gate"] = stack(
                f"model.layers.{{i}}.{moe_base}.shared_experts"
                ".gate_proj.weight", lambda w: to_dt(w).T)
            p["w_up"] = stack(
                f"model.layers.{{i}}.{moe_base}.shared_experts"
                ".up_proj.weight", lambda w: to_dt(w).T)
            p["w_down"] = stack(
                f"model.layers.{{i}}.{moe_base}.shared_experts"
                ".down_proj.weight", lambda w: to_dt(w).T)
    elif has(f"model.layers.{l0}.mlp.gate_up_proj.weight"):
        # Phi-3 fuses gate/up rows: [2F, E], gate first (read once/layer)
        gu = [to_dt(g(f"model.layers.{i}.mlp.gate_up_proj.weight"))
              for i in layers]
        p["w_gate"] = jnp.stack([w[:f].T for w in gu])
        p["w_up"] = jnp.stack([w[f:].T for w in gu])
        p["w_down"] = stack(
            "model.layers.{i}.mlp.down_proj.weight", lambda w: to_dt(w).T
        )
    else:
        p["w_gate"] = stack(
            "model.layers.{i}.mlp.gate_proj.weight", lambda w: to_dt(w).T
        )
        p["w_up"] = stack("model.layers.{i}.mlp.up_proj.weight", lambda w: to_dt(w).T)
        p["w_down"] = stack(
            "model.layers.{i}.mlp.down_proj.weight", lambda w: to_dt(w).T
        )
    out.update({pre + k: v for k, v in p.items()})
