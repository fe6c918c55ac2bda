"""Kimi-K2-Instruct (model_type kimi_k2, the DeepSeek-V3 block): the forward
pass in plain jax.numpy, float32, matmuls at precision "highest".

Full sequence, no cache, no kernels, no batching; MLA in its expanded
(non-absorbed) form, experts as a loop. It follows the published model
(https://huggingface.co/moonshotai/Kimi-K2-Instruct, config.json and
modeling_deepseek.py) and is what the program is compared with: on the CPU
at a small size (tests/test_kimi_k2.py) and on the chip at the published
widths (benchmarks/chip/compare_reference.py, which keeps a copy of this
file). Each departure from the published code is marked DEPARTURE at its line.

The layer, as equations (pre-norm residual block, RMSNorm eps 1e-6):

    h <- h + Attn(norm(h));  h <- h + FFN(norm(h))

    MLA   c_q = RMSNorm(x W_qa);  [q_nope | q_rope]_h = c_q W_qb
          [c | k_r] = x W_kva;  c_kv = RMSNorm(c);  k_rope = RoPE(k_r)
          k_nope_h = c_kv W_UK_h;  v_h = c_kv W_UV_h
          score_h = (q_nope_h . k_nope_h + RoPE(q_rope_h) . k_rope)
                    * (nope + rope)^-1/2 * m^2,
          m = 0.1 * mscale_all_dim * ln(factor) + 1;  causal softmax
          out = concat_h(sum p v_h) W_o
    FFN   layer < first_k_dense_replace: one SwiGLU of the dense width
          else  s = sigmoid(x W_r) in float32; pick the k largest of s + b
                (b = e_score_correction_bias: selection only)
                w_i = s_i / (sum_sel s + 1e-20) * routed_scaling_factor
                y = sum_sel w_i E_i(x) + E_shared(x)
          E(x) = W_down(silu(W_gate x) * W_up x)

A SHARE of the model (one chip of an expert-parallel deployment, see the
model-configs guide, section 4) is the same forward with the sum over the
selected experts restricted to those held: the router keeps its whole width
and its k, what the absent experts would have added is left out, and that
partial result is what goes on to the next layer. The vocabulary's slice is
a smaller vocabulary: embedding rows and head columns of the slice only.

Weights come in the program's layout, as float32 (`dequantize`): stacked on
a leading layer axis, the leading dense layers under the "dense." prefix
(models/llama.py param_specs). The lanes of a rotary half are in the
half-split order (lane i turns with lane i + d/2): the loader permutes the
checkpoint's interleaved pairs into it, so that is the order the weights
this reference sees are in. DEPARTURE (layout only): the published code
de-interleaves at run time instead.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
DENSE_PREFIX = "dense."


@dataclasses.dataclass(frozen=True)
class Config:
    """The published config.json's keys, under their published names."""
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int      # the router's width, whatever is held here
    num_experts_per_tok: int
    n_shared_experts: int
    first_k_dense_replace: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    rms_norm_eps: float
    rope_theta: float
    rope_scaling: Optional[dict]

    @staticmethod
    def from_hf(cfg: dict) -> "Config":
        if cfg.get("scoring_func") != "sigmoid":
            raise ValueError("this reference is the sigmoid-scored router")
        if (cfg.get("n_group") or 1) != 1 or (cfg.get("topk_group") or 1) != 1:
            raise ValueError("group-limited routing is not written down here")
        share = cfg.get("deployment_share") or {}
        keys = [f.name for f in dataclasses.fields(Config)]
        vals = {k: cfg.get(k) for k in keys}
        vals["n_routed_experts"] = share.get(
            "n_routed_experts_total", cfg["n_routed_experts"])
        return Config(**vals)


@dataclasses.dataclass(frozen=True)
class Share:
    """Which routed experts' weights `params` holds: [first, first + held).
    None everywhere below means the uncut model."""
    first_expert: int
    experts_held: int


def dequantize(params: Dict) -> Dict[str, jax.Array]:
    """The program's parameter tree as plain float32 arrays: an int8
    weight with per-channel scales becomes q * scale, exactly."""
    out = {}
    for name, w in params.items():
        if hasattr(w, "q") and hasattr(w, "scale"):
            out[name] = jnp.asarray(w.q, F32) * jnp.asarray(w.scale, F32)
        else:
            out[name] = jnp.asarray(w, F32)
    return out


def layer_params(cfg: Config, params: Dict, i: int) -> Dict[str, jax.Array]:
    """Layer i's weights under their plain names."""
    k = cfg.first_k_dense_replace
    if i < k:
        return {n[len(DENSE_PREFIX):]: w[i] for n, w in params.items()
                if n.startswith(DENSE_PREFIX)}
    return {n: w[i - k] for n, w in params.items()
            if not n.startswith(DENSE_PREFIX)
            and n not in ("embed", "lm_head", "final_norm")}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


# ------------------------------------------------------------------- RoPE --

def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1.0 else 0.1 * mscale * math.log(scale) + 1.0


def rope_inv_freq(cfg: Config):
    """(inverse frequencies [d/2], magnitude on cos/sin), as
    DeepseekV3YarnRotaryEmbedding computes them."""
    d, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (jnp.arange(0, d, 2, dtype=F32) / d)
    rs = cfg.rope_scaling
    if not rs:
        return extra, 1.0
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def correction_dim(n_rot):
        return d * math.log(orig / (n_rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), d - 1)
    if low == high:
        # the published ramp divides by (high - low): it adds 0.001 to the
        # upper end where the two coincide. Kimi-K2's beta_fast =
        # beta_slow = 1 gives one correction dimension (19.16 at d 64,
        # theta 50000, original 4096) whose floor and ceiling differ, so
        # this line is not reached for it; kept because the published
        # code has it.
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp  # 1 where the lane keeps its extrapolated frequency
    inv = extra / factor * (1.0 - keep) + extra * keep
    mag = (yarn_get_mscale(factor, rs["mscale"])
           / yarn_get_mscale(factor, rs["mscale_all_dim"]))
    return inv, mag


def rope(x, positions, inv, mag):
    """x [S, ..., d], half-split pairs: lane i turns with lane i + d/2."""
    ang = positions.astype(F32)[:, None] * inv  # [S, d/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (inv.shape[0],)
    cos, sin = (jnp.cos(ang) * mag).reshape(shape), (jnp.sin(ang) * mag
                                                     ).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -------------------------------------------------------------- the block --

def attention(cfg: Config, lp: Dict, x, positions, q_block: int = 0):
    """Expanded-form MLA over the whole sequence, causal. q_block > 0
    computes the scores a block of queries at a time (the same numbers; so
    that 8k positions of 64 heads fit a device's memory)."""
    nope, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    lora, eps = cfg.kv_lora_rank, cfg.rms_norm_eps
    inv, mag = rope_inv_freq(cfg)
    c_q = rms_norm(x @ lp["wq_a"], lp["q_a_norm"], eps)
    q = jnp.einsum("sr,rhd->shd", c_q, lp["wq_b"])          # [S, H, nope+r]
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], positions, inv, mag)
    kv = x @ lp["w_kv_a"]                                   # [S, lora + r]
    c_kv = rms_norm(kv[:, :lora], lp["kv_a_norm"], eps)
    k_rope = rope(kv[:, lora:], positions, inv, mag)        # one for all heads
    k_nope = jnp.einsum("sr,hnr->shn", c_kv, lp["w_uk"])    # [S, H, nope]
    v = jnp.einsum("sr,hrv->shv", c_kv, lp["w_uv"])         # [S, H, vd]
    scale = (nope + r) ** -0.5
    rs = cfg.rope_scaling
    if rs and rs.get("mscale_all_dim"):
        m = yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
        scale = scale * m * m
    s = x.shape[0]
    outs = []
    step = q_block or s
    for a in range(0, s, step):
        b = min(a + step, s)
        sc = (jnp.einsum("qhn,khn->hqk", q_nope[a:b], k_nope)
              + jnp.einsum("qhr,kr->hqk", q_rope[a:b], k_rope)) * scale
        causal = positions[None, :] <= positions[a:b, None]
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khv->qhv", p, v))
    o = jnp.concatenate(outs, axis=0)                       # [S, H, vd]
    return jnp.einsum("shv,hve->se", o, lp["wo"])


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(cfg: Config, lp: Dict, x):
    """(picked expert ids [S, k], their weights [S, k]) over the router's
    whole width."""
    s = jax.nn.sigmoid((x @ lp["router"]).astype(F32))
    _, picked = jax.lax.top_k(s + lp["router_bias"], cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, picked, axis=-1)  # of s, not of s + bias
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return picked, w * cfg.routed_scaling_factor


def experts(cfg: Config, lp: Dict, x, share: Optional[Share] = None,
            with_shared: bool = True):
    """The expert layer's output for the experts `lp` holds (all of them
    without a share), plus the shared expert unless with_shared is False
    (the sum-of-shares identity counts it once)."""
    picked, w = route(cfg, lp, x)
    first = share.first_expert if share else 0
    held = share.experts_held if share else cfg.n_routed_experts
    y = jnp.zeros_like(x)
    for j in range(held):  # experts as a loop
        gate = jnp.sum(jnp.where(picked == first + j, w, 0.0), axis=-1)
        y = y + gate[:, None] * swiglu(
            x, lp["moe_w_gate"][j], lp["moe_w_up"][j], lp["moe_w_down"][j])
    if with_shared and cfg.n_shared_experts:
        y = y + swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
    return y


def layer(cfg: Config, lp: Dict, h, positions, share: Optional[Share] = None,
          q_block: int = 0):
    eps = cfg.rms_norm_eps
    h = h + attention(cfg, lp, rms_norm(h, lp["attn_norm"], eps), positions,
                      q_block)
    x = rms_norm(h, lp["mlp_norm"], eps)
    if "router" in lp:
        return h + experts(cfg, lp, x, share)
    return h + swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def forward(cfg: Config, params: Dict, tokens, share: Optional[Share] = None,
            q_block: int = 0):
    """tokens [S] (ids within the vocabulary slice `params` holds) ->
    logits [S, V held], float32."""
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(tokens.shape[0])
        h = params["embed"][tokens]
        for i in range(cfg.num_hidden_layers):
            h = layer(cfg, layer_params(cfg, params, i), h, positions, share,
                      q_block)
        h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        return h @ params["lm_head"]  # untied head
