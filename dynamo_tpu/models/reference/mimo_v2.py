"""MiMo-V2.5's language model (model_type mimo_v2): the forward pass in plain
jax.numpy, float32, matmuls at precision "highest".

Full sequence, no cache, no kernels, no batching; attention as a [T, T] mask
a kind over the full causal scores, a block of queries at a time (so that a
few thousand tokens fit), experts as a loop. It follows the published
config.json (https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json)
and is what the program is compared with: on the CPU at a small size
(tests/test_mimo_v2.py) and on the chip at the published widths
(benchmarks/chip/compare_reference_mimo_v2.py, which keeps a copy of this
file). It shares no code with dynamo_tpu. ASSUMED marks what the config
leaves open (the sandbox's transformers carries no `mimo_v2` model to settle
it); DEPARTURE marks a departure from the source.

The layer, as equations (token i, E hidden; 64 query heads on both kinds; a
layer's kind is full where hybrid_layer_pattern[l] == 0, else sliding; n_kv
= num_key_value_heads on a full layer, swa_num_key_value_heads on a sliding
one; Dk = head_dim, Dv = v_head_dim; RMSNorm eps layernorm_epsilon; no bias):

    h = RMSNorm(x);  q = h W_q [H, Dk];  k = h W_k [n_kv, Dk];  v = h W_v [n_kv, Dv]
    rotary on the FIRST R = int(Dk * partial_rotary_factor) lanes of every
        head of q and k, the other Dk - R untouched; angle = pos *
        theta^(-2i / R); theta = rope_theta on a full layer, swa_rope_theta
        on a sliding one; rope_scaling "default": none
        ASSUMED (a) the lanes are the first R and the pairs rotate-half
        (lane i turns with lane i + R / 2)
    s_ij = q_i . k_j / sqrt(Dk), j <= i; a sliding layer also i - j <
        sliding_window  ASSUMED (b) the window counts the query's own key;
        ASSUMED (c) the scale is 1 / sqrt(head_dim), the keys' width
    full:     p_ij = exp(s_ij) / sum_j' exp(s_ij')
    sliding:  p_ij = exp(s_ij) / (sum_j' exp(s_ij') + exp(sink_h))
        ASSUMED (d) add_swa_attention_sink_bias: one learned scalar a query
        head, a logit that enters the denominator ONLY (the probabilities of
        a row sum to less than one); add_full_attention_sink_bias false:
        none on a full layer
    o_i = attention_value_scale * sum_j p_ij v_j  (head h reads KV head
        h // (H / n_kv))  ASSUMED (e) the scale multiplies V: the sum is
        linear, so it is the same number wherever it is applied
    x <- x + o W_o  [H x Dv -> E]   (no output gate)
    h2 = RMSNorm(x)
    moe_layer_freq[l] == 0:  x <- x + SwiGLU(h2) of width intermediate_size
    else  r = h2 W_r [n_routed_experts] in float32;  s = sigmoid(r); the
          num_experts_per_tok largest of s + e_score_correction_bias (n_group
          1, topk_group 1: no groups; the bias picks and does not weigh);
          w = s[picked] / (sum s[picked] + 1e-20)  (norm_topk_prob);
          routed_scaling_factor null -> 1
          x <- x + sum_e w_e SwiGLU_e(h2)  (width moe_intermediate_size; no
          shared expert: n_shared_experts null)
    final RMSNorm, untied head.
    SwiGLU(x) = W_down(silu(W_gate x) * W_up x)
    ASSUMED (f) attention_chunk_size names the same 128 as sliding_window
    and adds no mechanism; hybrid_block_size null.

LEFT OUT: the vision and audio towers and the three multi-token-prediction
layers the model card describes (the config read here holds no key of them).

Weights come in the program's layout, as float32 (`dequantize`): stacked on
a leading layer axis; the leading dense layers under the "dense." prefix; the
attention leaves whose shapes follow a kind's head counts (wq, wk, wv, wo,
the sink) stacked by kind, a sliding layer's under the "win." prefix
(models/llama.py param_specs); the selection bias under "router_bias".
DEPARTURE (layout only, ASSUMED of a checkpoint, whose projections are fused
(`attention_projection_layout: fused_qkv`): none is loaded here).

A SHARE (`Share`) restricts the sum over the picked experts to those `lp`
holds: the router scores and picks over ALL n_routed_experts, and the
held experts' part alone is added. The shares of a layer sum to the whole.

`forward` / `layer` take `variant`: "model", or a CONTROL that must not pass
for the model: "no_sink" (the sliding softmax without its sink),
"window_127" / "window_256" (another span), "one_theta" (a sliding layer
turned with the full layers' base), "rotary_all_lanes" (the rotary over all
Dk lanes), "value_scale_1" (the heads' outputs unscaled), "no_select_bias"
(the pick by the scores alone), "kv_heads_of_full" (a sliding layer's query
heads grouped as a full layer's: head h reads KV head h // (H / n_kv_full)
% n_kv).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
DENSE_PREFIX = "dense."
FULL, SLIDING = "full_attention", "sliding_attention"
KIND_PREFIX = {FULL: "", SLIDING: "win."}
KIND_LEAVES = ("wq", "wk", "wv", "wo", "sink")
VARIANTS = ("model", "no_sink", "window_127", "window_256", "one_theta",
            "rotary_all_lanes", "value_scale_1", "no_select_bias",
            "kv_heads_of_full")


@dataclasses.dataclass(frozen=True)
class Config:
    """The published config.json's keys, under their published names
    (n_routed_experts: the router's whole width)."""
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    swa_num_key_value_heads: int
    head_dim: int
    v_head_dim: int
    hybrid_layer_pattern: Tuple[int, ...]
    moe_layer_freq: Tuple[int, ...]
    sliding_window: int
    partial_rotary_factor: float
    rope_theta: float
    swa_rope_theta: float
    attention_value_scale: float
    add_swa_attention_sink_bias: bool
    n_routed_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    layernorm_epsilon: float

    @staticmethod
    def from_hf(cfg: dict) -> "Config":
        if cfg.get("scoring_func") != "sigmoid" or int(
                cfg.get("n_group") or 1) != 1 or int(
                cfg.get("topk_group") or 1) != 1:
            raise ValueError("this reference writes down sigmoid scores "
                             "without groups")
        if cfg.get("add_full_attention_sink_bias") or cfg.get(
                "attention_bias") or cfg.get("n_shared_experts"):
            raise ValueError("a sink on full layers / a bias / a shared "
                             "expert: not here")
        share = cfg.get("deployment_share") or {}
        return Config(
            hidden_size=cfg["hidden_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"],
            swa_num_key_value_heads=cfg["swa_num_key_value_heads"],
            head_dim=cfg["head_dim"], v_head_dim=cfg["v_head_dim"],
            hybrid_layer_pattern=tuple(cfg["hybrid_layer_pattern"]),
            moe_layer_freq=tuple(cfg["moe_layer_freq"]),
            sliding_window=cfg["sliding_window"],
            partial_rotary_factor=cfg["partial_rotary_factor"],
            rope_theta=float(cfg["rope_theta"]),
            swa_rope_theta=float(cfg["swa_rope_theta"]),
            attention_value_scale=float(cfg["attention_value_scale"]),
            add_swa_attention_sink_bias=bool(
                cfg["add_swa_attention_sink_bias"]),
            n_routed_experts=int(share.get("n_routed_experts_total")
                                 or cfg["n_routed_experts"]),
            num_experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            routed_scaling_factor=float(
                cfg.get("routed_scaling_factor") or 1.0),
            layernorm_epsilon=cfg["layernorm_epsilon"])

    def kind(self, i: int) -> str:
        return SLIDING if self.hybrid_layer_pattern[i] else FULL

    @property
    def dense_layers(self) -> int:
        return self.num_hidden_layers - sum(self.moe_layer_freq)


@dataclasses.dataclass(frozen=True)
class Share:
    """Which routed experts' weights `lp` holds: [first, first + held)."""
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


def layer_index(cfg: Config, i: int) -> Dict[str, int]:
    """Where layer i's leaves sit in the program's stacks: {"dense": row}
    for a leading dense layer, else {"": row of the common stack, "kind":
    row of its kind's stack}."""
    k = cfg.dense_layers
    if i < k:
        return {"dense": i}
    kinds = [cfg.kind(j) for j in range(k, i)]
    return {"": i - k, "kind": kinds.count(cfg.kind(i))}


def layer_params(cfg: Config, params: Dict, i: int) -> Dict[str, jax.Array]:
    """Layer i's weights under their plain names."""
    at = layer_index(cfg, i)
    if "dense" in at:
        return {n[len(DENSE_PREFIX):]: w[at["dense"]]
                for n, w in params.items() if n.startswith(DENSE_PREFIX)}
    pre = KIND_PREFIX[cfg.kind(i)]
    out = {}
    for n, w in params.items():
        if n in ("embed", "lm_head", "final_norm") or n.startswith(
                DENSE_PREFIX):
            continue
        plain = n.rsplit(".", 1)[-1]
        if plain in KIND_LEAVES:
            if n == pre + plain:
                out[plain] = w[at["kind"]]
        elif "." not in n:
            out[n] = w[at[""]]
    return out


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta: float, lanes: int):
    """x [S, heads, D]: the first `lanes` lanes turned (half-split pairs
    within them), the rest untouched."""
    inv = 1.0 / theta ** (jnp.arange(0, lanes, 2, dtype=F32) / lanes)
    ang = positions.astype(F32)[:, None] * inv  # [S, lanes / 2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x[..., :lanes], 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., lanes:]], -1)


# -------------------------------------------------------------- attention --

def attention(cfg: Config, lp: Dict, h, positions, kind: str,
              q_block: int = 0, variant: str = "model"):
    """h [S, E] (normed) -> the attention branch's output [S, E]."""
    s = h.shape[0]
    dk, heads = cfg.head_dim, cfg.num_attention_heads
    lanes = (dk if variant == "rotary_all_lanes"
             else int(dk * cfg.partial_rotary_factor))
    theta = (cfg.swa_rope_theta
             if kind == SLIDING and variant != "one_theta"
             else cfg.rope_theta)
    q = rope(jnp.einsum("se,ehd->shd", h, lp["wq"]), positions, theta, lanes)
    k = rope(jnp.einsum("se,ekd->skd", h, lp["wk"]), positions, theta, lanes)
    v = jnp.einsum("se,ekd->skd", h, lp["wv"])
    n_kv = k.shape[1]
    if variant == "kv_heads_of_full" and kind == SLIDING:
        # head h reads the KV head a full layer's grouping would name
        of = (jnp.arange(heads) // (heads // cfg.num_key_value_heads)) % n_kv
    else:
        of = jnp.arange(heads) // (heads // n_kv)
    k, v = k[:, of], v[:, of]
    window = {"window_127": 127, "window_256": 256}.get(
        variant, cfg.sliding_window) if kind == SLIDING else 0
    sink = (lp["sink"] if kind == SLIDING and variant != "no_sink"
            and cfg.add_swa_attention_sink_bias else None)
    step = q_block or s
    pad = (-s) % step
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    pos_p = jnp.pad(positions, (0, pad))

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, step)
        qi = jax.lax.dynamic_slice_in_dim(pos_p, start, step)[:, None]
        kj = positions[None, :]
        mask = kj <= qi
        if window:
            mask &= kj > qi - window
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(dk)
        sc = jnp.where(mask[None], sc, -jnp.inf)
        if sink is None:
            p = jax.nn.softmax(sc, axis=-1)
        else:  # the sink: one more logit in the denominator, none in the sum
            sk = sink.astype(F32)[:, None, None]
            m = jnp.maximum(jnp.max(sc, axis=-1, keepdims=True), sk)
            e = jnp.exp(sc - m)
            p = e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sk - m))
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = jax.lax.map(block, jnp.arange(0, s + pad, step))
    o = o.reshape((-1,) + o.shape[2:])[:s]  # [S, H, Dv]
    if variant != "value_scale_1":
        o = o * cfg.attention_value_scale
    return jnp.einsum("shd,hde->se", o, lp["wo"])


# ------------------------------------------------------------------- FFNs --

def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(cfg: Config, lp: Dict, x, variant: str = "model"):
    """(picked expert ids [S, k], their weights [S, k])."""
    s = jax.nn.sigmoid((x @ lp["router"]).astype(F32))
    by = s if variant == "no_select_bias" else s + lp["router_bias"]
    _, picked = jax.lax.top_k(by, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, picked, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return picked, w * cfg.routed_scaling_factor


def experts(cfg: Config, lp: Dict, x, share: Optional[Share] = None,
            variant: str = "model"):
    """The expert layer's output for the experts `lp` holds (all of them
    without a share)."""
    picked, w = route(cfg, lp, x, variant)
    first = share.first_expert if share else 0
    held = share.experts_held if share else cfg.n_routed_experts
    y = jnp.zeros_like(x)
    for j in range(held):  # experts as a loop
        gate = jnp.sum(jnp.where(picked == first + j, w, 0.0), axis=-1)
        y = y + gate[:, None] * swiglu(
            x, lp["moe_w_gate"][j], lp["moe_w_up"][j], lp["moe_w_down"][j])
    return y


def layer(cfg: Config, lp: Dict, h, positions, kind: str, q_block: int = 0,
          variant: str = "model", share: Optional[Share] = None):
    eps = cfg.layernorm_epsilon
    h = h + attention(cfg, lp, rms_norm(h, lp["attn_norm"], eps), positions,
                      kind, q_block, variant)
    x = rms_norm(h, lp["mlp_norm"], eps)
    if "router" in lp:
        return h + experts(cfg, lp, x, share, variant)
    return h + swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def forward(cfg: Config, params: Dict, tokens, q_block: int = 0,
            variant: str = "model", share: Optional[Share] = None):
    """tokens [S] -> logits [S, V] float32."""
    assert variant in VARIANTS, variant
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(tokens.shape[0])
        h = params["embed"][tokens]
        for i in range(cfg.num_hidden_layers):
            h = layer(cfg, layer_params(cfg, params, i), h, positions,
                      cfg.kind(i), q_block, variant, share)
        h = rms_norm(h, params["final_norm"], cfg.layernorm_epsilon)
        return h @ params["lm_head"]  # untied head
