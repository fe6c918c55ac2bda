"""LFM2-8B-A1B's block (`model_type: lfm2_moe`) as a plain float32 reference.

Plain `jax.numpy`, float32, matmuls at precision "highest", no cache, no
kernels, no batching: the whole sequence at once, the short convolution as a
sum of three shifted products, attention under a full [T, T] mask, every
expert for every token and masked. It is what `tests/test_lfm2_moe*.py` and
`benchmarks/chip/compare_reference_lfm2_moe.py` hold the program to; the
benchmark keeps a byte-identical copy under `benchmarks/chip/reference/`.

The layers, as the published config and the catalog row's description give
them (hidden E, K = conv_L_cache, D = E / heads):

    x = embed[token]
    for l in layers:
      h = rms_norm(x; operator_norm_l)           w * x / sqrt(mean x^2 + eps)
      layer_types[l] == "conv":
        [B | C | u] = W_in h                     three runs of E, in this order
        g_t = B_t * u_t
        c_t = sum_k w[k] * g_{t-(K-1)+k}         depthwise, causal, g_{<0} = 0,
                                                 no bias, no activation
        y_t = W_out (C_t * c_t)
      layer_types[l] == "full_attention":
        q, k, v = W_q h, W_k h, W_v h            [H x D], [KV x D], [KV x D]
        q, k = rms_norm a head (q_norm, k_norm [D]), then the rotary
        (rotate-half, all D lanes, theta), s = q . k / sqrt(D), causal
        softmax, head h reads KV head h // (H / KV);  y = W_o o
      x = x + y
      h2 = rms_norm(x; ffn_norm_l)
      l < num_dense_layers:  x = x + W_2 (silu(W_1 h2) * W_3 h2)
      else: s = sigmoid(W_r h2) in float32; pick the k largest of
            s + expert_bias; w = s[picked] / (sum s[picked] + 1e-6);
            w = w * routed_scaling_factor;
            x = x + sum_e w_e W_2e (silu(W_1e h2) * W_3e h2)
    logits = embed^T rms_norm(x; embedding_norm)     (head tied)

ASSUMED (none of it is in the catalog row, and no modelling file is on this
machine; each is the family's published convention as ISSUE 52's author
knows it, and `benchmarks/chip/configs/lfm2-8b-a1b-w8a8-1chip.json` lists
them under `assumed`):
 a. the order [B | C | u] of in_proj's three runs;
 b. the conv's state is the last K-1 rows of B * u (the published cache keeps
    K columns, of which a step reads K-1);
 c. per-head q/k RMS norms over D lanes with weights of their own, BEFORE the
    rotary (the row has no key for them);
 d. the final norm's published name `embedding_norm` (`final_norm` here);
 e. `tie_word_embeddings: true`;
 f. rotate-half over all D lanes, no scaling;
 g. the router in float32, sigmoid scores, the pick under `expert_bias`
    (float32), the weights from the scores WITHOUT the bias;
 h. the renormalisation's epsilon: 1e-6 HERE, as published; the program's
    `ops/moe.route_topk` adds 1e-20. With a sum of k sigmoids >= 0.1 the
    quotients differ by under 1e-5 relative: no tolerance below can tell
    them apart, and none is asked to;
 i. the experts' matrices w1 / w3 / w2 = gate / up / down.

DEPARTURES (layout only): parameters come in the program's tree
(`models/llama._operator_param_specs`): a stack an operator kind (`conv_in`
[L_c, E, 3E], `conv_w` [L_c, K, E], `conv_out`; `wq` [L_a, E, H, D], `wk`,
`wv`, `wo` [L_a, H, D, E], `q_norm` / `k_norm` [L_a, D]), the dense FFNs
under `dense.` [num_dense_layers, ...], `router` [L_e, E, X], `router_bias`
[L_e, X] (the published expert_bias), `moe_w_gate` / `moe_w_up` [L_e, X_held,
E, F' >= F], `moe_w_down` [L_e, X_held, F', E] (lanes past the model's F are
zero in the program's tree, silu(0) * 0 = 0: they add nothing, and this file
multiplies them like any other), `operator_norm` / `ffn_norm` [L, E]. `experts`
takes the HELD share [first, first + count) of the router's experts: it
routes over all of them and adds the held ones' part only, so that the
shares of a deployment sum to the whole layer.

`forward` takes `variant`: "model", or a CONTROL that must not pass for the
model: "no_oldest_tap" (the conv's oldest tap dropped: K - 1 taps),
"no_b_gate" (g = u), "no_c_gate" (y = W_out c), "swap_bc" (B and C change
places), "no_qk_norm", "no_rope", "no_select_bias" (the pick without the
bias), and `zero_state_every` > 0: g before every multiple of that many rows
reads as zero for the rows behind it (a state zeroed at every chunk boundary).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
CONV, ATTENTION = "conv", "full_attention"
DENSE = "dense."
# the leaves of each kind's stack (models/llama._operator_param_specs)
STACKS = {
    CONV: ("conv_in", "conv_w", "conv_out"),
    ATTENTION: ("wq", "wk", "wv", "wo", "q_norm", "k_norm"),
}
DENSE_FFN = ("w_gate", "w_up", "w_down")
EXPERT_FFN = ("router", "router_bias", "moe_w_gate", "moe_w_up",
              "moe_w_down")
VARIANTS = ("model", "no_oldest_tap", "no_b_gate", "no_c_gate", "swap_bc",
            "no_qk_norm", "no_rope", "no_select_bias")
ROUTE_EPS = 1e-6  # ASSUMED (h)


@dataclasses.dataclass(frozen=True)
class Config:
    """The published config.json's keys, under their published names."""
    hidden_size: int
    layer_types: Tuple[str, ...]
    conv_L_cache: int
    num_attention_heads: int
    num_key_value_heads: int
    num_dense_layers: int
    num_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    norm_eps: float
    rope_theta: float

    @staticmethod
    def from_hf(cfg: dict) -> "Config":
        if cfg.get("conv_bias"):
            raise ValueError("this reference writes down no conv bias")
        if not cfg.get("use_expert_bias", False):
            raise ValueError("this reference picks under the expert bias")
        kinds = tuple(cfg["layer_types"])
        if set(kinds) - {CONV, ATTENTION} or len(kinds) != cfg[
                "num_hidden_layers"]:
            raise ValueError(f"layer_types {kinds!r}")
        return Config(
            hidden_size=cfg["hidden_size"], layer_types=kinds,
            conv_L_cache=cfg["conv_L_cache"],
            num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"],
            num_dense_layers=int(cfg.get("num_dense_layers") or 0),
            num_experts=cfg["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            norm_eps=float(cfg.get("norm_eps") or 1e-5),
            rope_theta=float(cfg.get("rope_theta", 1000000.0)))


def dequantize(params: Dict) -> Dict:
    """The program's parameter tree as float32 arrays (an int8 leaf is its
    (q, scale) pair: q * scale)."""
    def leaf(v):
        if isinstance(v, tuple) and hasattr(v, "q"):
            return v.q.astype(F32) * v.scale.astype(F32)
        return jnp.asarray(v, F32)
    return {k: leaf(v) for k, v in params.items()}


def layer_params(cfg: Config, params: Dict, i: int) -> Dict:
    """Layer i's leaves: its operator kind's stack at the layer's index
    among the layers of its kind, its FFN kind's at the layer's index among
    those, and its two norms."""
    kind = cfg.layer_types[i]
    j = cfg.layer_types[:i].count(kind)
    lp = {k: params[k][j] for k in STACKS[kind]}
    if i < cfg.num_dense_layers:
        lp.update({k: params[DENSE + k][i] for k in DENSE_FFN})
    else:
        lp.update({k: params[k][i - cfg.num_dense_layers]
                   for k in EXPERT_FFN})
    lp["operator_norm"] = params["operator_norm"][i]
    lp["ffn_norm"] = params["ffn_norm"][i]
    return lp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


# --------------------------------------------------- gated short convolution --

def short_conv(cfg: Config, lp: Dict, h, variant: str = "model",
               zero_state_every: int = 0):
    """h [T, E] (normed) -> [T, E]: C * conv_K(B * u) between W_in and
    W_out, the conv as a plain sum of K shifted products."""
    t, e = h.shape
    k = cfg.conv_L_cache
    bcu = h @ lp["conv_in"]
    b, c, u = bcu[:, :e], bcu[:, e:2 * e], bcu[:, 2 * e:]  # ASSUMED (a)
    if variant == "swap_bc":
        b, c = c, b
    g = u if variant == "no_b_gate" else b * u
    rows = jnp.arange(t)
    acc = jnp.zeros_like(g)
    for j in range(k):
        if variant == "no_oldest_tap" and j == 0:
            continue
        back = k - 1 - j  # tap j reads g_{t - back}
        shifted = jnp.pad(g, ((back, 0), (0, 0)))[:t]
        if zero_state_every:  # nothing crosses a chunk boundary
            shifted = jnp.where(
                ((rows % zero_state_every) >= back)[:, None], shifted, 0.0)
        acc = acc + lp["conv_w"][j] * shifted
    return (acc if variant == "no_c_gate" else c * acc) @ lp["conv_out"]


# ---------------------------------------------------------------- attention --

def _rotate(a, positions, theta):
    """Half-split (rotate-half) rotary over all lanes: ASSUMED (f)."""
    d = a.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a1, a2 = a[..., :d // 2], a[..., d // 2:]
    return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin], -1)


def attention(cfg: Config, lp: Dict, h, q_block: int = 0,
              variant: str = "model"):
    """h [S, E] (normed) -> [S, E]; a block of `q_block` queries at a time
    (0: all at once), every block under its rows of the full [S, S] mask."""
    s = h.shape[0]
    d = lp["wq"].shape[-1]  # E / heads as published; the weights' own
    q = jnp.einsum("se,ehd->shd", h, lp["wq"])
    k = jnp.einsum("se,ekd->skd", h, lp["wk"])
    v = jnp.einsum("se,ekd->skd", h, lp["wv"])
    positions = jnp.arange(s)
    if variant != "no_qk_norm":  # ASSUMED (c): a head, before the rotary
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    if variant != "no_rope":
        q = _rotate(q, positions, cfg.rope_theta)
        k = _rotate(k, positions, cfg.rope_theta)
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    step = q_block or s
    pad = -s % step
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    pos_p = jnp.pad(positions, (0, pad), constant_values=s - 1)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, step)
        qi = jax.lax.dynamic_slice_in_dim(pos_p, start, step)[:, None]
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        p = jax.nn.softmax(
            jnp.where((positions[None, :] <= qi)[None], sc, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = jax.lax.map(block, jnp.arange(0, s + pad, step))
    o = o.reshape((-1,) + o.shape[2:])[:s]  # [S, H, D]
    return jnp.einsum("shd,hde->se", o, lp["wo"])


# --------------------------------------------------------------------- FFNs --

def gated_mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(cfg: Config, lp: Dict, x, variant: str = "model"):
    """(picked expert ids [T, k], their weights [T, k]): ASSUMED (g), (h)."""
    s = jax.nn.sigmoid((x @ lp["router"]).astype(F32))
    biased = s if variant == "no_select_bias" else s + lp["router_bias"]
    _, picked = jax.lax.top_k(biased, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, picked, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_EPS)
    return picked, w * cfg.routed_scaling_factor


def experts(cfg: Config, lp: Dict, x, first: int = 0, count: int = -1,
            variant: str = "model"):
    """The expert layer's output over experts [first, first + count) of the
    router's (`lp["moe_w_*"][j]` is expert first + j; all of them by
    default): routed over ALL the router's experts, the held ones' part
    alone added, so the shares of a deployment sum to the layer."""
    picked, w = route(cfg, lp, x, variant)
    if count < 0:
        count = cfg.num_experts
    y = jnp.zeros_like(x)
    for j in range(count):  # every held expert for every token, masked
        gate = jnp.sum(jnp.where(picked == first + j, w, 0.0), axis=-1)
        y = y + gate[:, None] * gated_mlp(
            x, lp["moe_w_gate"][j], lp["moe_w_up"][j], lp["moe_w_down"][j])
    return y


# ------------------------------------------------------------------ forward --

def operator(cfg: Config, lp: Dict, h, kind: str, q_block: int = 0,
             variant: str = "model", zero_state_every: int = 0):
    if kind == CONV:
        return short_conv(cfg, lp, h, variant, zero_state_every)
    return attention(cfg, lp, h, q_block, variant)


def forward(cfg: Config, params: Dict, tokens, q_block: int = 0,
            variant: str = "model", first: int = 0, count: int = -1,
            zero_state_every: int = 0):
    """tokens [S] -> logits [S, V] float32. `first` / `count`: the held
    share of every expert layer (all by default)."""
    assert variant in VARIANTS, variant
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for i, kind in enumerate(cfg.layer_types):
            lp = layer_params(cfg, params, i)
            x = x + operator(cfg, lp, rms_norm(x, lp["operator_norm"],
                                               cfg.norm_eps),
                             kind, q_block, variant, zero_state_every)
            h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
            if i < cfg.num_dense_layers:
                x = x + gated_mlp(h, lp["w_gate"], lp["w_up"], lp["w_down"])
            else:
                x = x + experts(cfg, lp, h, first, count, variant)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x @ params["embed"].T  # ASSUMED (e): the head is tied
