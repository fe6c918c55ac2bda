"""DeepSeek-V3.2-Exp (model_type deepseek_v32): the forward pass in plain
jax.numpy, float32, matmuls at precision "highest".

Full sequence, no cache, no kernels, no batching; MLA in its expanded
(non-absorbed) form, experts as a loop, the sparse selection as a mask over
the full causal scores. It follows the published model
(https://huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp: config.json and
inference/model.py) and is what the program is compared with: on the CPU at
a small size (tests/test_deepseek_v32.py) and on the chip at the published
widths (benchmarks/chip/compare_reference_deepseek_v32.py, which keeps a
copy of this file). Each departure from the published code is marked
DEPARTURE at its line. It stands alone (it shares no code with
reference/kimi_k2.py, whose block it extends by two mechanisms).

The layer, as equations (pre-norm residual block, RMSNorm eps 1e-6,
x = norm(h)):

    h <- h + Attn(norm(h));  h <- h + FFN(norm(h))

    MLA   c_q = RMSNorm(x W_qa);  [q_nope | q_rope]_h = c_q W_qb
          [c | k_r] = x W_kva;  c_kv = RMSNorm(c);  k_rope = RoPE(k_r)
          k_nope_h = c_kv W_UK_h;  v_h = c_kv W_UV_h
          score_h = (q_nope_h . k_nope_h + RoPE(q_rope_h) . k_rope)
                    * (nope + rope)^-1/2 * m^2,
          m = 0.1 * mscale_all_dim * ln(factor) + 1
    Indexer (a layer's own; query token t, key token s <= t)
          q^I_{t,j} = (c_q,t W^I_qb)_j, j = 1..index_n_heads (from the SAME
                      normalised q-LoRA latent c_q)
          k^I_s = LayerNorm(x_s W^I_k)  (weight and bias, eps 1e-6)
          RoPE on the FIRST qk_rope_head_dim lanes of every q^I_{t,j} and
          of k^I_s, the other lanes untouched
          w_{t,j} = (x_t W^I_w)_j * index_n_heads^-1/2 * index_head_dim^-1/2
          I_{t,s} = sum_j w_{t,j} * ReLU(q^I_{t,j} . k^I_s)
          S_t = the min(index_topk, t + 1) positions s <= t of largest
                I_{t,s}; ties to the lower position (jax.lax.top_k)
    Sparse attention: head h's softmax at query t runs over s in S_t only
          (every other score -inf); out = concat_h(sum p v_h) W_o.
          Where t + 1 <= index_topk this is causal attention.
    FFN   layer < first_k_dense_replace: one SwiGLU of the dense width
          else  s = sigmoid(x W_r) in float32;  c = s + b
                (b = e_score_correction_bias: selection only)
                the experts in n_group groups of consecutive ids; a group
                scores the sum of its 2 largest c; the topk_group best
                groups are kept; the k largest c inside them are picked
                w_i = s_i / (sum_sel s + 1e-20) * routed_scaling_factor
                y = sum_sel w_i E_i(x) + E_shared(x)
          E(x) = W_down(silu(W_gate x) * W_up x)

DEPARTURE: the published code turns q^I and k^I by a Hadamard matrix and
rounds both to FP8 before their product. The rotation is orthogonal, so in
real arithmetic it changes no product; the rounding is a storage format of
the published kernels. Neither is written down here (nor in the program: a
v5e has no FP8; its indexer keeps bfloat16).
Left out: the multi-token-prediction module (num_nextn_predict_layers):
layer 62 of a 61-layer model, never part of this forward.

A SHARE of the model (one chip of an expert-parallel deployment, see the
model-configs guide, section 4) is the same forward with the sum over the
selected experts restricted to those held: the router keeps its whole width,
its groups and its k; what the absent experts would have added is left out,
and that partial result goes on to the next layer. The vocabulary's slice is
a smaller vocabulary: embedding rows and head columns of the slice only.

Weights come in the program's layout, as float32 (`dequantize`): stacked on
a leading layer axis, the leading dense layers under the "dense." prefix
(models/llama.py param_specs), the indexer's under "idx_". The lanes of a
rotary half are in the half-split order (lane i turns with lane i + d/2),
in the attention and in the indexer alike. DEPARTURE (layout only): the
published code takes interleaved pairs and de-interleaves at run time.

`forward` takes `select`: "indexer" (the model), or a CONTROL that must not
pass for the model: "recency" (the last index_topk positions), "no_relu"
(the indexer without its ReLU), "no_weights" (every w_{t,j} = 1). It can
also be handed the sets to use (`given`: per layer a bool mask [S, S]), so
that the attention arithmetic is compared apart from a flipped pick.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
DENSE_PREFIX = "dense."
INDEX_LN_EPS = 1e-6


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
    n_group: int
    topk_group: int
    first_k_dense_replace: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    rms_norm_eps: float
    rope_theta: float
    rope_scaling: Optional[dict]
    index_n_heads: int
    index_head_dim: int
    index_topk: int

    @staticmethod
    def from_hf(cfg: dict) -> "Config":
        if cfg.get("scoring_func") != "sigmoid":
            raise ValueError("this reference is the sigmoid-scored router")
        if (cfg.get("num_nextn_predict_layers") or 0) > 0:
            raise ValueError("the multi-token-prediction module is not "
                             "written down here")
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


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


# ------------------------------------------------------------------- RoPE --

def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1.0 else 0.1 * mscale * math.log(scale) + 1.0


def rope_inv_freq(cfg: Config):
    """(inverse frequencies [d/2], magnitude on cos/sin) of the YaRN
    rotary, as the published precompute_freqs_cis computes them."""
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
        high += 0.001  # the published ramp divides by (high - low)
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


# ---------------------------------------------------------- the selection --

def index_scores(cfg: Config, lp: Dict, x, c_q, positions, rows,
                 select: str = "indexer"):
    """I[t, s] for the queries t in `rows` against every key s: [len(rows),
    S], float32, -inf where s > t."""
    r = cfg.qk_rope_head_dim
    inv, mag = rope_inv_freq(cfg)

    def turn(v, pos):  # the rotary on the first r lanes
        return jnp.concatenate(
            [rope(v[..., :r], pos, inv, mag), v[..., r:]], axis=-1)

    q = turn(jnp.einsum("sr,rhd->shd", c_q[rows], lp["idx_wq_b"]),
             positions[rows])                               # [q, Hi, Di]
    k = turn(layer_norm(x @ lp["idx_wk"], lp["idx_k_norm"],
                        lp["idx_k_bias"], INDEX_LN_EPS), positions)  # [S, Di]
    w = (x[rows] @ lp["idx_w"]) * (cfg.index_n_heads ** -0.5
                                   * cfg.index_head_dim ** -0.5)
    dots = jnp.einsum("qhd,sd->qhs", q, k)
    if select != "no_relu":
        dots = jax.nn.relu(dots)
    if select == "no_weights":
        w = jnp.ones_like(w)
    scores = jnp.sum(dots * w[:, :, None], axis=1)
    causal = positions[None, :] <= positions[rows, None]
    return jnp.where(causal, scores, -jnp.inf)


def selected(cfg: Config, scores):
    """scores [q, S] (-inf where unseen) -> (bool mask [q, S] of S_t, the
    float32 score of the last position taken: the threshold)."""
    s = scores.shape[-1]
    vals, sel = jax.lax.top_k(scores, min(cfg.index_topk, s))
    valid = vals > -jnp.inf
    rows = jnp.arange(scores.shape[0])[:, None]
    mask = jnp.zeros(scores.shape, bool).at[rows, sel].set(valid)
    threshold = jnp.min(jnp.where(valid, vals, jnp.inf), axis=-1)
    return mask, threshold


def recency(cfg: Config, positions, rows):
    """CONTROL: the last index_topk positions at or before each query."""
    d = positions[rows, None] - positions[None, :]
    return (d >= 0) & (d < cfg.index_topk)


# -------------------------------------------------------------- the block --

def attention(cfg: Config, lp: Dict, x, positions, q_block: int = 0,
              select: str = "indexer", given=None):
    """Expanded-form MLA over the whole sequence under the selection.
    q_block > 0 computes a block of queries at a time, one block after
    another (jax.lax.map: the same numbers; so that 30k positions of 128
    heads fit a device's memory). Returns (out
    [S, E], sets [S, S] bool: S_t as a mask, threshold [S])."""
    nope, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    lora, eps = cfg.kv_lora_rank, cfg.rms_norm_eps
    inv, mag = rope_inv_freq(cfg)
    c_q = rms_norm(x @ lp["wq_a"], lp["q_a_norm"], eps)
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
    step = q_block or s

    def block(start):
        # a block of queries (the last one padded with the final row)
        rows = jnp.minimum(start + jnp.arange(step), s - 1)
        if given is not None:
            mask, thr = given[rows], jnp.zeros((step,), F32)
        elif select == "recency":
            mask, thr = recency(cfg, positions, rows), jnp.zeros((step,), F32)
        else:
            mask, thr = selected(cfg, index_scores(
                cfg, lp, x, c_q, positions, rows, select))
        q = jnp.einsum("sr,rhd->shd", c_q[rows], lp["wq_b"])  # [q, H, nope+r]
        q_nope = q[..., :nope]
        q_rope = rope(q[..., nope:], positions[rows], inv, mag)
        sc = (jnp.einsum("qhn,khn->hqk", q_nope, k_nope)
              + jnp.einsum("qhr,kr->hqk", q_rope, k_rope)) * scale
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khv->qhv", p, v), mask, thr

    o, sets, thr = jax.lax.map(block, jnp.arange(0, s, step))
    o = o.reshape((-1,) + o.shape[2:])[:s]                  # [S, H, vd]
    return (jnp.einsum("shv,hve->se", o, lp["wo"]),
            sets.reshape(-1, s)[:s], thr.reshape(-1)[:s])


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(cfg: Config, lp: Dict, x):
    """(picked expert ids [S, k], their weights [S, k]) over the router's
    whole width, the pick limited to the kept groups."""
    s = jax.nn.sigmoid((x @ lp["router"]).astype(F32))
    c = s + lp["router_bias"]
    if cfg.n_group > 1:
        per = c.reshape(c.shape[0], cfg.n_group, -1)
        group = jnp.sum(jax.lax.top_k(per, 2)[0], axis=-1)  # [S, n_group]
        _, kept = jax.lax.top_k(group, cfg.topk_group)
        keep = jnp.zeros(group.shape, bool).at[
            jnp.arange(c.shape[0])[:, None], kept].set(True)
        # DEPARTURE: the published code fills the other groups with 0.0,
        # which a negative c inside a kept group would lose to; -inf is
        # "the k largest c inside the kept groups"
        c = jnp.where(jnp.repeat(keep, per.shape[-1], axis=1), c, -jnp.inf)
    _, picked = jax.lax.top_k(c, cfg.num_experts_per_tok)
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
          q_block: int = 0, select: str = "indexer", given=None):
    """-> (h, sets [S, S] bool, thresholds [S])."""
    eps = cfg.rms_norm_eps
    a, sets, thr = attention(cfg, lp, rms_norm(h, lp["attn_norm"], eps),
                             positions, q_block, select, given)
    h = h + a
    x = rms_norm(h, lp["mlp_norm"], eps)
    if "router" in lp:
        return h + experts(cfg, lp, x, share), sets, thr
    return h + swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), sets, thr


def forward(cfg: Config, params: Dict, tokens, share: Optional[Share] = None,
            q_block: int = 0, select: str = "indexer",
            given: Optional[List] = None):
    """tokens [S] (ids within the vocabulary slice `params` holds) ->
    (logits [S, V held] float32, per layer the selected sets as a bool
    mask [S, S], per layer the thresholds [S])."""
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(tokens.shape[0])
        h = params["embed"][tokens]
        sets, thresholds = [], []
        for i in range(cfg.num_hidden_layers):
            h, m, thr = layer(cfg, layer_params(cfg, params, i), h, positions,
                              share, q_block, select,
                              None if given is None else given[i])
            sets.append(m)
            thresholds.append(thr)
        h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        return h @ params["lm_head"], sets, thresholds  # untied head
