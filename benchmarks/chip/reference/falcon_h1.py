"""Falcon-H1-34B-Instruct (model_type falcon_h1): the forward pass in plain
jax.numpy, float32, matmuls at precision "highest".

Full sequence, no cache, no kernels, no batching, no chunks: the state-space
mixer is the recurrence itself, token by token (`lax.scan` over time), so it
cannot share a mistake with the program's chunked form. It follows the
published config.json
(https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json)
and is what the program is compared with: on the CPU at a small size
(tests/test_falcon_h1.py) and on the chip at the published widths
(benchmarks/chip/compare_reference_falcon_h1.py, which keeps a copy of this
file). It shares no code with dynamo_tpu. ASSUMED marks the points the
config leaves open; DEPARTURE marks a departure from the source.

Every layer runs BOTH mixers on one normed input and sums them, then a gated
MLP (E hidden, eps rms_norm_eps, no bias but the conv's). The fourteen
published numbers scale twelve places (the five ssm_multipliers are one
vector), each applied HERE where the published description puts it:

  x0  = embed[token] * embedding_multiplier
  h   = rms_norm(x; input_layernorm)
  att = attention_out_multiplier * Attn(attention_in_multiplier * h)
  ssm = ssm_out_multiplier * Mamba2(ssm_in_multiplier * h)
  x   = x + att + ssm
  x   = x + MLP(rms_norm(x; pre_ff_layernorm))
  logits = lm_head(rms_norm(x; final_layernorm)) * lm_head_multiplier

  Attn(u): q = W_q u [H_q, D]; k = key_multiplier * W_k u [KV, D]; v = W_v u
    ASSUMED (rotary) rotate-half over ALL D lanes of q and k (lane i pairs
        with lane i + D/2), theta rope_theta, no scaling (rope_scaling null)
    softmax(q k^T / sqrt(D)) causal, H_q / KV query heads a KV head; W_o
  Mamba2(u) (H = mamba_n_heads, P = mamba_d_head, d = mamba_d_ssm = H P,
    G = mamba_n_groups, N = mamba_d_state, K = mamba_d_conv, C = d + 2 G N):
    ASSUMED (order) [z | x | B | C | dt] = (u W_in) * mup_vector, widths
        d | d | G N | G N | H; mup_vector is ssm_multipliers[0..4] spread
        over those five runs
    ASSUMED (d_ssm) the mixer's width is mamba_d_ssm (4,096), NOT
        mamba_expand * hidden (10,240); mamba_expand is carried and unused
    [x | B | C]_t <- silu(b + sum_{k<K} w_k * [x | B | C]_{t-K+1+k})
        (depthwise, causal, with bias: mamba_conv_bias)
    dt_t = softplus(dt_t + dt_bias) [H];  a = -exp(A_log) [H]
    head h, group g = h // (H / G), float32:
        S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t^g   [P, N]
        y_t = S_t C_t^g + D_h x_t
    ASSUMED (gate, norm) mamba_rms_norm true, mamba_norm_before_gate false:
        y <- w * rms_{d / G}(y * silu(z)): gate first, the mean over each
        group's lanes
    y W_out, d -> E
  MLP(u): W_down(W_up u * silu(mlp_multipliers[0] * W_gate u))
        * mlp_multipliers[1]

Weights come in the program's layout, as float32 (`dequantize`): every leaf
stacked on a leading layer axis (models/llama.py param_specs: attn_norm, wq,
wk, wv, wo, ssm_*, mlp_norm, w_gate, w_up, w_down). DEPARTURE (layout only,
ASSUMED of a checkpoint: none is loaded here).

`forward` takes `variant`: "model", or the CONTROL "bf16_state" (S rounded to
bfloat16 after every token), which must not pass for the model. The other
controls are other configurations: `dataclasses.replace(cfg, key_multiplier=
1.0)`, `attention_out_multiplier=0.0` (the attention branch left out), or any
one multiplier at 1.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
VARIANTS = ("model", "bf16_state")
# the leaves of a layer (models/llama.py param_specs), each stacked [L, ...]
LAYER_LEAVES = (
    "attn_norm", "wq", "wk", "wv", "wo", "ssm_in", "ssm_conv_w", "ssm_conv_b",
    "ssm_dt_bias", "ssm_a_log", "ssm_d", "ssm_norm", "ssm_out", "mlp_norm",
    "w_gate", "w_up", "w_down")
# the scalar multipliers, under their published names
SCALARS = ("embedding_multiplier", "lm_head_multiplier",
           "attention_in_multiplier", "attention_out_multiplier",
           "ssm_in_multiplier", "ssm_out_multiplier", "key_multiplier")


@dataclasses.dataclass(frozen=True)
class Config:
    """The published config.json's keys, under their published names."""
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_n_groups: int
    mamba_d_state: int
    mamba_d_conv: int
    rms_norm_eps: float
    rope_theta: float
    embedding_multiplier: float
    lm_head_multiplier: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    ssm_in_multiplier: float
    ssm_out_multiplier: float
    key_multiplier: float
    ssm_multipliers: Tuple[float, ...]  # z | x | B | C | dt
    mlp_multipliers: Tuple[float, ...]  # gate | down

    @staticmethod
    def from_hf(cfg: dict) -> "Config":
        if not cfg.get("mamba_rms_norm", True) or cfg.get(
                "mamba_norm_before_gate"):
            raise ValueError("this reference writes down the gate first, "
                             "then a norm by group")
        if cfg["mamba_d_ssm"] != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
            raise ValueError("mamba_d_ssm is heads x head size here")
        if cfg.get("rope_scaling") is not None:
            raise ValueError("this reference writes down a plain rotary")
        return Config(
            hidden_size=cfg["hidden_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            mamba_n_heads=cfg["mamba_n_heads"],
            mamba_d_head=cfg["mamba_d_head"],
            mamba_n_groups=cfg["mamba_n_groups"],
            mamba_d_state=cfg["mamba_d_state"],
            mamba_d_conv=cfg["mamba_d_conv"],
            rms_norm_eps=float(cfg.get("rms_norm_eps", 1e-5)),
            rope_theta=float(cfg["rope_theta"]),
            ssm_multipliers=tuple(float(m) for m in cfg["ssm_multipliers"]),
            mlp_multipliers=tuple(float(m) for m in cfg["mlp_multipliers"]),
            **{k: float(cfg[k]) for k in SCALARS})

    @property
    def mup_vector(self):
        """ssm_multipliers spread over [z | x | B | C | dt]."""
        d = self.mamba_n_heads * self.mamba_d_head
        gn = self.mamba_n_groups * self.mamba_d_state
        return jnp.concatenate([
            jnp.full((w,), m, F32) for w, m in zip(
                (d, d, gn, gn, self.mamba_n_heads), self.ssm_multipliers)])


def dequantize(params: Dict) -> Dict:
    """The program's parameter tree as float32 arrays (an int8 leaf is its
    (q, scale) pair: q * scale)."""
    def leaf(v):
        if isinstance(v, tuple) and hasattr(v, "q"):
            return v.q.astype(F32) * v.scale.astype(F32)
        return jnp.asarray(v, F32)
    return {k: leaf(v) for k, v in params.items()}


def layer_params(params: Dict, i: int) -> Dict:
    return {k: params[k][i] for k in LAYER_LEAVES}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


# ------------------------------------------------------------------ Mamba-2 --

def mamba(cfg: Config, lp: Dict, u, variant: str = "model",
          return_state: bool = False):
    """u [T, E] (normed, times ssm_in_multiplier) -> [T, E]: the recurrence,
    one token at a time. With return_state also the state S_T [H, P, N]
    after the last token."""
    h, p = cfg.mamba_n_heads, cfg.mamba_d_head
    g, n, k = cfg.mamba_n_groups, cfg.mamba_d_state, cfg.mamba_d_conv
    d = h * p
    t = u.shape[0]
    zxbcdt = (u @ lp["ssm_in"]) * cfg.mup_vector
    z, xbc, dt = (zxbcdt[:, :d], zxbcdt[:, d:2 * d + 2 * g * n],
                  zxbcdt[:, 2 * d + 2 * g * n:])
    # depthwise causal conv over time, zeros before the first token
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
    xbc = jax.nn.silu(lp["ssm_conv_b"] + sum(
        lp["ssm_conv_w"][j] * padded[j:j + t] for j in range(k)))
    x = xbc[:, :d].reshape(t, h, p)
    bm = xbc[:, d:d + g * n].reshape(t, g, n)
    cm = xbc[:, d + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"])  # [T, H]
    a = -jnp.exp(lp["ssm_a_log"])  # [H]
    bm, cm = (jnp.repeat(m, h // g, axis=1) for m in (bm, cm))  # [T, H, N]

    def token(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if variant == "bf16_state":
            # not astype(bfloat16).astype(float32): the TPU's compiler may
            # keep the excess precision of such a round trip
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)  # [H, P]

    last, y = jax.lax.scan(token, jnp.zeros((h, p, n), F32),
                           (x, dt, bm, cm))
    y = (y + lp["ssm_d"][None, :, None] * x).reshape(t, d)
    # gate first, then the RMS over each group's lanes
    v = (y * jax.nn.silu(z)).reshape(t, g, d // g)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                          + cfg.rms_norm_eps)
    out = (v.reshape(t, d) * lp["ssm_norm"]) @ lp["ssm_out"]
    return (out, last) if return_state else out


# ---------------------------------------------------------------- attention --

def _rotate(a, positions, theta):
    """Rotate-half rotary over all lanes: lane i pairs with lane i + D/2."""
    d = a.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a1, a2 = a[..., :d // 2], a[..., d // 2:]
    return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin], -1)


def attention(cfg: Config, lp: Dict, u, q_block: int = 0):
    """u [S, E] (normed, times attention_in_multiplier) -> [S, E]; a block
    of `q_block` queries at a time (0: all at once)."""
    s = u.shape[0]
    d = cfg.head_dim
    q = jnp.einsum("se,ehd->shd", u, lp["wq"])
    k = jnp.einsum("se,ekd->skd", u, lp["wk"]) * cfg.key_multiplier
    v = jnp.einsum("se,ekd->skd", u, lp["wv"])
    positions = jnp.arange(s)
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


# ---------------------------------------------------------------------- MLP --

def mlp(cfg: Config, lp: Dict, u):
    gate = jax.nn.silu(cfg.mlp_multipliers[0] * (u @ lp["w_gate"]))
    return (((u @ lp["w_up"]) * gate) @ lp["w_down"]) * cfg.mlp_multipliers[1]


# ------------------------------------------------------------------ forward --

def branches(cfg: Config, lp: Dict, x, q_block: int = 0,
             variant: str = "model", n_state: int = 0):
    """What a layer's three branches add to the stream x [S, E]:
    (attention, Mamba-2, MLP) each [S, E], and the Mamba-2 state after the
    first `n_state` tokens (None where 0)."""
    h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    att = cfg.attention_out_multiplier * attention(
        cfg, lp, cfg.attention_in_multiplier * h, q_block)
    u = cfg.ssm_in_multiplier * h
    ssm = cfg.ssm_out_multiplier * mamba(cfg, lp, u, variant)
    state = (mamba(cfg, lp, u[:n_state], variant, return_state=True)[1]
             if n_state else None)
    x = x + att + ssm
    return att, ssm, mlp(cfg, lp, rms_norm(x, lp["mlp_norm"],
                                           cfg.rms_norm_eps)), state


def layer(cfg: Config, lp: Dict, x, q_block: int = 0,
          variant: str = "model"):
    att, ssm, ff, _ = branches(cfg, lp, x, q_block, variant)
    return x + att + ssm + ff


def embed(cfg: Config, params: Dict, tokens):
    return params["embed"][tokens] * cfg.embedding_multiplier


def head(cfg: Config, params: Dict, x):
    return (rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
            @ params["lm_head"]) * cfg.lm_head_multiplier


def forward(cfg: Config, params: Dict, tokens, q_block: int = 0,
            variant: str = "model"):
    """tokens [S] -> logits [S, V] float32."""
    assert variant in VARIANTS, variant
    with jax.default_matmul_precision("highest"):
        x = embed(cfg, params, tokens)
        for i in range(cfg.num_hidden_layers):
            x = layer(cfg, layer_params(params, i), x, q_block, variant)
        return head(cfg, params, x)
