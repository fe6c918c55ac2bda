"""NVIDIA-Nemotron-3-Nano-30B-A3B (model_type nemotron_h): the forward pass
in plain jax.numpy, float32, matmuls at precision "highest".

Full sequence, no cache, no kernels, no batching, no chunks: the state-space
layer is the recurrence itself, token by token (`lax.scan` over time), so it
cannot share a mistake with the program's chunked form; every expert is
computed for every token and masked. It follows the published config.json
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json)
and is what the program is compared with: on the CPU at a small size
(tests/test_nemotron_h.py) and on the chip at the published widths
(benchmarks/chip/compare_reference_nemotron_h.py, which keeps a copy of this
file). It shares no code with dynamo_tpu. ASSUMED marks the points the
config leaves open; DEPARTURE marks a departure from the source.

Every layer is x <- x + mixer(RMSNorm(x)) with ONE mixer, its kind the
layer's letter in hybrid_override_pattern (E hidden, eps norm_eps, no bias
but the conv's):

  M, Mamba-2 (H = mamba_num_heads, P = mamba_head_dim, d_in = H P,
  G = n_groups, N = ssm_state_size, K = conv_kernel, C = d_in + 2 G N):
    ASSUMED (c) the order of W_in's output: [z | x B C | dt] = u W_in, widths
        d_in | C | H (Mamba-2's published form; transformers' mamba2 / bamba
        torch_forward agree, tests/test_nemotron_h.py)
    ASSUMED (d) d_in is heads x head size; `expand` is carried and unused
    xBC_t <- silu(b + sum_{k<K} w_k * xBC_{t-K+1+k})  (depthwise, causal),
        split [x: H x P | B: G x N | C: G x N]
    dt_t = softplus(dt_t + dt_bias) [H];  a = -exp(A_log) [H]
        ASSUMED (f) time_step_limit clamps nothing
    head h, group g = h // (H / G), float32:
        S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t^g   [P, N]
        y_t = S_t C_t^g + D_h x_t
        ASSUMED (e) the state is float32 (the family's serving recipe asks
        for a float32 state cache)
    ASSUMED (c) gate first, then a norm by group:
        y <- w * rms_{d_in / G}(y * silu(z)), the mean over each group's lanes
        (transformers' Zamba2RMSNormGated / MambaRMSNormGated agree)
    y W_out, d_in -> E
  E, experts (X = n_routed_experts, k = num_experts_per_tok):
    ASSUMED (b) s = sigmoid(u W_r) in float32; the k largest of s + b (a
        selection bias; n_group = topk_group = 1: no groups);
        w = routed_scaling_factor * s_sel / (sum s_sel + 1e-20)
        (DeepSeek-V3's noaux_tc, the family whose key names these are)
    expert e is (relu(u W_up^e))^2 W_down^e: two matrices, NO gate
        (mlp_hidden_act relu2); a shared expert of the same form at width
        moe_shared_expert_intermediate_size;  sum w_e expert_e(u) + shared(u)
  *, attention: q [T, H_q, D], k, v [T, KV, D] (H_q / KV query heads a KV
    head), softmax(q k^T / sqrt(D)) causal, o W_o
    ASSUMED (a) NO rotary: the family's published modelling code applies
        none (position reaches the layer through the state-space layers);
        rope_theta and partial_rotary_factor are carried and unused
  final RMSNorm, untied head, embeddings unscaled.

Weights come in the program's layout, as float32 (`dequantize`): one stack
a layer kind on a leading axis (models/llama.py param_specs), the norm
before every layer's mixer in `mixer_norm` [L, E]. DEPARTURE (layout only,
ASSUMED of a checkpoint: none is loaded here).

`experts` takes (first, count): the sum over those experts alone, so that
the comparison on the chip takes a layer's 128 a few at a time (their
float32 copies do not fit at once).

`forward` takes `variant`: "model", or a CONTROL that must not pass for the
model: "bf16_state" (S rounded to bfloat16 after every token),
"norm_before_gate" (w * rms(y) * silu(z)), "one_norm" (one RMS over all
d_in lanes), "swiglu" (the expert as silu(u W_up) * (u W_up), a gated
form), "rope" (a rotary on the attention layers' q and k).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
MAMBA, MOE, ATTENTION = "mamba", "moe", "attention"
LETTERS = {"M": MAMBA, "E": MOE, "*": ATTENTION}
# the leaves of each kind's stack (models/llama.py param_specs)
STACKS = {
    MAMBA: ("ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias", "ssm_a_log",
            "ssm_d", "ssm_norm", "ssm_out"),
    MOE: ("router", "router_bias", "moe_w_up", "moe_w_down", "w_up",
          "w_down"),
    ATTENTION: ("wq", "wk", "wv", "wo"),
}
VARIANTS = ("model", "bf16_state", "norm_before_gate", "one_norm", "swiglu",
            "rope")


@dataclasses.dataclass(frozen=True)
class Config:
    """The published config.json's keys, under their published names."""
    hidden_size: int
    hybrid_override_pattern: str
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    norm_eps: float
    rope_theta: float = 10000.0  # read by the "rope" control only

    @staticmethod
    def from_hf(cfg: dict) -> "Config":
        if cfg.get("mlp_hidden_act") != "relu2":
            raise ValueError("this reference writes down relu2 experts")
        if int(cfg.get("n_group") or 1) != 1:
            raise ValueError("this reference writes down no router groups")
        pattern = cfg["hybrid_override_pattern"]
        if set(pattern) - set(LETTERS) or len(pattern) != cfg[
                "num_hidden_layers"]:
            raise ValueError(f"pattern {pattern!r}")
        return Config(
            hidden_size=cfg["hidden_size"], hybrid_override_pattern=pattern,
            mamba_num_heads=cfg["mamba_num_heads"],
            mamba_head_dim=cfg["mamba_head_dim"], n_groups=cfg["n_groups"],
            ssm_state_size=cfg["ssm_state_size"],
            conv_kernel=cfg["conv_kernel"],
            num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            n_routed_experts=cfg["n_routed_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            norm_eps=float(cfg.get("norm_eps")
                           or cfg.get("layer_norm_epsilon") or 1e-5),
            rope_theta=float(cfg.get("rope_theta", 10000.0)))

    @property
    def kinds(self) -> Tuple[str, ...]:
        return tuple(LETTERS[c] for c in self.hybrid_override_pattern)


def dequantize(params: Dict) -> Dict:
    """The program's parameter tree as float32 arrays (an int8 leaf is its
    (q, scale) pair: q * scale)."""
    def leaf(v):
        if isinstance(v, tuple) and hasattr(v, "q"):
            return v.q.astype(F32) * v.scale.astype(F32)
        return jnp.asarray(v, F32)
    return {k: leaf(v) for k, v in params.items()}


def layer_params(cfg: Config, params: Dict, i: int) -> Dict:
    """Layer i's leaves: its kind's stack at the layer's index among the
    layers of its kind, and its norm."""
    kind = cfg.kinds[i]
    j = cfg.kinds[:i].count(kind)
    lp = {k: params[k][j] for k in STACKS[kind] if k in params}
    lp["mixer_norm"] = params["mixer_norm"][i]
    return lp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


# ------------------------------------------------------------------ Mamba-2 --

def mamba(cfg: Config, lp: Dict, u, variant: str = "model",
          return_state: bool = False):
    """u [T, E] (normed) -> [T, E]: the recurrence, one token at a time.
    With return_state also the state S_T [H, P, N] after the last token."""
    h, p = cfg.mamba_num_heads, cfg.mamba_head_dim
    g, n, k = cfg.n_groups, cfg.ssm_state_size, cfg.conv_kernel
    d_in = h * p
    t = u.shape[0]
    zxbcdt = u @ lp["ssm_in"]
    z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:2 * d_in + 2 * g * n],
                  zxbcdt[:, 2 * d_in + 2 * g * n:])
    # depthwise causal conv over time, zeros before the first token
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
    xbc = jax.nn.silu(lp["ssm_conv_b"] + sum(
        lp["ssm_conv_w"][j] * padded[j:j + t] for j in range(k)))
    x = xbc[:, :d_in].reshape(t, h, p)
    bm = xbc[:, d_in:d_in + g * n].reshape(t, g, n)
    cm = xbc[:, d_in + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"])  # [T, H]
    a = -jnp.exp(lp["ssm_a_log"])  # [H]
    bm, cm = (jnp.repeat(m, h // g, axis=1) for m in (bm, cm))  # [T, H, N]

    def token(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if variant == "bf16_state":
            # not astype(bfloat16).astype(float32): the TPU's compiler may
            # keep the excess precision of such a round trip (it did, on a
            # v5e: the control read exactly the model); this one it keeps
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)  # [H, P]

    last, y = jax.lax.scan(token, jnp.zeros((h, p, n), F32),
                           (x, dt, bm, cm))
    y = (y + lp["ssm_d"][None, :, None] * x).reshape(t, d_in)

    out = gate_norm(cfg, y, z, lp["ssm_norm"], variant) @ lp["ssm_out"]
    return (out, last) if return_state else out


def gate_norm(cfg: Config, y, z, w, variant: str = "model"):
    """y, z [T, d_in] -> w * rms_by_group(y * silu(z)): gate first, then
    the RMS over each of n_groups runs of lanes."""
    t, d_in = y.shape

    def by_group(v, groups):
        vg = v.reshape(t, groups, d_in // groups)
        return (vg * jax.lax.rsqrt(
            jnp.mean(vg * vg, axis=-1, keepdims=True) + cfg.norm_eps)
                ).reshape(t, d_in)

    if variant == "norm_before_gate":
        return by_group(y, cfg.n_groups) * w * jax.nn.silu(z)
    return by_group(y * jax.nn.silu(z),
                    1 if variant == "one_norm" else cfg.n_groups) * w


# ------------------------------------------------------------------ experts --

def relu2_mlp(x, w_up, w_down, variant: str = "model"):
    # DEPARTURE (layout only): the program may store an expert's matrices
    # with zero rows and lanes around the model's own (W_up [E' >= E, F' >=
    # F], W_down [F', E']); the model's are the leading E rows / E lanes,
    # and a zero lane of F' adds nothing
    e = x.shape[-1]
    w_up, w_down = w_up[:e], w_down[:, :e]
    u = x @ w_up
    act = jax.nn.silu(u) * u if variant == "swiglu" else jnp.square(
        jax.nn.relu(u))
    return act @ w_down


def route(cfg: Config, lp: Dict, x):
    """(picked expert ids [T, k], their weights [T, k])."""
    s = jax.nn.sigmoid((x @ lp["router"]).astype(F32))
    _, picked = jax.lax.top_k(s + lp["router_bias"],
                              cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, picked, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return picked, w * cfg.routed_scaling_factor


def experts(cfg: Config, lp: Dict, x, first: int = 0, count: int = -1,
            with_shared: bool = True, variant: str = "model"):
    """The expert layer's output over experts [first, first + count) of
    lp's stack (`lp["moe_w_*"][j]` is expert first + j; all of them by
    default), plus the shared expert unless with_shared is False (a sum
    over slices counts it once)."""
    picked, w = route(cfg, lp, x)
    if count < 0:
        count = cfg.n_routed_experts
    y = jnp.zeros_like(x)
    for j in range(count):  # every expert for every token, masked
        gate = jnp.sum(jnp.where(picked == first + j, w, 0.0), axis=-1)
        y = y + gate[:, None] * relu2_mlp(
            x, lp["moe_w_up"][j], lp["moe_w_down"][j], variant)
    if with_shared:
        y = y + relu2_mlp(x, lp["w_up"], lp["w_down"], variant)
    return y


# ---------------------------------------------------------------- attention --

def _rotate(a, positions, theta):
    """The "rope" control: half-split rotary over all lanes."""
    d = a.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a1, a2 = a[..., :d // 2], a[..., d // 2:]
    return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin], -1)


def attention(cfg: Config, lp: Dict, h, q_block: int = 0,
              variant: str = "model"):
    """h [S, E] (normed) -> [S, E]; a block of `q_block` queries at a time
    (0: all at once)."""
    s = h.shape[0]
    d = cfg.head_dim
    q = jnp.einsum("se,ehd->shd", h, lp["wq"])
    k = jnp.einsum("se,ekd->skd", h, lp["wk"])
    v = jnp.einsum("se,ekd->skd", h, lp["wv"])
    positions = jnp.arange(s)
    if variant == "rope":
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


# ------------------------------------------------------------------ forward --

def mixer(cfg: Config, lp: Dict, u, kind: str, q_block: int = 0,
          variant: str = "model"):
    if kind == MAMBA:
        return mamba(cfg, lp, u, variant)
    if kind == MOE:
        return experts(cfg, lp, u, variant=variant)
    return attention(cfg, lp, u, q_block, variant)


def forward(cfg: Config, params: Dict, tokens, q_block: int = 0,
            variant: str = "model"):
    """tokens [S] -> logits [S, V] float32."""
    assert variant in VARIANTS, variant
    with jax.default_matmul_precision("highest"):
        h = params["embed"][tokens]  # unscaled
        for i, kind in enumerate(cfg.kinds):
            lp = layer_params(cfg, params, i)
            h = h + mixer(cfg, lp, rms_norm(h, lp["mixer_norm"],
                                            cfg.norm_eps),
                          kind, q_block, variant)
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        return h @ params["lm_head"]  # untied head
