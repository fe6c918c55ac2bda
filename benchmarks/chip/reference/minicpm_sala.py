"""MiniCPM-SALA (`model_type: minicpm_sala`) as a plain float32 reference.

Plain `jax.numpy`, float32, matmuls at precision "highest", no cache, no
kernels, no batching: the whole sequence at once, the Lightning layers in
their one-token form over every token, the sparse layers' attention and
selection query block by query block (so that 16 k tokens fit). It is what
`tests/test_minicpm_sala*.py` and `benchmarks/chip/
compare_reference_minicpm_sala.py` hold the program to; the benchmark keeps
a byte-identical copy under `benchmarks/chip/reference/`.

The layers (ISSUE 56, section 1; E hidden, H heads of D lanes, KV key heads,
L layers, c = scale_depth / sqrt(L)):

    x = scale_emb * embed[token]
    for l in layers:
      u = rms_norm(x; operator_norm_l)
      mixer_types[l] == "lightning-attn":
        q, k, v = W_q u, W_k u, W_v u                 each [H x D]
        q, k = rms_norm a head (q_norm, k_norm [D]), then the rotary
        (rotate-half, all D lanes, theta); q = q / sqrt(D)
        s_h = 2^(-8 h / H) (1 - l / (L - 1 + 1e-5) + 1e-5)    h = 1 .. H
        S_t = e^(-s_h) S_(t-1) + k_t^T v_t ;  o_t = q_t S_t   [D x D] a head
        y = W_o (rms_norm(o over H * D lanes; out_norm) * sigmoid(W_g u))
      mixer_types[l] == "minicpm4":
        q = W_q u [H x D]; k, v = W_k u, W_v u [KV x D]; q, k = rms_norm a
        head; NO rotary; scores q . k / sqrt(D); for the query at position t
        (context n = t + 1) and KV head g with its H / KV query heads:
          n <= dense_len: causal attention over all n rows;
          n > dense_len: Kbar_g[j] = mean(k_g[stride j .. stride j + kernel -
            1]) for every j with stride j + kernel <= n; p_h = softmax_j(q_h
            . Kbar_g[j] / sqrt(D)); r_g[j] = sum over the group's heads of
            p_h[j]; a block b is the `block_size` tokens from block_size b,
            its score the max of r_g[j] over the pooled keys that overlap
            it; FORCED: the first init_blocks blocks and the window_size /
            block_size blocks that end with the query's own; selected =
            forced + the topk highest-scoring of the others (ties to the
            lower index); the group's heads attend, causally, the rows of
            the selected blocks only, one softmax over them.
        y = W_o (o * sigmoid(W_g u))
      x = x + c * y
      x = x + c * W_down (silu(W_gate u2) * W_up u2),  u2 = rms_norm(x; ffn_norm_l)
    logits = W_head rms_norm(x; final_norm) / (E / dim_model_base)

ASSUMED (what the catalog row does not settle; each is listed with its
source in `benchmarks/chip/configs/minicpm-sala-w8a8-1chip.json`):
 a. the seven sparse sizes are MiniCPM4-8B's published `sparse_config`
    (kernel 32, stride 16, block 64, topk 64, init_blocks 1, window 2,048,
    dense_len 8,192);
 b. top-64 is counted BESIDE the forced blocks (at most 97 blocks a query);
 c. only whole kernels are scored (stride j + kernel <= n);
 d. q / k norms per head over D lanes with weights [D] of their own;
 e. no activation on q, k, v of a Lightning layer (MiniMax-01 applies silu;
    this row has norms and a rotary instead);
 f. the slope's layer index runs over all L layers;
 g. both gates are elementwise sigmoids of a projection of the layer's
    normed input;
 h. the three muP scalings as the MiniCPM family places them; mup_denominator
    is an initialiser's number with no place in the forward pass;
 i. the gated silu FFN without bias;
 j. checkpoint key names are not read.

DEPARTURES (layout only): parameters come in the program's tree
(`models/llama._operator_param_specs`): the sparse layers' stack `w_q`
[L_s, E, H * D], `w_k` / `w_v` [L_s, E, KV * D] (the heads side by side),
`wo` [L_s, H, D, E], `q_norm` / `k_norm` [L_s, D], `w_og` [L_s, E, H * D];
the Lightning layers' under `lightning.` (k and v with H heads, and
`out_norm` [L_l, H * D]); `w_gate` /
`w_up` [L, E, F], `w_down` [L, F, E]; `operator_norm` / `ffn_norm` [L, E].

`forward` takes `variant`: "model", or a CONTROL that must not pass for the
model: "half_topk" (topk / 2), "wrong_page_pair" (pooled key j read as the
kernel one stride later), "no_window" (the local window not forced),
"wrong_slope" (every Lightning layer's slope taken from the layer mirrored
in depth, L - 1 - l), "bf16_state" (S rounded to bfloat16 after every token).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
LIGHTNING_PREFIX = "lightning."
LEAVES = ("w_q", "w_k", "w_v", "wo", "q_norm", "k_norm", "w_og")
FFN = ("w_gate", "w_up", "w_down")
VARIANTS = ("model", "half_topk", "wrong_page_pair", "no_window",
            "wrong_slope", "bf16_state")
# ASSUMED (a): MiniCPM4-8B's published sparse_config
SPARSE_DEFAULTS = dict(kernel_size=32, kernel_stride=16, block_size=64,
                       topk=64, init_blocks=1, window_size=2048,
                       dense_len=8192)


@dataclasses.dataclass(frozen=True)
class Config:
    """The published config.json's keys, under their published names."""
    hidden_size: int
    mixer_types: Tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rms_norm_eps: float
    rope_theta: float
    scale_emb: float
    scale_depth: float
    dim_model_base: int
    kernel_size: int
    kernel_stride: int
    block_size: int
    topk: int
    init_blocks: int
    window_size: int
    dense_len: int

    @staticmethod
    def from_hf(cfg: dict) -> "Config":
        sparse = dict(SPARSE_DEFAULTS, **(cfg.get("sparse_config") or {}))
        return Config(
            hidden_size=int(cfg["hidden_size"]),
            mixer_types=tuple(cfg["mixer_types"]),
            num_attention_heads=int(cfg["num_attention_heads"]),
            num_key_value_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]),
            scale_emb=float(cfg["scale_emb"]),
            scale_depth=float(cfg["scale_depth"]),
            dim_model_base=int(cfg["dim_model_base"]),
            **{k: int(sparse[k]) for k in SPARSE_DEFAULTS})

    @property
    def num_layers(self) -> int:
        return len(self.mixer_types)


def dequantize(params: Dict) -> Dict[str, jax.Array]:
    """The program's tree as float32 arrays (an int8 leaf: q * scale)."""
    return {k: (v.q.astype(F32) * v.scale.astype(F32) if hasattr(v, "q")
                else jnp.asarray(v, F32)) for k, v in params.items()}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope(x, positions, theta):
    """Rotate-half over all D lanes. x [T, heads, D]."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


def slopes(cfg: Config, layer: int) -> jax.Array:
    """s_h of `layer` (counted over all layers, ASSUMED (f)), h = 1 .. H."""
    h, l = cfg.num_attention_heads, cfg.num_layers
    base = 2.0 ** (-8.0 * jnp.arange(1, h + 1, dtype=F32) / h)
    return base * (1.0 - layer / (l - 1 + 1e-5) + 1e-5)


def _lightning_qkv(cfg: Config, lp, u):
    t = u.shape[0]
    h, d = cfg.num_attention_heads, cfg.head_dim
    pos = jnp.arange(t)
    q, k, v = ((u @ lp[name]).reshape(t, h, d)
               for name in ("w_q", "w_k", "w_v"))
    q = rope(rms_norm(q, lp["q_norm"], cfg.rms_norm_eps), pos, cfg.rope_theta)
    k = rope(rms_norm(k, lp["k_norm"], cfg.rms_norm_eps), pos, cfg.rope_theta)
    return q / math.sqrt(d), k, v


def _lightning_step(cfg: Config, layer: int, variant: str):
    """S, (q_t, k_t, v_t) -> S after the token, o_t."""
    if variant == "wrong_slope":
        layer = cfg.num_layers - 1 - layer
    decay = jnp.exp(-slopes(cfg, layer))  # [H]

    def step(s, qkv):
        qt, kt, vt = qkv
        s = decay[:, None, None] * s + kt[:, :, None] * vt[:, None, :]
        if variant == "bf16_state":
            # not astype(bfloat16).astype(float32): the TPU's compiler may
            # keep the excess precision of such a round trip
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("hd,hde->he", qt, s)

    return step


def lightning(cfg: Config, lp, u, layer: int, variant: str = "model"):
    """The Lightning layer over u [T, E] in its one-token form."""
    t = u.shape[0]
    h, d = cfg.num_attention_heads, cfg.head_dim
    _, o = jax.lax.scan(_lightning_step(cfg, layer, variant),
                        jnp.zeros((h, d, d), F32), _lightning_qkv(cfg, lp, u))
    o = rms_norm(o.reshape(t, h * d), lp["out_norm"], cfg.rms_norm_eps)
    o = o * jax.nn.sigmoid(u @ lp["w_og"])
    return jnp.einsum("thd,hde->te", o.reshape(t, h, d), lp["wo"])


def lightning_state(cfg: Config, lp, u, layer: int, variant: str = "model"):
    """S [H, D (k), D (v)] of the Lightning layer after all of u [T, E]."""
    h, d = cfg.num_attention_heads, cfg.head_dim
    s, _ = jax.lax.scan(_lightning_step(cfg, layer, variant),
                        jnp.zeros((h, d, d), F32), _lightning_qkv(cfg, lp, u))
    return s


def pooled_keys(cfg: Config, k, variant: str = "model"):
    """Kbar [nj, KV, D]: the mean of every whole kernel of k [T, KV, D]."""
    t = k.shape[0]
    ks, st = cfg.kernel_size, cfg.kernel_stride
    nj = max((t - ks) // st + 1, 0)
    shift = st if variant == "wrong_page_pair" else 0
    starts = jnp.minimum(jnp.arange(nj) * st + shift, max(t - ks, 0))
    return jnp.mean(k[starts[:, None] + jnp.arange(ks)[None, :]], axis=1)


def select_blocks(cfg: Config, q, kbar, contexts, nb: int,
                  variant: str = "model"):
    """The blocks each query attends -> bool [Q, KV, nb]. q [Q, H, D];
    kbar [nj, KV, D] (`pooled_keys` of at least the longest context's keys);
    contexts [Q], each query's tokens in context including its own. Every
    block up to its own for a query at or under dense_len."""
    nq, h, d = q.shape
    nj, kvh = kbar.shape[0], kbar.shape[1]
    bs, ks, st = cfg.block_size, cfg.kernel_size, cfg.kernel_stride
    blocks = jnp.arange(nb)
    own = ((contexts - 1) // bs)[:, None]  # [Q, 1]
    upto = blocks[None, :] <= own
    if nj == 0:
        return jnp.broadcast_to(upto[:, None, :], (nq, kvh, nb))
    s = jnp.einsum("qgmd,jgd->qgmj", q.reshape(nq, kvh, h // kvh, d),
                   kbar) / math.sqrt(d)
    # whole kernels only, ASSUMED (c): stride j + kernel <= n
    whole = (jnp.arange(nj)[None, :] * st + ks <= contexts[:, None])
    s = jnp.where(whole[:, None, None, :], s, -jnp.inf)
    p = jnp.where(whole[:, None, None, :], jax.nn.softmax(s, axis=-1), 0.0)
    r = jnp.where(whole[:, None, :], jnp.sum(p, axis=2), -jnp.inf)
    # block b = tokens [bs b, bs b + bs); pooled key j = [st j, st j + ks)
    lo = -(-(blocks * bs - ks + 1) // st)  # the first j with st j + ks > bs b
    width = (bs + ks - 2) // st + 1
    js = lo[:, None] + jnp.arange(width)[None, :]  # [nb, width]
    ok = (js >= 0) & (js < nj) & (js * st < (blocks[:, None] + 1) * bs)
    picked = jnp.where(ok[None, None], r[:, :, jnp.clip(js, 0, nj - 1)],
                       -jnp.inf)
    score = jnp.max(picked, axis=-1)  # [Q, KV, nb]
    forced = jnp.broadcast_to(blocks[None, :] < cfg.init_blocks, upto.shape)
    if variant != "no_window":
        forced |= blocks[None, :] > own - cfg.window_size // bs
    forced &= upto
    topk = cfg.topk // 2 if variant == "half_topk" else cfg.topk
    cand = upto & ~forced
    cs = jnp.where(cand[:, None, :], score, -jnp.inf)
    _, best = jax.lax.top_k(cs, min(topk, nb))  # ties: the lower index first
    took = jnp.any(best[..., None] == blocks, axis=-2) & cand[:, None, :]
    dense = (contexts <= cfg.dense_len)[:, None, None]
    return jnp.where(dense, upto[:, None, :], forced[:, None, :] | took)


@functools.partial(jax.jit, static_argnames=("cfg", "variant"))
def _attend_block(cfg: Config, variant: str, qb, contexts, k, v, kbar):
    """A block of queries qb [Q, H, D] at `contexts` [Q] over all keys k, v
    [T, KV, D] under each query's own mask -> (o [Q, H, D], the selection
    [Q, KV, blocks], the dense softmax's mass on the rows left out [Q, H])."""
    t, kvh, d = k.shape
    h, bs = qb.shape[1], cfg.block_size
    kpos = jnp.arange(t)
    member = select_blocks(cfg, qb, kbar, contexts, -(-t // bs), variant)
    rows = jnp.repeat(member, bs, axis=-1)[..., :t]
    causal = (kpos[None, :] < contexts[:, None])[:, None, :]
    rows &= causal
    s = jnp.einsum("qgmd,sgd->qgms", qb.reshape(-1, kvh, h // kvh, d),
                   k) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(rows[:, :, None, :], s, -jnp.inf), axis=-1)
    lost = jnp.sum(jnp.where(
        rows[:, :, None, :], 0.0,
        jax.nn.softmax(jnp.where(causal[:, :, None, :], s, -jnp.inf),
                       axis=-1)), axis=-1)
    return (jnp.einsum("qgms,sgd->qgmd", p, v).reshape(-1, h, d), member,
            lost.reshape(-1, h))


def sparse_attention(cfg: Config, lp, u, variant: str = "model",
                     query_block: int = 128, members=None, dropped=None):
    """The sparse layer over u [T, E], `query_block` queries at a time over
    all T keys under each query's own mask. `members`: a list that receives
    the selection, bool [T, KV, blocks]. `dropped`: a list that receives,
    for every query [T, H], the share of its DENSE softmax's mass that lay
    on the rows it does not attend (0 up to dense_len)."""
    t = u.shape[0]
    h, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    q = rms_norm((u @ lp["w_q"]).reshape(t, h, d), lp["q_norm"],
                 cfg.rms_norm_eps)
    k = rms_norm((u @ lp["w_k"]).reshape(t, kvh, d), lp["k_norm"],
                 cfg.rms_norm_eps)
    v = (u @ lp["w_v"]).reshape(t, kvh, d)
    kbar = pooled_keys(cfg, k, variant)
    outs, mems, losts = [], [], []
    qp = jnp.pad(q, ((0, -t % query_block), (0, 0), (0, 0)))
    for start in range(0, t, query_block):
        contexts = jnp.minimum(start + 1 + jnp.arange(query_block), t)
        o, member, lost = _attend_block(
            cfg, variant, qp[start:start + query_block], contexts, k, v, kbar)
        outs.append(o)
        mems.append(member)
        losts.append(lost)
    if members is not None:
        members.append(jnp.concatenate(mems)[:t])
    if dropped is not None:
        dropped.append(jnp.concatenate(losts)[:t])
    o = jnp.concatenate(outs)[:t].reshape(t, h * d)
    o = o * jax.nn.sigmoid(u @ lp["w_og"])
    return jnp.einsum("thd,hde->te", o.reshape(t, h, d), lp["wo"])


def forward(cfg: Config, params: Dict[str, jax.Array], tokens,
            variant: str = "model", members=None) -> jax.Array:
    """Logits [T, V] of `tokens` [T] (float32 params in the program's
    tree). `members`: a list that receives every sparse layer's selection
    in layer order, bool [T, KV, blocks]."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}")
    with jax.default_matmul_precision("highest"):
        c = cfg.scale_depth / math.sqrt(cfg.num_layers)
        x = cfg.scale_emb * params["embed"][jnp.asarray(tokens)]
        seen = {SPARSE: 0, LIGHTNING: 0}
        for l, kind in enumerate(cfg.mixer_types):
            j = seen[kind]
            seen[kind] += 1
            pre = LIGHTNING_PREFIX if kind == LIGHTNING else ""
            lp = {name: params[pre + name][j] for name in LEAVES}
            u = rms_norm(x, params["operator_norm"][l], cfg.rms_norm_eps)
            if kind == LIGHTNING:
                lp["out_norm"] = params[pre + "out_norm"][j]
                y = lightning(cfg, lp, u, l, variant)
            else:
                y = sparse_attention(cfg, lp, u, variant, members=members)
            x = x + c * y
            u = rms_norm(x, params["ffn_norm"][l], cfg.rms_norm_eps)
            g, up, down = (params[name][l] for name in FFN)
            x = x + c * ((jax.nn.silu(u @ g) * (u @ up)) @ down)
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return (x / (cfg.hidden_size / cfg.dim_model_base)) @ params["lm_head"]
