"""Functional llama-family decoder (Llama 3.x, Qwen2/3, Mixtral-MoE) with a
paged KV cache, written as pure JAX over a layer-stacked parameter pytree.

Design notes (TPU-first):
- Parameters are stacked on a leading `num_layers` axis and the forward pass is
  a `lax.scan` over layers — one compiled layer body regardless of depth, which
  keeps XLA compile time flat for 80-layer models (the reference's TRT engine
  build is the analogous cold-start cost, SURVEY.md §5 checkpoint/resume).
- Attention/MLP projections keep heads/features as explicit axes so the
  sharding rules in `dynamo_tpu.parallel.sharding` partition them on the
  `model` mesh axis without reshapes.
- The same code path serves the architectures the reference deploys via its
  three engine backends (/root/reference/examples/deploy/{vllm,sglang,trtllm}),
  selected purely by `ModelConfig` (qk_norm -> Qwen3, attention_bias -> Qwen2,
  num_experts>0 -> Mixtral-style MoE).

All public entry points are shape-static and jit-safe; batching/paging policy
lives in `dynamo_tpu.engine`.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import numpy as np
import jax.numpy as jnp

from dynamo_tpu.models.config import (ATTENTION, CONV, EXPERTS, FULL,
                                      LIGHTNING, MAMBA, SLIDING, SPARSE,
                                      ModelConfig)
from dynamo_tpu.models import quant
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import moe as moe_ops
from dynamo_tpu.ops import short_conv
from dynamo_tpu.ops import sparse_blocks as sparse_ops
from dynamo_tpu.ops import ssm as ssm_ops
from dynamo_tpu.ops.rope import apply_rope

qeinsum = quant.einsum  # einsum that understands int8 QTensor weights

Params = Dict[str, jax.Array]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def rms_norm(x: jax.Array, w: jax.Array, eps: float,
             unit_offset: bool = False) -> jax.Array:
    """unit_offset: Gemma checkpoints store norm weights as w with the
    model applying (1 + w) — zero-init means identity scale."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return normed * (1.0 + w) if unit_offset else normed * w


def _embed_rows(cfg: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    x = quant.take_rows(params["embed"], tokens, _dtype(cfg))
    if cfg.embed_scale:
        # Gemma normalizer: embeddings scale by sqrt(E) (fp32, cast back)
        x = (x.astype(jnp.float32) * (cfg.hidden_size ** 0.5)).astype(x.dtype)
    if cfg.multipliers is not None:  # falcon_h1's embedding_multiplier
        x = (x.astype(jnp.float32)
             * cfg.multipliers.embedding).astype(x.dtype)
    if cfg.scale_emb != 1.0:  # the MiniCPM family's scale_emb
        x = (x.astype(jnp.float32) * cfg.scale_emb).astype(x.dtype)
    return x


def _act(cfg: ModelConfig, g: jax.Array) -> jax.Array:
    if cfg.hidden_act == "gelu_tanh":  # Gemma GeGLU
        return jax.nn.gelu(g, approximate=True)
    return jax.nn.silu(g)


def _attn_kwargs(cfg: ModelConfig, page_off, pages_per_layer: int) -> dict:
    """window/logit_cap kwargs for the attention ops (gemma-2 family).

    Sliding-window models derive THIS layer's window from the scanned
    body's page offset (its only layer handle): layer (i+1) %
    sliding_window_pattern == 0 is global (window 0 = unbounded through
    the same traced scalar). Dense models return {} so the Pallas
    dispatch path is untouched."""
    kw = {}
    if cfg.attn_logit_softcapping > 0.0:
        kw["logit_cap"] = cfg.attn_logit_softcapping
    if cfg.sliding_window > 0:
        kw["window"] = jnp.where(
            _is_global_layer(cfg, page_off, pages_per_layer), 0,
            cfg.sliding_window).astype(jnp.int32)
    return kw


def _is_global_layer(cfg: ModelConfig, page_off, pages_per_layer: int):
    """THE local/global predicate (traced): layer (i+1) %
    sliding_window_pattern == 0 is global; pattern <= 0 means EVERY layer
    is local (Mistral-v0.1-style uniform sliding window). Shared by the
    window mask and the per-layer rope so the two can never
    desynchronize."""
    if cfg.sliding_window_pattern <= 0:
        return jnp.bool_(False)
    layer = page_off // pages_per_layer
    return (layer + 1) % cfg.sliding_window_pattern == 0


def _yarn_softmax_scale(cfg: ModelConfig, q: jax.Array) -> jax.Array:
    """YaRN's attention-magnitude correction: the softmax scale gains
    yarn_get_mscale(factor, mscale_all_dim)^2 (HF DeepSeek-V2 semantics) —
    folded into q like query_pre_attn_scalar so the attention ops stay
    signature-free of it."""
    if cfg.rope_yarn_scaling is None:
        return q
    from dynamo_tpu.ops.rope import yarn_get_mscale

    factor, _, _, _, _, msad, af = cfg.rope_yarn_scaling
    if af >= 0.0:
        return q  # explicit attention_factor lives on cos/sin instead
    m = yarn_get_mscale(factor, msad)
    if m == 1.0:
        return q
    return q * jnp.asarray(m * m, q.dtype)


def _longrope_args(cfg: ModelConfig):
    """Phi-3 longrope apply_rope argument: (short_factors, long_factors,
    original_max_pos, attention magnitude) or None. The magnitude is
    sqrt(1 + ln(s)/ln(orig)) over the checkpoint's advertised context
    extension; factor selection is per position inside apply_rope."""
    if cfg.rope_longrope_scaling is None:
        return None
    from dynamo_tpu.ops.rope import longrope_attention_factor

    short, long, orig = cfg.rope_longrope_scaling
    return short, long, orig, longrope_attention_factor(
        cfg.max_position_embeddings, orig)


def _layer_rope(cfg: ModelConfig, page_off, pages_per_layer: int):
    """Gemma-3 per-layer rope: local (sliding) layers use
    rope_local_theta; GLOBAL layers use rope_theta with positions divided
    by rope_scaling_factor (HF linear scaling). None for single-theta
    models — the common path stays untouched."""
    if cfg.rope_local_theta <= 0:
        return None
    is_global = _is_global_layer(cfg, page_off, pages_per_layer)
    theta = jnp.where(is_global, cfg.rope_theta, cfg.rope_local_theta)
    scale = jnp.where(is_global, cfg.rope_scaling_factor, 1.0)
    return theta, scale


def _post(cfg: ModelConfig, lp: Params, name: str, y: jax.Array) -> jax.Array:
    """Gemma-2 sandwich norm on a residual-branch OUTPUT (post_attn_norm /
    post_mlp_norm); identity for every other family."""
    if not cfg.post_norms:
        return y
    return rms_norm(y, lp[name], cfg.rms_norm_eps, cfg.rms_norm_unit_offset)


# leaf-name prefix of the leading dense layers' own parameter stack
DENSE_PREFIX = "dense."
# the Lightning layers' stack of a minicpm_sala model (quant.quant_axes reads
# the name behind the last dot, as for DENSE_PREFIX)
LIGHTNING_PREFIX = "lightning."
# leaf-name prefix of the head-shaped attention leaves (wq, wo, wg, a sink)
# by kind, where a model's layers are of more than one kind
# (cfg.layer_types): the kinds' head counts may differ, so each kind stacks
# its own; wk and wv too where the kinds' KV heads differ (cfg.kv_by_kind)
KIND_PREFIX = {FULL: "", SLIDING: "win."}


def _kind_leaves(cfg: ModelConfig) -> Tuple[str, ...]:
    return ("wq", "wo", "wg", "sink") + (
        ("wk", "wv") if cfg.kv_by_kind else ())


class ByKind(NamedTuple):
    """One value for each attention kind of a model whose layers are of
    more than one kind: the KV pools ([layers of the kind, pages, page_size,
    lanes] each, sized apart: engine/kv_cache.py) and the page tables (a
    full layer's table addresses the whole context; a sliding layer's is a
    RING of `window.shape[-1]` pages: logical page p lives in ring slot
    p % W, so a page out of every query's reach is written over)."""
    full: Any
    window: Any


_POOL_OF = {FULL: 0, SLIDING: 1}


def param_specs(cfg: ModelConfig) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """Shape/init spec for every parameter: name -> (shape, kind, sigma).

    kind: "normal" (random weight with stddev sigma), "ones", "zeros".
    Single source of truth for param shapes — `init_params` and the loader's
    fast random-int8 path both build from it, so they cannot drift."""
    if cfg.mixer_types:
        return _hybrid_param_specs(cfg)
    e, h, kv, d, f = (
        cfg.hidden_size,
        cfg.num_heads,
        cfg.num_kv_heads,
        cfg.head_dim,
        cfg.intermediate_size,
    )
    # the scanned stack: every layer, or the expert layers that follow
    # the leading dense ones (those get their own stack, below)
    l = cfg.num_layers - cfg.first_k_dense

    def w(shape, sigma=None):
        return (shape, "normal",
                sigma if sigma is not None else 1.0 / shape[-1] ** 0.5)

    # NOTE: insertion ORDER is load-bearing for existing configs —
    # init_params assigns PRNG subkeys positionally, so reordering names
    # would silently change every random-init weight
    # Gemma's (1+w) norm convention makes ZERO the identity scale
    nk = "zeros" if cfg.rms_norm_unit_offset else "ones"
    p = {
        "embed": w((cfg.vocab_size, e), 0.02),
        "final_norm": ((e,), nk, 0.0),
        "attn_norm": ((l, e), nk, 0.0),
    }
    def attention(p, l, pre="", stacks=None):
        # `stacks`: (leaf prefix, kind, layers) for each stack of
        # head-shaped leaves: one (of no kind), or one a kind where
        # cfg.layer_types
        stacks = stacks or (("", None, l),)

        def heads(k):
            return h if k is None else cfg.kind_heads(k)

        def kv_heads(k):
            return kv if k is None else cfg.kind_kv_heads(k)

        vd = cfg.value_head_dim  # head_dim, or a model of kinds' narrower
        if cfg.is_mla:
            # multi-head latent attention (DeepSeek-V2 family): queries
            # project per-head to [nope | rope] (through a low-rank latent
            # with its own norm when q_lora_rank > 0); keys/values come
            # from ONE shared latent row per token via the up-projections
            # W_UK / W_UV
            nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
            lora, vd = cfg.kv_lora_rank, cfg.v_head_dim
            if cfg.q_lora_rank > 0:
                qr = cfg.q_lora_rank
                p[pre + "wq_a"] = w((l, e, qr))
                p[pre + "q_a_norm"] = ((l, qr), "ones", 0.0)
                # HF's view: one [qr, H*(nope+rope)] matrix, fan-in qr
                p[pre + "wq_b"] = w((l, qr, h, nope + rope), 1.0 / qr ** 0.5)
            else:
                p[pre + "wq_mla"] = w((l, e, h, nope + rope))
            p[pre + "w_kv_a"] = w((l, e, lora + rope))
            p[pre + "kv_a_norm"] = ((l, lora), "ones", 0.0)
            p[pre + "w_uk"] = w((l, h, nope, lora))
            p[pre + "w_uv"] = w((l, h, lora, vd))
            p[pre + "wo"] = w((l, h, vd, e))
            if cfg.is_dsa:
                # the lightning indexer (DeepSeek-V3.2), a layer's own:
                # queries from the q-LoRA latent, one key a token through
                # a LayerNorm (weight and bias), a weight an index head
                hi, di = cfg.index_n_heads, cfg.index_head_dim
                p[pre + "idx_wq_b"] = w((l, cfg.q_lora_rank, hi, di))
                p[pre + "idx_wk"] = w((l, e, di))
                p[pre + "idx_k_norm"] = ((l, di), "ones", 0.0)
                p[pre + "idx_k_bias"] = ((l, di), "zeros", 0.0)
                p[pre + "idx_w"] = w((l, e, hi))
        else:
            for kp, k, lk in stacks:
                p[pre + kp + "wq"] = w((lk, e, heads(k), d))
            # one K/V stack for all layers, or one a kind where the kinds'
            # KV heads differ
            for kp, k, lk in (stacks if cfg.kv_by_kind
                              else (("", None, l),)):
                p[pre + kp + "wk"] = w((lk, e, kv_heads(k), d))
                p[pre + kp + "wv"] = w((lk, e, kv_heads(k), vd))
            for kp, k, lk in stacks:
                p[pre + kp + "wo"] = w((lk, heads(k), vd, e))
            if cfg.attn_gate:
                # the per-head output gate: one scalar a head from the
                # layer's normed input (model dtype: 3072 x 72 at most)
                for kp, k, lk in stacks:
                    p[pre + kp + "wg"] = w((lk, e, heads(k)))
            for kp, k, lk in stacks:
                if k in cfg.attn_sink_kinds:  # a logit a query head, f32
                    p[pre + kp + "sink"] = ((lk, heads(k)), "sink", 0.0)

    scanned_kinds = cfg.layer_types[cfg.first_k_dense:]
    attention(p, l, stacks=tuple(
        (KIND_PREFIX[k], k, scanned_kinds.count(k))
        for k in (FULL, SLIDING) if k in scanned_kinds) or None)
    p["mlp_norm"] = ((l, e), nk, 0.0)
    if cfg.post_norms:  # gemma-2 sandwich norms on branch outputs
        p["post_attn_norm"] = ((l, e), nk, 0.0)
        p["post_mlp_norm"] = ((l, e), nk, 0.0)
    if not cfg.tie_word_embeddings:
        p["lm_head"] = w((e, cfg.vocab_size), 0.02)
    if cfg.attention_bias:
        p["bq"] = ((l, h, d), "zeros", 0.0)
        p["bk"] = ((l, kv, d), "zeros", 0.0)
        p["bv"] = ((l, kv, d), "zeros", 0.0)
    if cfg.qk_norm:
        p["q_norm"] = ((l, d), nk, 0.0)
        p["k_norm"] = ((l, d), nk, 0.0)
    if cfg.is_moe:
        # the router keeps its whole width; the expert weights are those
        # HELD here (all of them unless the config states a share)
        x = cfg.held_experts
        p["router"] = w((l, e, cfg.num_experts), 0.02)
        if cfg.router_bias:
            # HF e_score_correction_bias: float32, selection only
            p["router_bias"] = ((l, cfg.num_experts), "zeros", 0.0)
        p["moe_w_gate"] = w((l, x, e, f))
        p["moe_w_up"] = w((l, x, e, f))
        p["moe_w_down"] = w((l, x, f, e))
        if cfg.num_shared_experts > 0:
            # DeepSeek-style always-active shared experts: one fused dense
            # SwiGLU of width shared*f alongside the routed top-k (reuses
            # the dense-MLP param names/rules)
            fs = cfg.shared_expert_width
            p["w_gate"] = w((l, e, fs))
            p["w_up"] = w((l, e, fs))
            p["w_down"] = w((l, fs, e))
    else:
        p["w_gate"] = w((l, e, f))
        p["w_up"] = w((l, e, f))
        p["w_down"] = w((l, f, e))
    if cfg.first_k_dense:
        # leading dense layers: the same attention, one SwiGLU of the
        # dense width; own stack under the "dense." prefix (run unrolled
        # ahead of the scan, see _scan_layers_paged)
        ld, fd = cfg.first_k_dense, cfg.dense_intermediate_size
        pre = DENSE_PREFIX
        p[pre + "attn_norm"] = ((ld, e), nk, 0.0)
        attention(p, ld, pre, stacks=(
            (("", cfg.layer_types[0], ld),) if cfg.layer_types else None))
        p[pre + "mlp_norm"] = ((ld, e), nk, 0.0)
        p[pre + "w_gate"] = w((ld, e, fd))
        p[pre + "w_up"] = w((ld, e, fd))
        p[pre + "w_down"] = w((ld, fd, e))
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random init with the exact shapes/names the loader and sharder expect."""
    dt = _dtype(cfg)
    specs = param_specs(cfg)
    ks = jax.random.split(key, len(specs))
    p: Params = {}
    for k, (name, (shape, kind, sigma)) in zip(ks, specs.items()):
        if name == "router_bias":
            p[name] = jnp.zeros(shape, jnp.float32)
        elif kind in SSM_INITS:
            p[name] = jnp.asarray(SSM_INITS[kind](
                jax.random.uniform(k, shape, dtype=jnp.float32)), jnp.float32)
        elif kind == "ones":
            p[name] = jnp.ones(shape, dt)
        elif kind == "zeros":
            p[name] = jnp.zeros(shape, dt)
        else:
            p[name] = (
                jax.random.normal(k, shape, dtype=jnp.float32) * sigma
            ).astype(dt)
    for name, cuts in zero_lanes(cfg).items():
        p[name] = _zero_past(p[name], cuts)
    return p


def _live_slots(block_tables: jax.Array) -> jax.Array:
    """[B] bool: decode slots that hold a sequence (an inactive slot's
    table is all trash page 0, a live one's first page never is)."""
    return block_tables[:, 0] > 0


def _live_rows(cfg: ModelConfig, block_tables: jax.Array):
    """`_live_slots` as an expert layer's token mask. Only the grouped
    expert layer asks: it computes and counts no row of an empty slot.
    None elsewhere, so the other models' MLPs do not change."""
    return _live_slots(block_tables) if cfg.moe_grouped else None


def _layer_params(p: Params) -> Params:
    """The subtree that carries a leading layer axis (scanned)."""
    return {
        k: v
        for k, v in p.items()
        if k not in ("embed", "lm_head", "final_norm")
        and not k.startswith(DENSE_PREFIX)
    }


def _dense_layer_params(p: Params, i: int) -> Params:
    """Leading dense layer i's parameters under their plain names."""
    return {
        k[len(DENSE_PREFIX):]: jax.tree.map(lambda a: a[i], v)
        for k, v in p.items() if k.startswith(DENSE_PREFIX)
    }


_EXPERT_STACKS = ("moe_w_gate", "moe_w_up", "moe_w_down")


def _scan_layers_paged(cfg: ModelConfig, params: Params, body, x,
                       k_pages, v_pages):
    """Run every layer with the KV pools carried FLAT: [L, P, ps, KV*D] is
    viewed as [L*P, ps, KV*D] (a bitcast), layer l's page p lives at flat
    id l*P + p, and `body` receives (x, flat_k, flat_v, lp,
    layer_page_offset) and returns the updated (x, flat_k, flat_v) and
    what its expert layer counted (_mlp's second result: None for most).

    The leading dense layers of a DeepSeek-V3-style model (cfg.first_k_dense,
    own parameter stack) run unrolled first; the rest is one lax.scan over
    (layer params, layer index) — one compiled layer body whatever the
    depth. Page offsets count all layers. Returns (x, k_pages, v_pages,
    moe_stats): moe_stats is the grouped expert layers' counts summed over
    layers (int32 [6]), or None where no layer counts.

    Why flat: offsetting page ids instead of slicing a [P, ps, KV*D] layer
    out of the pool means each iteration touches only the written rows and
    the gathered pages. Before pools moved into the carry with flat
    addressing, the per-layer slice/stack/copy traffic cost ~10ms of a
    25ms decode step on the 8B model (XProf hlo_stats: 'data formatting'
    copies + dynamic-slice fusions at full-pool size).

    An MLA model's V pool has no lanes (engine/kv_cache.py): the latent row
    lives once, in the K pool, and the attention ops read V from it.

    A model whose layers are of more than one kind (cfg.layer_types) scans
    over PERIODS of its kinds instead: _scan_periods_paged."""
    if cfg.layer_types:
        return _scan_periods_paged(cfg, params, body, x, k_pages, v_pages)
    l, p = k_pages.shape[:2]
    kpf = k_pages.reshape((l * p,) + k_pages.shape[2:])
    vpf = v_pages.reshape((l * p,) + v_pages.shape[2:])
    # the grouped expert layers' counts ride the carry only where a layer
    # counts: every other model's programs stay as they were
    extra = ((jnp.zeros((len(moe_ops.MOE_STATS),), jnp.int32),)
             if cfg.moe_grouped else ())

    def layer(carry, lp, page_off):
        x, kp, vp, counts = body(*carry[:3], lp, page_off)
        # a leading dense layer of a counting model counts nothing
        return (x, kp, vp) + tuple(
            acc if counts is None else acc + counts for acc in carry[3:])

    carry = (x, kpf, vpf) + extra
    for i in range(cfg.first_k_dense):
        carry = layer(carry, _dense_layer_params(params, i), i * p)

    scanned = _layer_params(params)
    # the grouped expert matmul takes the experts' WHOLE stack and the
    # layer's index (ops/moe.moe_mlp_grouped says why): those leaves ride
    # the scan's closure, not its sliced operands
    whole = ({k: scanned.pop(k) for k in _EXPERT_STACKS}
             if cfg.moe_grouped else {})

    def wrapped(carry, xs):
        lp, idx = xs
        if whole:
            lp = dict(lp, **whole, moe_layer=idx - cfg.first_k_dense)
        return layer(carry, lp, idx * p), None

    carry, _ = jax.lax.scan(
        wrapped, carry, (scanned, jnp.arange(cfg.first_k_dense, l)))
    x, kpf, vpf = carry[:3]
    return (x, kpf.reshape(k_pages.shape), vpf.reshape(v_pages.shape),
            carry[3] if extra else None)


def _kind_runs(kinds) -> Tuple[Tuple[str, int, int], ...]:
    """(kind, first position, length) of each run of equal neighbours."""
    runs, j = [], 0
    while j < len(kinds):
        n = 1
        while j + n < len(kinds) and kinds[j + n] == kinds[j]:
            n += 1
        runs.append((kinds[j], j, n))
        j += n
    return tuple(runs)


def _scan_periods_paged(cfg: ModelConfig, params: Params, body, x,
                        k_pages: ByKind, v_pages: ByKind):
    """_scan_layers_paged for layers of more than one KIND: the leading
    dense layers unrolled as there, then ONE lax.scan over the periods of
    cfg.layer_types, then the layers of a last period cut short. Inside a
    period each RUN of layers of one kind in a row is a lax.scan of its own
    (a run of one is called as it is), so a program holds one layer body a
    run, not one a layer: a period of five sliding layers and a full one is
    two bodies. A layer's kind stays static in its body: the kernels are
    specialised on the window, the head counts are shapes.

    The parameter stacks ride the scans' closure and a layer's leaves are
    sliced out of them where the layer runs, by its index in the scanned
    stack and in its kind's own (the head-shaped leaves, `_kind_leaves`,
    stack by kind under KIND_PREFIX): a stack sliced by an outer scan and
    again by an inner one would be copied a period at a time.

    Each kind has its own pools, carried flat as there; layer i of a kind
    (counted over the whole model, dense ones too) lives at i * P_kind.
    `body` is called as body(x, kp, vp, lp, page_off, kind) with the pools
    of the layer's kind."""
    pools = [(k.reshape((-1,) + k.shape[2:]), v.reshape((-1,) + v.shape[2:]))
             for k, v in zip(k_pages, v_pages)]
    per_layer = [k.shape[1] for k in k_pages]
    carry = (x, tuple(pools)) + (
        (jnp.zeros((len(moe_ops.MOE_STATS),), jnp.int32),)
        if cfg.moe_grouped else ())
    # where layer i of the model sits in its kind's pool
    seen = {FULL: 0, SLIDING: 0}
    pool_index = []
    for kind in cfg.layer_types:
        pool_index.append(seen[kind])
        seen[kind] += 1

    def layer(carry, lp, kind, page_off):
        x, pools = carry[:2]
        which = _POOL_OF[kind]
        x, kp, vp, counts = body(x, *pools[which], lp, page_off, kind)
        pools = tuple((kp, vp) if i == which else pl
                      for i, pl in enumerate(pools))
        return (x, pools) + tuple(
            acc if counts is None else acc + counts for acc in carry[2:])

    nd = cfg.first_k_dense
    for i in range(nd):
        kind = cfg.layer_types[i]
        carry = layer(carry, _dense_layer_params(params, i), kind,
                      pool_index[i] * per_layer[_POOL_OF[kind]])

    scanned = _layer_params(params)
    whole = ({k: scanned.pop(k) for k in _EXPERT_STACKS}
             if cfg.moe_grouped else {})
    by_kind = {kind: {leaf: scanned.pop(pre + leaf)
                      for leaf in _kind_leaves(cfg) if pre + leaf in scanned}
               for kind, pre in KIND_PREFIX.items()}
    kinds = cfg.layer_types[nd:]
    period = cfg.kind_period
    n_periods, tail = divmod(len(kinds), period)
    per_period = {k: kinds[:period].count(k) for k in KIND_PREFIX}
    # position j of a period -> its index among the period's layers of its kind
    within = [kinds[:j].count(kinds[j]) for j in range(period)]

    def one(carry, kind, layer_idx, kind_idx):
        """The layer at `layer_idx` of the scanned stack, `kind_idx` of
        its kind's own stack (either may be traced)."""
        lp = dict(jax.tree.map(lambda a: a[layer_idx], scanned),
                  **jax.tree.map(lambda a: a[kind_idx], by_kind[kind]))
        if whole:
            lp.update(whole, moe_layer=layer_idx)
        which = _POOL_OF[kind]
        first = cfg.layer_types[:nd].count(kind)
        return layer(carry, lp, kind, (first + kind_idx) * per_layer[which])

    def runs_of(carry, kinds, t):
        """The runs of `kinds`, the leading layers of period t."""
        for kind, j, n in _kind_runs(kinds):
            layer0 = t * period + j
            kind0 = t * per_period[kind] + within[j]
            if n == 1:
                carry = one(carry, kind, layer0, kind0)
            else:
                carry, _ = jax.lax.scan(
                    lambda c, i, kind=kind, layer0=layer0, kind0=kind0: (
                        one(c, kind, layer0 + i, kind0 + i), None),
                    carry, jnp.arange(n))
        return carry

    if n_periods:
        carry, _ = jax.lax.scan(
            lambda c, t: (runs_of(c, kinds[:period], t), None),
            carry, jnp.arange(n_periods))
    if tail:
        carry = runs_of(carry, kinds[:tail], n_periods)
    x, pools = carry[:2]
    return (x,
            ByKind(*(kf.reshape(k.shape) for (kf, _), k
                     in zip(pools, k_pages))),
            ByKind(*(vf.reshape(v.shape) for (_, vf), v
                     in zip(pools, v_pages))),
            carry[2] if cfg.moe_grouped else None)


def _qkv(cfg: ModelConfig, lp: Params, x: jax.Array, positions: jax.Array,
         rope=None, lora_slots=None, kind=None):
    """x: [T, E] -> q [T, H, D], k/v [T, KV, D] with rope applied.

    `rope`: optional per-layer (theta, position_scale) from _layer_rope
    (gemma-3's interleaved rope bases); None = cfg.rope_theta everywhere.

    `lora_slots`: [T] int32 per-token adapter-slot indices (multi-LoRA
    serving, dynamo_tpu.lora): when given and the param tree carries
    stacked LoRA matrices, each projection gains its token's adapter delta
    `(x @ A[s]) @ B[s]` via one gathered einsum — slot 0 is the all-zero
    base slot, so mixed adapter/base batches run one fused program.

    MLA models route through _qkv_mla: the returned "k"/"v" are the SHARED
    latent rows [T, 1, lora+rope] (what the paged cache stores) and q is
    the absorbed query over the latent space — the generic paged-attention
    ops then serve MLA unchanged."""
    if cfg.is_mla:
        return _qkv_mla(cfg, lp, x, positions)  # is_dsa: see _dsa_index
    q = qeinsum("te,ehd->thd", x, lp["wq"])
    k = qeinsum("te,ekd->tkd", x, lp["wk"])
    v = qeinsum("te,ekd->tkd", x, lp["wv"])
    if lora_slots is not None and "lora_qa" in lp:
        from dynamo_tpu.lora import apply as _lora

        q = q + _lora.delta(jnp, x, lp["lora_qa"], lp["lora_qb"],
                            lora_slots).reshape(q.shape)
        k = k + _lora.delta(jnp, x, lp["lora_ka"], lp["lora_kb"],
                            lora_slots).reshape(k.shape)
        v = v + _lora.delta(jnp, x, lp["lora_va"], lp["lora_vb"],
                            lora_slots).reshape(v.shape)
    if cfg.attention_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    if cfg.qk_norm:
        with jax.named_scope("attn_qk_norm"):
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps,
                         cfg.rms_norm_unit_offset)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps,
                         cfg.rms_norm_unit_offset)
    if kind is not None:
        return _rope_of_kind(cfg, kind, q, positions), _rope_of_kind(
            cfg, kind, k, positions), v
    theta, pos = cfg.rope_theta, positions
    if rope is not None:
        theta, scale = rope
        pos = positions.astype(jnp.float32) / scale
    l3, yarn = cfg.rope_llama3_scaling, cfg.rope_yarn_scaling
    lr = _longrope_args(cfg)
    with jax.named_scope("attn_qk_rope"):
        q = apply_rope(q, pos, theta, llama3_scaling=l3, yarn_scaling=yarn,
                       longrope_scaling=lr)
        k = apply_rope(k, pos, theta, llama3_scaling=l3, yarn_scaling=yarn,
                       longrope_scaling=lr)
    q = _yarn_softmax_scale(cfg, q)
    if cfg.query_pre_attn_scalar > 0:
        # the attention ops scale scores by head_dim^-0.5; gemma-2 wants
        # query_pre_attn_scalar^-0.5 — pre-scale q by the ratio so the
        # ops stay signature-free of it
        q = q * jnp.asarray(
            (cfg.head_dim / cfg.query_pre_attn_scalar) ** 0.5, q.dtype)
    return q, k, v


def _rope_of_kind(cfg: ModelConfig, kind: str, a: jax.Array,
                  positions: jax.Array) -> jax.Array:
    """The rotary of a layer of `kind` (cfg.rope_by_kind): its own theta
    and YaRN remap over the first `share * head_dim` lanes (rotate-half
    WITHIN those lanes, frequencies computed for that lane count, as HF's
    partial_rotary_factor does); the other lanes pass through. An explicit
    YaRN attention_factor scales cos and sin (apply_rope), so it reaches
    the rotated lanes of q and k only."""
    (theta, share, yarn), = [r[1:] for r in cfg.rope_by_kind if r[0] == kind]
    lanes = round(a.shape[-1] * share)  # 64 / 192 is no binary fraction
    if lanes == a.shape[-1]:
        return apply_rope(a, positions, theta, yarn_scaling=yarn)
    with jax.named_scope("attn_qk_rope"):
        turned = apply_rope(a[..., :lanes], positions, theta,
                            yarn_scaling=yarn)
        return jnp.concatenate([turned, a[..., lanes:]], axis=-1)


def _qkv_mla(cfg: ModelConfig, lp: Params, x: jax.Array,
             positions: jax.Array):
    """Absorbed-form MLA projections (DeepSeek-V2 family).

    The cache stores ONE [c_kv | k_rope] row per token (kv_lora_rank +
    qk_rope_head_dim lanes, shared by every head) — the 4x+ KV compression
    that makes MLA a bandwidth win on TPU. Decode never reconstructs
    per-head keys: q_nope is folded through W_UK once per step
    (q_eff = [q_nope @ W_UK | q_rope]), so the generic paged ops score
    queries directly against the latent rows. Their internal
    1/sqrt(latent_width) scale is corrected to MLA's 1/sqrt(nope+rope)
    here. The row is stored ONCE: an MLA model's V pool has no lanes, and
    the attention ops read V from the K rows they already hold. The
    attention output's first kv_lora_rank lanes are probs @ c_kv, which
    _attn_out expands through W_UV (the k_rope lanes are sliced away there).
    """
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    lora = cfg.kv_lora_rank
    if cfg.q_lora_rank > 0:
        # query low-rank path: x -> q_lora_rank -> RMSNorm -> heads
        with jax.named_scope("mla_q_lora"):
            c_q = rms_norm(qeinsum("te,er->tr", x, lp["wq_a"]),
                           lp["q_a_norm"], cfg.rms_norm_eps,
                           cfg.rms_norm_unit_offset)
            q = qeinsum("tr,rhd->thd", c_q, lp["wq_b"])
    else:
        q = qeinsum("te,ehd->thd", x, lp["wq_mla"])  # [T, H, nope+rope]
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta,
                        llama3_scaling=cfg.rope_llama3_scaling,
                        yarn_scaling=cfg.rope_yarn_scaling)
    with jax.named_scope("mla_qkv"):
        kv = qeinsum("te,er->tr", x, lp["w_kv_a"])  # [T, lora+rope]
        c_kv = rms_norm(kv[:, :lora], lp["kv_a_norm"], cfg.rms_norm_eps,
                        cfg.rms_norm_unit_offset)
        k_rope = apply_rope(kv[:, None, lora:], positions, cfg.rope_theta,
                            llama3_scaling=cfg.rope_llama3_scaling,
                            yarn_scaling=cfg.rope_yarn_scaling)[:, 0]
        q_lat = jnp.einsum("thn,hnr->thr", q_nope.astype(jnp.float32),
                           lp["w_uk"].astype(jnp.float32)).astype(q.dtype)
    # generic ops scale scores by 1/sqrt(q.shape[-1]) — the PADDED cache
    # width (cache_head_dim rounds real latent rows up to a 128-lane
    # multiple for Pallas DMA tiling; zero lanes add nothing to scores);
    # MLA's true scale is 1/sqrt(nope+rope)
    width = cfg.cache_head_dim
    fix = (width / (nope + rope)) ** 0.5
    q_eff = jnp.concatenate([q_lat, q_rope], axis=-1) * jnp.asarray(
        fix, q.dtype)
    q_eff = _yarn_softmax_scale(cfg, q_eff)  # DeepSeek yarn mscale^2
    row = jnp.concatenate([c_kv, k_rope], axis=-1)[:, None, :]  # [T, 1, W]
    pad = width - (lora + rope)
    if pad:
        q_eff = jnp.pad(q_eff, ((0, 0), (0, 0), (0, pad)))
        row = jnp.pad(row, ((0, 0), (0, 0), (0, pad)))
    if cfg.is_dsa:
        q_idx, w_idx, k_idx = _dsa_index(cfg, lp, x, c_q, positions)
        return DsaQuery(q_eff, q_idx, w_idx), row, k_idx
    return q_eff, row, row


class DsaQuery(NamedTuple):
    """What an indexed model's attention is handed as `q`: the absorbed
    MLA query and the indexer's side of the selection. `_qkv` returns it
    beside the latent row (`k`) and the indexer's key row (`v`: the row
    the V pool caches, engine/kv_cache.py)."""
    q: jax.Array  # [T, H, W] absorbed query over the latent row
    q_idx: jax.Array  # [T, Hi, Di] indexer queries
    w_idx: jax.Array  # [T, Hi] float32 index-head weights


_INDEX_LN_EPS = 1e-6  # the indexer's LayerNorm (weight and bias)


def _dsa_index(cfg: ModelConfig, lp: Params, x: jax.Array, c_q: jax.Array,
               positions: jax.Array):
    """The lightning indexer's projections (DeepSeek-V3.2): per token,
    index_n_heads queries from the SAME normalised q-LoRA latent the
    attention's queries come from, one key through a LayerNorm, and a
    float32 weight an index head (scaled Hi^-1/2 * Di^-1/2). The rotary
    turns the first qk_rope_head_dim lanes of queries and key. The scores,
    the selection and the sparse attention are ops/attention.dsa_*; the key
    is cached beside the latent row. DEPARTURE from the published code: no
    Hadamard rotation and no FP8 (bf16 here): the rotation is orthogonal,
    so it changes no product."""
    rope, hi, di = cfg.qk_rope_head_dim, cfg.index_n_heads, cfg.index_head_dim
    with jax.named_scope("dsa_indexer"):
        q_idx = qeinsum("tr,rhd->thd", c_q, lp["idx_wq_b"])
        k = qeinsum("te,ed->td", x, lp["idx_wk"]).astype(jnp.float32)
        mu = jnp.mean(k, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(k - mu), axis=-1, keepdims=True)
        k = ((k - mu) * jax.lax.rsqrt(var + _INDEX_LN_EPS)
             * lp["idx_k_norm"].astype(jnp.float32)
             + lp["idx_k_bias"].astype(jnp.float32)).astype(x.dtype)
        k_idx = k[:, None, :]  # [T, 1, Di]

        def turn(a):
            r = apply_rope(a[..., :rope], positions, cfg.rope_theta,
                           llama3_scaling=cfg.rope_llama3_scaling,
                           yarn_scaling=cfg.rope_yarn_scaling)
            return jnp.concatenate([r, a[..., rope:]], axis=-1)

        q_idx, k_idx = turn(q_idx), turn(k_idx)
        w_idx = jnp.einsum("te,eh->th", x, lp["idx_w"],
                           preferred_element_type=jnp.float32
                           ) * (hi ** -0.5 * di ** -0.5)
    return q_idx, w_idx, k_idx


def _selects(cfg: ModelConfig, extent: int) -> bool:
    """Whether a program whose page table (or prompt bucket) addresses
    `extent` tokens runs the selection. At most index_topk tokens: every
    token would be selected, so it takes today's kernels with the V pool
    (the indexer's keys) kept away from them."""
    return cfg.is_dsa and extent > cfg.index_topk


def _dsa_kwargs(cfg: ModelConfig, page_off, pages_per_layer: int,
                page_size: int) -> dict:
    """What ops/attention.dsa_* take beside the operands: the layer's slice
    of the flat pool is named so that the selecting sort can carry a page
    id of `pages_per_layer`'s width, not of the whole pool's."""
    return dict(page_size=page_size, topk=cfg.index_topk, page_off=page_off,
                layer_pages=pages_per_layer)


def _chunk_key_pages(pages: jax.Array, chunk_tokens: int,
                     page_size: int) -> int:
    """Leading entries of a chunked prompt's page table that can hold a key
    one of its queries may see: the prompt bucket's pages, that is the
    table less the trash tail of this program's chunk
    (KVCacheSpec.page_table_width; the engine sizes the tail for the
    longer of the classic and the mixed chunk, so where they differ a few
    trash slots stay, masked as before). The chunk's selection is built
    over those entries alone."""
    return pages.shape[0] - att.chunk_table_tail(chunk_tokens, page_size)


def _dsa_rows(q: DsaQuery, rows) -> DsaQuery:
    """The query's three parts at `rows` (a slice, or an index array)."""
    return DsaQuery(*(x[rows] for x in q))


def _dense_qv(cfg: ModelConfig, q, vp):
    """(query, V pool) as today's attention ops take them: an indexed
    model's absorbed query alone, and None for V (its V pool holds the
    indexer's keys; attention reads V from the K rows)."""
    return (q.q, None) if cfg.is_dsa else (q, vp)


# decode slots the selection runs over: the smallest of these row counts
# that holds the live slots, else the whole batch. Measured alone on a v5e
# (PERF.md section 6, PR 32): a layer's dsa_decode_attention takes 7.7 ms
# over 64 slots and 0.74 ms over 8, whatever is live, because the index
# keys of EVERY slot's whole page table are gathered and scored.
_LIVE_RUNGS = (8, 32)


def _dsa_live_plan(block_tables: jax.Array):
    """Once a step, outside the layer scan: (rung index, slot order with
    the live slots first) for `_dsa_decode_rows`, or None where the batch
    is no larger than the smallest rung."""
    b = block_tables.shape[0]
    rungs = [r for r in _LIVE_RUNGS if r < b]
    if not rungs:
        return None
    live = _live_slots(block_tables)
    n_live = jnp.sum(live.astype(jnp.int32))
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    which = sum((n_live > r).astype(jnp.int32) for r in rungs)
    return which, order


def _dsa_decode_rows(q: DsaQuery, kp, vp, tables, context_lens, plan,
                     **dsa_kw) -> jax.Array:
    """Decode rows' selection and sparse attention over the live slots
    only: `jax.lax.switch` on the device to the smallest rung that holds
    them (the rows gathered, their outputs scattered back, zeros for the
    slots left out: they hold nothing and the engine discards their
    logits); the last rung is the whole batch, the program without the
    ladder. The pools are read where they lie."""
    def whole(q, tables, ctx):
        return att.dsa_decode_attention(*q, kp, vp, tables, ctx, **dsa_kw)

    if plan is None:
        return whole(q, tables, context_lens)
    which, order = plan
    b = tables.shape[0]

    def rung(r):
        def run(q, tables, ctx):
            rows = order[:r]
            o = whole(_dsa_rows(q, rows), tables[rows], ctx[rows])
            return jnp.zeros((b,) + o.shape[1:], o.dtype).at[rows].set(o)
        return run

    branches = [rung(r) for r in _LIVE_RUNGS if r < b] + [whole]
    return jax.lax.switch(which, branches, q, tables, context_lens)


# names on the HLO of the two attention kinds (beside attn_gate, _attn_out)
_ATTN_SCOPE = {FULL: "attn_full", SLIDING: "attn_window"}


def _window_first_page(cfg: ModelConfig, qpos, page_size: int):
    """The logical page that holds the oldest key in reach of a sliding
    layer's query at position `qpos` (itself and the sliding_window - 1
    before it)."""
    return jnp.maximum(qpos - (cfg.sliding_window - 1), 0) // page_size


def _ring_view(ring: jax.Array, first_page) -> jax.Array:
    """A sliding layer's page ring [..., W] as the table the paged ops
    read: entry j is the page of logical page first_page + j (ring slot
    (first_page + j) % W). Positions handed to the ops beside it are
    counted from first_page * page_size, so the ops and kernels see an
    ordinary table whose first page is the oldest one in reach; entries
    past the live ones name pages the ops never read (a horizon bounds
    every read) or, for writes past a prompt's end, pages out of every
    later query's reach."""
    w = ring.shape[-1]
    idx = (jnp.asarray(first_page, jnp.int32)[..., None]
           + jnp.arange(w, dtype=jnp.int32)) % w
    return jnp.take_along_axis(ring, idx, axis=-1)


def _decode_views(cfg: ModelConfig, tables: ByKind, positions, context_lens,
                  page_size: int) -> dict:
    """kind -> (page tables, write positions, context lengths, attention
    kwargs) of the decode rows, once a step outside the layers: a full
    layer's as they are; a sliding layer's ring turned so that the table
    starts at the oldest page in reach, positions counted from there and
    the window handed on as a STATIC int (the kernels mask below it)."""
    out = {FULL: (tables.full, positions, context_lens, {})}
    if SLIDING in cfg.layer_types:
        first = _window_first_page(cfg, positions, page_size)
        base = first * page_size
        out[SLIDING] = (_ring_view(tables.window, first), positions - base,
                        context_lens - base,
                        {"window": cfg.sliding_window})
    return out


def _chunk_views(cfg: ModelConfig, pages: ByKind, start, c: int,
                 page_size: int) -> dict:
    """kind -> (the chunk's page table, its start counted from the table's
    first page, the pages its c rows are written to, attention kwargs)."""
    def view(table, wstart, **akw):
        write = jax.lax.dynamic_slice(
            table, (wstart // page_size,), (c // page_size,))
        return table, wstart, write, akw

    out = {FULL: view(pages.full, start)}
    if SLIDING in cfg.layer_types:
        first = _window_first_page(cfg, start, page_size)
        # the ring holds the pages in reach of the chunk's first query and
        # the chunk's own: the engine sizes it so (kv_cache.window_ring_pages)
        out[SLIDING] = view(_ring_view(pages.window, first),
                            start - first * page_size,
                            window=cfg.sliding_window)
    return out


def _kind_sink(cfg: ModelConfig, lp: Params, kind: str) -> dict:
    """The attention ops' `sink` argument of a layer of `kind`: the layer's
    learned logit a query head [H] float32 where the kind's softmax carries
    one (an operand: read from the parameters, not baked into the
    program), nothing where it does not."""
    return {"sink": lp["sink"]} if kind in cfg.attn_sink_kinds else {}


def _kind_layer_tail(cfg: ModelConfig, lp: Params, x, h, o, token_mask):
    """What follows attention in a layer of a model of kinds: the heads'
    outputs under the model's value scale, the gated output projection on
    the residual, then the MLP or expert layer. Returns (x, the expert
    layer's counts)."""
    if cfg.attn_value_scale != 1.0:
        # on the averaged values: the sum is linear, so the same number as
        # on every V row, and the cache keeps V as projected
        o = o * jnp.asarray(cfg.attn_value_scale, o.dtype)
    x = x + _attn_out(cfg, lp, o, gate_in=h)
    y, counts = _mlp(cfg, lp, rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps),
                     token_mask=token_mask)
    return x + y, counts


def _no_kinds(cfg: ModelConfig, what: str) -> None:
    if cfg.layer_types:
        raise NotImplementedError(
            f"{what} is not implemented for a model whose layers are of "
            "more than one kind (cfg.layer_types): the sliding layers' page "
            "rings are turned for decode rows and one chunk only")


def _no_speculation_under_selection(cfg: ModelConfig) -> None:
    if cfg.is_dsa:
        raise NotImplementedError(
            "speculative verify windows under the sparse selection are not "
            "implemented: each draft position needs its own selected rows "
            "(ops/attention.dsa_*); serve this model without speculation")


def _attn_out(cfg: ModelConfig, lp: Params, o: jax.Array,
              lora_slots=None, gate_in=None) -> jax.Array:
    """Attention output [..., H, D] -> residual [..., E].

    MLA: o's first kv_lora_rank lanes are probs @ c_kv; expand through
    W_UV per head, then the normal output projection. `lora_slots` adds
    the o-projection's per-token adapter delta (see _qkv). `gate_in`: the
    layer's normed input [T, E] where the model gates its heads' outputs
    (cfg.attn_gate "per-head": o <- o * sigmoid(gate_in W_g)[..., None],
    float32, before W_o)."""
    lead = o.shape[:-2]
    h = o.shape[-2]
    o2 = o.reshape((-1, h, o.shape[-1]))
    if cfg.attn_gate and gate_in is not None:
        with jax.named_scope("attn_gate"):
            g = jax.nn.sigmoid(jnp.einsum(
                "te,eh->th", gate_in, lp["wg"],
                preferred_element_type=jnp.float32))
            o2 = (o2.astype(jnp.float32) * g[..., None]).astype(o.dtype)
    if cfg.is_mla:
        with jax.named_scope("mla_out"):
            o2 = jnp.einsum("thr,hrv->thv",
                            o2[..., :cfg.kv_lora_rank].astype(jnp.float32),
                            lp["w_uv"].astype(jnp.float32)).astype(o.dtype)
    out = qeinsum("thd,hde->te", o2, lp["wo"])
    if lora_slots is not None and "lora_oa" in lp:
        from dynamo_tpu.lora import apply as _lora

        out = out + _lora.delta(jnp, o2.reshape(o2.shape[0], -1),
                                lp["lora_oa"], lp["lora_ob"], lora_slots)
    return out.reshape(lead + (out.shape[-1],))


def _mlp(cfg: ModelConfig, lp: Params, x: jax.Array,
         token_mask: jax.Array | None = None):
    """SwiGLU MLP or MoE block. x: [T, E]; token_mask: [T] bool, False for
    padding rows (prefill pads to a page multiple). Returns (y [T, E],
    counts): what the grouped expert layer counted (moe_ops.MOE_STATS,
    int32), None on every other path."""
    def dense(x):
        if cfg.expert_act:  # two matrices, no gate (hybrid models)
            u = qeinsum("te,ef->tf", x, lp["w_up"])
            return qeinsum("tf,fe->te",
                           moe_ops.two_matrix_act(cfg.expert_act, u),
                           lp["w_down"])
        g = qeinsum("te,ef->tf", x, lp["w_gate"])
        u = qeinsum("te,ef->tf", x, lp["w_up"])
        return qeinsum("tf,fe->te", _act(cfg, g) * u, lp["w_down"])

    if not cfg.is_moe or "router" not in lp:
        # dense model, or a leading dense layer of an MoE model (its own
        # parameter stack carries no router)
        return dense(x), None
    if cfg.num_shared_experts > 0:
        with jax.named_scope("moe_shared_expert"):
            shared = dense(x)
    else:
        shared = 0.0
    # MoE: top-k routing, then one of two exact dispatch paths
    # (dynamo_tpu.ops.moe): grouped matmuls over each expert's own tokens
    # where a token picks few of many experts, or this chip holds a share
    # of a wider router (cfg.moe_grouped, decided from shapes); else
    # dense-masked dispatch through a [T, X] combine matrix. Both
    # partition over the `expert` mesh axis via the sharding rules on
    # moe_w_*.
    with jax.named_scope("moe_router"):
        logits = jnp.einsum("te,ex->tx", x, lp["router"],
                            preferred_element_type=jnp.float32)
        topi, weights = moe_ops.route_topk(
            logits, cfg.num_experts_per_tok,
            renormalize=cfg.norm_topk_prob,
            scaling_factor=cfg.routed_scaling_factor,
            scoring=cfg.moe_scoring,
            select_bias=lp.get("router_bias"),
            n_group=cfg.n_group, topk_group=cfg.topk_group)
    if cfg.moe_grouped:
        y, stats = moe_ops.moe_mlp_grouped(
            x, topi, weights, lp.get("moe_w_gate"), lp["moe_w_up"],
            lp["moe_w_down"], expert_offset=cfg.local_expert_offset,
            num_experts=cfg.num_experts, token_mask=token_mask,
            layer=lp.get("moe_layer"), act=cfg.expert_act)
        return shared + y, stats
    combine = moe_ops.scatter_combine(topi, weights, cfg.num_experts,
                                      x.dtype)
    if token_mask is not None:
        # padding rows weigh nothing in any expert
        combine = combine * token_mask.astype(combine.dtype)[:, None]
    return shared + moe_ops.moe_mlp_dense(
        x, combine, lp.get("moe_w_gate"), lp["moe_w_up"], lp["moe_w_down"],
        act=cfg.expert_act), None



# ------------------------------------------------------------------ hybrid --
# A HYBRID model (cfg.mixer_types), in one of three forms. nemotron_h: every
# layer is x + mixer(norm(x)) with ONE mixer of a static kind (Mamba-2 |
# experts | attention); the layers run unrolled (the published pattern has
# no period), each kind over its own parameter stack. falcon_h1
# (cfg.parallel_mixers): every layer is attention AND Mamba-2 on one normed
# input, summed, then a gated MLP; the layers are alike and run as ONE scan
# (_parallel_layers). lfm2_moe (cfg.operator_ffn): every layer an OPERATOR
# (a gated short convolution | GQA attention under q/k norms and a rotary)
# and then an FFN (dense | experts), as two scans whose operator is taken by
# the layer's kind (_operator_layers). Either way a layer that attends owns
# KV pages, and a layer with a Mamba-2 mixer or a short convolution owns a
# STATE SLOT a decode slot (the convolution's is its last K-1 rows alone: no
# recurrence, `k_pages.state` is empty): a sequence's
# state lives in the slot the engine reserved for it at admission (a chunked
# prompt's state rides there from chunk to chunk) and decode row b updates
# slot b where it lies.


class StatePools(NamedTuple):
    """`k_pages` / `v_pages` of a hybrid model, so that the engine's
    plumbing (donation, the fused windows' carry) keeps one shape: the
    attention layers' paged pool [attention layers, pages, page_size,
    KV*D], and one array a Mamba-2 layer over the decode slots: in
    `k_pages` the state S [slots, H, P, N] float32, in `v_pages` the conv's
    last K-1 input rows [slots, K-1, C]. Where the layers run as one scan
    (cfg.parallel_mixers) `state` is ONE array over (layer, slot), [L,
    slots, ...], addressed flat as the pages are (row l * slots + slot): a
    tuple of arrays cannot ride a scan, and a scanned stack would be copied
    whole (64 x 4.2 MB a layer a step). A model whose state layers are
    short convolutions (cfg.operator_ffn) keeps NO recurrent state:
    `k_pages.state` is () and `v_pages.state` the one array of conv rows
    [conv layers, slots, K-1, E]. A model of block-sparse and Lightning
    layers (minicpm_sala) keeps the Lightning states as the one array of
    `k_pages.state` [L_l, slots, H, D, D] float32, no conv rows, and in
    `k_pages.pooled` ONE array of the sparse layers' pooled-key sums
    [L_s, slots, pages a sequence, KV*D] float32 (a row a slot's page:
    ops/sparse_blocks.py)."""
    pages: Any
    state: Any
    pooled: Any = ()


class SlotPages(NamedTuple):
    """A prompt's `pages` operand of a hybrid model: its page table and the
    state slot (a scalar int32) its chunks carry their state in."""
    pages: Any
    slot: Any


# time_step_min / _max / _floor of the family's config: read by the random
# initialiser alone (a checkpoint brings its own dt_bias)
_DT_MIN, _DT_MAX, _DT_FLOOR = 1e-3, 1e-1, 1e-4


def _init_a_log(u):
    """A uniform in [1, 16), as Mamba-2 initialises it; u uniform [0, 1)."""
    return jnp.log(1.0 + 15.0 * u)


def _init_dt_bias(u):
    """softplus^-1 of a step log-uniform in [dt_min, dt_max], floored."""
    dt = jnp.maximum(jnp.exp(math.log(_DT_MIN) + u * (
        math.log(_DT_MAX) - math.log(_DT_MIN))), _DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))


def _init_sink(u):
    """An attention sink's logit, uniform in [-2, 2): never the zero that
    would hide a sink that is dropped or misplaced."""
    return 4.0 * u - 2.0


# param_specs kinds of the float32 vectors (the state-space ones, an
# attention sink) -> their initialiser over uniform [0, 1) draws
# (init_params and the loader's random-int8 path)
SSM_INITS = {"ssm_a_log": _init_a_log, "ssm_dt_bias": _init_dt_bias,
             "sink": _init_sink}
# the leaves of each mixer kind's parameter stack
_MIXER_LEAVES = {
    MAMBA: ("ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias", "ssm_a_log",
            "ssm_d", "ssm_norm", "ssm_out"),
    EXPERTS: ("router", "router_bias", "w_up", "w_down"),
    ATTENTION: ("wq", "wk", "wv", "wo"),
}
# the leaves of each operator kind's stack of an operator-then-FFN model,
# and of its two FFN kinds' (the dense one under DENSE_PREFIX)
_OPERATOR_LEAVES = {
    CONV: ("conv_in", "conv_w", "conv_out"),
    ATTENTION: ("wq", "wk", "wv", "wo", "q_norm", "k_norm"),
    # minicpm_sala: the sparse layers' stack under the plain names, the
    # Lightning layers' under LIGHTNING_PREFIX (the same leaves and out_norm)
    # (w_q / w_k / w_v: the projections with their heads side by side,
    # [L, E, heads * D]. As [L, E, H, D] stacks of 128-lane heads the TPU
    # compiler relaid every one out head-major in every step program, 0.4 GB
    # a Lightning stack: compiled for a described v5e, PR 56)
    SPARSE: ("w_q", "w_k", "w_v", "wo", "q_norm", "k_norm", "w_og"),
    LIGHTNING: tuple(LIGHTNING_PREFIX + k for k in (
        "w_q", "w_k", "w_v", "wo", "q_norm", "k_norm", "w_og", "out_norm")),
}
_DENSE_FFN = ("w_gate", "w_up", "w_down")
_EXPERT_FFN = ("router", "router_bias")


def _operator_param_specs(cfg: ModelConfig):
    """param_specs of an operator-then-FFN model (lfm2_moe): a stack an
    operator kind over that kind's layers, the leading dense FFNs' stack
    under DENSE_PREFIX, the expert layers' router and experts, and the two
    norms of every layer in `operator_norm` / `ffn_norm` [L, E]. The conv's
    taps [L_c, K, E] stay in the model's dtype (6 K numbers a layer)."""
    e, l, kd = cfg.hidden_size, cfg.num_layers, cfg.first_k_dense
    lc, la = cfg.mixer_layers(CONV), cfg.mixer_layers(ATTENTION)

    def w(shape, sigma=None):
        return (shape, "normal",
                sigma if sigma is not None else 1.0 / shape[-1] ** 0.5)

    p = {"embed": w((cfg.vocab_size, e), 0.02),
         "final_norm": ((e,), "ones", 0.0)}
    if not cfg.tie_word_embeddings:
        p["lm_head"] = w((e, cfg.vocab_size), 0.02)
    p["operator_norm"] = ((l, e), "ones", 0.0)
    p["ffn_norm"] = ((l, e), "ones", 0.0)
    if cfg.is_sala:
        # minicpm_sala: a stack a kind (the Lightning layers' k and v have
        # as many heads as q), the output gates [E, H * D], every FFN dense
        h, kv, d, f = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                       cfg.intermediate_size)
        for pre, n, nk in (("", cfg.mixer_layers(SPARSE), kv),
                           (LIGHTNING_PREFIX, cfg.mixer_layers(LIGHTNING),
                            h)):
            if not n:
                continue
            p[pre + "w_q"] = w((n, e, h * d), 1.0 / e ** 0.5)
            p[pre + "w_k"] = w((n, e, nk * d), 1.0 / e ** 0.5)
            p[pre + "w_v"] = w((n, e, nk * d), 1.0 / e ** 0.5)
            p[pre + "wo"] = w((n, h, d, e), 1.0 / (h * d) ** 0.5)
            p[pre + "q_norm"] = ((n, d), "ones", 0.0)
            p[pre + "k_norm"] = ((n, d), "ones", 0.0)
            p[pre + "w_og"] = w((n, e, h * d), 1.0 / e ** 0.5)
            if pre:
                p[pre + "out_norm"] = ((n, h * d), "ones", 0.0)
        p["w_gate"] = w((l, e, f), 1.0 / e ** 0.5)
        p["w_up"] = w((l, e, f), 1.0 / e ** 0.5)
        p["w_down"] = w((l, f, e), 1.0 / f ** 0.5)
        return p
    p["conv_in"] = w((lc, e, 3 * e), 1.0 / e ** 0.5)  # [B | C | u]
    p["conv_w"] = w((lc, cfg.conv_kernel, e), 1.0 / cfg.conv_kernel ** 0.5)
    p["conv_out"] = w((lc, e, e), 1.0 / e ** 0.5)
    if la:
        h, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        p["wq"] = w((la, e, h, d), 1.0 / e ** 0.5)
        p["wk"] = w((la, e, kv, d), 1.0 / e ** 0.5)
        p["wv"] = w((la, e, kv, d), 1.0 / e ** 0.5)
        p["wo"] = w((la, h, d, e), 1.0 / (h * d) ** 0.5)
        p["q_norm"] = ((la, d), "ones", 0.0)
        p["k_norm"] = ((la, d), "ones", 0.0)
    if kd:
        fd = cfg.dense_intermediate_size
        p[DENSE_PREFIX + "w_gate"] = w((kd, e, fd), 1.0 / e ** 0.5)
        p[DENSE_PREFIX + "w_up"] = w((kd, e, fd), 1.0 / e ** 0.5)
        p[DENSE_PREFIX + "w_down"] = w((kd, fd, e), 1.0 / fd ** 0.5)
    if cfg.is_moe:
        le, x, f = l - kd, cfg.held_experts, cfg.intermediate_size
        fp = cfg.expert_dims_stored[1]  # f and zero lanes (zero_lanes)
        p["router"] = w((le, e, cfg.num_experts), 0.02)
        p["router_bias"] = ((le, cfg.num_experts), "zeros", 0.0)
        p["moe_w_gate"] = w((le, x, e, fp), 1.0 / e ** 0.5)
        p["moe_w_up"] = w((le, x, e, fp), 1.0 / e ** 0.5)
        p["moe_w_down"] = w((le, x, fp, e), 1.0 / f ** 0.5)
    return p


def _hybrid_param_specs(cfg: ModelConfig):
    """param_specs of a hybrid model: one stack a mixer kind on a leading
    axis of that kind's layers, the norm before every layer's mixer in
    `mixer_norm` [L, E]. An operator-then-FFN model's: _operator_param_specs."""
    if cfg.operator_ffn:
        return _operator_param_specs(cfg)
    e, f = cfg.hidden_size, cfg.intermediate_size
    lm, le, la = (cfg.mixer_layers(k) for k in (MAMBA, EXPERTS, ATTENTION))

    def w(shape, sigma=None):
        return (shape, "normal",
                sigma if sigma is not None else 1.0 / shape[-1] ** 0.5)

    p = {
        "embed": w((cfg.vocab_size, e), 0.02),
        "final_norm": ((e,), "ones", 0.0),
        "lm_head": w((e, cfg.vocab_size), 0.02),
    }
    if cfg.parallel_mixers:
        # every leaf over ALL layers: the norm before the two mixers, both
        # mixers' leaves, the norm before the MLP, the gated MLP
        lm = la = cfg.num_layers
        p["attn_norm"] = ((lm, e), "ones", 0.0)
    else:
        p["mixer_norm"] = ((cfg.num_layers, e), "ones", 0.0)
    if lm:
        hm, d_in, c = (cfg.mamba_num_heads, cfg.mamba_d_inner,
                       cfg.mamba_conv_dim)
        # [z | x B C | dt]
        p["ssm_in"] = w((lm, e, d_in + c + hm), 1.0 / e ** 0.5)
        p["ssm_conv_w"] = w((lm, cfg.conv_kernel, c),
                            1.0 / cfg.conv_kernel ** 0.5)
        p["ssm_conv_b"] = ((lm, c), "zeros", 0.0)
        p["ssm_dt_bias"] = ((lm, hm), "ssm_dt_bias", 0.0)
        p["ssm_a_log"] = ((lm, hm), "ssm_a_log", 0.0)
        p["ssm_d"] = ((lm, hm), "ones", 0.0)
        p["ssm_norm"] = ((lm, d_in), "ones", 0.0)
        p["ssm_out"] = w((lm, d_in, e), 1.0 / d_in ** 0.5)
    if la:
        h, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        p["wq"] = w((la, e, h, d), 1.0 / e ** 0.5)
        p["wk"] = w((la, e, kv, d), 1.0 / e ** 0.5)
        p["wv"] = w((la, e, kv, d), 1.0 / e ** 0.5)
        p["wo"] = w((la, h, d, e), 1.0 / (h * d) ** 0.5)
    if le:
        x, fs = cfg.held_experts, cfg.shared_expert_width
        # e x f and zero rows / lanes around them (zero_lanes)
        ep, fp = cfg.expert_dims_stored
        p["router"] = w((le, e, cfg.num_experts), 0.02)
        p["router_bias"] = ((le, cfg.num_experts), "zeros", 0.0)
        p["moe_w_up"] = w((le, x, ep, fp), 1.0 / e ** 0.5)
        p["moe_w_down"] = w((le, x, fp, ep), 1.0 / f ** 0.5)
        if fs:
            p["w_up"] = w((le, e, fs), 1.0 / e ** 0.5)
            p["w_down"] = w((le, fs, e), 1.0 / fs ** 0.5)
    if cfg.parallel_mixers:
        l = cfg.num_layers
        p["mlp_norm"] = ((l, e), "ones", 0.0)
        p["w_gate"] = w((l, e, f), 1.0 / e ** 0.5)
        p["w_up"] = w((l, e, f), 1.0 / e ** 0.5)
        p["w_down"] = w((l, f, e), 1.0 / f ** 0.5)
    return p


def zero_lanes(cfg: ModelConfig) -> Dict[str, Tuple[Tuple[int, int], ...]]:
    """name -> ((axis, real extent), ...) of the leaves a hybrid model
    stores larger than the model is (ModelConfig.expert_dims_stored):
    everything past a real extent along its axis is ZERO, in random weights
    as in loaded ones. {} where nothing is padded."""
    if not cfg.mixer_types:
        return {}
    e, f = cfg.hidden_size, cfg.intermediate_size
    if cfg.operator_ffn:
        # the width alone is stored wider (gated experts: silu(0) * 0 = 0)
        if not cfg.is_moe or cfg.expert_dims_stored[1] == f:
            return {}
        return {"moe_w_gate": ((3, f),), "moe_w_up": ((3, f),),
                "moe_w_down": ((2, f),)}
    if cfg.expert_dims_stored == (e, f):
        return {}
    return {"moe_w_up": ((2, e), (3, f)), "moe_w_down": ((2, f), (3, e))}


def _zero_past(a, cuts):
    """`a` with everything past each (axis, extent) of `cuts` zero (a
    QTensor: its int8 values; the scales are per output channel and stay)."""
    def cut(v):
        for axis, extent in cuts:
            keep = jnp.arange(v.shape[axis]) < extent
            shape = [1] * v.ndim
            shape[axis] = -1
            v = v * keep.reshape(shape).astype(v.dtype)
        return v
    if isinstance(a, quant.QTensor):
        return type(a)(cut(a.q), a.scale)
    return cut(a)


def _mup_vector(cfg: ModelConfig) -> jax.Array:
    """falcon_h1's ssm_in_multiplier and ssm_multipliers as ONE vector over
    the input projection's output lanes [z | x | B | C | dt] (the
    projection is linear: scaling its input scales its output)."""
    m = cfg.multipliers
    gn = cfg.mamba_n_groups * cfg.ssm_state_size
    widths = (cfg.mamba_d_inner, cfg.mamba_d_inner, gn, gn,
              cfg.mamba_num_heads)
    return jnp.concatenate([jnp.full((w,), m.ssm_in * v, jnp.float32)
                            for w, v in zip(widths, m.ssm)])


def _mamba_mixer(cfg: ModelConfig, lp: Params, u: jax.Array, ssm, conv,
                 decode=None, chunk=None, base=None):
    """The Mamba-2 mixer over u [T, E] (normed) -> (y [T, E], ssm, conv).

    `base` (a traced scalar, or None): ssm and conv are pools over (layer,
    slot) addressed flat, and this layer's slot i is row base + i.

    The rows are `decode` = (b, live [b] bool, its ssm_ops.LiveSlots): b
    rows, one token a decode slot, row i updating slot i where live; then
    `chunk` = (c, slot, n_valid, fresh): c rows of one prompt whose state
    lies in `slot`, the first n_valid real, starting from zero where
    `fresh` (a traced bool: the prompt's first chunk). The projections, the
    gate and the norm run over all rows at once; the conv and the
    recurrence a part each. An empty slot's state stays as it was (the
    kernel never visits it; the XLA twin hands it dt = 0), and so does a
    state under a chunk's padding rows (dt = 0: ops/ssm.py)."""
    hm, pm = cfg.mamba_num_heads, cfg.mamba_head_dim
    g, n = cfg.mamba_n_groups, cfg.ssm_state_size
    d_in, c_dim = cfg.mamba_d_inner, cfg.mamba_conv_dim
    with jax.named_scope("ssm_in_proj"):
        zxbcdt = qeinsum("te,ef->tf", u, lp["ssm_in"])
        if cfg.multipliers is not None:
            zxbcdt = (zxbcdt.astype(jnp.float32)
                      * _mup_vector(cfg)).astype(zxbcdt.dtype)
    z, xbc = zxbcdt[:, :d_in], zxbcdt[:, d_in:d_in + c_dim]
    dt = jax.nn.softplus(zxbcdt[:, d_in + c_dim:].astype(jnp.float32)
                         + lp["ssm_dt_bias"].astype(jnp.float32))
    a = -jnp.exp(lp["ssm_a_log"].astype(jnp.float32))

    def parts(xo):
        t = xo.shape[0]
        return (xo[:, :d_in].reshape(t, hm, pm),
                xo[:, d_in:d_in + g * n].reshape(t, g, n),
                xo[:, d_in + g * n:].reshape(t, g, n))

    ys, off = [], 0
    if decode is not None:
        b, live, slots = decode
        with jax.named_scope("ssm_conv"):
            if base is None:
                xo, conv = ssm_ops.conv_step(
                    xbc[:b], conv, lp["ssm_conv_w"], lp["ssm_conv_b"], live)
            else:
                xo, own = ssm_ops.conv_step(
                    xbc[:b], jax.lax.dynamic_slice_in_dim(conv, base, b),
                    lp["ssm_conv_w"], lp["ssm_conv_b"], live)
                conv = jax.lax.dynamic_update_slice_in_dim(conv, own, base, 0)
        with jax.named_scope("ssm_scan"):
            x, bm, cm = parts(xo)
            y, ssm = ssm_ops.update(x, dt[:b], a, bm, cm, lp["ssm_d"], ssm,
                                    live, slots, base=base)
        ys.append(y.reshape(b, d_in))
        off = b
    if chunk is not None:
        c, slot, n_valid, fresh = chunk
        if base is not None:
            slot = slot + base
        rows = slice(off, off + c)
        with jax.named_scope("ssm_conv"):
            prev = jnp.where(fresh, jnp.zeros_like(conv[slot]), conv[slot])
            xo, kept = ssm_ops.conv_rows(
                xbc[rows], prev, lp["ssm_conv_w"], lp["ssm_conv_b"], n_valid)
        with jax.named_scope("ssm_scan"):
            x, bm, cm = parts(xo)
            init = jnp.where(fresh, jnp.zeros_like(ssm[slot]), ssm[slot])
            y, final = ssm_ops.scan_chunked(
                x, jnp.where((jnp.arange(c) < n_valid)[:, None], dt[rows],
                             0.0),
                a, bm, cm, lp["ssm_d"], init, cfg.ssm_chunk_size)
            ssm = ssm.at[slot].set(final.astype(ssm.dtype))
            conv = conv.at[slot].set(kept)
        ys.append(y.reshape(c, d_in))
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
    with jax.named_scope("ssm_gate_norm"):
        y = ssm_ops.gate_norm(y, z, lp["ssm_norm"], g,
                              cfg.rms_norm_eps).astype(u.dtype)
    with jax.named_scope("ssm_out_proj"):
        return qeinsum("tf,fe->te", y, lp["ssm_out"]), ssm, conv


def _parallel_layers(cfg: ModelConfig, params: Params, x: jax.Array,
                     k_pages: StatePools, v_pages: StatePools, attend,
                     positions, decode=None, chunk=None):
    """_hybrid_layers for a model whose every layer is attention AND
    Mamba-2 on one normed input, summed, then a gated MLP (falcon_h1): ONE
    scan over the layers. The pages and the states ride the carry FLAT
    (_scan_layers_paged says why): layer l's page p is row l * P + p of the
    pool and its slot b row l * B + b of the states, so an iteration
    touches the rows it writes and the pages and live slots it reads, and
    no layer's pool or states are sliced out or stacked back. The fixed
    multipliers apply where the tensors are narrowest (a projection is
    linear): tests/test_falcon_h1.py holds this placement to the
    reference's, which applies each where the published description does."""
    m = cfg.multipliers
    pool, vpool = k_pages.pages.shape, v_pages.pages.shape
    (ssm,), (conv,) = k_pages.state, v_pages.state
    slots = ssm.shape[1]
    carry = (x, k_pages.pages.reshape((-1,) + pool[2:]),
             v_pages.pages.reshape((-1,) + vpool[2:]),
             ssm.reshape((-1,) + ssm.shape[2:]),
             conv.reshape((-1,) + conv.shape[2:]))

    def layer(carry, xs):
        x, kp, vp, sp, cp = carry
        lp, idx = xs
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        with jax.named_scope("mixer_attn"):
            q, k, v = _qkv(cfg, lp, h, positions)
            # attention_in on q, k, v (their projections are linear);
            # key_multiplier on k: a rotary is linear too
            if m.attention_in != 1.0:
                q, v = q * m.attention_in, v * m.attention_in
            k = k * (m.attention_in * m.key)
            with jax.named_scope("attn_full"):
                o, kp, vp = attend(q, k, v, kp, vp, idx * pool[1])
            ya = _attn_out(cfg, lp, o) * m.attention_out
        with jax.named_scope("mixer_ssm"):
            ys, sp, cp = _mamba_mixer(cfg, lp, h, sp, cp, decode, chunk,
                                      base=idx * slots)
            ys = ys * m.ssm_out
        with jax.named_scope("mixer_sum"):
            x = x + ya + ys
        with jax.named_scope("mlp_dense"):
            h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            g = qeinsum("te,ef->tf", h, lp["w_gate"])
            u = qeinsum("te,ef->tf", h, lp["w_up"])
            y = qeinsum("tf,fe->te", jax.nn.silu(g * m.mlp[0]) * u,
                        lp["w_down"])
            x = x + y * m.mlp[1]
        return (x, kp, vp, sp, cp), None

    (x, kp, vp, sp, cp), _ = jax.lax.scan(
        layer, carry, (_layer_params(params), jnp.arange(cfg.num_layers)))
    return (x, StatePools(kp.reshape(pool), (sp.reshape(ssm.shape),)),
            StatePools(vp.reshape(vpool), (cp.reshape(conv.shape),)), None)


def _conv_operator(cfg: ModelConfig, lp: Params, h: jax.Array, conv,
                   decode, chunk, base):
    """The gated short convolution over h [T, E] (normed) -> (y [T, E],
    conv). `conv` is the pool of conv rows over (layer, slot) addressed
    flat, this layer's slot i at row base + i; the rows are `decode` /
    `chunk` as _mamba_mixer's. The decode rows read and write the layer's
    slots' rows as ONE block (slots x (K-1) x E: 512 KB at 64 slots, once a
    layer-step), an empty slot's coming back bit for bit; a chunk reads and
    writes its own slot's rows."""
    with jax.named_scope("conv_in_proj"):
        bcu = qeinsum("te,ef->tf", h, lp["conv_in"])  # [B | C | u]
    ys, off = [], 0
    with jax.named_scope("conv_gate_taps"):
        if decode is not None:
            b, live, _ = decode
            y, own = short_conv.gated_step(
                bcu[:b], jax.lax.dynamic_slice_in_dim(conv, base, b),
                lp["conv_w"], live)
            conv = jax.lax.dynamic_update_slice_in_dim(conv, own, base, 0)
            ys.append(y)
            off = b
        if chunk is not None:
            c, slot, n_valid, fresh = chunk
            row = base + slot
            prev = jax.lax.dynamic_index_in_dim(conv, row, keepdims=False)
            y, kept = short_conv.gated_rows(
                bcu[off:off + c], jnp.where(fresh, jnp.zeros_like(prev), prev),
                lp["conv_w"], n_valid)
            conv = jax.lax.dynamic_update_index_in_dim(conv, kept, row, 0)
            ys.append(y)
        y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
    with jax.named_scope("conv_out_proj"):
        return qeinsum("te,ef->tf", y, lp["conv_out"]), conv


def lightning_slopes(cfg: ModelConfig) -> jax.Array:
    """[L, H] float32: the decay rate of head h (1 .. H) of layer l (0 .. L
    - 1, counted over ALL layers) of a Lightning layer, 2^(-8 h / H) (1 - l
    / (L - 1 + 1e-5) + 1e-5), as MiniMax-01's Lightning attention sets it
    (transformers' MiniMaxLightningAttention.get_slope_rate)."""
    h, l = cfg.mamba_num_heads, cfg.num_layers
    # in float64 on the host (the config is static): the last layer's
    # factor is 2e-5, what float32 leaves of 1 - (l - 1) / (l - 1 + 1e-5)
    base = 2.0 ** (-8.0 * np.arange(1, h + 1) / h)
    factor = 1.0 - np.arange(l) / (l - 1 + 1e-5) + 1e-5
    return jnp.asarray(factor[:, None] * base[None, :], jnp.float32)


def _gated(o: jax.Array, h: jax.Array, w_og) -> jax.Array:
    """o [T, H * D] (float32) under minicpm_sala's output gate: o *
    sigmoid(h W_g), lane by lane, in the model's dtype."""
    with jax.named_scope("attn_gate"):
        g = jax.nn.sigmoid(qeinsum("te,ef->tf", h, w_og).astype(jnp.float32))
        return (o.astype(jnp.float32) * g).astype(h.dtype)


def _lightning_operator(cfg: ModelConfig, lp: Params, h: jax.Array, state,
                        decode, chunk, base, positions, slope):
    """Lightning linear attention over h [T, E] (normed) -> (y [T, E],
    state): S_t = e^(-s) S_(t-1) + k_t (x) v_t, o_t = q_t S_t a head, which
    IS ops/ssm.py's recurrence with x = v, B = k, C = q, a = -s, D = 0 and
    dt = 1 on a real row, 0 on padding. `state` is the pool of states over
    (layer, slot) addressed flat, this layer's slot i at row base + i; the
    rows are `decode` / `chunk` as _mamba_mixer's; `slope` [H] float32."""
    hm, d = cfg.mamba_num_heads, cfg.mamba_head_dim
    t = h.shape[0]
    with jax.named_scope("lightning_in_proj"):
        q, k, v = (qeinsum("te,ef->tf", h, lp[name]).reshape(t, hm, d)
                   for name in ("w_q", "w_k", "w_v"))
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        q = q * jnp.asarray(d ** -0.5, q.dtype)
    a, zero = -slope, jnp.zeros((hm,), jnp.float32)
    ys, off = [], 0
    if decode is not None:
        b, live, slots = decode
        with jax.named_scope("lightning_update"):
            y, state = ssm_ops.update(
                v[:b], jnp.ones((b, hm), jnp.float32), a, k[:b], q[:b], zero,
                state, live, slots, base=base)
        ys.append(y)
        off = b
    if chunk is not None:
        c, slot, n_valid, fresh = chunk
        row = base + slot
        with jax.named_scope("lightning_scan"):
            prev = jax.lax.dynamic_index_in_dim(state, row, keepdims=False)
            real = (jnp.arange(c) < n_valid).astype(jnp.float32)
            y, final = ssm_ops.scan_chunked(
                v[off:off + c], jnp.broadcast_to(real[:, None], (c, hm)), a,
                k[off:off + c], q[off:off + c], zero,
                jnp.where(fresh, jnp.zeros_like(prev), prev),
                cfg.ssm_chunk_size)
            state = jax.lax.dynamic_update_index_in_dim(state, final, row, 0)
        ys.append(y)
    y = (ys[0] if len(ys) == 1 else jnp.concatenate(ys)).reshape(t, hm * d)
    with jax.named_scope("lightning_gate_norm"):
        y = rms_norm(y, lp["out_norm"].astype(jnp.float32), cfg.rms_norm_eps)
        y = _gated(y, h, lp["w_og"])
    with jax.named_scope("lightning_out_proj"):
        return qeinsum("thd,hde->te", y.reshape(t, hm, d), lp["wo"]), state


def _operator_layers(cfg: ModelConfig, params: Params, x: jax.Array,
                     k_pages: StatePools, v_pages: StatePools, attend,
                     token_mask, positions, decode=None, chunk=None):
    """_hybrid_layers for an operator-then-FFN model: layer l is
    x += operator_l(norm(x)); x += ffn_l(norm(x)). lfm2_moe: the operator a
    gated short convolution or attention by cfg.mixer_types, the FFN dense
    in the first cfg.first_k_dense layers and the experts behind them.
    minicpm_sala: the operator block-sparse attention or Lightning linear
    attention, every FFN dense, each branch under the family's residual
    scale.

    A scan a run of layers with one FFN kind (lfm2_moe: the dense layers
    and the expert layers; minicpm_sala: ONE). A scan whose layers are of
    both operator kinds takes the operator by a `lax.cond` on the layer's
    kind (one body a kind, the kind's parameter stack indexed by the
    layer's index within its kind); the pages and the states (lfm2_moe: the
    conv rows; minicpm_sala: the Lightning states and the sparse layers'
    page sums, a row a slot) ride the carry FLAT as _parallel_layers carries
    them (layer l's page p at row l * P + p, its slot b at row l * B + b), all
    through either branch, so no pool and no stack is sliced out or copied.
    The experts' whole stack and the layer's index go to the grouped matmul
    as _scan_layers_paged hands them."""
    pool, vpool = k_pages.pages.shape, v_pages.pages.shape
    sala = cfg.is_sala
    # the state arrays the operators carry, each over (layer, slot | page)
    held = ((k_pages.state[0], k_pages.pooled[0]) if sala
            else (v_pages.state[0],))
    slots = held[0].shape[1]
    kinds, kd = cfg.mixer_types, cfg.first_k_dense
    # a layer's index among the layers of its kind
    within = [kinds[:i].count(k) for i, k in enumerate(kinds)]
    stacks = {kind: {k.rsplit(".", 1)[-1]: params[k] for k in leaves
                     if k in params}
              for kind, leaves in _OPERATOR_LEAVES.items() if kind in kinds}
    # the cond's first branch, then its second
    pair = (SPARSE, LIGHTNING) if sala else (CONV, ATTENTION)

    def at(kind, j):
        return {k: jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, j, keepdims=False), v)
            for k, v in stacks[kind].items()}

    def conv_op(h, kp, vp, st, j):
        y, cp = _conv_operator(cfg, at(CONV, j), h, st[0], decode, chunk,
                               j * slots)
        return y, kp, vp, (cp,)

    def attn_op(h, kp, vp, st, j):
        lp = at(ATTENTION, j)
        q, k, v = _qkv(cfg, lp, h, positions)
        with jax.named_scope("attn_full"):
            o, kp, vp = attend(q, k, v, kp, vp, j * pool[1])
        return _attn_out(cfg, lp, o), kp, vp, st

    def sparse_op(h, kp, vp, st, j, slope):
        lp = at(SPARSE, j)
        q, k, v = (qeinsum("te,ef->tf", h, lp[name]).reshape(
            h.shape[0], -1, cfg.head_dim) for name in ("w_q", "w_k", "w_v"))
        with jax.named_scope("attn_qk_norm"):  # and no rotary
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
        with jax.named_scope("attn_sparse"):
            o, kp, vp, sums, seen = attend(q, k, v, kp, vp, j * pool[1],
                                           st[1], j * slots)
        o = _gated(o.reshape(o.shape[0], -1), h, lp["w_og"])
        y = qeinsum("thd,hde->te", o.reshape(q.shape), lp["wo"])
        return y, kp, vp, (st[0], sums, st[2] + seen)

    def lightning_op(h, kp, vp, st, j, slope):
        y, sp = _lightning_operator(cfg, at(LIGHTNING, j), h, st[0], decode,
                                    chunk, j * slots, positions, slope)
        return y, kp, vp, (sp,) + st[1:]

    ops = {CONV: conv_op, ATTENTION: attn_op, SPARSE: sparse_op,
           LIGHTNING: lightning_op}
    # minicpm_sala's residual scale (scale_depth / sqrt(layers))
    branch = (jnp.asarray(cfg.scale_depth / cfg.num_layers ** 0.5,
                          x.dtype) if cfg.scale_depth else None)

    def segment(carry, first, last, ffn, scanned):
        """Layers [first, last) as one scan; ffn(xs, h) -> (y, counts) with
        `scanned`, the FFN kind's leaves over these layers, sliced in xs."""
        of = set(kinds[first:last])

        def layer(carry, xs):
            x, kp, vp, st, counts = carry
            h = rms_norm(x, xs["operator_norm"], cfg.rms_norm_eps)
            extra = (xs["slope"],) if sala else ()
            if len(of) == 1:
                y, kp, vp, st = ops[min(of, key=pair.index)](
                    h, kp, vp, st, xs["within"], *extra)
            else:
                y, kp, vp, st = jax.lax.cond(
                    xs["is_first"], ops[pair[0]], ops[pair[1]], h, kp, vp,
                    st, xs["within"], *extra)
            x = x + (y if branch is None else y * branch)
            y, c = ffn(xs, rms_norm(x, xs["ffn_norm"], cfg.rms_norm_eps))
            if c is not None:
                counts = counts + c
            return (x + (y if branch is None else y * branch), kp, vp, st,
                    counts), None

        xs = {"operator_norm": params["operator_norm"][first:last],
              "ffn_norm": params["ffn_norm"][first:last],
              "is_first": jnp.asarray(
                  [k == pair[0] for k in kinds[first:last]]),
              "within": jnp.asarray(within[first:last], jnp.int32),
              "ffn_layer": jnp.arange(last - first, dtype=jnp.int32),
              **scanned}
        if sala:
            xs["slope"] = lightning_slopes(cfg)[first:last]
        return jax.lax.scan(layer, carry, xs)[0]

    def dense_ffn(xs, h):
        with jax.named_scope("mlp_dense"):
            return _mlp(cfg, {k: xs[DENSE_PREFIX + k] for k in _DENSE_FFN}, h)

    def expert_ffn(xs, h):
        lp = {k: xs[k] for k in _EXPERT_FFN}
        lp.update({k: params[k] for k in _EXPERT_STACKS},
                  moe_layer=xs["ffn_layer"])
        return _mlp(cfg, lp, h, token_mask=token_mask)

    def every_ffn(xs, h):
        with jax.named_scope("mlp_dense"):
            return _mlp(cfg, {k: xs[k] for k in _DENSE_FFN}, h)

    kp = k_pages.pages.reshape((-1,) + pool[2:])
    vp = v_pages.pages.reshape((-1,) + vpool[2:])
    flat = tuple(a.reshape((-1,) + a.shape[2:]) for a in held)
    if sala:  # and what the chunks' masked attention visited and skipped
        flat += (jnp.zeros((len(sparse_ops.CHUNK_STATS),), jnp.int32),)
    carry = (x, kp, vp, flat,
             jnp.zeros((len(moe_ops.MOE_STATS),), jnp.int32))
    if sala:
        carry = segment(carry, 0, cfg.num_layers, every_ffn,
                        {k: params[k] for k in _DENSE_FFN})
        x, kp, vp, (sp, sums, seen), _ = carry
        return (x, StatePools(kp.reshape(pool), (sp.reshape(held[0].shape),),
                              (sums.reshape(held[1].shape),)),
                StatePools(vp.reshape(vpool), ()), seen)
    if kd:
        carry = segment(carry, 0, kd, dense_ffn, {
            DENSE_PREFIX + k: params[DENSE_PREFIX + k] for k in _DENSE_FFN})
    if cfg.is_moe:
        carry = segment(carry, kd, cfg.num_layers, expert_ffn,
                        {k: params[k] for k in _EXPERT_FFN})
    x, kp, vp, (cp,), counts = carry
    return (x, StatePools(kp.reshape(pool), ()),
            StatePools(vp.reshape(vpool), (cp.reshape(held[0].shape),)),
            counts if cfg.moe_grouped else None)


def _hybrid_layers(cfg: ModelConfig, params: Params, x: jax.Array,
                   k_pages: StatePools, v_pages: StatePools, attend,
                   token_mask, decode=None, chunk=None, positions=None):
    """Every layer of a hybrid model over x [T, E]: x + mixer(norm(x)).
    `attend(q, k, v, kp, vp, page_off)` -> (o, kp, vp) writes the rows'
    K / V into the flat pool and attends; `decode` / `chunk` are
    _mamba_mixer's. Returns (x, k_pages, v_pages, the expert layers'
    counts or None). `positions` [T]: the rows' positions, for the
    rotary of a model whose attention takes one (None elsewhere)."""
    if cfg.parallel_mixers:
        return _parallel_layers(cfg, params, x, k_pages, v_pages, attend,
                                positions, decode, chunk)
    if cfg.operator_ffn:
        return _operator_layers(cfg, params, x, k_pages, v_pages, attend,
                                token_mask, positions, decode, chunk)
    pool = k_pages.pages.shape
    kp = k_pages.pages.reshape((-1,) + pool[2:])
    vp = v_pages.pages.reshape((-1,) + pool[2:])
    ssm, conv = list(k_pages.state), list(v_pages.state)
    counts = (jnp.zeros((len(moe_ops.MOE_STATS),), jnp.int32)
              if cfg.moe_grouped else None)
    seen = dict.fromkeys(_MIXER_LEAVES, 0)
    for i, kind in enumerate(cfg.mixer_types):
        j = seen[kind]
        seen[kind] += 1
        lp = {k: jax.tree.map(lambda a: a[j], params[k])
              for k in _MIXER_LEAVES[kind] if k in params}
        h = rms_norm(x, params["mixer_norm"][i], cfg.rms_norm_eps)
        if kind == MAMBA:
            y, ssm[j], conv[j] = _mamba_mixer(cfg, lp, h, ssm[j], conv[j],
                                              decode, chunk)
        elif kind == ATTENTION:
            # no rotary: position reaches the layer through the states
            q = qeinsum("te,ehd->thd", h, lp["wq"])
            k = qeinsum("te,ekd->tkd", h, lp["wk"])
            v = qeinsum("te,ekd->tkd", h, lp["wv"])
            with jax.named_scope("attn_full"):
                o, kp, vp = attend(q, k, v, kp, vp, j * pool[1])
            y = _attn_out(cfg, lp, o)
        else:
            # the experts' WHOLE stack and the layer's index, as
            # _scan_layers_paged hands them (ops/moe.moe_mlp_grouped)
            lp.update(moe_w_up=params["moe_w_up"],
                      moe_w_down=params["moe_w_down"], moe_layer=j)
            y, c = _mlp(cfg, lp, h, token_mask=token_mask)
            if counts is not None:
                counts = counts + c
        x = x + y
    return (x, StatePools(kp.reshape(pool), tuple(ssm)),
            StatePools(vp.reshape(v_pages.pages.shape), tuple(conv)), counts)


def _hybrid_rotary(cfg: ModelConfig) -> bool:
    """Whether a hybrid model's attention takes a rotary (nemotron_h's
    takes none: position reaches it through the states)."""
    return cfg.parallel_mixers or cfg.operator_ffn


def _sparse_selects(cfg: ModelConfig, table_pages: int, chunk_tokens: int,
                    page_size: int) -> bool:
    """Whether a program whose page table has `table_pages` entries (a
    chunk's: its trash tail taken off again; 0 chunk tokens: a decode
    table) can hold a context past sparse_dense_len: any other keeps the
    dense kernels and traces no selection at all."""
    tail = att.chunk_table_tail(chunk_tokens, page_size) if chunk_tokens else 0
    return (table_pages - tail) * page_size > cfg.sparse_dense_len


def _sparse_chunk(cfg: ModelConfig, q, kp, vp, sums, row, table, start,
                  page_size: int, dense):
    """A sparse layer's chunk attention -> (o, what the masked attention
    counted: sparse_ops.CHUNK_STATS); `row`: the sequence's row of `sums`.
    Under a table that cannot hold a
    context past sparse_dense_len: `dense()`, the chunk's attention over
    its whole context through the kernels as they are. Under any other the
    masked attention, every query under its own membership mask (a query at
    or under sparse_dense_len: every block up to its own), with NO
    conditional between the two: a prompt crosses the threshold between two
    chunks of one program, and a `lax.cond` around a read of the pools had
    both pools copied, 1 GB each (compiled for a described v5e, PR 56)."""
    if not _sparse_selects(cfg, table.shape[0], q.shape[0], page_size):
        return dense(), jnp.zeros((len(sparse_ops.CHUNK_STATS),), jnp.int32)
    o, seen, skipped = sparse_ops.chunk_attention(
        q, kp, vp, jax.lax.dynamic_slice_in_dim(sums, row, 1), table, start,
        sparse_ops.sizes_of(cfg), page_size=page_size,
        num_kv_heads=cfg.cache_kv_heads)
    return o, jnp.stack([seen, skipped])


def _hybrid_prefill(cfg, params, tokens, n_valid, k_pages, v_pages,
                    pages: SlotPages, start, page_size: int):
    """A whole prompt (start None: from position 0, attention over the
    prompt itself) or one chunk of it from `start` (attention over the
    cached pages and the chunk): the final state goes to pages.slot."""
    c = tokens.shape[0]
    token_mask = jnp.arange(c) < n_valid
    table = pages.pages
    if start is None and cfg.operator_ffn:
        # the whole prompt as ONE chunk from position 0: behind the
        # operator's conditional the whole-prompt form (attention over q, k,
        # v themselves, the pools written and not read) had both pools
        # copied through the convolution's branch, 1.6 GB a program
        # (compiled for a described v5e, PR 52); the chunk's form is not
        start = 0

    def attend(q, k, v, kp, vp, off):
        if start is None:
            o = att.prefill_attention(q, k, v, n_valid)
            kp, vp = att.write_kv_prefill(kp, vp, k, v, table + off,
                                          page_size=page_size)
            return o, kp, vp
        write = jax.lax.dynamic_slice(table, (start // page_size,),
                                      (c // page_size,))
        kp, vp = att.write_kv_prefill(kp, vp, k, v, write + off,
                                      page_size=page_size)
        o = att.chunk_attention(q, kp, vp, table + off, start,
                                page_size=page_size,
                                num_kv_heads=cfg.cache_kv_heads)
        return o, kp, vp

    if cfg.is_sala:
        def attend(q, k, v, kp, vp, off, sums, base):  # a sparse layer's
            write = jax.lax.dynamic_slice(table, (start // page_size,),
                                          (c // page_size,)) + off
            kp, vp = att.write_kv_prefill(kp, vp, k, v, write,
                                          page_size=page_size)
            sums = sparse_ops.page_sums_prefill(
                sums, k, base + pages.slot, start, n_valid,
                page_size=page_size, dtype=kp.dtype)
            o, seen = _sparse_chunk(
                cfg, q, kp, vp, sums, base + pages.slot, table + off, start,
                page_size, lambda: att.chunk_attention(
                    q, kp, vp, table + off, start, page_size=page_size,
                    num_kv_heads=cfg.cache_kv_heads))
            return o, kp, vp, sums, seen

    fresh = jnp.bool_(True) if start is None else start == 0
    # the rows' positions, for the rotary of a model whose attention has one
    positions = ((0 if start is None else start) + jnp.arange(c)
                 if _hybrid_rotary(cfg) else None)
    x, k_pages, v_pages, counts = _hybrid_layers(
        cfg, params, _embed_rows(cfg, params, tokens), k_pages, v_pages,
        attend, token_mask, chunk=(c, pages.slot, n_valid, fresh),
        positions=positions)
    last = jnp.take(x, n_valid - 1, axis=0)[None]
    return PrefillOut(_logits(cfg, params, last)[0], k_pages, v_pages, counts)


def live_state_slots(cfg: ModelConfig, block_tables: jax.Array):
    """The live decode slots as a hybrid model's state updates walk them
    (ops/ssm.LiveSlots), None for any other model. A step program builds it
    once, and a fused window once for all its steps (`decode_step`'s
    `state_slots`): the table does not change inside a window."""
    if not cfg.mixer_types:
        return None
    return ssm_ops.live_slots(_live_slots(block_tables))


def _hybrid_step(cfg, params, tokens, positions, block_tables, context_lens,
                 k_pages, v_pages, page_size: int, chunk=None,
                 state_slots=None):
    """Every decode slot a token and, with `chunk` = (tokens [C], start,
    n_valid, pages: SlotPages), one chunk of a prompt in the same forward:
    rows [B decode | C chunk]. Returns (x [B(+C), E] after the last layer,
    k_pages, v_pages, counts)."""
    b = tokens.shape[0]
    n_slots = (k_pages if cfg.is_sala else v_pages).state[0].shape[
        1 if cfg.state_stacked else 0]
    if b != n_slots:
        raise ValueError(
            f"{b} decode rows over {n_slots} state slots: "
            "decode row i updates state slot i")
    live = _live_slots(block_tables)
    if state_slots is None:
        state_slots = ssm_ops.live_slots(live)
    kernel_lens = jnp.where(live, context_lens, 0)
    tables = block_tables
    all_tokens, token_mask, mchunk = tokens, live, None
    # every row's position, for the rotary of a model whose attention has one
    rope_pos = positions if _hybrid_rotary(cfg) else None
    if chunk is not None:
        c_tokens, start, n_valid, pages = chunk
        c = c_tokens.shape[0]
        if rope_pos is not None:
            rope_pos = jnp.concatenate([positions, start + jnp.arange(c)])
        all_tokens = jnp.concatenate([tokens, c_tokens])
        token_mask = jnp.concatenate([live, jnp.arange(c) < n_valid])
        mchunk = (c, pages.slot, n_valid, start == 0)
        write = jax.lax.dynamic_slice(pages.pages, (start // page_size,),
                                      (c // page_size,))

    def attend(q, k, v, kp, vp, off):
        kp, vp = att.write_kv_token(kp, vp, k[:b], v[:b], tables + off,
                                    positions, page_size=page_size)
        if chunk is None:
            o = att.paged_attention_decode(
                q, kp, vp, tables + off, context_lens, page_size=page_size,
                num_kv_heads=cfg.cache_kv_heads, kernel_lens=kernel_lens)
            return o, kp, vp
        kp, vp = att.write_kv_prefill(kp, vp, k[b:], v[b:], write + off,
                                      page_size=page_size)
        o = att.ragged_mixed_attention(
            q, kp, vp, tables + off, context_lens, pages.pages + off, start,
            page_size=page_size, num_kv_heads=cfg.cache_kv_heads,
            num_decode=b, kernel_lens=kernel_lens)
        return o, kp, vp

    if cfg.is_sala:
        dense_attend = attend
        sz = sparse_ops.sizes_of(cfg)
        none = jnp.zeros((len(sparse_ops.CHUNK_STATS),), jnp.int32)
        # a decode table that can hold a context past sparse_dense_len: the
        # decode rows select (each by its own context), the chunk apart;
        # under a narrower one the kernels as they are, and the page sums
        selects = _sparse_selects(cfg, tables.shape[1], 0, page_size)

        def page_sums_at(sums, kp, k, off, base):
            """The sums of the rows' pages just written, and the chunk's."""
            sums = sparse_ops.page_sums_token(
                sums, kp, tables + off, positions, live, base,
                page_size=page_size)
            if chunk is None:
                return sums
            return sparse_ops.page_sums_prefill(
                sums, k[b:], base + pages.slot, start, n_valid,
                page_size=page_size, dtype=kp.dtype)

        def attend(q, k, v, kp, vp, off, sums, base):  # a sparse layer's
            if not selects:
                o, kp, vp = dense_attend(q, k, v, kp, vp, off)
                return o, kp, vp, page_sums_at(sums, kp, k, off, base), none
            kp, vp = att.write_kv_token(kp, vp, k[:b], v[:b], tables + off,
                                        positions, page_size=page_size)
            if chunk is not None:
                kp, vp = att.write_kv_prefill(kp, vp, k[b:], v[b:],
                                              write + off,
                                              page_size=page_size)
            sums = page_sums_at(sums, kp, k, off, base)
            o = sparse_ops.decode_attention(
                q[:b], kp, vp, jax.lax.dynamic_slice_in_dim(sums, base, b),
                tables + off, context_lens, kernel_lens, sz,
                page_size=page_size, num_kv_heads=cfg.cache_kv_heads)
            if chunk is None:
                return o, kp, vp, sums, none
            oc, seen = _sparse_chunk(
                cfg, q[b:], kp, vp, sums, base + pages.slot,
                pages.pages + off, start, page_size,
                lambda: att.chunk_attention(
                    q[b:], kp, vp, pages.pages + off, start,
                    page_size=page_size, num_kv_heads=cfg.cache_kv_heads))
            return jnp.concatenate([o, oc]), kp, vp, sums, seen

    return _hybrid_layers(
        cfg, params, _embed_rows(cfg, params, all_tokens), k_pages, v_pages,
        attend, token_mask, decode=(b, live, state_slots), chunk=mchunk,
        positions=rope_pos)


def _no_hybrid(cfg: ModelConfig, what: str) -> None:
    if cfg.mixer_types:
        raise NotImplementedError(
            f"{what} is not implemented for a hybrid model "
            "(cfg.mixer_types: a Mamba-2 mixer in some or in every layer): "
            "a state slot holds ONE sequence's state at ONE position, so "
            "lanes of one prompt batch and a verify window that may roll "
            "back have nowhere to keep theirs")


class PrefillOut(NamedTuple):
    last_logits: jax.Array  # [V] logits at the final real token
    k_pages: jax.Array
    v_pages: jax.Array
    moe_stats: Any = None  # grouped expert layers' counts, or None


def _logits(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps, cfg.rms_norm_unit_offset)
    if cfg.multipliers is not None:
        # falcon_h1's lm_head_multiplier, on the [T, E] rows and not on
        # [T, V] logits (the head is linear; the published 2^-7 is exact)
        x = x * cfg.multipliers.lm_head
    if cfg.dim_model_base:
        # the MiniCPM family's muP width: the final hidden over hidden_size
        # / dim_model_base, on the [T, E] rows (the head is linear)
        x = x * jnp.asarray(cfg.dim_model_base / cfg.hidden_size, x.dtype)
    if cfg.tie_word_embeddings:
        out = quant.tied_head_einsum(x, params["embed"])
    else:
        out = qeinsum("te,ev->tv", x, params["lm_head"])
    if cfg.final_logit_softcapping > 0.0:  # gemma-2
        cap = cfg.final_logit_softcapping
        out = cap * jnp.tanh(out / cap)
    return out


def prefill(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [S] padded to a multiple of page_size
    seq_len: jax.Array,  # scalar int32: true length
    k_pages: jax.Array,  # [L, P, ps, KV*D] (page-major fused-head layout)
    v_pages: jax.Array,
    pages: jax.Array,  # [S // page_size] page ids for this sequence
    *,
    page_size: int,
    adapter_slots=None,  # scalar int32 LoRA slot for this sequence, or None
) -> PrefillOut:
    """Process a full prompt, writing its KV into the paged cache.

    Mirrors the prefill role of the reference's disaggregated workers
    (/root/reference/examples/deploy/vllm/disagg.yaml:37 `--is-prefill-worker`).
    """
    if cfg.mixer_types:
        return _hybrid_prefill(cfg, params, tokens, seq_len, k_pages,
                               v_pages, pages, None, page_size)
    s = tokens.shape[0]
    positions = jnp.arange(s)
    token_mask = positions < seq_len  # padding rows past the true length
    slots = (None if adapter_slots is None
             else jnp.full((s,), adapter_slots, jnp.int32))
    x = _embed_rows(cfg, params, tokens)
    if cfg.layer_types and s // page_size > pages.window.shape[0]:
        raise ValueError(
            f"a whole-prompt prefill of {s} tokens does not fit a sliding "
            f"layer's ring of {pages.window.shape[0]} pages: prompts past "
            "the chunk size are prefilled in chunks")

    def body(x, kp, vp, lp, page_off, kind=None):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps, cfg.rms_norm_unit_offset)
        if kind is not None:
            q, k, v = _qkv(cfg, lp, h, positions, kind=kind)
            sink = _kind_sink(cfg, lp, kind)
            own = (pages.full if kind == FULL
                   else pages.window[:s // page_size])
            kp, vp = att.write_kv_prefill(
                kp, vp, k, v, own + page_off, page_size=page_size)
            with jax.named_scope(_ATTN_SCOPE[kind]):
                if kind == SLIDING and s > cfg.sliding_window:
                    # the flash kernel takes no window: the rows just
                    # written are attended as ONE chunk from position 0 by
                    # the paged kernel that masks below a static window
                    # (the ring holds the whole bucket: checked above)
                    o = att.chunk_attention(
                        q, kp, vp, own + page_off, 0, page_size=page_size,
                        num_kv_heads=cfg.kind_kv_heads(kind),
                        window=cfg.sliding_window, **sink)
                else:
                    # a bucket no longer than the window: the mask bites
                    # nowhere
                    o = att.prefill_attention(q, k, v, seq_len, **sink)
            x, counts = _kind_layer_tail(cfg, lp, x, h, o, token_mask)
            return x, kp, vp, counts
        q, k, v = _qkv(cfg, lp, h, positions,
                       rope=_layer_rope(cfg, page_off,
                                        k_pages.shape[1]),
                       lora_slots=slots)
        if cfg.is_dsa:
            kp, vp = att.write_kv_prefill(
                kp, vp, k, v, pages + page_off, page_size=page_size)
            if _selects(cfg, s):
                o = att.dsa_chunk_attention(
                    *q, kp, vp, pages + page_off, 0,
                    **_dsa_kwargs(cfg, page_off, k_pages.shape[1],
                                  page_size))
            else:
                o = att.prefill_attention(q.q, k, k, seq_len)
        else:
            o = att.prefill_attention(
                q, k, v, seq_len,
                **_attn_kwargs(cfg, page_off, k_pages.shape[1]))
        x = x + _post(cfg, lp, "post_attn_norm",
                      _attn_out(cfg, lp, o, lora_slots=slots))
        if not cfg.is_dsa:
            kp, vp = att.write_kv_prefill(
                kp, vp, k, v, pages + page_off, page_size=page_size
            )
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps, cfg.rms_norm_unit_offset)
        y, counts = _mlp(cfg, lp, h, token_mask=token_mask)
        x = x + _post(cfg, lp, "post_mlp_norm", y)
        return x, kp, vp, counts

    x, k_pages, v_pages, moe_stats = _scan_layers_paged(
        cfg, params, body, x, k_pages, v_pages)
    last = jnp.take(x, seq_len - 1, axis=0)[None]  # [1, E]
    logits = _logits(cfg, params, last)[0]
    return PrefillOut(logits, k_pages, v_pages, moe_stats)


def prefill_chunk(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [C] one chunk, padded to a multiple of page_size
    start: jax.Array,  # scalar int32: absolute position of tokens[0]
    chunk_len: jax.Array,  # scalar int32: valid tokens in this chunk
    k_pages: jax.Array,  # [L, P, ps, KV*D]
    v_pages: jax.Array,
    pages: jax.Array,  # [Wp] ALL page ids of the sequence, 0-padded to its
    # bucket's pages + this chunk's trash tail (KVCacheSpec.page_table_width)
    *,
    page_size: int,
    adapter_slots=None,  # scalar int32 LoRA slot for this sequence, or None
) -> PrefillOut:
    """One chunk of an incremental (chunked) prefill.

    Chunked prefill bounds the decode stall a long prompt causes: the engine
    interleaves these chunk dispatches with decode windows, mirroring the
    continuous-batching chunked prefill of the reference's consumed engines
    (the 25ms ITL SLA of /root/reference/examples/dgdr/trtllm/dgdr.yaml:26 is
    unreachable if admission can monopolize the chip for a full prompt).

    The chunk's K/V is scattered into its pages, then every chunk token
    attends over all previously cached pages plus the in-chunk causal
    prefix (ops.attention.chunk_attention — one page gather serves the whole
    chunk). Returns the logits at the chunk's last valid token (only
    meaningful on the final chunk).
    """
    if cfg.mixer_types:
        return _hybrid_prefill(cfg, params, tokens, chunk_len, k_pages,
                               v_pages, pages, start, page_size)
    c = tokens.shape[0]
    positions = start + jnp.arange(c)
    token_mask = jnp.arange(c) < chunk_len
    chunk_pages = None if cfg.layer_types else jax.lax.dynamic_slice(
        pages, (start // page_size,), (c // page_size,)
    )
    slots = (None if adapter_slots is None
             else jnp.full((c,), adapter_slots, jnp.int32))
    x = _embed_rows(cfg, params, tokens)
    views = (_chunk_views(cfg, pages, start, c, page_size)
             if cfg.layer_types else None)

    def body(x, kp, vp, lp, page_off, kind=None):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps, cfg.rms_norm_unit_offset)
        if kind is not None:
            q, k, v = _qkv(cfg, lp, h, positions, kind=kind)
            table, wstart, write, akw = views[kind]
            kp, vp = att.write_kv_prefill(
                kp, vp, k, v, write + page_off, page_size=page_size)
            with jax.named_scope(_ATTN_SCOPE[kind]):
                o = att.chunk_attention(
                    q, kp, vp, table + page_off, wstart, page_size=page_size,
                    num_kv_heads=cfg.kind_kv_heads(kind),
                    **_kind_sink(cfg, lp, kind), **akw)
            x, counts = _kind_layer_tail(cfg, lp, x, h, o, token_mask)
            return x, kp, vp, counts
        q, k, v = _qkv(cfg, lp, h, positions,
                       rope=_layer_rope(cfg, page_off,
                                        k_pages.shape[1]),
                       lora_slots=slots)
        kp, vp = att.write_kv_prefill(
            kp, vp, k, v, chunk_pages + page_off, page_size=page_size
        )
        if _selects(cfg, pages.shape[0] * page_size):
            o = att.dsa_chunk_attention(
                *q, kp, vp, pages + page_off, start,
                key_pages=_chunk_key_pages(pages, c, page_size),
                **_dsa_kwargs(cfg, page_off, k_pages.shape[1], page_size))
        else:
            qd, vd = _dense_qv(cfg, q, vp)
            o = att.chunk_attention(
                qd, kp, vd, pages + page_off, start, page_size=page_size,
                num_kv_heads=cfg.cache_kv_heads,
                **_attn_kwargs(cfg, page_off, k_pages.shape[1]),
            )
        x = x + _post(cfg, lp, "post_attn_norm",
                      _attn_out(cfg, lp, o, lora_slots=slots))
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps, cfg.rms_norm_unit_offset)
        y, counts = _mlp(cfg, lp, h, token_mask=token_mask)
        x = x + _post(cfg, lp, "post_mlp_norm", y)
        return x, kp, vp, counts

    x, k_pages, v_pages, moe_stats = _scan_layers_paged(
        cfg, params, body, x, k_pages, v_pages)
    last = jnp.take(x, chunk_len - 1, axis=0)[None]  # [1, E]
    logits = _logits(cfg, params, last)[0]
    return PrefillOut(logits, k_pages, v_pages, moe_stats)


class PrefillBatchOut(NamedTuple):
    last_logits: jax.Array  # [N, V] logits at each sequence's final token
    k_pages: jax.Array
    v_pages: jax.Array
    moe_stats: Any = None  # grouped expert layers' counts, or None


def prefill_batch(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [N, S] same-bucket prompts, zero-padded
    seq_lens: jax.Array,  # [N] true lengths (>= 1; dummy lanes use 1)
    k_pages: jax.Array,  # [L, P, ps, KV*D]
    v_pages: jax.Array,
    pages: jax.Array,  # [N, S // page_size] page ids (trash 0 for padding
    #                     AND for every page of a dummy lane)
    *,
    page_size: int,
    adapter_slots=None,  # [N] int32 per-lane LoRA slots, or None
) -> PrefillBatchOut:
    """Prefill N same-bucket prompts in ONE dispatch.

    Admission batching: under bursty load the per-dispatch host round trip
    weighs on short-prompt TTFT; grouping same-bucket admissions amortizes
    it N-fold. Attention is the per-seq
    prefill kernel vmapped over the group; KV writes share one flat
    scatter (lane i's pages are disjoint by construction). Dummy padding
    lanes carry all-trash page rows, so their writes land in the reserved
    page and their logits are discarded by the engine."""
    _no_kinds(cfg, "a batched prefill")
    _no_hybrid(cfg, "a batched prefill")
    n, s = tokens.shape
    positions = jnp.tile(jnp.arange(s), n)  # [N*S] per-lane positions
    token_mask = (jnp.arange(s)[None, :] < seq_lens[:, None]).reshape(-1)
    slots = (None if adapter_slots is None
             else jnp.repeat(adapter_slots.astype(jnp.int32), s))
    x = _embed_rows(cfg, params, tokens.reshape(-1))

    def body(x, kp, vp, lp, page_off):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps, cfg.rms_norm_unit_offset)
        q, k, v = _qkv(cfg, lp, h, positions,
                       rope=_layer_rope(cfg, page_off,
                                        k_pages.shape[1]),
                       lora_slots=slots)  # [N*S,...]
        akw = _attn_kwargs(cfg, page_off, k_pages.shape[1])

        def lanes(a):
            return a.reshape(n, s, *a.shape[1:])

        if cfg.is_dsa:
            kp, vp = att.write_kv_prefill(
                kp, vp, k, v, pages.reshape(-1) + page_off,
                page_size=page_size)
            if _selects(cfg, s):
                o = jax.vmap(
                    lambda qq, pg: att.dsa_chunk_attention(
                        *qq, kp, vp, pg + page_off, 0,
                        **_dsa_kwargs(cfg, page_off, k_pages.shape[1],
                                      page_size))
                )(DsaQuery(*(lanes(a) for a in q)), pages)
            else:
                o = jax.vmap(
                    lambda qq, kk, sl: att.prefill_attention(qq, kk, kk, sl)
                )(lanes(q.q), lanes(k), seq_lens)
        else:
            o = jax.vmap(
                lambda qq, kk, vv, sl: att.prefill_attention(
                    qq, kk, vv, sl, **akw)
            )(
                q.reshape(n, s, *q.shape[1:]),
                k.reshape(n, s, *k.shape[1:]),
                v.reshape(n, s, *v.shape[1:]),
                seq_lens,
            )
        x = x + _post(cfg, lp, "post_attn_norm",
                  _attn_out(cfg, lp, o.reshape(n * s, *o.shape[2:]),
                            lora_slots=slots))
        if not cfg.is_dsa:
            kp, vp = att.write_kv_prefill(
                kp, vp, k, v, pages.reshape(-1) + page_off,
                page_size=page_size
            )
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps, cfg.rms_norm_unit_offset)
        y, counts = _mlp(cfg, lp, h, token_mask=token_mask)
        x = x + _post(cfg, lp, "post_mlp_norm", y)
        return x, kp, vp, counts

    x, k_pages, v_pages, moe_stats = _scan_layers_paged(
        cfg, params, body, x, k_pages, v_pages)
    last = jnp.take_along_axis(
        x.reshape(n, s, -1), (seq_lens - 1)[:, None, None], axis=1
    )[:, 0]  # [N, E]
    logits = _logits(cfg, params, last)
    return PrefillBatchOut(logits, k_pages, v_pages, moe_stats)


class DecodeOut(NamedTuple):
    logits: jax.Array  # [B, V]
    k_pages: jax.Array
    v_pages: jax.Array
    moe_stats: Any = None  # grouped expert layers' counts, or None


class VerifyOut(NamedTuple):
    logits: jax.Array  # [B, K1, V] — logits at every query position
    k_pages: jax.Array
    v_pages: jax.Array
    moe_stats: Any = None  # grouped expert layers' counts, or None


def decode_verify(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, K1] current token + K speculative drafts
    positions: jax.Array,  # [B] absolute position of tokens[:, 0]
    block_tables: jax.Array,  # [B, Pmax]
    room: jax.Array,  # [B] bool: pages/limits cover all K draft writes
    k_pages: jax.Array,  # [L, P, ps, KV*D]
    v_pages: jax.Array,
    *,
    page_size: int,
    adapter_slots=None,  # [B] int32 per-slot LoRA slots, or None
) -> VerifyOut:
    """Speculative-decoding verification step: run current + K draft tokens
    per sequence through one forward, returning logits at every position so
    the sampler can accept the longest draft prefix the model agrees with
    (vLLM/TRT-LLM ship the same capability on the reference's engines).
    Adapter sequences keep their gathered-LoRA deltas inside the verify
    forward (each slot's adapter applied to all K1 of its rows), so drafts
    are verified against the same adapted distribution decode would sample
    from — the PR 5 base-logits fallback is gone.

    Draft K/V is written into the sequence's pages before attending (like
    prefill_chunk); rejected drafts leave garbage K/V past the accepted
    context length, which is masked by every later attention and overwritten
    when real tokens reach those positions. Slots without `room` (end of
    page table / near max_seq_len) divert their DRAFT writes to the trash
    page and behave as a plain decode step for position 0; the engine
    forces their acceptance count to zero.
    """
    _no_speculation_under_selection(cfg)
    _no_kinds(cfg, "a speculative verify window")
    _no_hybrid(cfg, "a speculative verify window")
    b, k1 = tokens.shape
    pos2 = positions[:, None] + jnp.arange(k1)[None, :]  # [B, K1]
    flat_pos = pos2.reshape(b * k1)
    flat_tables = jnp.repeat(block_tables, k1, axis=0)  # [B*K1, Pmax]
    # j == 0 (the real current token) always writes; draft rows of a
    # roomless slot target the trash page at position 0 instead of running
    # off the page table (take_along_axis would clamp into the last page)
    valid = (jnp.arange(b * k1) % k1 == 0) | jnp.repeat(room, k1)
    flat_pos = jnp.where(valid, flat_pos, 0)
    flat_tables = jnp.where(valid[:, None], flat_tables, 0)
    slots = (None if adapter_slots is None
             else jnp.repeat(adapter_slots.astype(jnp.int32), k1))
    x = _embed_rows(cfg, params, tokens.reshape(b * k1))
    live = _live_rows(cfg, block_tables)
    if live is not None:
        live = jnp.repeat(live, k1)

    def body(x, kp, vp, lp, page_off):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps, cfg.rms_norm_unit_offset)
        q, k, v = _qkv(cfg, lp, h, flat_pos,
                       rope=_layer_rope(cfg, page_off,
                                        k_pages.shape[1]),
                       lora_slots=slots)
        kp, vp = att.write_kv_token(
            kp, vp, k, v, flat_tables + page_off, flat_pos,
            page_size=page_size,
        )
        o = att.verify_attention(
            q.reshape(b, k1, *q.shape[1:]), kp, vp,
            block_tables + page_off, positions, page_size=page_size,
            num_kv_heads=cfg.cache_kv_heads,
            **_attn_kwargs(cfg, page_off, k_pages.shape[1]),
        )
        x = x + _post(cfg, lp, "post_attn_norm",
                  _attn_out(cfg, lp, o.reshape(b * k1, *o.shape[2:]),
                            lora_slots=slots))
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps, cfg.rms_norm_unit_offset)
        y, counts = _mlp(cfg, lp, h, token_mask=live)
        x = x + _post(cfg, lp, "post_mlp_norm", y)
        return x, kp, vp, counts

    x, k_pages, v_pages, moe_stats = _scan_layers_paged(
        cfg, params, body, x, k_pages, v_pages)
    logits = _logits(cfg, params, x).reshape(b, k1, -1)
    return VerifyOut(logits, k_pages, v_pages, moe_stats)


def decode_step(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B] current token per sequence
    positions: jax.Array,  # [B] position of that token
    block_tables: jax.Array,  # [B, Pmax]
    context_lens: jax.Array,  # [B] length INCLUDING current token
    k_pages: jax.Array,  # [L, P, ps, KV*D] (page-major fused-head layout)
    v_pages: jax.Array,
    *,
    page_size: int,
    adapter_slots=None,  # [B] int32 per-slot LoRA slots, or None
    state_slots=None,  # live_state_slots(), built by a window for its steps
) -> DecodeOut:
    """One continuous-batching decode step over all batch slots."""
    if cfg.mixer_types:
        x, k_pages, v_pages, counts = _hybrid_step(
            cfg, params, tokens, positions, block_tables, context_lens,
            k_pages, v_pages, page_size, state_slots=state_slots)
        return DecodeOut(_logits(cfg, params, x), k_pages, v_pages, counts)
    x = _embed_rows(cfg, params, tokens)  # [B, E]
    slots = (None if adapter_slots is None
             else adapter_slots.astype(jnp.int32))
    if cfg.layer_types:
        views = _decode_views(cfg, block_tables, positions, context_lens,
                              page_size)
        block_tables = block_tables.full  # who is live; a full layer's table
    live = _live_rows(cfg, block_tables)
    # the engine pins an empty slot at context 1 on the trash page; the
    # Pallas kernel is handed context 0 there and does nothing for the
    # slot. Once, outside the layer scan.
    kernel_lens = jnp.where(_live_slots(block_tables), context_lens, 0)
    dsa_plan = _dsa_live_plan(block_tables) if cfg.is_dsa else None

    def body(x, kp, vp, lp, page_off, kind=None):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps, cfg.rms_norm_unit_offset)
        if kind is not None:
            q, k, v = _qkv(cfg, lp, h, positions, kind=kind)
            tb, wpos, ctx, akw = views[kind]
            kp, vp = att.write_kv_token(
                kp, vp, k, v, tb + page_off, wpos, page_size=page_size)
            with jax.named_scope(_ATTN_SCOPE[kind]):
                o = att.paged_attention_decode(
                    q, kp, vp, tb + page_off, ctx, page_size=page_size,
                    num_kv_heads=cfg.kind_kv_heads(kind),
                    kernel_lens=jnp.where(kernel_lens > 0, ctx, 0),
                    **_kind_sink(cfg, lp, kind), **akw)
            x, counts = _kind_layer_tail(cfg, lp, x, h, o, live)
            return x, kp, vp, counts
        q, k, v = _qkv(cfg, lp, h, positions,
                       rope=_layer_rope(cfg, page_off,
                                        k_pages.shape[1]),
                       lora_slots=slots)
        tables = block_tables + page_off
        kp, vp = att.write_kv_token(
            kp, vp, k, v, tables, positions, page_size=page_size
        )
        if _selects(cfg, block_tables.shape[1] * page_size):
            o = _dsa_decode_rows(
                q, kp, vp, tables, context_lens, dsa_plan,
                **_dsa_kwargs(cfg, page_off, k_pages.shape[1], page_size))
        else:
            qd, vd = _dense_qv(cfg, q, vp)
            o = att.paged_attention_decode(
                qd, kp, vd, tables, context_lens, page_size=page_size,
                num_kv_heads=cfg.cache_kv_heads, kernel_lens=kernel_lens,
                **_attn_kwargs(cfg, page_off, k_pages.shape[1]),
            )
        x = x + _post(cfg, lp, "post_attn_norm",
                      _attn_out(cfg, lp, o, lora_slots=slots))
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps, cfg.rms_norm_unit_offset)
        y, counts = _mlp(cfg, lp, h, token_mask=live)
        x = x + _post(cfg, lp, "post_mlp_norm", y)
        return x, kp, vp, counts

    x, k_pages, v_pages, moe_stats = _scan_layers_paged(
        cfg, params, body, x, k_pages, v_pages)
    logits = _logits(cfg, params, x)
    return DecodeOut(logits, k_pages, v_pages, moe_stats)


class MixedOut(NamedTuple):
    logits: jax.Array  # [B, V] decode-slot logits
    chunk_logits: jax.Array  # [V] logits at the chunk's last valid token
    k_pages: jax.Array
    v_pages: jax.Array
    moe_stats: Any = None  # grouped expert layers' counts, or None


def mixed_step(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B] current token per decode slot
    positions: jax.Array,  # [B] position of that token
    block_tables: jax.Array,  # [B, Pmax]
    context_lens: jax.Array,  # [B] length INCLUDING current token
    chunk_tokens: jax.Array,  # [C] one prefill chunk, page-multiple padded
    chunk_start: jax.Array,  # scalar int32: absolute position of chunk[0]
    chunk_len: jax.Array,  # scalar int32: valid tokens in this chunk
    chunk_pages: jax.Array,  # [Wp] ALL page ids of the chunk's sequence,
    # 0-padded to its bucket's pages + the chunk's trash tail, like
    # prefill_chunk's
    k_pages: jax.Array,  # [L, P, ps, KV*D]
    v_pages: jax.Array,
    *,
    page_size: int,
    adapter_slots=None,  # [B] int32 per-slot LoRA slots, or None
    chunk_adapter_slot=None,  # scalar int32 LoRA slot of the chunk's seq
) -> MixedOut:
    """ONE ragged step: every decode slot advances a token AND one prefill
    chunk makes progress, in a single forward (the RPA unification — the
    chunk no longer preempts decode between fused windows, which was the
    ITL p95 tail in the TPU snapshot).

    Row layout is decode-first: [B decode rows | C chunk rows]. All
    projections, rope, LoRA deltas, and the MLP are per-token, so running
    them over the concatenated batch is bit-identical to the separate
    decode_step + prefill_chunk dispatches; attention routes through
    ops.attention.ragged_mixed_attention, whose XLA composition is the
    exact per-path reference (and whose Pallas kernel serves both row
    kinds from one grid on TPU). KV writes stay disjoint: decode tokens
    scatter through their block tables, chunk rows through the chunk's
    own pages (prefix-cached pages are read-only full pages, and chunk
    starts are page-aligned, so a shared prefix is never rewritten).

    MoE note: both expert paths are exact a row, so a row's result does
    not depend on the batch it rides in (mixed-vs-separate token identity).
    """
    b = tokens.shape[0]
    c = chunk_tokens.shape[0]
    if cfg.mixer_types:
        x, k_pages, v_pages, counts = _hybrid_step(
            cfg, params, tokens, positions, block_tables, context_lens,
            k_pages, v_pages, page_size,
            chunk=(chunk_tokens, chunk_start, chunk_len, chunk_pages))
        last = jnp.take(x[b:], chunk_len - 1, axis=0)[None]
        logits = _logits(cfg, params, jnp.concatenate([x[:b], last]))
        return MixedOut(logits[:b], logits[b], k_pages, v_pages, counts)
    all_pos = jnp.concatenate([positions, chunk_start + jnp.arange(c)])
    if cfg.layer_types:
        views = _decode_views(cfg, block_tables, positions, context_lens,
                              page_size)
        cviews = _chunk_views(cfg, chunk_pages, chunk_start, c, page_size)
        block_tables = block_tables.full  # who is live
    live = _live_rows(cfg, block_tables)
    # as decode_step: the ragged kernel does nothing for an empty slot
    kernel_lens = jnp.where(_live_slots(block_tables), context_lens, 0)
    token_mask = jnp.concatenate(
        [jnp.ones((b,), bool) if live is None else live,
         jnp.arange(c) < chunk_len])
    write_pages = None if cfg.layer_types else jax.lax.dynamic_slice(
        chunk_pages, (chunk_start // page_size,), (c // page_size,)
    )
    slots = None
    if adapter_slots is not None:
        ca = (jnp.int32(0) if chunk_adapter_slot is None
              else chunk_adapter_slot)
        slots = jnp.concatenate(
            [adapter_slots.astype(jnp.int32),
             jnp.full((c,), ca, jnp.int32)])
    x = _embed_rows(cfg, params, jnp.concatenate([tokens, chunk_tokens]))
    dsa_plan = _dsa_live_plan(block_tables) if cfg.is_dsa else None

    def body(x, kp, vp, lp, page_off, kind=None):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps, cfg.rms_norm_unit_offset)
        if kind is not None:
            q, k, v = _qkv(cfg, lp, h, all_pos, kind=kind)
            tb, wpos, ctx, akw = views[kind]
            table, wstart, write, _ = cviews[kind]
            kp, vp = att.write_kv_token(
                kp, vp, k[:b], v[:b], tb + page_off, wpos,
                page_size=page_size)
            kp, vp = att.write_kv_prefill(
                kp, vp, k[b:], v[b:], write + page_off, page_size=page_size)
            with jax.named_scope(_ATTN_SCOPE[kind]):
                o = att.ragged_mixed_attention(
                    q, kp, vp, tb + page_off, ctx, table + page_off, wstart,
                    page_size=page_size,
                    num_kv_heads=cfg.kind_kv_heads(kind), num_decode=b,
                    kernel_lens=jnp.where(kernel_lens > 0, ctx, 0),
                    **_kind_sink(cfg, lp, kind), **akw)
            x, counts = _kind_layer_tail(cfg, lp, x, h, o, token_mask)
            return x, kp, vp, counts
        q, k, v = _qkv(cfg, lp, h, all_pos,
                       rope=_layer_rope(cfg, page_off,
                                        k_pages.shape[1]),
                       lora_slots=slots)
        tables = block_tables + page_off
        kp, vp = att.write_kv_token(
            kp, vp, k[:b], v[:b], tables, positions, page_size=page_size
        )
        kp, vp = att.write_kv_prefill(
            kp, vp, k[b:], v[b:], write_pages + page_off,
            page_size=page_size
        )
        if _selects(cfg, max(block_tables.shape[1], chunk_pages.shape[0])
                    * page_size):
            # decode rows and the chunk's rows each select their own rows
            dsa_kw = _dsa_kwargs(cfg, page_off, k_pages.shape[1], page_size)
            o = jnp.concatenate([
                _dsa_decode_rows(_dsa_rows(q, slice(0, b)), kp, vp, tables,
                                 context_lens, dsa_plan, **dsa_kw),
                att.dsa_chunk_attention(
                    *_dsa_rows(q, slice(b, b + c)), kp, vp,
                    chunk_pages + page_off, chunk_start,
                    key_pages=_chunk_key_pages(chunk_pages, c, page_size),
                    **dsa_kw),
            ])
        else:
            qd, vd = _dense_qv(cfg, q, vp)
            o = att.ragged_mixed_attention(
                qd, kp, vd, tables, context_lens, chunk_pages + page_off,
                chunk_start, page_size=page_size,
                num_kv_heads=cfg.cache_kv_heads, num_decode=b,
                kernel_lens=kernel_lens,
                **_attn_kwargs(cfg, page_off, k_pages.shape[1]),
            )
        x = x + _post(cfg, lp, "post_attn_norm",
                      _attn_out(cfg, lp, o, lora_slots=slots))
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps, cfg.rms_norm_unit_offset)
        y, counts = _mlp(cfg, lp, h, token_mask=token_mask)
        x = x + _post(cfg, lp, "post_mlp_norm", y)
        return x, kp, vp, counts

    x, k_pages, v_pages, moe_stats = _scan_layers_paged(
        cfg, params, body, x, k_pages, v_pages)
    last = jnp.take(x[b:], chunk_len - 1, axis=0)[None]  # [1, E]
    rows = jnp.concatenate([x[:b], last])
    logits = _logits(cfg, params, rows)
    return MixedOut(logits[:b], logits[b], k_pages, v_pages, moe_stats)


class MixedVerifyOut(NamedTuple):
    logits: jax.Array  # [B, K1, V] — verify logits at every window position
    chunk_logits: jax.Array  # [V] logits at the chunk's last valid token
    k_pages: jax.Array
    v_pages: jax.Array
    moe_stats: Any = None  # grouped expert layers' counts, or None


def mixed_verify_step(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, K1] current token + K drafts per decode slot
    positions: jax.Array,  # [B] absolute position of tokens[:, 0]
    block_tables: jax.Array,  # [B, Pmax]
    room: jax.Array,  # [B] bool: pages/limits cover all K draft writes
    chunk_tokens: jax.Array,  # [C] one prefill chunk, page-multiple padded
    chunk_start: jax.Array,  # scalar int32: absolute position of chunk[0]
    chunk_len: jax.Array,  # scalar int32: valid tokens in this chunk
    chunk_pages: jax.Array,  # [Wp] ALL page ids of the chunk's sequence
    k_pages: jax.Array,  # [L, P, ps, KV*D]
    v_pages: jax.Array,
    *,
    page_size: int,
    adapter_slots=None,  # [B] int32 per-slot LoRA slots, or None
    chunk_adapter_slot=None,  # scalar int32 LoRA slot of the chunk's seq
) -> MixedVerifyOut:
    """ONE ragged step where every decode slot runs a K+1-token speculative
    verify window AND one prefill chunk makes progress — the spec-decode
    extension of mixed_step (a speculating slot is just a ragged row of
    q_len = K+1 instead of 1; see ops/ragged_attention.py).

    Row layout is windows-first: [B*K1 verify rows | C chunk rows].
    Per-token math (projections, rope, LoRA deltas, MLP) over the
    concatenated batch is bit-identical to the separate decode_verify +
    prefill_chunk dispatches; attention routes through
    ops.attention.ragged_verify_attention, whose XLA composition is the
    exact per-path reference. KV writes follow decode_verify's room
    contract (roomless slots divert draft writes to the trash page and
    behave as plain decode for position 0) plus mixed_step's disjoint
    chunk-page scatter. MoE rows use dense dispatch for identity, as in
    mixed_step.
    """
    _no_speculation_under_selection(cfg)
    _no_kinds(cfg, "a speculative verify window")
    _no_hybrid(cfg, "a speculative verify window")
    b, k1 = tokens.shape
    c = chunk_tokens.shape[0]
    n = b * k1
    pos2 = positions[:, None] + jnp.arange(k1)[None, :]  # [B, K1]
    flat_pos = pos2.reshape(n)
    flat_tables = jnp.repeat(block_tables, k1, axis=0)  # [B*K1, Pmax]
    valid = (jnp.arange(n) % k1 == 0) | jnp.repeat(room, k1)
    flat_pos = jnp.where(valid, flat_pos, 0)
    flat_tables = jnp.where(valid[:, None], flat_tables, 0)
    all_pos = jnp.concatenate([flat_pos, chunk_start + jnp.arange(c)])
    live = _live_rows(cfg, block_tables)
    token_mask = jnp.concatenate(
        [jnp.ones((n,), bool) if live is None else jnp.repeat(live, k1),
         jnp.arange(c) < chunk_len])
    write_pages = jax.lax.dynamic_slice(
        chunk_pages, (chunk_start // page_size,), (c // page_size,)
    )
    slots = None
    if adapter_slots is not None:
        ca = (jnp.int32(0) if chunk_adapter_slot is None
              else chunk_adapter_slot)
        slots = jnp.concatenate(
            [jnp.repeat(adapter_slots.astype(jnp.int32), k1),
             jnp.full((c,), ca, jnp.int32)])
    x = _embed_rows(cfg, params,
                    jnp.concatenate([tokens.reshape(n), chunk_tokens]))

    def body(x, kp, vp, lp, page_off):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps, cfg.rms_norm_unit_offset)
        q, k, v = _qkv(cfg, lp, h, all_pos,
                       rope=_layer_rope(cfg, page_off,
                                        k_pages.shape[1]),
                       lora_slots=slots)
        kp, vp = att.write_kv_token(
            kp, vp, k[:n], v[:n], flat_tables + page_off, flat_pos,
            page_size=page_size,
        )
        kp, vp = att.write_kv_prefill(
            kp, vp, k[n:], v[n:], write_pages + page_off,
            page_size=page_size
        )
        o = att.ragged_verify_attention(
            q, kp, vp, block_tables + page_off, positions,
            chunk_pages + page_off, chunk_start, page_size=page_size,
            num_kv_heads=cfg.cache_kv_heads, num_verify=b, verify_width=k1,
            **_attn_kwargs(cfg, page_off, k_pages.shape[1]),
        )
        x = x + _post(cfg, lp, "post_attn_norm",
                      _attn_out(cfg, lp, o, lora_slots=slots))
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps, cfg.rms_norm_unit_offset)
        y, counts = _mlp(cfg, lp, h, token_mask=token_mask)
        x = x + _post(cfg, lp, "post_mlp_norm", y)
        return x, kp, vp, counts

    x, k_pages, v_pages, moe_stats = _scan_layers_paged(
        cfg, params, body, x, k_pages, v_pages)
    last = jnp.take(x[n:], chunk_len - 1, axis=0)[None]  # [1, E]
    rows = jnp.concatenate([x[:n], last])
    logits = _logits(cfg, params, rows)
    return MixedVerifyOut(logits[:n].reshape(b, k1, -1), logits[n],
                          k_pages, v_pages, moe_stats)
