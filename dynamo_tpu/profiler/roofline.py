"""Analytic roofline model for TPU LLM serving.

Estimates TTFT / ITL / throughput for a (model, mesh, batch) point on a TPU
system, the way aiconfigurator estimates GPU engine configs for the DGDR SLA
sweep (/root/reference/examples/dgdr/trtllm/dgdr.yaml:22-31). The model is the
standard serving roofline:

- prefill is compute-bound on the MXU: TTFT ~ FLOPs(isl) / (chips * peak * MFU)
  plus TP all-reduce time over ICI and a fixed dispatch overhead;
- decode is HBM-bandwidth-bound: ITL ~ bytes(weights + KV batch) / aggregate
  HBM bandwidth, floored by the compute term, plus collectives + dispatch;
- capacity requires sharded weights + paged KV for the batch to fit in HBM.

All sizes assume bfloat16 (2 bytes) params and KV, the TPU-native dtype.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.profiler.systems import SystemSpec

BYTES = 2  # bfloat16

# Utilization factors: peak-fraction actually achieved. Prefill MFU on TPU for
# dense transformer matmuls is high (large static shapes feed the MXU well);
# decode matmuls are thin so compute efficiency is lower; HBM streaming
# achieves most of datasheet bandwidth.
MFU_PREFILL = 0.55
MFU_DECODE = 0.30
HBM_EFF = 0.80
ICI_EFF = 0.75
DISPATCH_OVERHEAD_S = 0.004  # per-step host dispatch + scheduling


def param_count(cfg: ModelConfig) -> float:
    """Parameters held here: every expert of an MoE model, or the experts
    of this chip's share (cfg.held_experts; the router keeps its width)."""
    h, hd = cfg.hidden_size, cfg.head_dim
    if cfg.is_mla:
        nh, nope, rope = (cfg.num_heads, cfg.qk_nope_head_dim,
                          cfg.qk_rope_head_dim)
        lora, vd = cfg.kv_lora_rank, cfg.v_head_dim
        qr = cfg.q_lora_rank
        q_proj = (h * qr + qr + qr * nh * (nope + rope) if qr
                  else h * nh * (nope + rope))  # low-rank path, or one matrix
        attn = (q_proj
                + h * (lora + rope) + lora  # latent down-projection, norm
                + nh * nope * lora          # W_UK
                + nh * lora * vd            # W_UV
                + nh * vd * h)              # output projection
        if cfg.is_dsa:
            # the indexer: queries from the q-LoRA latent, one key a token
            # (with its LayerNorm's weight and bias), a weight a head
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            attn += qr * hi * di + h * di + 2 * di + h * hi
    else:
        attn = (h * cfg.num_heads * hd + 2 * h * cfg.num_kv_heads * hd
                + cfg.num_heads * hd * h)
    mlp_one = 3 * h * cfg.intermediate_size
    mlp = mlp_one * max(cfg.held_experts, 1)
    if cfg.is_moe and cfg.num_shared_experts:
        mlp += mlp_one * cfg.num_shared_experts
    router = (h + cfg.router_bias) * cfg.num_experts if cfg.is_moe else 0
    per_layer = attn + mlp + router + 2 * h  # + rmsnorm scales
    # leading dense layers: the same attention, one SwiGLU of their width
    dense_layer = attn + 3 * h * cfg.dense_intermediate_size + 2 * h
    embed = cfg.vocab_size * h * (1 if cfg.tie_word_embeddings else 2)
    return ((cfg.num_layers - cfg.first_k_dense) * per_layer
            + cfg.first_k_dense * dense_layer + embed + h)


def active_param_count(cfg: ModelConfig) -> float:
    """Params touched per token (MoE: routed top-k + shared experts)."""
    if not cfg.is_moe:
        return param_count(cfg)
    h = cfg.hidden_size
    mlp_one = 3 * h * cfg.intermediate_size
    # of a token's picks, the part that lands on experts held here
    picks_here = (cfg.num_experts_per_tok * cfg.held_experts
                  / cfg.num_experts)
    inactive = (cfg.held_experts - picks_here) * mlp_one
    return param_count(cfg) - cfg.num_moe_layers * inactive


def moe_expert_cost(cfg: ModelConfig, rows: float,
                    experts_touched: float) -> dict:
    """Operations and bytes of the grouped expert matmuls (ops/moe.py
    moe_mlp_grouped) for `rows` assignments to held experts of which
    `experts_touched` had at least one, int8 weights and rows, int32 out:
    each row meets its own expert's gate, up and down matrices only, an
    expert no row picked is not read. The chip benchmark's
    kernel_costs/grouped_expert_matmul.py counts the same."""
    e, f = cfg.hidden_size, cfg.intermediate_size
    return {"ops": rows * 3 * 2 * e * f,
            "bytes": (experts_touched * 3 * e * f + rows * (2 * e + f)
                      + rows * 4 * (2 * f + e))}


def mla_attention_cost(cfg: ModelConfig, kv_rows_read: float,
                       qk_pairs: float) -> dict:
    """Operations and bytes of absorbed-form MLA attention over the paged
    cache, the form decode AND prefill chunks use here: every head scores
    against one shared [c_kv | k_rope] bf16 row a token (read once: the
    cache holds it once) and averages its first kv_lora_rank lanes. The
    chip benchmark's kernel_costs/mla_paged_attention.py counts the same."""
    lanes = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return {"ops": qk_pairs * cfg.num_heads * 2 * (lanes + cfg.kv_lora_rank),
            "bytes": kv_rows_read * lanes * BYTES}


def dsa_indexer_cost(cfg: ModelConfig, keys_scored: float) -> dict:
    """Operations and bytes of the sparse-attention indexer's scores
    (ops/attention.dsa_*) for `keys_scored` (query, key) pairs: every index
    head's product over index_head_dim lanes, and each query's keys read
    once as bf16 rows. No sharing between sequences, or between the
    queries of a chunk, is assumed: a chunk's queries read the same keys,
    so a kernel that shares them can pass this count's time. The chip
    benchmark's kernel_costs/dsa_indexer.py counts the same."""
    return {"ops": keys_scored * 2 * cfg.index_n_heads * cfg.index_head_dim,
            "bytes": keys_scored * cfg.index_head_dim * BYTES}


def dsa_sparse_attention_cost(cfg: ModelConfig, rows_selected: float) -> dict:
    """Operations and bytes of absorbed-form MLA over the selected rows
    alone: each selected row is read once as one cached row
    (cache_head_dim bf16 lanes: the gather moves whole rows) and meets
    every head's scores and averages. The chip benchmark's
    kernel_costs/dsa_sparse_attention.py counts the same."""
    lanes = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return {"ops": rows_selected * cfg.num_heads * 2
            * (lanes + cfg.kv_lora_rank),
            "bytes": rows_selected * cfg.cache_head_dim * BYTES}


def attention_flops_per_pair(cfg: ModelConfig) -> float:
    """Operations one (query token, key token) pair costs over all heads:
    scores and the weighted average. MLA in the absorbed form (the one the
    program runs, for chunks too) pays the latent width per head."""
    if cfg.is_mla:
        return mla_attention_cost(cfg, 0, 1)["ops"]
    return 4.0 * cfg.num_heads * cfg.head_dim


def router_flops_per_token(cfg: ModelConfig) -> float:
    """The router's matmul over its whole width (sigmoid or softmax and
    the top-k are negligible beside it), per expert layer."""
    return 2.0 * cfg.hidden_size * cfg.num_experts if cfg.is_moe else 0.0


def kv_bytes_per_token(cfg: ModelConfig, kv_dtype: str = "auto",
                       tp: int = 1) -> float:
    # cache geometry, not attention geometry: MLA stores one shared latent
    # row per token (cache_kv_heads == 1) in REPLICATED pools — no TP lane
    # blocking applies
    kv_heads, head_dim = cfg.cache_kv_heads, cfg.cache_head_dim
    if cfg.is_mla:
        tp = 1
    lanes = kv_heads * head_dim
    # MLA holds its latent row ONCE (engine/kv_cache.py: the V pool has no
    # lanes); every other model keeps a K row and a V row
    pools = 1.0 if cfg.is_mla else 2.0
    if kv_dtype == "int8":
        # packed-scale int8 rows, lane-BLOCKED per TP shard and padded to a
        # 128 multiple PER BLOCK (dynamo_tpu.ops.attention.kv_lane_width) —
        # at high tp the padding can eat the entire saving (e.g. 8 KV heads
        # of dim 128 at tp=8: 8 x 256-lane blocks = bf16-sized rows), so
        # the roofline must model the real layout, not lanes/2
        kv_l = max(kv_heads // max(tp, 1), 1)
        block = -(-(kv_l * head_dim + 2 * kv_l) // 128) * 128
        return pools * cfg.num_layers * max(tp, 1) * block
    # an indexed model's second row kind: the indexer's key, same pages
    return cfg.num_layers * (pools * lanes + cfg.cache_index_dim) * BYTES


# Serving quantization tiers the engine implements (`--quantization`,
# `--kv-cache-dtype`), in PREFERENCE order: unquantized first — quantization
# is only recommended when the plain config can't fit or can't meet the SLA
# (matching how an operator would actually use the levers).
QUANT_TIERS = (
    ("none", "auto"),
    ("w8a8", "auto"),
    ("w8a8", "int8"),
)


def weight_bytes(quant: str) -> float:
    return 1.0 if quant in ("int8", "w8a8") else float(BYTES)


@dataclasses.dataclass(frozen=True)
class Estimate:
    """Roofline estimate for one (tp, batch, quant tier) point."""
    tp: int
    replicas: int            # data-parallel engine replicas (chips // tp)
    batch: int               # per-replica decode batch (max_num_seqs)
    ttft_s: float
    itl_s: float
    tok_s_per_chip: float    # aggregate decode throughput / total chips
    hbm_used_frac: float     # worst-chip HBM occupancy at full batch
    feasible: bool
    quantization: str = "none"   # none | w8a8 (weights/activations)
    kv_dtype: str = "auto"       # auto (model dtype) | int8

    def meets(self, ttft_ms: Optional[float], itl_ms: Optional[float]) -> bool:
        if not self.feasible:
            return False
        if ttft_ms is not None and self.ttft_s * 1e3 > ttft_ms:
            return False
        if itl_ms is not None and self.itl_s * 1e3 > itl_ms:
            return False
        return True


def kvbm_restore_seconds(n_bytes: float, bytes_per_s: float,
                         overhead_s: float = 0.0005) -> float:
    """Time to restore demoted KV blocks onto the device: bytes over the
    host<->device link plus one scatter-dispatch overhead. One side of the
    KVBM onboard gate (kvbm/cost_model.py)."""
    return overhead_s + n_bytes / max(bytes_per_s, 1.0)


def kvbm_recompute_seconds(cfg: ModelConfig, n_tokens: int,
                           chip_flops: float,
                           n_dispatches: int = 1,
                           mfu: float = MFU_PREFILL) -> float:
    """Time to RECOMPUTE a cached prefix instead of restoring it: the
    compute-bound prefill roofline for `n_tokens` (linear term only — the
    quadratic attention term would only widen restore's win) plus the
    per-chunk dispatch overhead of the chunked-prefill path that would
    actually run. The other side of the KVBM onboard gate."""
    flops = 2.0 * active_param_count(cfg) * n_tokens
    return (n_dispatches * DISPATCH_OVERHEAD_S
            + flops / max(chip_flops * mfu, 1.0))


def _allreduce_time(bytes_per_device: float, tp: int, sys: SystemSpec) -> float:
    """Ring all-reduce over ICI: 2*(tp-1)/tp of the buffer crosses each link."""
    if tp <= 1:
        return 0.0
    wire = 2.0 * (tp - 1) / tp * bytes_per_device
    return wire / (sys.chip.ici_bisection_bw * ICI_EFF)


def estimate(
    cfg: ModelConfig,
    sys: SystemSpec,
    tp: int,
    batch: int,
    isl: int,
    osl: int,
    quantization: str = "none",
    kv_dtype: str = "auto",
) -> Estimate:
    """Roofline TTFT/ITL/throughput for tp-way sharding and a decode batch.

    `quantization`/`kv_dtype` model the engine's serving levers: int8
    weights halve the weight footprint AND stream; w8a8 additionally runs
    int8xint8 MXU contractions (modeled only through bytes — conservative);
    int8 KV halves the per-token page stream and pool pressure."""
    replicas = max(sys.num_chips // tp, 1)
    p_total = param_count(cfg)
    p_active = active_param_count(cfg)
    chip = sys.chip
    wb = weight_bytes(quantization)
    kvb = kv_bytes_per_token(cfg, kv_dtype, tp=tp)
    if (kv_dtype == "int8" and not cfg.is_mla
            and cfg.cache_kv_heads % tp != 0):
        # the lane-blocked int8 layout requires tp | cache KV heads
        # (engine.KVCacheSpec.from_model raises for this combination;
        # MLA pools replicate, so the blocking never applies there)
        return Estimate(tp=tp, replicas=max(sys.num_chips // tp, 1),
                        batch=batch, ttft_s=float("inf"),
                        itl_s=float("inf"), tok_s_per_chip=0.0,
                        hbm_used_frac=float("inf"), feasible=False,
                        quantization=quantization, kv_dtype=kv_dtype)
    # MLA latent pools REPLICATE across the model axis: every chip holds
    # and streams the full KV pool (tp shards only the weights)
    kv_shards = 1 if cfg.is_mla else tp

    # --- capacity: per-chip share of weights + this replica's KV pages.
    avg_ctx = isl + osl / 2.0
    kv_per_seq_full = kvb * (isl + osl)
    weights_per_chip = p_total * wb / tp
    kv_per_chip = batch * kv_per_seq_full / kv_shards
    hbm_frac = (weights_per_chip + kv_per_chip) / (chip.hbm_bytes * 0.92)
    feasible = hbm_frac <= 1.0

    # --- prefill (one request of isl tokens on one tp group).
    l, nh, hd = cfg.num_layers, cfg.num_heads, cfg.head_dim
    flops_prefill = (2.0 * p_active * isl
                     + l * attention_flops_per_pair(cfg) * isl * isl)
    t_compute = flops_prefill / (tp * chip.bf16_flops * MFU_PREFILL)
    # 2 all-reduces per layer of the activations (attn out + mlp out)
    act_bytes = isl * cfg.hidden_size * BYTES
    t_coll = 2 * l * _allreduce_time(act_bytes, tp, sys)
    ttft = t_compute + t_coll + DISPATCH_OVERHEAD_S

    # --- decode step for the full batch at average context length
    # (per-chip read bytes over per-chip bandwidth; replicated MLA pools
    # get no TP bandwidth speedup on the KV stream).
    read_per_chip = (p_total * wb / tp
                     + batch * kvb * avg_ctx / kv_shards)
    t_mem = read_per_chip / (chip.hbm_bw * HBM_EFF)
    t_flops = 2.0 * p_active * batch / (tp * chip.bf16_flops * MFU_DECODE)
    dec_act = batch * cfg.hidden_size * BYTES
    t_dcoll = 2 * l * _allreduce_time(dec_act, tp, sys)
    itl = max(t_mem, t_flops) + t_dcoll + DISPATCH_OVERHEAD_S

    tok_s = replicas * batch / itl
    return Estimate(
        tp=tp, replicas=replicas, batch=batch,
        ttft_s=ttft, itl_s=itl,
        tok_s_per_chip=tok_s / sys.num_chips,
        hbm_used_frac=hbm_frac, feasible=feasible,
        quantization=quantization, kv_dtype=kv_dtype,
    )
