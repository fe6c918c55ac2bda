"""Operations and bytes of the paged attention kernels at MLA's latent
geometry (ops/pallas_attention.py decode kernel, ops/ragged_attention.py
mixed-step kernel; absorbed form: every head scores against ONE shared
[c_kv | k_rope] row a token, and averages its first kv_lora_rank lanes),
from what the engine counted at dispatch (EngineMetrics.attn, a layer's
worth a step; x layers here).

Counted: what the algorithm needs over LIVE rows. The row is read once (the
kernels copy K alone since PR 27): kv_lora_rank + qk_rope_head_dim bf16
lanes, not the 128-lane padding. A decode row reads its whole context; a
chunk's query block (8 tokens) reads its causal horizon once, and each of
its tokens scores its own horizon.

    bytes       KV rows read x (rank + rope) x 2
    operations  (query token, KV row) pairs x heads x 2 x ((rank + rope)
                + rank)                        (scores, then the average)

dynamo_tpu/profiler/roofline.py (`mla_attention_cost`) counts the same and a
test holds the two together.
"""


def cost(kv_rows_read: float, qk_pairs: float, heads: int,
         kv_lora_rank: int, qk_rope_head_dim: int) -> dict:
    lanes = kv_lora_rank + qk_rope_head_dim
    return {"ops": qk_pairs * heads * 2 * (lanes + kv_lora_rank),
            "bytes": kv_rows_read * lanes * 2}


def from_counters(grew, args: dict) -> dict:
    a = "metrics.attn."
    if args["which"] == "decode":
        rows = pairs = grew(a + "decode_kv_rows")
    else:
        rows = (grew(a + "mixed_decode_kv_rows")
                + grew(a + "mixed_chunk_block_kv_rows"))
        pairs = (grew(a + "mixed_decode_kv_rows")
                 + grew(a + "mixed_chunk_kv_pairs"))
    c = cost(rows * args["layers"], pairs * args["layers"], args["heads"],
             args["kv_lora_rank"], args["qk_rope_head_dim"])
    return dict(c, peak="peak_bf16_flops_per_s")
