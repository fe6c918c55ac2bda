"""Operations and bytes of absorbed-form MLA attention over the rows the
sparse selection kept (ops/attention.py `_gather_rows` + `_dsa_attend` under
the `dsa_sparse_attn` scope), from what the engine counted at dispatch
(EngineMetrics.dsa.rows_selected: min(index_topk, context) a query, a
layer's worth a step; x layers here).

Counted: each selected row is read once, as the whole cached row (the
token-granular gather moves rows of `row_lanes` bf16 lanes: 640 = the 576
of [c_kv | k_rope] padded to a lane multiple), and meets every head's score
and average.

    bytes       rows selected x row_lanes x 2
    operations  rows selected x heads x 2 x ((rank + rope) + rank)

The gathered copy's write and second read are the implementation's, not the
algorithm's, and are left out: a kernel that attends from the pages as it
gathers does without them. dynamo_tpu/profiler/roofline.py
(`dsa_sparse_attention_cost`) counts the same and a test holds the two
together.
"""


def cost(rows_selected: float, heads: int, kv_lora_rank: int,
         qk_rope_head_dim: int, row_lanes: int) -> dict:
    lanes = kv_lora_rank + qk_rope_head_dim
    return {"ops": rows_selected * heads * 2 * (lanes + kv_lora_rank),
            "bytes": rows_selected * row_lanes * 2}


def from_counters(grew, args: dict) -> dict:
    rows = grew("metrics.dsa.rows_selected") * args["layers"]
    c = cost(rows, args["heads"], args["kv_lora_rank"],
             args["qk_rope_head_dim"], args["row_lanes"])
    return dict(c, peak="peak_bf16_flops_per_s")
