"""Operations and bytes of a model's block-sparse attention layers
(InfLLM-v2 selection over mean-pooled keys inside paged GQA attention:
dynamo_tpu/ops/sparse_blocks.py), from what the engine counted at dispatch
(EngineMetrics.sparse: a layer's worth a step; x the sparse layers here).

Counted: what was ASKED, whatever implements it, and the least it can cost.
H query heads over KV key heads of D lanes; a cached row is K and V, bf16; a
pooled key is read as float32 sums.

  which = "decode": a live row a layer scores `keys_scored` pooled keys (all
    H heads against KV x D lanes each) and attends `rows_attended` cached
    rows a KV head (its selected blocks; its whole context up to dense_len).
        bytes       rows_attended x KV x D x 2 (K, V) x 2 B
                  + keys_scored x KV x D x 4 B
        operations  rows_attended x H x D x 4   (q.k and p.v)
                  + keys_scored x H x D x 2
    The program reads every KV head's lanes of a row for each KV head's
    table (twice the page copies), and two page sums a pooled key: what that
    costs is the program's, not the algorithm's.

  which = "mixed": a prompt's chunk: its real queries' `chunk_keys_scored`
    and `chunk_rows_attended`, the same arithmetic; the rows are read once
    for the chunk's queries together at best, so the bytes are those of ONE
    query's rows a chunk (the longest's: rows_attended / queries at the
    mean is the estimate used) and the share is bound by the operations.
        operations  chunk_rows_attended x H x D x 4
                  + chunk_keys_scored x H x D x 2
        bytes       (chunk_rows_attended / chunk_queries) x chunk programs
                    x KV x D x 2 x 2 B + chunk_keys_scored / chunk_queries
                    x chunk programs x KV x D x 4 B
"""


def cost(rows: float, keys: float, heads: int, kv_heads: int,
         head_dim: int) -> dict:
    return {"ops": (rows * 4 + keys * 2) * heads * head_dim,
            "bytes": (rows * 2 * 2 + keys * 4) * kv_heads * head_dim}


def from_counters(grew, args: dict) -> dict:
    """`grew(path)`: growth of a /worker/stats counter. args: which
    ("decode" | "mixed"), layers, heads, kv_heads, head_dim, chunk_tokens."""
    n = args["layers"]
    if args["which"] == "decode":
        c = cost(grew("metrics.sparse.rows_attended") * n,
                 grew("metrics.sparse.keys_scored") * n, args["heads"],
                 args["kv_heads"], args["head_dim"])
    else:
        queries = grew("metrics.sparse.chunk_queries")
        rows = grew("metrics.sparse.chunk_rows_attended") * n
        keys = grew("metrics.sparse.chunk_keys_scored") * n
        c = cost(rows, keys, args["heads"], args["kv_heads"],
                 args["head_dim"])
        once = max(queries, 1.0) / args["chunk_tokens"]  # chunk programs
        c["bytes"] = cost(rows / max(queries, 1.0) * once,
                          keys / max(queries, 1.0) * once, args["heads"],
                          args["kv_heads"], args["head_dim"])["bytes"]
    return dict(c, peak="peak_bf16_flops_per_s")
