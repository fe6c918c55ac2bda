"""Operations and bytes of the sparse-attention indexer's scores
(ops/attention.py `_dsa_scores` under the `dsa_indexer` scope: DeepSeek-V3.2's
lightning indexer, I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])), from what
the engine counted at dispatch (EngineMetrics.dsa, a layer's worth a step; x
layers here).

Counted: what the algorithm needs for the (query, key) pairs scored. Every
index head's product over index_head_dim lanes; each query's keys read once
as bf16 rows. NO SHARING between sequences, or between the queries of one
chunk, is assumed: a chunk's 256 queries read the same keys, so a kernel
that shares them needs fewer bytes than this and can pass 100% of the byte
bound (the bound that binds here is the operations': 64 FLOP a byte).

    operations  pairs scored x index_n_heads x index_head_dim x 2
    bytes       pairs scored x index_head_dim x 2

The projections (q from the q-LoRA latent, the key, the head weights), the
ReLU and the weighted sum are left out: O(heads) a pair beside O(heads x
lanes). dynamo_tpu/profiler/roofline.py (`dsa_indexer_cost`) counts the
same and a test holds the two together.
"""


def cost(keys_scored: float, index_n_heads: int, index_head_dim: int) -> dict:
    return {"ops": keys_scored * 2 * index_n_heads * index_head_dim,
            "bytes": keys_scored * index_head_dim * 2}


def from_counters(grew, args: dict) -> dict:
    d = "metrics.dsa."
    pairs = grew(d + "decode_keys_scored") + grew(d + "chunk_keys_scored")
    c = cost(pairs * args["layers"], args["index_n_heads"],
             args["index_head_dim"])
    return dict(c, peak="peak_bf16_flops_per_s")
