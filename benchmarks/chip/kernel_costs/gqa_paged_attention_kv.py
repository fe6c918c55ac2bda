"""Operations and bytes of the paged attention kernels at per-head K/V
geometry where the two KINDS of layer differ in KV heads and a row's keys are
wider than its values (ops/pallas_attention.py decode kernel,
ops/ragged_attention.py mixed-step kernel, each under a static window and
with a learned sink on the sliding layers): every query head scores against
its KV head's `qk_dim`-lane K row and averages its `v_dim`-lane V row, from
what the engine counted at dispatch (EngineMetrics.attn_kinds, by kind, a
layer's worth a step; x the layers of the kind here).
kernel_costs/gqa_paged_attention.py is the same count at one KV-head count
and one width for both kinds and for K and V.

Counted: what the algorithm needs over the rows WITHIN REACH: a full layer's
query reads its whole context, a sliding layer's min(context, window) rows.
A decode row reads its rows once; a chunk's query block (8 tokens) reads
the rows from its first token's reach to its last token once, and each of
its tokens scores its own. The sink is one more logit a row: nothing worth
counting.

    bytes       KV rows read x KV heads of the kind x (qk_dim + v_dim) x 2 (bf16)
    operations  (query token, KV row) pairs x query heads x 2 x (qk_dim + v_dim)
                                               (scores, then the average)
"""

KINDS = ("full", "window")


def cost(kv_rows_read: float, qk_pairs: float, heads: int, kv_heads: int,
         qk_dim: int, v_dim: int) -> dict:
    return {"ops": qk_pairs * heads * 2 * (qk_dim + v_dim),
            "bytes": kv_rows_read * kv_heads * (qk_dim + v_dim) * 2}


def from_counters(grew, args: dict) -> dict:
    """`grew(path)`: growth of a /worker/stats counter. args: which
    ("decode" | "mixed"), layers_<kind>, heads_<kind>, kv_heads_<kind>,
    qk_dim, v_dim."""
    ops = nbytes = 0.0
    for kind in KINDS:
        a = f"metrics.attn_kinds.{kind}."
        if args["which"] == "decode":
            rows = pairs = grew(a + "decode_kv_rows")
        else:
            rows = (grew(a + "mixed_decode_kv_rows")
                    + grew(a + "mixed_chunk_block_kv_rows"))
            pairs = (grew(a + "mixed_decode_kv_rows")
                     + grew(a + "mixed_chunk_kv_pairs"))
        n = args["layers_" + kind]
        c = cost(rows * n, pairs * n, args["heads_" + kind],
                 args["kv_heads_" + kind], args["qk_dim"], args["v_dim"])
        ops += c["ops"]
        nbytes += c["bytes"]
    return {"ops": ops, "bytes": nbytes, "peak": "peak_bf16_flops_per_s"}
