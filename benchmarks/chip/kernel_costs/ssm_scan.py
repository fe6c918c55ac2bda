"""Operations and bytes of a hybrid model's Mamba-2 state work (ops/ssm.py:
`step`, the one-token update of a batch of states, and `scan_chunked`, the
chunked scan of a prompt's chunk), from what the engine counted at dispatch
(EngineMetrics.ssm: a layer's worth a step; x the Mamba-2 layers here).

Counted: the least the work can cost, never more. H heads of P lanes, N
state lanes a head, G groups of B / C rows, scan chunks of Q tokens.

  which = "decode": a live row a layer reads its state S [H, P, N] float32
    once and writes it once; five elementwise operations a state lane (the
    decay, the outer product's two, the read-out's two).
        bytes       rows x 2 x H P N x 4
        operations  rows x 5 x H P N
    The program updates every slot where it lies, live or not: what the
    empty slots cost is the program's, not the algorithm's.

  which = "chunk": a prompt token a layer, in a scan chunk of Q: the causal
    half of the chunk's quadratic form (C B^T scores a group, then the
    scores times x a head), the state's read-out and its update; a chunk
    program a layer reads the sequence's state once and writes it once.
        operations  tokens x (Q G N + Q H P + 4 H P N)   (matmul FLOPs)
        bytes       calls x 2 x H P N x 4
                  + tokens x (2 (H P + 2 G N) + 4 H + 4 H P)
                    (the conv's bf16 row and dt in, the float32 row out)
    against the bf16 peak: the program runs these in float32 at precision
    HIGHEST, several passes each; the least time is one.
"""


def cost_decode(rows: float, heads: int, head_dim: int, state: int) -> dict:
    lanes = heads * head_dim * state
    return {"ops": rows * 5 * lanes, "bytes": rows * 2 * lanes * 4}


def cost_chunk(tokens: float, calls: float, heads: int, head_dim: int,
               groups: int, state: int, scan_chunk: int) -> dict:
    h, p, g, n, q = heads, head_dim, groups, state, scan_chunk
    return {"ops": tokens * (q * g * n + q * h * p + 4 * h * p * n),
            "bytes": (calls * 2 * h * p * n * 4
                      + tokens * (2 * (h * p + 2 * g * n) + 4 * h
                                  + 4 * h * p))}


def from_counters(grew, args: dict) -> dict:
    """`grew(path)`: growth of a /worker/stats counter. args: which
    ("decode" | "chunk"), layers, heads, head_dim, groups, state,
    scan_chunk."""
    n = args["layers"]
    if args["which"] == "decode":
        c = cost_decode(grew("metrics.ssm.decode_rows") * n, args["heads"],
                        args["head_dim"], args["state"])
    else:
        c = cost_chunk(grew("metrics.ssm.chunk_tokens") * n,
                       grew("metrics.ssm.chunk_calls") * n, args["heads"],
                       args["head_dim"], args["groups"], args["state"],
                       args["scan_chunk"])
    return dict(c, peak="peak_bf16_flops_per_s")
