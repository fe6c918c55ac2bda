"""Operations and bytes of the gated short convolution's elementwise work
between its two projections (ops/short_conv.py: `gated_step`, one token a
decode slot, and `gated_rows`, a prompt's chunk), from what the engine
counted at dispatch (EngineMetrics.conv: a layer's worth a step; x the conv
layers here). The projections are the weight stream's and are not counted.

Counted: the least the work can cost, never more. A row a layer, E lanes in
bf16: the three runs [B | C | u] read and the gated row written; the K - 1 =
2 state rows are read and written once a SEQUENCE a call: with every decode
row (each is its own sequence's), once a chunk whatever rows it holds. Seven
elementwise operations a lane a row (the B gate, the three taps' multiplies
and two adds, the C gate).

    bytes       E x 2 x (4 x (decode rows + chunk tokens)
                         + 4 x (decode rows + chunk calls))
    operations  (decode rows + chunk tokens) x E x 7

The program's decode form reads and writes ALL slots' rows, live or not: what
the empty slots cost is the program's, not the algorithm's.
"""


def cost(rows: float, states: float, hidden_size: int) -> dict:
    """rows: token rows; states: sequences whose two rows moved."""
    return {"ops": rows * hidden_size * 7,
            "bytes": hidden_size * 2 * (4 * rows + 4 * states)}


def from_counters(grew, args: dict) -> dict:
    """`grew(path)`: growth of a /worker/stats counter. args: layers,
    hidden_size."""
    decode = grew("metrics.conv.decode_rows")
    rows = (decode + grew("metrics.conv.chunk_tokens")) * args["layers"]
    states = (decode + grew("metrics.conv.chunk_calls")) * args["layers"]
    return dict(cost(rows, states, args.get("hidden_size", 2048)),
                peak="peak_bf16_flops_per_s")
