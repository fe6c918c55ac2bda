"""Operations and bytes of the grouped expert matmuls (ops/moe.py
moe_mlp_grouped: gate, up and down projections of the experts held here as
three `jax.lax.ragged_dot` calls an expert layer), from what the layer
counted.

Counted: what the algorithm needs, not what a tile rounds up to. A row is one
assignment of a token to a held expert; it meets its own expert's three
matrices only. An expert no row picked is not read. dynamo_tpu/profiler/
roofline.py (`moe_expert_cost`) counts the same and a test holds the two
together.

    operations  rows x 3 x 2 x hidden x width          (int8 x int8 -> int32)
    bytes       experts touched x 3 x hidden x width   (int8 weights)
              + rows x (2 x hidden + width)            (int8 rows in: the
                                                        token row for gate
                                                        and for up, the
                                                        hidden row for down)
              + rows x 4 x (2 x width + hidden)        (int32 rows out)
"""


def cost(rows: float, experts_touched: float, hidden_size: int,
         expert_width: int) -> dict:
    """rows: assignments to held experts; experts_touched: held experts
    with at least one row, summed over the expert layers and steps counted.
    -> {"ops", "bytes"}."""
    e, f = hidden_size, expert_width
    ops = rows * 3 * 2 * e * f
    nbytes = (experts_touched * 3 * e * f
              + rows * (2 * e + f)
              + rows * 4 * (2 * f + e))
    return {"ops": ops, "bytes": nbytes}


def from_counters(grew, args: dict) -> dict:
    """`grew(path)`: growth of a /worker/stats counter over the window."""
    return dict(cost(grew("metrics.moe.assignments_held"),
                     grew("metrics.moe.experts_touched"),
                     args["hidden_size"], args["expert_width"]),
                peak="peak_int8_ops_per_s")
