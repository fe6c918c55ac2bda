"""Operations and bytes of the grouped expert matmuls where an expert is TWO
matrices, act(x W_up) W_down with no gate (ops/moe.py moe_mlp_grouped with
w_gate None: the up and down projections of the experts held here as two
`jax.lax.ragged_dot` calls an expert layer), from what the layer counted.

kernel_costs/grouped_expert_matmul.py counts three matrices an expert; read
with it, a two-matrix layer's share of its roofline would come out half as
high again as the work allows. Counted here: what the algorithm needs at
the model's own extents (the weights are stored with zero rows and lanes
around them, ModelConfig.expert_dims_stored: 3,072 x 2,048 around
Nemotron-H's 2,688 x 1,856; those are no work the model asks for), not what
a tile rounds up to. A row is one assignment of a token to a
held expert; it meets its own expert's two matrices only. An expert no row
picked is not read.

    operations  rows x 2 x 2 x hidden x width          (int8 x int8 -> int32)
    bytes       experts touched x 2 x hidden x width   (int8 weights)
              + rows x (hidden + width)                (int8 rows in)
              + rows x 4 x (width + hidden)            (int32 rows out)
"""


def cost(rows: float, experts_touched: float, hidden_size: int,
         expert_width: int) -> dict:
    """rows: assignments to held experts; experts_touched: held experts
    with at least one row, summed over the expert layers and steps counted.
    -> {"ops", "bytes"}."""
    e, f = hidden_size, expert_width
    return {"ops": rows * 2 * 2 * e * f,
            "bytes": (experts_touched * 2 * e * f + rows * (e + f)
                      + rows * 4 * (f + e))}


def from_counters(grew, args: dict) -> dict:
    """`grew(path)`: growth of a /worker/stats counter over the window."""
    return dict(cost(grew("metrics.moe.assignments_held"),
                     grew("metrics.moe.experts_touched"),
                     args["hidden_size"], args["expert_width"]),
                peak="peak_int8_ops_per_s")
