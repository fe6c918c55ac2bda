"""A W8A8 grouped matmul of our own for a layer of FEW, SMALL experts.

    rows [R, K] int8, sorted by expert;  w [G, K, N] int8;  sizes [X]
    -> [R, N]: row r times the matrix of its own expert, times the row's
       activation scale and the expert's output-channel scales

`jax.lax.ragged_dot` pays a row tile and a launch's worth of set-up for every
group it touches: at LFM2-8B-A1B's 4 MB matrices that is 13.5 us a touched
expert where the bytes take 5 (PR 52: 38% of the roofline, 84% of a decode
step). Here the touched experts' matrices are ONE stream: the grid walks a
work list of (expert, row tile) pairs built from the group sizes (every pair
whose expert has a row in that tile, experts in order), a pair's weight block
is the whole [K, N] matrix, and Pallas fetches the next pair's matrix while
this one multiplies. An expert no row picked is in no pair and is not read.
A row tile that holds several experts' rows is multiplied once a pair and
each pair keeps its own rows (a masked store into the resident output
block), as megablox's gmm does; the work list is static in length (row tiles
+ X - 1, the most pairs X experts can make) and the pairs past the real ones
name the last real pair's blocks and do nothing.

`w` may be a whole layer stack [L, X, K, N] with `layer` a traced index: the
index map adds layer * X to the expert, so no layer's experts are sliced out
(ops/moe.moe_mlp_grouped says what that copy costs).

Rows at or behind sum(sizes) belong to no pair and are never written: the
caller selects (ops/moe._expert_rows_kernel does).

Taken by ops/moe.moe_mlp_grouped where `serves` says so: from the shapes
alone (models/config.grouped_kernel_shapes: at most 32 experts a layer of at
most 4 MiB a matrix, lane-aligned; such a layer's experts are stored at the
model's own extents, ModelConfig.expert_dims_stored) and under a kernel
backend. Measured on the chip at LFM2-8B-A1B's shape only (PERF.md section
6, PR 52); every other configuration's experts stay on `ragged_dot`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.models.config import grouped_kernel_shapes
from dynamo_tpu.ops import attention as att

# rows a pair multiplies. A pair costs the MXU a pass over the whole matrix
# whatever rows it is handed up to 128 (loading a weight tile takes as long
# as 128 rows take to stream past it), about what the matrix's DMA takes; so
# the tile is as tall as is free, which makes the fewest pairs
TILE_ROWS = 128
_OP = "grouped_matmul"


def serves(rows: int, experts: int, k: int, n: int) -> str | None:
    """The backend (`pallas` | `pallas_interpret`) if this kernel takes a
    layer of `experts` int8 matrices [k, n] over `rows` sorted rows under
    the scoped backend, else None (the caller keeps `ragged_dot`)."""
    backend = att._resolve_backend()
    if backend not in att._KERNEL_BACKENDS:
        return None
    if not grouped_kernel_shapes(experts, k, n) or rows % TILE_ROWS:
        return None
    return backend


class Pairs(NamedTuple):
    """The work list of one expert layer (int32; built once a layer, read
    by its three matmuls): pair i multiplies row tile `tile[i]` with expert
    `expert[i]`'s matrix and keeps rows [start[e], end[e]); `count` [1]
    pairs are real."""
    expert: jax.Array  # [W]
    tile: jax.Array    # [W]
    start: jax.Array   # [X]
    end: jax.Array     # [X]
    count: jax.Array   # [1]


def pairs(sizes: jax.Array, rows: int, tile_rows: int = TILE_ROWS) -> Pairs:
    """sizes [X] (rows of each expert, in the rows' sorted order) -> the
    work list over `rows` // tile_rows row tiles."""
    x = sizes.shape[0]
    n_tiles = rows // tile_rows
    sizes = sizes.astype(jnp.int32)
    end = jnp.cumsum(sizes)
    start = end - sizes
    first = start // tile_rows
    spans = jnp.where(sizes > 0, (end - 1) // tile_rows - first + 1, 0)
    upto = jnp.cumsum(spans)  # pairs of experts 0..e
    count = upto[-1]
    # a pair past the real ones names the last real pair's blocks
    i = jnp.minimum(jnp.arange(n_tiles + x - 1, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    # the expert whose pairs hold pair i (a count, not a search: no loop)
    expert = jnp.minimum(
        jnp.sum(upto[None, :] <= i[:, None], axis=1, dtype=jnp.int32), x - 1)
    tile = first[expert] + i - (upto[expert] - spans[expert])
    return Pairs(expert, jnp.clip(tile, 0, n_tiles - 1), start, end,
                 count[None])


def _kernel(expert_ref, tile_ref, start_ref, end_ref, count_ref, base_ref,
            x_ref,   # [tile_rows, K] int8
            xs_ref,  # [tile_rows, 1] float32: the rows' activation scales
            w_ref,   # [1, K, N] int8: the pair's whole matrix
            ws_ref,  # [1, 1, N] float32: the expert's channel scales
            o_ref):  # [tile_rows, N]
    del base_ref  # the index maps read it
    tile_rows = x_ref.shape[0]
    i = pl.program_id(0)

    @pl.when(i < count_ref[0])
    def _():
        e = expert_ref[i]
        acc = jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        y = (acc.astype(jnp.float32) * xs_ref[...]) * ws_ref[0]
        row = tile_ref[i] * tile_rows + jax.lax.broadcasted_iota(
            jnp.int32, (tile_rows, 1), 0)
        mine = (row >= start_ref[e]) & (row < end_ref[e])
        # the block stays resident while the tile does: the rows of the
        # experts before this one in the tile are in it already
        o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def grouped_matmul(xq: jax.Array, xs: jax.Array, wq: jax.Array,
                   w_scale: jax.Array, work: Pairs, layer=None, *,
                   out_dtype=jnp.bfloat16,
                   interpret: bool = False) -> jax.Array:
    """xq [R, K] int8 (sorted by expert) with scales xs [R, 1] float32; wq
    [X, K, N] or [L, X, K, N] int8 with w_scale [.., 1, N] float32; `work`
    = pairs(sizes, R); `layer` the stack's index (None: wq is one layer's)
    -> [R, N] out_dtype, (int32 product * xs * w_scale) on the rows of an
    expert, anything on the rows behind them."""
    r, k = xq.shape
    n = wq.shape[-1]
    x = work.start.shape[0]
    # the work list's length says how tall its tiles are
    tile_rows = r // (work.expert.shape[0] - x + 1)
    if r % tile_rows:
        raise ValueError(f"{r} rows in tiles of {tile_rows}")
    wq = wq.reshape((-1, k, n))
    w_scale = w_scale.reshape((-1, 1, n))
    base = (jnp.zeros((1,), jnp.int32) if layer is None
            else (jnp.asarray(layer, jnp.int32) * x)[None])

    def by_tile(i, expert, tile, *_):
        return tile[i], 0

    def by_expert(i, expert, tile, start, end, count, base):
        return base[0] + expert[i], 0, 0

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=work.expert.shape,
            in_specs=[pl.BlockSpec((tile_rows, k), by_tile),
                      pl.BlockSpec((tile_rows, 1), by_tile),
                      pl.BlockSpec((1, k, n), by_expert),
                      pl.BlockSpec((1, 1, n), by_expert)],
            out_specs=pl.BlockSpec((tile_rows, n), by_tile)),
        out_shape=jax.ShapeDtypeStruct((r, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            # in order: a pair counts on the output block the pair before
            # it left resident
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 << 20),
        interpret=interpret,
        name="grouped_matmul_w8a8",
    )(work.expert, work.tile, work.start, work.end, work.count, base,
      xq, xs, wq, w_scale)
