"""Mixture-of-experts dispatch paths.

The reference serves MoE models through its consumed engines (BASELINE.json
config #5: Mixtral/DeepSeek expert-parallel via the smart router); here the
expert compute itself is TPU-native. Two paths, both exact (no token is
ever dropped), jit-safe and GSPMD-partitionable over the `expert` mesh axis
(sharding rules in dynamo_tpu.parallel.sharding map moe_w_* onto
P('expert', ...)); the choice between them is made from the model's shapes
(ModelConfig.moe_grouped), there is no option:

- `moe_mlp_dense`: every expert processes every token, the top-k combine
  matrix zeroes the rest. No gathers; the right choice where a token picks
  a large share of few experts (Mixtral: 2 of 8).
- `moe_mlp_grouped`: each token is computed only in the experts it picked.
  The T*k assignments are sorted by expert and the three projections run as
  grouped matmuls (`jax.lax.ragged_dot`; int8 x int8 -> int32 for W8A8
  weights) over the groups; an expert no token picked is not read. It is
  told which experts it holds (`expert_offset`, the weights' leading axis):
  the router keeps its full width, assignments to experts held elsewhere
  are left out — that part of the sum is another chip's — and no code
  stands in for their exchange. The matmuls run over the smallest of a few
  static row counts that holds the rows the held experts really received
  (`row_rungs`), and not at all where no row picked a held expert. A layer
  of few, small experts under a kernel backend takes ops/grouped_matmul's
  kernel instead of `ragged_dot` (`_kernel_for`, `_expert_rows_kernel`).

`route_topk` is the single router: (expert ids [T, k], weights [T, k]);
`topk_combine` scatters them into the dense combine matrix [T, X] the dense
path contracts with.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dynamo_tpu.models import quant
from dynamo_tpu.models.quant import einsum as qeinsum
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import grouped_matmul as gmm


def route_topk(logits: jax.Array, k: int,
               renormalize: bool = True,
               scaling_factor: float = 1.0,
               scoring: str = "softmax",
               select_bias: jax.Array | None = None,
               n_group: int = 1, topk_group: int = 1):
    """Router logits [T, X] (float32) -> (expert ids [T, k], weights
    [T, k] float32).

    scoring="softmax", renormalize=True (Mixtral/Qwen3): softmax over the
    selected top-k logits, weights sum to 1. renormalize=False (DeepSeek-V2
    norm_topk_prob=false): the GLOBAL softmax probabilities of the selected
    experts, sum < 1. scoring="sigmoid" (DeepSeek-V3 / Kimi-K2, HF
    topk_method noaux_tc): s = sigmoid(logits); the k largest
    of s + select_bias are picked — the bias moves the PICK only — and the
    weights are the picked s, divided by their sum + 1e-20 when
    renormalize. Either way times scaling_factor.

    n_group > 1 (DeepSeek-V3 / V3.2; sigmoid only) limits the pick to
    groups: the X outputs are n_group groups of X / n_group consecutive
    experts, a group scores the sum of its 2 largest s + select_bias, the
    topk_group best groups are kept (ties to the lower group, as top_k
    has it) and the k picks come from the kept groups alone. n_group = 1
    traces none of this: the program is the ungrouped one."""
    if n_group > 1 and scoring != "sigmoid":
        raise ValueError("group-limited routing is the sigmoid router's")
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choose = scores if select_bias is None else (
            scores + select_bias.astype(scores.dtype))
        if n_group > 1:
            t, x = choose.shape
            per = choose.reshape(t, n_group, x // n_group)
            group_score = jnp.sum(jax.lax.top_k(per, 2)[0], axis=-1)
            _, kept = jax.lax.top_k(group_score, topk_group)  # [T, kept]
            keep = jnp.zeros((t, n_group), bool).at[
                jnp.arange(t)[:, None], kept].set(True)
            # the published code fills the other groups' scores with 0.0;
            # -inf keeps its picks whatever the bias's sign (the kept
            # groups hold >= k experts: ModelConfig checks)
            choose = jnp.where(jnp.repeat(keep, x // n_group, axis=1),
                               choose, -jnp.inf)
        _, topi = jax.lax.top_k(choose, k)
        weights = jnp.take_along_axis(scores, topi, axis=-1)
        if renormalize:
            weights = weights / (
                jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    else:
        topv, topi = jax.lax.top_k(logits, k)
        if renormalize:
            weights = jax.nn.softmax(topv, axis=-1)
        else:
            weights = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                                          topi, axis=-1)
    if scaling_factor != 1.0:
        weights = weights * scaling_factor
    return topi, weights


def topk_combine(logits: jax.Array, k: int, dtype,
                 renormalize: bool = True,
                 scaling_factor: float = 1.0, **route) -> jax.Array:
    """Router logits [T, X] -> dense combine matrix [T, X]: route_topk's
    gate weights scattered back, zeros elsewhere."""
    topi, weights = route_topk(logits, k, renormalize, scaling_factor,
                               **route)
    return scatter_combine(topi, weights, logits.shape[-1], dtype)


def scatter_combine(topi: jax.Array, weights: jax.Array, num_experts: int,
                    dtype) -> jax.Array:
    weights = weights.astype(dtype)  # [T, K]
    t = topi.shape[0]
    return (
        jnp.zeros((t, num_experts), dtype)
        .at[jnp.arange(t)[:, None], topi]
        .add(weights)
    )


def two_matrix_act(act: str, u: jax.Array) -> jax.Array:
    """The activation of an expert of TWO matrices, act(x W_up) W_down
    (ModelConfig.expert_act): "relu2" = relu(u)^2, "silu"."""
    if act == "relu2":
        return jnp.square(jax.nn.relu(u))
    if act == "silu":
        return jax.nn.silu(u)
    raise ValueError(f"unknown two-matrix expert activation {act!r}")


def _rows_of(w) -> int:
    """Input rows [.., K, N] of a (maybe quantized) expert stack: K."""
    return (w.q if isinstance(w, quant.QTensor) else w).shape[-2]


def _pad_lanes(x: jax.Array, width: int) -> jax.Array:
    """x [T, E] with zero lanes up to `width` (a two-matrix expert's
    matrices may be stored with zero rows past the model's hidden size:
    ModelConfig.expert_dims_stored); x itself where it has them already."""
    extra = width - x.shape[-1]
    return jnp.pad(x, ((0, 0), (0, extra))) if extra else x


def moe_mlp_dense(
    x: jax.Array,        # [T, E]
    combine: jax.Array,  # [T, X]
    w_gate: jax.Array,   # [X, E, F]; None: an expert of two matrices
    w_up: jax.Array,
    w_down: jax.Array,   # [X, F, E]
    act: str = "",
) -> jax.Array:
    """All experts see all tokens; combine zeroes non-selected outputs."""
    if w_gate is None:
        h = two_matrix_act(act, qeinsum(
            "te,xef->txf", _pad_lanes(x, _rows_of(w_up)), w_up))
        y = qeinsum("txf,xfe->txe", h, w_down)[..., :x.shape[-1]]
        return jnp.einsum("txe,tx->te", y, combine)
    g = qeinsum("te,xef->txf", x, w_gate)
    u = qeinsum("te,xef->txf", x, w_up)
    y = qeinsum("txf,xfe->txe", jax.nn.silu(g) * u, w_down)
    return jnp.einsum("txe,tx->te", y, combine)


# what moe_mlp_grouped counts for a layer (int32 [6]); summed over layers
# and steps by the model and the engine, read at /worker/stats
MOE_STATS = ("assignments", "assignments_held", "busiest_held_sum",
             "experts_touched", "layer_steps", "rows_computed")

# the rows of XLA's int8 grouped-matmul tile: no rung lies under the smallest
# (32), and past the largest (512) fewer rows no longer shorten the matmul
_MIN_TILE_ROWS, _MAX_TILE_ROWS = 32, 512


def row_rungs(assignments: int, share: float) -> tuple[int, ...]:
    """The static row counts the grouped matmuls are compiled for, from the
    shapes alone: A = T*k assignments of which the share `share` (held
    experts over the router's width) is expected here. 0 | R1 | 4*R1 | A
    with R1 the power of two at or above A*share; 4*R1 only while it is a
    smaller tile than A's, and a rung that is not under A is left out: a
    layer that holds every expert, or a tiny one, keeps 0 | A.

    Why these (measured on the chip, PR 31, benchmarks/chip/records/
    pr31-ragged-dot-rows.json): XLA's ragged_dot pays one row tile of
    min(rows handed, 512) for every group it touches, whatever the group
    holds (30 us a touched expert at 32 rows, 43 at 256, 58 at 512 and
    above, at Kimi-K2's widths), and the gather, quantisation and
    scatter-add around it pay 0.5 us a row handed. Every rung is one more
    copy of the layer's body in every program's compile."""
    r1 = _MIN_TILE_ROWS
    while r1 < assignments * share:
        r1 *= 2
    tail = (4 * r1,) if 4 * r1 <= _MAX_TILE_ROWS else ()
    return (0,) + tuple(
        r for r in (r1,) + tail if r < assignments) + (assignments,)


def _flat_groups(w: jax.Array) -> jax.Array:
    """[L, X, K, N] (a whole layer stack) -> [L * X, K, N]: a view."""
    return w.reshape((-1,) + w.shape[-2:])


def _grouped_dot(x: jax.Array, w, group_sizes: jax.Array,
                 row_expert: jax.Array) -> jax.Array:
    """rows [A, K] (sorted by group) x w [G, K, N] -> [A, N]: row a meets
    the matrix of its own group only (row_expert[a]; group_sizes [G]).
    QTensorA8 weights contract int8 x int8 -> int32 on the MXU with
    per-row activation scales, like models.quant.einsum; weight-only int8
    converts the weights. `w` may be a whole layer stack [L, X, K, N]: its
    groups are then all layers' experts (see moe_mlp_grouped)."""
    if not isinstance(w, quant.QTensor):
        return jax.lax.ragged_dot(x, _flat_groups(w), group_sizes)
    w = type(w)(_flat_groups(w.q), _flat_groups(w.scale))
    # scale [G, 1, N] -> each row's own expert's output-channel scales
    w_scale = jnp.take(w.scale[:, 0, :], row_expert, axis=0)  # [A, N]
    if isinstance(w, quant.QTensorA8):
        x32 = x.astype(jnp.float32)
        amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
        xs = jnp.where(amax > 0, amax / 127.0, 1.0)
        xq = jnp.clip(jnp.round(x32 / xs), -127, 127).astype(jnp.int8)
        acc = jax.lax.ragged_dot(xq, w.q, group_sizes,
                                 preferred_element_type=jnp.int32)
        return (acc.astype(jnp.float32) * xs * w_scale).astype(x.dtype)
    y = jax.lax.ragged_dot(x, w.q.astype(x.dtype), group_sizes)
    return y * w_scale.astype(y.dtype)


def _expert_rows(rows: int, act: str, x, tok, row_expert, wr, n_held,
                 group_sizes, w_gate, w_up, w_down) -> jax.Array:
    """The expert layer over the first `rows` sorted assignments (those
    that belong to a group come first: n_held <= rows): gather the token
    rows, gate / up / down as grouped matmuls (up / down alone where
    w_gate is None: an expert of two matrices, `act` its activation),
    weight each result row and add it back to its token -> [T, E]."""
    if not rows:
        return jnp.zeros_like(x)
    tok, row_expert, wr = tok[:rows], row_expert[:rows], wr[:rows]
    live = (jnp.arange(rows) < n_held)[:, None]
    with jax.named_scope("moe_experts"):
        xs = jnp.take(x, tok, axis=0)  # [R, E]
        if w_gate is None:
            u = _grouped_dot(_pad_lanes(xs, _rows_of(w_up)), w_up,
                             group_sizes, row_expert)
            h = jnp.where(live, two_matrix_act(act, u), 0)
        else:
            g = _grouped_dot(xs, w_gate, group_sizes, row_expert)
            u = _grouped_dot(xs, w_up, group_sizes, row_expert)
            h = jnp.where(live, jax.nn.silu(g) * u, 0)
        y = _grouped_dot(h, w_down, group_sizes, row_expert)
        if w_gate is None:
            y = y[:, :x.shape[-1]]  # the zero lanes of a stored-wider W_down
        # rows behind the last group were never written by the grouped
        # matmul: select, do not multiply (they may hold anything)
        y = jnp.where(live, y.astype(jnp.float32) * wr[:, None], 0)
        return jnp.zeros(x.shape, jnp.float32).at[tok].add(y).astype(x.dtype)


def _kernel_for(rows: int, w_gate, w_up, w_down):
    """The backend under which ops/grouped_matmul takes this layer's three
    matmuls over `rows` sorted rows (its `serves`: W8A8, gated, few small
    lane-aligned experts, a kernel backend scoped), else None."""
    if not rows or w_gate is None or not all(
            isinstance(w, quant.QTensorA8) for w in (w_gate, w_up, w_down)):
        return None
    took = {gmm.serves(rows, *w.q.shape[-3:]) for w in (w_gate, w_up, w_down)}
    return took.pop() if len(took) == 1 else None


def _quantise_rows(x: jax.Array):
    """x [R, K] -> (int8 rows, float32 scales [R, 1]), a row's largest
    value at 127: what `_grouped_dot` does to its rows under W8A8."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    xs = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x32 / xs), -127, 127).astype(jnp.int8), xs


def _expert_rows_kernel(rows: int, backend: str, order, sizes, layer,
                        x, tok, row_expert, wr, n_held, group_sizes,
                        w_gate, w_up, w_down) -> jax.Array:
    """`_expert_rows` for a gated W8A8 layer ops/grouped_matmul takes
    (`_kernel_for`): the three projections are its kernel's over the
    layer's own `sizes` [Xh] and index `layer` (row_expert and the whole
    stack's group_sizes are `ragged_dot`'s and unread), the scales ride the
    kernel, the down projection's rows come out weighted (a row's gate
    weight folded into its activation scale), and a token's k results are
    GATHERED back by rank (`order`: the sort's permutation) and summed: no
    scatter-add over the rows."""
    del row_expert, group_sizes
    t, a = x.shape[0], order.shape[0]
    interpret = backend == "pallas_interpret"
    live = (jnp.arange(rows) < n_held)[:, None]
    work = gmm.pairs(sizes, rows)
    with jax.named_scope("moe_experts"):
        xq, xs = _quantise_rows(jnp.take(x, tok[:rows], axis=0))
        g = gmm.grouped_matmul(xq, xs, w_gate.q, w_gate.scale, work, layer,
                               out_dtype=x.dtype, interpret=interpret)
        u = gmm.grouped_matmul(xq, xs, w_up.q, w_up.scale, work, layer,
                               out_dtype=x.dtype, interpret=interpret)
        # rows behind the last group were never written: select
        hq, hs = _quantise_rows(jnp.where(live, jax.nn.silu(g) * u, 0))
        y = gmm.grouped_matmul(
            hq, hs * wr[:rows, None], w_down.q, w_down.scale, work, layer,
            out_dtype=jnp.float32, interpret=interpret)
        # assignment (token, j) is sorted row rank[token, j]; one behind
        # the held rows (another chip's expert, a masked token) adds nothing
        rank = jnp.zeros((a,), jnp.int32).at[order].set(
            jnp.arange(a, dtype=jnp.int32))
        got = jnp.take(y, jnp.minimum(rank, rows - 1), axis=0)
        got = jnp.where((rank < n_held)[:, None], got, 0)
        return got.reshape(t, a // t, -1).sum(axis=1).astype(x.dtype)


def moe_mlp_grouped(
    x: jax.Array,        # [T, E]
    topi: jax.Array,     # [T, K] expert ids over the router's whole width
    weights: jax.Array,  # [T, K] gate weights
    w_gate,              # [Xh, E, F] the experts HELD here; None: experts
    w_up,                # of two matrices, act(x W_up) W_down
    w_down,              # [Xh, F, E]
    *,
    expert_offset: int = 0,
    num_experts: int | None = None,
    token_mask: jax.Array | None = None,
    layer=None,
    act: str = "",
):
    """Each token is computed only in the experts it picked, and only in
    those held here: experts [expert_offset, expert_offset + Xh) of the
    router's `num_experts` (None: Xh, every expert is held). Returns
    (y [T, E], stats int32 [6] as MOE_STATS).

    The T*K assignments are sorted by held expert (assignments to experts
    held elsewhere, and those of masked rows, sort behind the last group
    and belong to no group), the token rows are gathered in that order and
    the projections run as grouped matmuls; each result row is weighted and
    added back to its token.

    XLA's grouped matmul pays, for every group it touches, a row tile
    sized by the rows it is HANDED, whatever the groups cover, and a share
    of a wide router receives few of them (row_rungs has the numbers): so
    the gather, the matmuls and the scatter run over the first R sorted
    rows, R the smallest rung of `row_rungs` that holds this layer's own
    count (chosen on the device; every rung is compiled). The last rung is
    every assignment — all of them held here — so no token is ever
    dropped at any imbalance, and the first is none: a layer no row of
    which picked a held expert runs no matmul.

    `layer` (a traced index) with weights [L, Xh, ...]: the WHOLE layer
    stack is handed to the grouped matmul, whose groups are then all
    layers' experts with every size zero but this layer's. Slicing a
    layer's experts out of the stack inside the layer scan would copy
    them (the grouped matmul is a custom call, a slice cannot fuse into
    it): 1.06 GB a layer at Kimi-K2's widths, read or not (seen on the
    chip, PR 27: 70% of a decode step's device time)."""
    t, k = topi.shape
    stack = (w_up.q if isinstance(w_up, quant.QTensor) else w_up).shape
    xh = stack[-3]
    local = topi.astype(jnp.int32) - expert_offset
    held = (local >= 0) & (local < xh)
    if token_mask is not None:
        held &= token_mask[:, None]
    key = jnp.where(held, local, xh).reshape(t * k)
    order = jnp.argsort(key)  # stable: ties keep token order
    row_expert = jnp.minimum(key[order], xh - 1)
    tok = order // k
    group_sizes = jnp.bincount(key, length=xh + 1)[:xh].astype(jnp.int32)
    n_held = jnp.sum(group_sizes)
    layer_sizes = group_sizes
    if layer is not None:
        first = layer * xh
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((stack[0] * xh,), jnp.int32), group_sizes, (first,))
        row_expert = row_expert + first
    wr = weights.reshape(t * k)[order].astype(jnp.float32)
    rungs = row_rungs(t * k, xh / (num_experts or xh))
    ladder = jnp.asarray(rungs, jnp.int32)
    rung = jnp.sum(ladder[:-1] < n_held)  # the first that holds n_held
    # a rung ops/grouped_matmul takes runs `_expert_rows_kernel` over the
    # layer's own sizes and index; every other branch is traced as it was
    took = [_kernel_for(r, w_gate, w_up, w_down) for r in rungs]
    for backend in filter(None, took):
        att._note_impl(gmm._OP, backend)
    out = jax.lax.switch(
        rung, [functools.partial(_expert_rows_kernel, r, backend, order,
                                 layer_sizes, layer) if backend else
               functools.partial(_expert_rows, r, act)
               for r, backend in zip(rungs, took)],
        x, tok, row_expert, wr, n_held, group_sizes, w_gate, w_up, w_down)
    n_all = (jnp.sum(token_mask) * k if token_mask is not None
             else jnp.int32(t * k))
    stats = jnp.stack([
        n_all.astype(jnp.int32), n_held.astype(jnp.int32),
        jnp.max(layer_sizes), jnp.sum(layer_sizes > 0).astype(jnp.int32),
        jnp.int32(1), ladder[rung]])
    return out, stats
