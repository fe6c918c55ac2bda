"""Mamba-2 state-space mixer pieces: the depthwise causal conv with carried
rows, the chunked scan (SSD) with an initial state handed in and the final
state handed out, and the one-token update of a batch of states.

A sequence's state a layer is `S` [H, P, N] float32 (H heads of P lanes, N
state lanes a head; head h reads the B / C rows of group h // (H / G)) and
the conv's last K-1 input rows [K-1, C]. Neither grows with the context.

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t      y_t = S_t C_t + D x_t

Plain XLA compositions, and ONE kernel: the one-token update of the decode
slots (`update_live`) reads and writes the LIVE slots' states only, where
they lie (`step` is its XLA twin over every slot, the reference and the
path of backend `xla` and of a CPU). Every product that touches the state
runs in float32 (the matmuls at precision HIGHEST): the state is what a
2,000-token prompt accumulates into, and a bf16 pass over it is the "state
kept in bf16" the tests refuse. Their FLOPs are small beside the
projections' (a 256-token chunk: 0.6 GFLOP a layer).

Rows that are padding (a prompt padded to its bucket, a chunk to 256, an
empty decode slot) are handed to the XLA compositions with dt = 0: exp(0) =
1 and dt x = 0, so they leave `S` bit for bit; the conv rows kept are the
last K-1 REAL ones. The kernel never visits an empty slot.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops import attention as att

_HI = jax.lax.Precision.HIGHEST


def tap_sum(acc, cat: jax.Array, w: jax.Array, t: int) -> jax.Array:
    """acc + sum_k w_k * cat[k : k + t] in float32: the taps of a depthwise
    causal conv over `cat` [K-1+T, C] (the K-1 rows before the first, then
    the T rows), w [K, C]. What comes before (a bias) and after (an
    activation, a gate) is the caller's: Mamba-2's conv_rows here, the gated
    short convolution in ops/short_conv.py."""
    for j in range(w.shape[0]):
        acc = acc + w[j].astype(jnp.float32) * cat[j:j + t].astype(
            jnp.float32)
    return acc


def conv_rows(xbc: jax.Array, prev: jax.Array, w: jax.Array, b: jax.Array,
              n_valid) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal conv over time with bias, then silu.

    xbc [T, C] (the first `n_valid` rows are real), prev [K-1, C] the rows
    before xbc[0] (zeros at a sequence's start), w [K, C], b [C] ->
    (out [T, C], the last K-1 real rows seen [K-1, C]):
    out_t = silu(b + sum_k w_k * in_{t-(K-1)+k})."""
    k = w.shape[0]
    t = xbc.shape[0]
    cat = jnp.concatenate([prev.astype(xbc.dtype), xbc])  # [K-1+T, C]
    acc = tap_sum(b.astype(jnp.float32), cat, w, t)
    # real rows are cat[K-1 : K-1+n_valid]; the K-1 before the next token
    kept = jax.lax.dynamic_slice_in_dim(cat, n_valid, k - 1)
    return jax.nn.silu(acc).astype(xbc.dtype), kept.astype(prev.dtype)


def conv_step(xbc: jax.Array, prev: jax.Array, w: jax.Array, b: jax.Array,
              live: jax.Array) -> tuple[jax.Array, jax.Array]:
    """conv_rows for one token a slot: xbc [B, C], prev [B, K-1, C], live
    [B] bool -> (out [B, C], prev shifted by the token where live). One
    row a slot: the same taps as one contraction over the K rows (an
    einsum, not tap_sum's loop, which only conv_rows and
    ops/short_conv.gated_rows share)."""
    cat = jnp.concatenate([prev.astype(xbc.dtype), xbc[:, None]], axis=1)
    acc = b.astype(jnp.float32) + jnp.einsum(
        "bkc,kc->bc", cat.astype(jnp.float32), w.astype(jnp.float32))
    kept = jnp.where(live[:, None, None], cat[:, 1:].astype(prev.dtype), prev)
    return jax.nn.silu(acc).astype(xbc.dtype), kept


def _by_head(a: jax.Array, heads: int) -> jax.Array:
    """[..., G, N] (a row a group) -> [..., H, N] (each head its group's)."""
    return jnp.repeat(a, heads // a.shape[-2], axis=-2)


def scan_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
                 cm: jax.Array, d: jax.Array, init: jax.Array,
                 chunk: int) -> tuple[jax.Array, jax.Array]:
    """The recurrence over T tokens in chunks of `chunk` (T a multiple of
    min(chunk, T)): inside a chunk the quadratic form as matmuls, between
    chunks the state passed on.

    x [T, H, P], dt [T, H] float32 (softplus applied; 0 on padding rows),
    a [H] (negative), bm / cm [T, G, N], d [H], init [H, P, N] float32 ->
    (y [T, H, P] float32, final state [H, P, N] float32)."""
    t, h, p = x.shape
    q = min(chunk, t)
    if t % q:
        raise ValueError(f"{t} rows are no multiple of the scan chunk {q}")
    g, n = bm.shape[-2:]
    f32 = jnp.float32
    xs = x.astype(f32).reshape(t // q, q, h, p)
    dts = dt.astype(f32).reshape(t // q, q, h)
    bs = bm.astype(f32).reshape(t // q, q, g, n)
    cs = cm.astype(f32).reshape(t // q, q, g, n)
    causal = jnp.tril(jnp.ones((q, q), bool))

    def one(s_in, c):
        xc, dtc, bc, cc = c
        seg = jnp.cumsum(dtc * a.astype(f32), axis=0)  # [Q, H], <= 0
        # decay from token j to token i of the chunk (i >= j)
        decay = jnp.exp(jnp.where(causal[:, :, None],
                                  seg[:, None, :] - seg[None, :, :], -jnp.inf))
        scores = jnp.einsum("ign,jgn->ijg", cc, bc, precision=_HI)
        scores = jnp.repeat(scores, h // g, axis=-1) * decay  # [Q, Q, H]
        xdt = xc * dtc[..., None]  # [Q, H, P]
        y = jnp.einsum("ijh,jhp->ihp", scores, xdt, precision=_HI)
        # what the chunk found in the state
        y = y + jnp.exp(seg)[..., None] * jnp.einsum(
            "ihn,hpn->ihp", _by_head(cc, h), s_in, precision=_HI)
        tail = jnp.exp(seg[-1][None] - seg)  # [Q, H] decay to the chunk's end
        s_out = jnp.exp(seg[-1])[:, None, None] * s_in + jnp.einsum(
            "jhp,jhn->hpn", xdt * tail[..., None], _by_head(bc, h),
            precision=_HI)
        return s_out, y

    final, ys = jax.lax.scan(one, init.astype(f32), (xs, dts, bs, cs))
    y = ys.reshape(t, h, p) + d.astype(f32)[None, :, None] * x.astype(f32)
    return y, final


def step(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
         cm: jax.Array, d: jax.Array,
         state: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One token a slot: x [B, H, P], dt [B, H] float32 (0 on an empty
    slot), bm / cm [B, G, N], state [B, H, P, N] float32 ->
    (y [B, H, P] float32, the states after the token)."""
    f32 = jnp.float32
    h = x.shape[1]
    xf, dt = x.astype(f32), dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32))  # [B, H]
    new = (decay[..., None, None] * state
           + (xf * dt[..., None])[..., None]
           * _by_head(bm.astype(f32), h)[:, :, None, :])
    y = jnp.sum(new * _by_head(cm.astype(f32), h)[:, :, None, :], axis=-1)
    return y + d.astype(f32)[None, :, None] * xf, new


def step_every_slot(x, dt, a, bm, cm, d, state, live):
    """`step` with dt = 0 on the slots that are not `live` [B]: every slot
    is read and written, an empty one bit for bit as it was."""
    return step(x, jnp.where(live[:, None], dt, 0.0), a, bm, cm, d, state)


class LiveSlots(NamedTuple):
    """The decode slots that hold a sequence, as `update_live` walks them:
    `ids` [B] int32, the live slots first and in order (what follows them
    is never read as a slot), and `count` [1] int32. Built once a program
    (once a fused window: its slots do not change inside it), not once a
    layer."""
    ids: jax.Array
    count: jax.Array


def live_slots(live: jax.Array) -> LiveSlots:
    """`live` [B] bool -> its LiveSlots."""
    ids = jnp.nonzero(live, size=live.shape[0], fill_value=0)[0]
    return LiveSlots(ids.astype(jnp.int32),
                     jnp.sum(live, dtype=jnp.int32)[None])


# a grid step's block of one slot's state: as many heads as fit, the served
# [64, 64, 128] state whole. Measured alone on a v5e at 27 of 64 slots live
# (scripts/ssm_microbench.py, PR 43): 0.208 ms a layer in blocks of 64
# heads, 0.225 of 32, 0.253 of 16 (XLA over every slot: 0.424); a grid step
# costs 0.35 us, a skipped one 0.13. At a head's state of [128, 256] (128
# KB: 32 heads, 4.2 MB a slot; PR 44, SSM_MICROBENCH_SHAPE=64,32,128,2,256)
# the 2 MB block is 16 heads, two grid steps a live slot: 0.468 ms a layer
# at 32 of 64 live (574 GB/s of live bytes), 0.871 at 64 (617); blocks of 8
# heads 0.489 / 0.888; XLA over every slot 0.84-0.86 whatever is live; over
# a pool of layers' states under a `base` the same within 1%. A block of 32
# heads (4 MB, twice, in and out) does not fit the kernel's VMEM.
_STATE_BLOCK_BYTES = 2 << 20
_OP = "ssm state update"


def _head_block(heads: int, head_bytes: int) -> int:
    """The most heads a block (a divisor of `heads`) that fits."""
    fits = max(1, _STATE_BLOCK_BYTES // head_bytes)
    return max(h for h in range(1, heads + 1)
               if heads % h == 0 and h <= fits)


def update_backend(state_shape) -> str:
    """Which implementation `update` takes for states [B, H, P, N] under
    the scoped attention backend (ops/attention: `auto` is the kernel on a
    TPU): `pallas` | `pallas_interpret` | `xla`. The engine asks too, to
    count the slots a dispatch touches."""
    backend = att._resolve_backend()
    if backend == "pallas" and (state_shape[-1] % 128 or state_shape[-2] % 8):
        return "xla"  # a head's state is no multiple of the float32 tile
    return backend if backend in att._KERNEL_BACKENDS else "xla"


def _update_kernel_based(ids_ref, n_ref, dt_ref, decay_ref, d_ref, base_ref,
                         *refs, **kw):
    """`_update_kernel` under a sixth prefetched scalar, the states' `base`
    row: only the index maps read it."""
    del base_ref
    _update_kernel(ids_ref, n_ref, dt_ref, decay_ref, d_ref, *refs, **kw)


def _update_kernel(ids_ref, n_ref, dt_ref, decay_ref, d_ref,  # SMEM
                   x_ref,  # [1, 1, P, hb]: this block's heads in the lanes
                   b_ref,  # [1, G, N]
                   c_ref,  # [1, G, N]
                   s_ref,  # [1, hb, P, N]
                   y_ref,  # [1, 1, P, hb]
                   o_ref,  # [1, hb, P, N], the same memory as s_ref's
                   *, heads: int, head_block: int, group_heads: int):
    """Grid step (i, j): heads [j hb, (j + 1) hb) of the i-th live slot. A
    step past the live count does nothing: its blocks are the last live
    step's (the index maps clamp), so nothing is copied in for it and the
    block that leaves at the end is that step's result."""
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]

    @pl.when(i < n)
    def _():
        slot = ids_ref[i]
        x = x_ref[0, 0]
        # unrolled: a head's x is then a STATIC lane of x, which costs a
        # lane broadcast. In a loop over heads the lane is dynamic, and a
        # masked lane sum or a dynamic roll to find it made the kernel
        # compute-bound (12 and 22 us a slot against 6.9: PR 43)
        for h in range(head_block):
            head = j * head_block + h
            at = slot * heads + head
            g = jax.lax.div(head, jnp.int32(group_heads))
            xc = x[:, h:h + 1]  # [P, 1]
            new = (decay_ref[at] * s_ref[0, h]
                   + (xc * dt_ref[at]) * b_ref[0, pl.ds(g, 1), :])
            o_ref[0, h] = new
            y_ref[0, 0, :, h:h + 1] = (
                jnp.sum(new * c_ref[0, pl.ds(g, 1), :], axis=-1,
                        keepdims=True) + d_ref[head] * xc)

    # no live slot at all: every step names the same block, and a block
    # that was visited is written back. Hand it back as it came.
    @pl.when((n == 0) & (i == 0) & (j == 0))
    def _():
        o_ref[...] = s_ref[...]


# jitted on its own: the kernel's unrolled body takes Python half a second
# to trace, and a model's layers in each of an engine's 22 step programs
# would each pay it (+60 s of warm set-up, measured on the chip at PR 43).
# As a jit of its own it is traced once a process and lowered once a program.
@functools.partial(jax.jit, static_argnames=("interpret", "head_block"))
def update_live(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
                cm: jax.Array, d: jax.Array, state: jax.Array,
                live: jax.Array, slots: LiveSlots, *,
                base: jax.Array | None = None,
                interpret: bool = False,
                head_block: int | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """`step` over the live slots only, in place: x [B, H, P], dt [B, H]
    float32, bm / cm [B, G, N], state [B, H, P, N] float32 (row i is slot
    i), live [B] bool and its `slots` -> (y [B, H, P] float32, exactly 0
    on a row that is not live; the states, a live slot's after the token
    and any other untouched). The state operand is the result's memory
    (`input_output_aliases`): a slot the grid never names is neither read
    nor written. The arithmetic is `step`'s, product for product.

    `base` (a scalar int32, or None): the states are a POOL [L * B, H, P,
    N] of which slot i is row base + i (a model whose layers run as one
    scan carries every layer's states as one array and hands the layer's
    offset: models/llama.py); x, dt, bm, cm and y stay indexed by the slot
    alone."""
    f32 = jnp.float32
    _, h, p, n = state.shape
    b = x.shape[0]
    g = bm.shape[-2]
    hb = head_block or _head_block(h, p * n * 4)
    if h % hb or h % g:
        raise ValueError(f"{h} heads in blocks of {hb}, {g} groups")
    nj = h // hb
    dt = dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32))  # [B, H]
    # heads in the lanes, so that a head's x is a column beside its state
    xt = x.astype(f32).reshape(b, nj, hb, p).transpose(0, 1, 3, 2)

    def block(i, j, ids, count, *_):
        """(slot, head block) of grid step (i, j); past the live count,
        the last live step's."""
        last = jnp.maximum(count[0] - 1, 0)
        return ids[jnp.minimum(i, last)], jnp.where(i < count[0], j, nj - 1)

    def by_heads(i, j, *refs):
        return (*block(i, j, *refs), 0, 0)

    def by_slot(i, j, *refs):
        return block(i, j, *refs)[0], 0, 0

    def pooled(i, j, *refs):
        slot, jb = block(i, j, *refs)
        return slot + refs[5][0], jb, 0, 0

    based = () if base is None else (jnp.asarray(base, jnp.int32)[None],)
    small = pl.BlockSpec((1, 1, p, hb), by_heads)
    rows = pl.BlockSpec((1, g, n), by_slot)
    big = pl.BlockSpec((1, hb, p, n), pooled if based else by_heads)
    y, new = pl.pallas_call(
        functools.partial(_update_kernel_based if based else _update_kernel,
                          heads=h, head_block=hb, group_heads=h // g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5 + len(based), grid=(b, nj),
            in_specs=[small, rows, rows, big], out_specs=[small, big]),
        out_shape=[jax.ShapeDtypeStruct((b, nj, p, hb), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={8 + len(based): 1},
        compiler_params=pltpu.CompilerParams(
            # in order: a step past the live count counts on the blocks of
            # the step before it
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_update_live",
    )(slots.ids, slots.count, dt.reshape(-1), decay.reshape(-1),
      d.astype(f32), *based, xt, bm.astype(f32), cm.astype(f32), state)
    # a row the grid never wrote holds whatever the memory held
    y = jnp.where(live[:, None, None],
                  y.transpose(0, 1, 3, 2).reshape(b, h, p), 0.0)
    return y, new


def update(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
           cm: jax.Array, d: jax.Array, state: jax.Array, live: jax.Array,
           slots: LiveSlots, base=None) -> tuple[jax.Array, jax.Array]:
    """The decode rows' one-token update, row i on slot i where `live`:
    `update_live` where the scoped backend is a kernel's, else `step` with
    dt = 0 on the empty slots (which then rewrites every slot). `base`:
    `update_live`'s, the rows' slots are state[base : base + B]."""
    backend = update_backend(state.shape)
    att._note_impl(_OP, backend)
    if backend == "xla":
        att._demote(att._resolve_backend(), _OP, "state_tiling",
                    f"a head's state {state.shape[-2:]} is no multiple of "
                    "the float32 tile (8, 128)")
        if base is None:
            return step_every_slot(x, dt, a, bm, cm, d, state, live)
        own = jax.lax.dynamic_slice_in_dim(state, base, x.shape[0])
        y, own = step_every_slot(x, dt, a, bm, cm, d, own, live)
        return y, jax.lax.dynamic_update_slice_in_dim(state, own, base, 0)
    return update_live(x, dt, a, bm, cm, d, state, live, slots, base=base,
                       interpret=backend == "pallas_interpret")


def gate_norm(y: jax.Array, z: jax.Array, w: jax.Array, groups: int,
              eps: float) -> jax.Array:
    """Gate first, then an RMS norm by group: w * rms_g(y * silu(z)), the
    mean over each of `groups` runs of lanes. y, z [T, d_in] -> [T, d_in]
    float32."""
    v = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    t, width = v.shape
    vg = v.reshape(t, groups, width // groups)
    vg = vg * jax.lax.rsqrt(jnp.mean(vg * vg, axis=-1, keepdims=True) + eps)
    return vg.reshape(t, width) * w.astype(jnp.float32)
