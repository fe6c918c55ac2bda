"""Pallas TPU kernels for paged decode attention and prefill flash attention.

Same contracts as the XLA reference ops in `dynamo_tpu.ops.attention` (the KV
layout parity point is the reference's SGLang `--page-size 16` flag,
/root/reference/examples/deploy/sglang/agg.yaml:38-39).

Decode kernel design (bandwidth-first — this is the hot op of the serving
loop, and decode attention is HBM-bandwidth-bound by definition):

- **Page-major fused-head KV layout** `[num_pages, page_size, KV*D]`: one
  page is a single contiguous `[ps, KV*D]` slab (16KB at ps=16/KV=8/D=64),
  so each page moves HBM->VMEM in ONE big DMA instead of one tiny DMA per
  KV head. TPU DMA requires the trailing dim be a multiple of 128 lanes;
  KV*D satisfies that for every model this repo serves (8*64, 8*128, ...).
- **Multi-page superblocks**: a block is `block_pages` pages (default 8 =>
  128 tokens) fetched by parallel async copies.
- **Live KV only**: the grid is one step a batch slot; inside it a loop with
  a dynamic trip count runs over that slot's OWN superblocks,
  `ceil(context / tokens_per_block)` of them, read off the scalar-prefetched
  context lens. A slot at context 0 (an empty slot: `llama.decode_step`
  hands the kernel 0 where the table is all trash) owns no block: no page
  copy, no product, zeros written. A block's copies never follow the table
  past the context (the tail re-reads the last live page), so the work, the
  bytes and the result depend on nothing a sequence does not own below its
  context.
- **A DMA ring across slots**: the copies for the next `num_bufs - 1` blocks
  (this slot's, or the next slots that own any) are in flight while block i
  is computed, threaded through a persistent SMEM cursor that steps over the
  slots that own no block — so in steady state the kernel is never waiting
  on HBM latency, only throughput. The grid is `arbitrary` (sequential) on
  purpose: the software pipeline carries state across grid steps.
- **Block-diagonal GQA matmuls**: all H query heads are packed into one
  `[H, KV*D]` block-diagonal matrix (row r nonzero only in its KV head's
  D-lane span), so scores for every head come from ONE `[H,KV*D]x[KV*D,T]`
  MXU op with zero cross-head score waste in the VPU, and the PV product
  accumulates `[H, KV*D]` whose off-head lanes are sliced away once at
  finalize. No reshapes or transposes of KV data anywhere.
- Tokens past the context length inside a slot's last block are masked
  in-compute; the tiled query and its block-diagonal mask are built once a
  slot, not once a block.
- **int8 KV pools** (packed-scale rows, see dynamo_tpu.ops.attention) are
  read natively: the superblock DMA moves the int8 rows (half the HBM
  bytes), and `_dequant_rows` rebuilds values in-VMEM with iota-selector
  matmuls plus an exact shift-and-bitcast bf16 scale decode. Under TP the
  rows are lane-blocked per shard, so the same head-parallel shard_map
  applies unchanged.

The prefill kernel is a standard flash (online-softmax) kernel over the
`[S, KV, D]` pre-paging tensors, gridded over KV heads with queries blocked
`group` per KV head so each K/V block is fetched exactly once.

Both kernels are head-parallel: under tensor parallelism they run inside
`shard_map` over the `model` mesh axis with zero collectives — each TP shard
attends over its local KV-head lane span.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.attention import _v_head_dim, shared_kv

NEG_INF = float("-inf")


# The int8-KV dequant-in-chunk path compiles and passes the on-chip parity
# check (ops/kernel_parity.py), but a changed default path is judged on a
# benchmark cell (ROADMAP S3/S4) and none has run it. Until one does,
# ops/attention.chunk_attention sends an int8 pool to the XLA gather path,
# counted in dynamo_pallas_fallback_total (`int8_not_validated`).
CHUNK_KERNEL_INT8_HW_VALIDATED = False

# pages per decode superblock (tokens per block = this * page_size)
DEFAULT_BLOCK_PAGES = 8
# KV block buffers in the DMA ring: num_bufs - 1 blocks are in flight ahead
# of the one being consumed (pipeline depth)
DEFAULT_NUM_BUFS = 4


def _kv_block(kbuf, vbuf, cur, tokens: int, n_kv: int, d: int,
              lane_width: int, quantized: bool, shared: bool):
    """(k, v) [tokens, KV*D] of ring slot `cur`, as the products take them.

    Per-head K/V: float32 (int8 rows dequantized), as measured best at 28/4
    and 32/8 heads, where the MXU is not the limit (PR 26). A shared latent
    row (MLA: 64 heads on one 640-lane row, V read from K) stays in the
    pool's bf16 and meets bf16 queries and probabilities with float32
    accumulation: there every row is multiplied by all the heads, the MXU IS
    the limit, and float32 operands cost 3-6 passes of it."""
    if quantized:
        k = _dequant_rows(kbuf[cur].reshape(tokens, lane_width), n_kv, d,
                          lane_width)
        v = k if shared else _dequant_rows(
            vbuf[cur].reshape(tokens, lane_width), n_kv, d, lane_width)
        return k, v
    k = kbuf[cur].reshape(tokens, n_kv * d)
    if shared:
        return k, k
    # V's rows may be narrower than K's (`dv` lanes a head: the V ring's own)
    return (k.astype(jnp.float32),
            vbuf[cur].reshape(tokens, vbuf.shape[-1]).astype(jnp.float32))


def _v_ring(shared: bool, shape, dtype):
    """The V block ring's scratch; a token one when V is read from K."""
    return pltpu.VMEM((1, 1, 8, 128) if shared else shape, dtype)


def _sink_specs(sink, rows: int, index_map) -> list:
    """The sink operand's block ([rows, 1] float32, the same block at every
    grid step); no operand without a sink."""
    return [] if sink is None else [pl.BlockSpec((rows, 1), index_map)]


def _sink_rows(sink, repeat: int = 1) -> tuple:
    """sink [H] -> ([repeat * H, 1] float32,): row r is head r % H's, as
    the kernels lay a query block's rows (query-major, head-minor)."""
    if sink is None:
        return ()
    s = sink.astype(jnp.float32)
    return ((jnp.tile(s, repeat) if repeat > 1 else s)[:, None],)


# -------------------------------------------------------------- int8 dequant --


def _dequant_rows(rows, n_kv: int, d: int, lane_width: int):
    """Dequantize one lane block of packed int8 KV rows in-VMEM.

    rows: [T, lane_width] int8 with layout [KV*D values | 2*KV scale lanes
    (bf16 bitcast bytes, little-endian) | zero pad] — the single-shard form
    of the layout in dynamo_tpu.ops.attention (int8 KV section). Returns
    [T, KV*D] float32 dequantized values.

    Mosaic-friendly construction: only whole-region lane slices (the values
    span and the 128-aligned scale+pad tail), byte de-interleave and the
    per-head D-lane broadcast both expressed as tiny iota-built selector
    matmuls (MXU work is free here — the decode kernel is DMA-bound), and
    the bf16 scale rebuilt EXACTLY by u16 << 16 + same-width int32->f32
    bitcast (no exp2 rounding)."""
    kvd = n_kv * d
    vals = rows[:, :kvd].astype(jnp.float32)
    r = lane_width - kvd  # scale lanes + pad (>= 2 * n_kv)
    tail = (rows[:, kvd:].astype(jnp.int32) & 0xFF).astype(jnp.float32)
    row_i = jax.lax.broadcasted_iota(jnp.int32, (r, n_kv), 0)
    col_i = jax.lax.broadcasted_iota(jnp.int32, (r, n_kv), 1)
    sel_lo = (row_i == 2 * col_i).astype(jnp.float32)
    sel_hi = (row_i == 2 * col_i + 1).astype(jnp.float32)
    lo = jax.lax.dot(tail, sel_lo, preferred_element_type=jnp.float32)
    hi = jax.lax.dot(tail, sel_hi, preferred_element_type=jnp.float32)
    # u16 bit pattern reassembled in f32 (exact below 2^24), then widened to
    # the bf16 value's f32 bit pattern by the 16-bit shift
    bits = (lo + 256.0 * hi).astype(jnp.int32) << 16
    scale = jax.lax.bitcast_convert_type(bits, jnp.float32)  # [T, KV]
    head_i = jax.lax.broadcasted_iota(jnp.int32, (n_kv, kvd), 0)
    lane_kv = jax.lax.broadcasted_iota(jnp.int32, (n_kv, kvd), 1) // d
    expand = (head_i == lane_kv).astype(jnp.float32)  # [KV, KVD]
    scale_full = jax.lax.dot(scale, expand,
                             preferred_element_type=jnp.float32)  # [T, KVD]
    return vals * scale_full


# ------------------------------------------------------ flash accumulation --


def _flash_reset(m_ref, l_ref, acc_ref, sink=None):
    """`sink` [R, 1] float32: a learned logit a row that joins the softmax's
    running max and denominator and adds nothing to the numerator: the
    online softmax simply STARTS from it (m = sink, l = exp(sink - m) = 1),
    and every later `_flash_update` rescales that 1 with the rest."""
    if sink is None:
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
    else:
        m_ref[...] = jnp.broadcast_to(sink, m_ref.shape)
        l_ref[...] = jnp.ones_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _flash_update(m_ref, l_ref, acc_ref, s, v):
    """Online-softmax step: fold scores s [R, C] and values v [C, D] into the
    running (max, denominator, numerator) scratch. Rows whose entries are all
    -inf so far keep alpha = exp(-inf - finite) = 0, which zeroes nothing
    incorrectly because acc is also still zero."""
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = jnp.broadcast_to(
        alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), l_ref.shape
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    # p meets v in v's dtype: float32 as stored-then-cast rows are, or the
    # pool's own bf16 where a kernel keeps them so (_kv_block)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )


def _flash_normalize(l_ref, acc_ref):
    """acc / l with rows that saw no valid token (l == 0) emitting zeros."""
    l = l_ref[:, :1]
    return acc_ref[...] / jnp.where(l == 0.0, 1.0, l)


# ------------------------------------------------------------------ decode --


def _div(x, n: int):
    """x // n for x >= 0, as ONE operation: `//` on a traced integer is
    Python's floor division, seven operations that truncation does not
    need here, and a kernel's size is paid at every trace and lowering.
    (`ragged_attention` keeps its own copy until ROADMAP D1 folds the
    decode kernel into it.)"""
    return jax.lax.div(x, jnp.int32(n))


def _decode_kernel(
    # scalar prefetch
    bt_ref,  # [B, Pmax] int32 block table
    cl_ref,  # [B] int32 context lens (incl. current token); 0 = empty slot
    # inputs
    q_ref,  # [1, H, D] VMEM block (this slot's query)
    k_hbm,  # [P, ps, KVD] in ANY/HBM — manually DMA'd
    v_hbm,  # [P, ps, KV*Dv] (Dv = D unless V's rows are narrower)
    *rest,  # [sink_ref [H, 1] f32 where `sink`,] o_ref [1, H, Dv], scratch
    # (persistent across the sequential grid):
    # kbuf [NBUF, SB, ps, KVD] KV-dtype ring of block buffers
    # vbuf [NBUF, SB, ps, KV*Dv]
    # qbd_ref [H, KVD] block-diagonal queries as the product takes them
    # m_ref, l_ref [H, 128] f32 running max and denominator
    # acc_ref [H, KV*Dv] f32 running numerator (off-head lanes carry garbage
    #         that the finalize slice discards)
    # ptr_ref SMEM [4] int32: consumed count, issue cursor (b, i), issued count
    # sem DMA semaphores [NBUF, 2, SB]
    page_size: int,
    pages_per_seq: int,
    block_pages: int,
    num_bufs: int,
    n_kv: int,
    scale: float,
    lane_width: int,
    quantized: bool,
    shared: bool = False,
    window: int = 0,
    sink: bool = False,
):
    """One grid step a slot; inside it a loop over that slot's OWN
    superblocks, `ceil(ctx / tokens_per_block)` of them. A slot with context
    0 owns none: no page copy, no product, zeros written. The DMA ring runs
    on across slots (issue order == consume order), its issue cursor
    stepping over the slots that own no block. Under a static `window` the
    query sees the last `window` tokens of its context: the superblocks
    below them are not its own either (`first_block`), and the mask has a
    lower edge. window = 0 traces none of it. `sink`: a learned logit a
    query head in the softmax (`_flash_reset`); False traces none of it."""
    sink_ref = rest[0] if sink else None
    (o_ref, kbuf, vbuf, qbd_ref, m_ref, l_ref, acc_ref, ptr_ref,
     sem) = rest[1:] if sink else rest
    b = pl.program_id(0)
    bsz = pl.num_programs(0)
    tokens_per_block = block_pages * page_size
    h, d = q_ref.shape[1], q_ref.shape[2]
    dv = o_ref.shape[2]  # V's lanes a head: d, or narrower
    group = h // n_kv

    def first_block(bb):
        if not window:
            return 0
        return _div(jnp.maximum(cl_ref[bb] - window, 0), tokens_per_block)

    def n_blocks(bb):
        n = _div(cl_ref[bb] + tokens_per_block - 1, tokens_per_block)
        return n - first_block(bb) if window else n

    def block_dma(bb, ii, slot, wait):
        """Start (or wait for) the page copies of block ii of slot bb into
        ring slot `slot`. Unrolled: as a loop the copies cost 0.14 us a
        block, 13-15% of the kernel where every slot is live (PR 28, v5e)."""
        if not wait:
            # live KV only: a superblock's tail past the context re-reads
            # the last live page instead of following the table, so neither
            # a table entry past the context nor the page it names is read
            last = jnp.minimum(_div(cl_ref[bb] - 1, page_size),
                               pages_per_seq - 1)
            if window:
                ii = ii + first_block(bb)
        for j in range(block_pages):
            # a wait needs the copy's shape and semaphore, not its source
            pg = 0 if wait else bt_ref[
                bb, jnp.minimum(ii * block_pages + j, last)]
            # MLA keeps its latent row once: K alone is copied then
            for hbm, buf, which in (((k_hbm, kbuf, 0),) if shared else (
                    (k_hbm, kbuf, 0), (v_hbm, vbuf, 1))):
                c = pltpu.make_async_copy(
                    hbm.at[pg], buf.at[slot, j], sem.at[slot, which, j])
                c.wait() if wait else c.start()

    def next_owner(bb):
        """The first slot at or after bb that owns a block; bsz if none."""
        return jax.lax.while_loop(
            lambda x: (x < bsz) & (cl_ref[jnp.minimum(x, bsz - 1)] <= 0),
            lambda x: x + 1, bb)

    def issue_one():
        """Issue the block at the issue cursor (if any remain) into ring
        slot `issued % num_bufs`, then advance the cursor to the next block
        anyone owns. The consume side reproduces the slot as
        `consumed % num_bufs` — issue order == consume order, so the ring
        stays in lockstep."""
        ib, ii = ptr_ref[1], ptr_ref[2]

        @pl.when(ib < bsz)
        def _():
            block_dma(ib, ii, jax.lax.rem(ptr_ref[3], num_bufs), wait=False)
            ptr_ref[3] = ptr_ref[3] + 1
            more = ii + 1 < n_blocks(ib)
            ptr_ref[2] = jnp.where(more, ii + 1, 0)

            @pl.when(jnp.logical_not(more))
            def _():
                ptr_ref[1] = next_owner(ib + 1)

    # Pipeline warm-up: the first grid step primes `num_bufs - 1` blocks
    # (the full ring minus the slot consumed+reissued each block).
    @pl.when(b == 0)
    def _init():
        ptr_ref[0] = 0  # consumed-block count
        ptr_ref[1] = next_owner(0)  # issue cursor: slot
        ptr_ref[2] = 0  # issue cursor: block within the slot
        ptr_ref[3] = 0  # issued-block count

        def prime(_, carry):
            issue_one()
            return carry

        # a loop: it runs once a call, and the kernel is lowered in every
        # decode-window program
        jax.lax.fori_loop(0, num_bufs - 1, prime, 0)

    ctx = cl_ref[b]

    @pl.when(ctx <= 0)
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(ctx > 0)
    def _live():
        # Block-diagonal lane mask over the fused KV*D axis: row r's own KV
        # head (r // group) occupies lanes [(r//group)*D, (r//group+1)*D).
        # Built with iota + lane tiling — no lane-splitting reshapes, which
        # Mosaic cannot lower. Once a slot, with the tiled query.
        def bd_mask(d):  # [H, KV*d]; before the loop (K's d), after (V's)
            row = jax.lax.broadcasted_iota(jnp.int32, (h, n_kv * d), 0)
            lane = jax.lax.broadcasted_iota(jnp.int32, (h, n_kv * d), 1)
            return _div(row, group) == _div(lane, d)

        q = q_ref[0].astype(jnp.float32) * scale  # [H, D]
        qbd_ref[...] = jnp.where(
            bd_mask(d), jnp.tile(q, (1, n_kv)), 0.0).astype(qbd_ref.dtype)
        _flash_reset(m_ref, l_ref, acc_ref,
                     sink_ref[...] if sink else None)

        def block(i, carry):
            cnt = ptr_ref[0]
            cur = jax.lax.rem(cnt, num_bufs)
            # keep the ring full: issue one block `num_bufs - 1` ahead of
            # the one being consumed (the new issue targets the slot
            # consumed `num_bufs - 1` blocks ago, which is complete and idle)
            issue_one()
            block_dma(b, i, cur, wait=True)
            ptr_ref[0] = cnt + 1
            k, v = _kv_block(kbuf, vbuf, cur, tokens_per_block, n_kv, d,
                             lane_width, quantized, shared)
            s = jax.lax.dot_general(
                qbd_ref[...], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [H, T] — block-diagonal q => per-head scores, no cross-talk
            tok = ((i + first_block(b)) if window else i
                   ) * tokens_per_block + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            # every block of the loop holds token i * T < ctx, so no row is
            # all -inf (under a window the first block visited holds token
            # ctx - window, the oldest in reach)
            if window:
                s = jnp.where((tok < ctx) & (tok >= ctx - window), s,
                              NEG_INF)
            else:
                s = jnp.where(tok < ctx, s, NEG_INF)
            _flash_update(m_ref, l_ref, acc_ref, s, v)
            return carry

        jax.lax.fori_loop(0, n_blocks(b), block, 0)

        out = _flash_normalize(l_ref, acc_ref)  # [H, KV*Dv]
        # keep each row's own KV-head lane span (off-head lanes carry
        # accumulated garbage), then fold the KV spans down to [H, Dv]
        # with static lane slices — again avoiding lane-split reshapes.
        out = jnp.where(bd_mask(dv), out, 0.0)
        folded = out[:, 0:dv]
        for kv in range(1, n_kv):
            folded = folded + out[:, kv * dv:(kv + 1) * dv]
        o_ref[0] = folded.astype(o_ref.dtype)


def paged_attention_decode(
    q: jax.Array,  # [B, H, D]
    k_pages: jax.Array,  # [P, ps, KV*D] (or int8 packed single-block rows)
    v_pages: jax.Array,
    block_table: jax.Array,  # [B, Pmax] int32
    context_lens: jax.Array,  # [B] int32
    *,
    page_size: int,
    num_kv_heads: int,
    block_pages: int = DEFAULT_BLOCK_PAGES,
    num_bufs: int = DEFAULT_NUM_BUFS,
    interpret: bool = False,
    window: int = 0,
    sink=None,  # [H] float32: a learned logit a query head in the softmax
) -> jax.Array:
    bsz, n_heads, head_dim = q.shape
    lane_width = k_pages.shape[2]
    quantized = k_pages.dtype == jnp.int8
    shared = shared_kv(v_pages)
    v_dim = _v_head_dim(v_pages, num_kv_heads, head_dim)
    if shared:
        v_pages = k_pages
    kvd = num_kv_heads * head_dim
    if quantized:
        assert lane_width >= kvd + 2 * num_kv_heads, (lane_width, kvd)
    else:
        assert lane_width == kvd, (lane_width, num_kv_heads, head_dim)
    kvdv = num_kv_heads * v_dim
    pmax = block_table.shape[1]
    block_pages = max(1, min(block_pages, pmax))
    num_bufs = max(2, num_bufs)
    scale = 1.0 / (head_dim**0.5)
    # the queries meet K in the dtype _kv_block hands it over in
    q_dtype = k_pages.dtype if shared and not quantized else jnp.float32

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, n_heads, head_dim), lambda b, bt, cl: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ] + _sink_specs(sink, n_heads, lambda b, bt, cl: (0, 0)),
        out_specs=pl.BlockSpec(
            (1, n_heads, v_dim), lambda b, bt, cl: (b, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((num_bufs, block_pages, page_size, lane_width),
                       k_pages.dtype),
            _v_ring(shared, (num_bufs, block_pages, page_size,
                             v_pages.shape[2]), v_pages.dtype),
            pltpu.VMEM((n_heads, kvd), q_dtype),
            pltpu.VMEM((n_heads, 128), jnp.float32),
            pltpu.VMEM((n_heads, 128), jnp.float32),
            pltpu.VMEM((n_heads, kvdv), jnp.float32),
            pltpu.SMEM((4,), jnp.int32),
            pltpu.SemaphoreType.DMA((num_bufs, 2, block_pages)),
        ],
    )
    kernel = functools.partial(
        _decode_kernel,
        page_size=page_size,
        pages_per_seq=pmax,
        block_pages=block_pages,
        num_bufs=num_bufs,
        n_kv=num_kv_heads,
        scale=scale,
        lane_width=lane_width,
        quantized=quantized,
        shared=shared,
        window=window,
        **({} if sink is None else {"sink": True}),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, n_heads, v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # sequential on purpose: the DMA pipeline carries state across
            # grid steps (see module docstring)
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(block_table.astype(jnp.int32), context_lens.astype(jnp.int32),
      q, k_pages, v_pages, *_sink_rows(sink))
    return out


# ----------------------------------------------------------------- prefill --


def _prefill_kernel(
    sl_ref,  # [1] int32 true sequence length
    q_ref,  # [1, G, Tq, D] — all `group` query heads of this KV head
    k_ref,  # [1, Tk, D]
    v_ref,  # [1, Tk, Dv]
    *rest,  # [sink_ref [1, G*Tq, 1] f32 where `sink`,] o_ref [1, G, Tq, Dv],
    # m_ref, l_ref [G*Tq, 128] f32, acc_ref [G*Tq, Dv] f32
    group: int,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
    scale: float,
    sink: bool = False,
):
    sink_ref = rest[0] if sink else None
    o_ref, m_ref, l_ref, acc_ref = rest[1:] if sink else rest
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _reset():
        _flash_reset(m_ref, l_ref, acc_ref, sink_ref[0] if sink else None)

    q_start = iq * block_q
    k_start = ik * block_k
    sl = sl_ref[0]

    # Skip fully-masked blocks: strictly above the causal diagonal, or wholly
    # past the true sequence length.
    @pl.when((k_start <= q_start + block_q - 1) & (k_start < sl))
    def _attend():
        head_dim = q_ref.shape[-1]
        q = q_ref[0].astype(jnp.float32).reshape(group * block_q, head_dim)
        k = k_ref[0].astype(jnp.float32)  # [Tk, D]
        v = v_ref[0].astype(jnp.float32)
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )  # [G*Tq, Tk]
        # row r of the (group, Tq) reshape is query position q_start + r % Tq
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        qi = q_start + jax.lax.rem(row, block_q)
        ki = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((ki <= qi) & (ki < sl), s, NEG_INF)
        # at ik == 0 every row has ki == 0 unmasked (sl >= 1), so m stays
        # finite from the first block on — no exp(-inf - -inf) NaN.
        _flash_update(m_ref, l_ref, acc_ref, s, v)

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        out = _flash_normalize(l_ref, acc_ref)
        o_ref[0] = out.reshape(group, block_q,
                               o_ref.shape[-1]).astype(o_ref.dtype)


def prefill_attention(
    q: jax.Array,  # [S, H, D]
    k: jax.Array,  # [S, KV, D]
    v: jax.Array,
    seq_len,  # scalar int or int32 array: true (unpadded) length
    *,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    sink=None,  # [H] float32: a learned logit a query head in the softmax
) -> jax.Array:
    s, n_heads, head_dim = q.shape
    n_kv = k.shape[1]
    v_dim = v.shape[2]  # head_dim, or narrower
    group = n_heads // n_kv
    scale = 1.0 / (head_dim**0.5)

    # the kernel holds all `group` query heads of a KV head per block:
    # keep group * block_q rows near 1024 so the f32 accumulator fits the
    # scoped VMEM at MLA's geometry (64 heads on one 640-lane row); at
    # group <= 8 this is the requested block
    # ... and a power of two, so that it tiles (8 sublanes) and divides the
    # padded length whatever the group (9 query heads a KV head: 64)
    cap = max(8, 1024 // group)
    block_q = min(block_q, 1 << (cap.bit_length() - 1))
    block_q = min(block_q, max(s, 8))
    block_k = min(block_k, max(s, 8))
    s_pad = -(-s // max(block_q, block_k)) * max(block_q, block_k)

    # [KV, G, S, D] so one grid step covers all `group` query heads of a KV
    # head — each K/V block is DMA'd exactly once.
    qt = jnp.moveaxis(q, 1, 0).reshape(n_kv, group, s, head_dim)
    kt = jnp.moveaxis(k, 1, 0)  # [KV, S, D]
    vt = jnp.moveaxis(v, 1, 0)
    if s_pad != s:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
        kt = jnp.pad(kt, ((0, 0), (0, s_pad - s), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, s_pad - s), (0, 0)))

    nq = s_pad // block_q
    nk = s_pad // block_k
    sl = jnp.asarray(seq_len, jnp.int32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_kv, nq, nk),
        in_specs=[
            pl.BlockSpec(
                (1, group, block_q, head_dim), lambda h, iq, ik, sl: (h, 0, iq, 0)
            ),
            pl.BlockSpec((1, block_k, head_dim), lambda h, iq, ik, sl: (h, ik, 0)),
            pl.BlockSpec((1, block_k, v_dim), lambda h, iq, ik, sl: (h, ik, 0)),
        ] + ([] if sink is None else [
            pl.BlockSpec((1, group * block_q, 1),
                         lambda h, iq, ik, sl: (h, 0, 0))]),
        out_specs=pl.BlockSpec(
            (1, group, block_q, v_dim), lambda h, iq, ik, sl: (h, 0, iq, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((group * block_q, 128), jnp.float32),
            pltpu.VMEM((group * block_q, 128), jnp.float32),
            pltpu.VMEM((group * block_q, v_dim), jnp.float32),
        ],
    )
    # row r of a KV head's (group, Tq) block is query head
    # kv * group + r // Tq: its sink, a row
    sinks = () if sink is None else (jnp.repeat(
        sink.astype(jnp.float32).reshape(n_kv, group), block_q,
        axis=1)[..., None],)
    kernel = functools.partial(
        _prefill_kernel,
        group=group,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=nk,
        scale=scale,
        **({} if sink is None else {"sink": True}),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_kv, group, s_pad, v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(sl, qt, kt, vt, *sinks)
    out = out.reshape(n_heads, s_pad, v_dim)
    return jnp.moveaxis(out[:, :s], 0, 1)  # [S, H, D]


# ------------------------------------------------------------ chunk prefill --


def _chunk_kernel(
    # scalar prefetch
    pages_ref,  # [W] int32 page ids of the sequence (trash-padded tail)
    start_ref,  # [1] int32 absolute position of the chunk's first token
    # inputs
    q_ref,  # [1, Cq, H, D] VMEM block (one query block of the chunk)
    k_hbm,  # [P, ps, KVD] in ANY/HBM — manually DMA'd
    v_hbm,  # [P, ps, KV*Dv] (Dv = D unless V's rows are narrower)
    o_ref,  # [1, Cq, H, Dv]
    # scratch (persistent across the sequential grid)
    kbuf,  # [NBUF, SB, ps, KVD]
    vbuf,  # [NBUF, SB, ps, KV*Dv]
    qbd_ref,  # [Cq*H, KVD] f32 — block-diagonal queries, built once per qb
    m_ref,  # [Cq*H, 128] f32
    l_ref,  # [Cq*H, 128] f32
    acc_ref,  # [Cq*H, KV*Dv] f32
    ptr_ref,  # SMEM [4]: consumed count, issue cursor (qb, kb), issued count
    sem,  # DMA semaphores [NBUF, 2, SB]
    *,
    page_size: int,
    table_width: int,
    block_pages: int,
    block_q: int,
    num_bufs: int,
    n_kv: int,
    scale: float,
    lane_width: int,
    quantized: bool,
    shared: bool = False,
):
    """Chunked-prefill flash attention over the paged KV cache.

    Identical bones to `_decode_kernel` — the same page-major superblock DMA
    ring pipelined across a sequential grid, the same block-diagonal GQA
    matmuls — but the query side carries a BLOCK of chunk tokens (rows =
    block_q * H, row r = query (r // H) of head (r % H)) and the mask is
    causal in absolute positions instead of a per-sequence context length.
    Each query block attends over every KV block up to its own causal
    horizon, so one kernel invocation covers prefix + in-chunk attention
    with each KV byte fetched once per query block.
    """
    qb = pl.program_id(0)
    kb = pl.program_id(1)
    nq = pl.num_programs(0)
    tokens_per_block = block_pages * page_size
    h, d = q_ref.shape[2], q_ref.shape[3]
    dv = o_ref.shape[3]
    group = h // n_kv
    rows = block_q * h
    start = start_ref[0]

    def block_copies(qq, kk, slot):
        out = []
        for j in range(block_pages):
            pg = pages_ref[jnp.minimum(kk * block_pages + j, table_width - 1)]
            out.append(pltpu.make_async_copy(
                k_hbm.at[pg], kbuf.at[slot, j], sem.at[slot, 0, j]))
            if not shared:
                out.append(pltpu.make_async_copy(
                    v_hbm.at[pg], vbuf.at[slot, j], sem.at[slot, 1, j]))
        return out

    def n_blocks(qq):
        # causal horizon of query block qq: tokens 0 .. start + (qq+1)*Cq - 1
        horizon = start + (qq + 1) * block_q
        return (horizon + tokens_per_block - 1) // tokens_per_block

    def issue_one():
        iq, ik = ptr_ref[1], ptr_ref[2]

        @pl.when(iq < nq)
        def _():
            slot = jax.lax.rem(ptr_ref[3], num_bufs)
            for c in block_copies(iq, ik, slot):
                c.start()
            ptr_ref[3] = ptr_ref[3] + 1
            nxt = ik + 1
            done = nxt >= n_blocks(iq)
            ptr_ref[1] = jnp.where(done, iq + 1, iq)
            ptr_ref[2] = jnp.where(done, 0, nxt)

    nb_q = n_blocks(qb)

    @pl.when((qb == 0) & (kb == 0))
    def _init():
        ptr_ref[0] = 0
        ptr_ref[1] = 0
        ptr_ref[2] = 0
        ptr_ref[3] = 0
        for _ in range(num_bufs - 1):
            issue_one()

    @pl.when(kb < nb_q)
    def _active():
        cnt = ptr_ref[0]
        cur = jax.lax.rem(cnt, num_bufs)
        issue_one()
        for c in block_copies(qb, kb, cur):
            c.wait()
        ptr_ref[0] = cnt + 1

        def lanes_of_head(d):  # [rows, KV*d]: the row's own KV head's
            row_kv = (jax.lax.broadcasted_iota(
                jnp.int32, (rows, n_kv * d), 0) % h) // group
            lane_kv = jax.lax.broadcasted_iota(
                jnp.int32, (rows, n_kv * d), 1) // d
            return row_kv == lane_kv

        bd_mask = lanes_of_head(d)  # K's lanes: the queries'
        out_mask = bd_mask if dv == d else lanes_of_head(dv)  # V's

        @pl.when(kb == 0)
        def _reset():
            _flash_reset(m_ref, l_ref, acc_ref)
            q = q_ref[0].astype(jnp.float32).reshape(rows, d) * scale
            qbd_ref[...] = jnp.where(bd_mask, jnp.tile(q, (1, n_kv)), 0.0)

        k, v = _kv_block(kbuf, vbuf, cur, tokens_per_block, n_kv, d,
                         lane_width, quantized, shared)
        s = jax.lax.dot_general(
            qbd_ref[...].astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rows, T]
        tok = kb * tokens_per_block + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        qpos = start + qb * block_q + (
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // h
        )
        s = jnp.where(tok <= qpos, s, NEG_INF)
        _flash_update(m_ref, l_ref, acc_ref, s, v)

        @pl.when(kb == nb_q - 1)
        def _finalize():
            out = _flash_normalize(l_ref, acc_ref)  # [rows, KV*Dv]
            out = jnp.where(out_mask, out, 0.0)
            folded = out[:, 0:dv]
            for kv in range(1, n_kv):
                folded = folded + out[:, kv * dv:(kv + 1) * dv]
            o_ref[0] = folded.reshape(block_q, h, dv).astype(o_ref.dtype)


def chunk_prefill_attention(
    q: jax.Array,  # [C, H, D] — one prefill chunk's queries
    k_pages: jax.Array,  # [P, ps, KV*D]
    v_pages: jax.Array,
    pages: jax.Array,  # [W] page ids (trash-padded tail)
    start,  # scalar int32
    *,
    page_size: int,
    num_kv_heads: int,
    block_q: int = 8,
    block_pages: int = DEFAULT_BLOCK_PAGES,
    num_bufs: int = DEFAULT_NUM_BUFS,
    interpret: bool = False,
) -> jax.Array:
    c, n_heads, head_dim = q.shape
    lane_width = k_pages.shape[2]
    quantized = k_pages.dtype == jnp.int8
    shared = shared_kv(v_pages)
    v_dim = _v_head_dim(v_pages, num_kv_heads, head_dim)
    if shared:
        v_pages = k_pages
    kvd = num_kv_heads * head_dim
    if quantized:
        assert lane_width >= kvd + 2 * num_kv_heads, (lane_width, kvd)
    else:
        assert lane_width == kvd, (lane_width, num_kv_heads, head_dim)
    width = pages.shape[0]
    block_pages = max(1, min(block_pages, width))
    num_bufs = max(2, num_bufs)
    # largest power-of-two divisor of c not exceeding the requested block
    # (chunks are page multiples, not necessarily block_q multiples)
    block_q = max(1, min(block_q, c))
    while c % block_q != 0:
        block_q //= 2
    nq = c // block_q
    # worst-case kv blocks: the final query block's causal horizon
    nk_max = -(-(width * page_size) // (block_pages * page_size))
    scale = 1.0 / (head_dim**0.5)
    rows = block_q * n_heads

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nq, nk_max),
        in_specs=[
            pl.BlockSpec((1, block_q, n_heads, head_dim),
                         lambda qb, kb, pg, st: (qb, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, n_heads, v_dim),
            lambda qb, kb, pg, st: (qb, 0, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((num_bufs, block_pages, page_size, lane_width),
                       k_pages.dtype),
            _v_ring(shared, (num_bufs, block_pages, page_size,
                             v_pages.shape[2]), v_pages.dtype),
            pltpu.VMEM((rows, kvd), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, num_kv_heads * v_dim), jnp.float32),
            pltpu.SMEM((4,), jnp.int32),
            pltpu.SemaphoreType.DMA((num_bufs, 2, block_pages)),
        ],
    )
    kernel = functools.partial(
        _chunk_kernel,
        page_size=page_size,
        table_width=width,
        block_pages=block_pages,
        block_q=block_q,
        num_bufs=num_bufs,
        n_kv=num_kv_heads,
        scale=scale,
        lane_width=lane_width,
        quantized=quantized,
        shared=shared,
    )
    q4 = q.reshape(nq, block_q, n_heads, head_dim)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nq, block_q, n_heads, v_dim),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(pages.astype(jnp.int32), jnp.asarray(start, jnp.int32).reshape(1),
      q4, k_pages, v_pages)
    return out.reshape(c, n_heads, v_dim)
