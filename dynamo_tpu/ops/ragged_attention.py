"""Pallas TPU ragged paged-attention kernel: one mixed prefill+decode launch.

The RPA-style unification (PAPERS.md, arxiv 2604.15464): instead of separate
decode / chunk-prefill programs, ONE kernel serves a ragged batch described by
per-sequence `(q_start, q_len, kv_len)` descriptors over the same paged KV
pool. Decode rows are length-1 "chunks"; a prefill chunk is a long row. Both
are cut into query blocks and laid on a single sequential grid, so prefill
tokens ride the same launch as decode slots instead of preempting them — the
scheduling shape that collapses the engine's fused-window zoo (see
`dynamo_tpu.engine` mixed step).

Kernel anatomy follows `_chunk_kernel` / `_decode_kernel` in
`pallas_attention.py` (page-major fused-head KV, multi-page superblock DMA
ring pipelined across a sequential grid via a persistent SMEM cursor,
block-diagonal GQA matmuls, int8 packed-scale rows dequantized in-VMEM).
Where it departs, it is to be small: the kernel is lowered for every mixed
program (16 at warmup, ~1 s each on a serving host before PR 26), so `//`
on traced integers is one `lax.div` and the wrapper is jitted so that
programs that share shapes share one trace (the page copies were a loop
from PR 26 to PR 45, which measured the loop at 12% of the kernel):

- Grid is `(num_q_blocks,)`: ONE grid step a query block, and inside it a
  loop over that block's OWN KV blocks, as many as its causal horizon holds
  (`n_blocks`, a dynamic trip count; under a static window from
  `first_block`). The first `num_decode` query blocks are the decode slots
  (one real row each, padded to `block_q`), the rest tile the prefill chunk
  `block_q` tokens at a time. Until PR 45 the grid was `(num_q_blocks,
  table width / block_pages)` whatever was live: at 64 slots and a
  6,144-token table 4,800 steps a call of which ≈ 400 did work (alone at
  that shape, 13 slots live: 0.78 -> 0.43 ms a call on a v5e; PERF.md
  section 6, PR 45).
- A decode row handed kv_len 0 (`ragged_mixed_attention`'s `kernel_lens`: a
  slot that holds no sequence) owns no KV block: no page copy, no product,
  zeros written. The DMA ring runs on across query blocks (issue order ==
  consume order) and its issue cursor steps over such rows (`next_owner`).
- Scalar-prefetched descriptor arrays drive everything ragged:
  `tables_ref [R, W]` (row r = sequence r's page table, trash-padded; the
  last row is the chunk's), `kvlen_ref [R]` (attention horizon per sequence,
  INCLUDING the tokens written this step) and `qstart_ref [R]` (absolute
  position of the sequence's first query token).
- The per-query-block KV block count is derived from its causal horizon
  clamped to the sequence's kv_len, so decode blocks fetch exactly their
  context and chunk blocks exactly their prefix — the DMA pipeline crosses
  sequence boundaries without bubbles, which is the whole point: short decode
  rows and long prefill rows share one software pipeline.
- Masking is causal in absolute positions (`tok <= q_pos`) AND bounded by the
  sequence horizon (`tok < kv_len`), which keeps the decode padding rows
  (whose outputs are discarded) from touching garbage pages past their
  context.

NaN-safety mirrors the house kernels: token 0 is unmasked for every row of a
block that owns KV blocks at the first one it visits (`q_start >= 0`,
`kv_len >= 1`), so the running max is finite from the first `_flash_update`
on (under a window a finite floor stands in: see the kernel's mask); a row
that owns nothing runs no `_flash_update` at all and reads zero.

Live KV only: a block's page copies never follow its table past the block's
horizon (a superblock's tail re-reads the last live page), so the work, the
bytes and the result depend on nothing a sequence does not own below its
kv_len — the property the mixed step pays for (the XLA composition it
replaced gathers max_num_seqs x max_seq_len whatever is live).

`ops/attention.py` routes the mixed step here on a kernel backend (`auto`
on a TPU) since PR 26: on-chip parity at the benchmark cells' own shapes
(ops/kernel_parity.py, `ragged_cell_*`), and both chat cells judged it as
the default step (PERF.md section 6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas_attention import (
    DEFAULT_BLOCK_PAGES,
    DEFAULT_NUM_BUFS,
    NEG_INF,
    _flash_normalize,
    _flash_reset,
    _flash_update,
    _kv_block,
    _sink_rows,
    _sink_specs,
    _v_head_dim,
    _v_ring,
    shared_kv,
)

# what a masked score reads under a window (see the kernel's mask)
_OUT_OF_REACH = -1e30


def _div(x, n: int):
    """x // n for x >= 0, as ONE operation: `//` on a traced integer is
    Python's floor division, seven operations that truncation does not
    need here, and the kernel's size is paid at every trace and lowering."""
    return jax.lax.div(x, jnp.int32(n))


def _ragged_kernel(
    # scalar prefetch
    tables_ref,  # [R, W] int32 page tables (row R-1 = the prefill chunk's)
    kvlen_ref,  # [R] int32 attention horizon per sequence (incl. this
    #             step); 0 = a decode slot that holds no sequence
    qstart_ref,  # [R] int32 absolute position of the first query token
    # inputs
    q_ref,  # [1, BQ, H, D] VMEM block (one ragged query block)
    k_hbm,  # [P, ps, KVD] in ANY/HBM — manually DMA'd
    v_hbm,  # [P, ps, KV*Dv] (Dv = D unless V's rows are narrower)
    *rest,  # [sink_ref [BQ*H, 1] f32 where `sink`,] o_ref [1, BQ, H, Dv],
    # scratch (persistent across the sequential grid):
    # kbuf [NBUF, SB, ps, KVD], vbuf [NBUF, SB, ps, KV*Dv]
    # qbd_ref [BQ*H, KVD] f32 — block-diagonal queries, built once per qb
    # m_ref, l_ref [BQ*H, 128] f32
    # acc_ref [BQ*H, KV*Dv] f32
    # ptr_ref SMEM [4]: consumed count, issue cursor (qb, kb), issued count
    # sem DMA semaphores [NBUF, 2, SB]
    page_size: int,
    table_width: int,
    block_pages: int,
    block_q: int,
    num_bufs: int,
    num_decode: int,
    n_kv: int,
    scale: float,
    lane_width: int,
    quantized: bool,
    shared: bool = False,
    window: int = 0,
    sink: bool = False,
):
    """One grid step a query block; inside it a loop over that block's OWN
    KV blocks (`n_blocks`). A decode row whose sequence has kv_len 0 owns
    none: no page copy, no product, zeros written. The DMA ring runs on
    across query blocks (issue order == consume order), its issue cursor
    stepping over the rows that own nothing (`_decode_kernel` is the
    model, for `sink` too)."""
    sink_ref = rest[0] if sink else None
    (o_ref, kbuf, vbuf, qbd_ref, m_ref, l_ref, acc_ref, ptr_ref,
     sem) = rest[1:] if sink else rest
    qb = pl.program_id(0)
    nq = pl.num_programs(0)
    tokens_per_block = block_pages * page_size
    h, d = q_ref.shape[2], q_ref.shape[3]
    dv = o_ref.shape[3]  # V's lanes a head: d, or narrower
    group = h // n_kv
    rows = block_q * h

    def seq_row(qq):
        # query blocks 0..num_decode-1 are the decode slots; every later
        # block belongs to the single prefill chunk (descriptor row
        # num_decode)
        return jnp.minimum(qq, num_decode)

    def q_off(qq):
        # the block's token offset within its sequence's query span
        return jnp.maximum(qq - num_decode, 0) * block_q

    def horizon(qq):
        # causal horizon of block qq clamped to its sequence's kv length
        # (a decode block stops at its context; a chunk block never reads
        # past the chunk end). Asked of owners only, and >= 1: see n_blocks
        r = seq_row(qq)
        hz = jnp.minimum(qstart_ref[r] + q_off(qq) + block_q, kvlen_ref[r])
        return jnp.maximum(hz, 1)

    def first_block(qq):
        """The first KV block a query block visits: 0, or under a static
        `window` the block that holds the oldest key in reach of the
        block's FIRST query (those before it are wholly out of every
        row's reach and are neither copied nor multiplied)."""
        if not window:
            return 0
        lo = qstart_ref[seq_row(qq)] + q_off(qq) - (window - 1)
        return _div(jnp.maximum(lo, 0), tokens_per_block)

    def block_dma(qq, kk, slot, wait):
        """Start (or wait for) the 2 * block_pages page copies of KV block
        kk of query block qq into ring slot `slot`. Unrolled, as the decode
        kernel has them: as a `fori_loop` the copies cost 0.16-0.18 us a block
        more, 12% of the kernel at 13 of 64 slots live and 13% at 64 (PR 45,
        v5e), and unrolled a mixed program lowers 0.02-0.035 s slower (the
        kernel is traced once a shape: the wrapper is a jit of its own)."""
        r = seq_row(qq)
        if not wait:
            # live KV only: a superblock's tail past the horizon re-reads
            # the last live page instead of following the table, so neither
            # a table entry past the horizon nor the page it names is read
            last = jnp.minimum(_div(horizon(qq) - 1, page_size),
                               table_width - 1)
            if window:
                kk = kk + first_block(qq)
        for j in range(block_pages):
            # a wait needs the copy's shape and semaphore, not its source
            pg = 0 if wait else tables_ref[
                r, jnp.minimum(kk * block_pages + j, last)]
            # MLA keeps its latent row once: K alone is copied then
            for hbm, buf, which in (((k_hbm, kbuf, 0),) if shared else (
                    (k_hbm, kbuf, 0), (v_hbm, vbuf, 1))):
                c = pltpu.make_async_copy(
                    hbm.at[pg], buf.at[slot, j], sem.at[slot, which, j])
                c.wait() if wait else c.start()

    def n_blocks(qq):
        # of an owner, and >= 1 (horizon is): the issue side starts an
        # owner's first block before it counts them, so an owner that
        # consumed none would break the ring's issue/consume pairing
        n = _div(horizon(qq) + tokens_per_block - 1, tokens_per_block)
        return n - first_block(qq) if window else n

    def owns(qq):
        # a chunk block always does (its own tokens: kv_len >= 1)
        return kvlen_ref[seq_row(qq)] > 0

    def next_owner(qq):
        """The first query block at or after qq that owns a KV block (any
        chunk block does, so at most num_decode); nq if qq is past the
        grid."""
        if not num_decode:
            return qq
        return jax.lax.while_loop(
            lambda x: (x < num_decode) & jnp.logical_not(owns(x)),
            lambda x: x + 1, qq)

    def issue_one():
        """Issue the block at the issue cursor (if any remain) into ring
        slot `issued % num_bufs`, then advance the cursor to the next
        block anyone owns; the consume side finds it at
        `consumed % num_bufs`."""
        iq, ik = ptr_ref[1], ptr_ref[2]

        @pl.when(iq < nq)
        def _():
            block_dma(iq, ik, jax.lax.rem(ptr_ref[3], num_bufs), wait=False)
            ptr_ref[3] = ptr_ref[3] + 1
            more = ik + 1 < n_blocks(iq)
            ptr_ref[2] = jnp.where(more, ik + 1, 0)

            @pl.when(jnp.logical_not(more))
            def _():
                ptr_ref[1] = next_owner(iq + 1)

    @pl.when(qb == 0)
    def _init():
        ptr_ref[0] = 0  # consumed-block count
        ptr_ref[1] = next_owner(0)  # issue cursor: query block
        ptr_ref[2] = 0  # issue cursor: kv block within it
        ptr_ref[3] = 0  # issued-block count

        def prime(_, carry):
            issue_one()
            return carry

        # a loop: it runs once a call, and the kernel's size is set-up time
        jax.lax.fori_loop(0, num_bufs - 1, prime, 0)

    live = owns(qb)

    @pl.when(jnp.logical_not(live))
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _live():
        def bd_mask(d):
            # before the loop (the queries: K's d), after it (the fold: V's)
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, n_kv * d), 0)
            lane = jax.lax.broadcasted_iota(jnp.int32, (rows, n_kv * d), 1)
            return _div(jax.lax.rem(row, h), group) == _div(lane, d)

        _flash_reset(m_ref, l_ref, acc_ref,
                     sink_ref[...] if sink else None)
        q = q_ref[0].astype(jnp.float32).reshape(rows, d) * scale
        qbd_ref[...] = jnp.where(bd_mask(d), jnp.tile(q, (1, n_kv)), 0.0)
        r = seq_row(qb)

        def block(kb, carry):
            cnt = ptr_ref[0]
            cur = jax.lax.rem(cnt, num_bufs)
            # keep the ring full: one block is issued `num_bufs - 1` ahead
            # of the one consumed, into the slot consumed that long ago
            issue_one()
            block_dma(qb, kb, cur, wait=True)
            ptr_ref[0] = cnt + 1
            k, v = _kv_block(kbuf, vbuf, cur, tokens_per_block, n_kv, d,
                             lane_width, quantized, shared)
            s = jax.lax.dot_general(
                qbd_ref[...].astype(k.dtype), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [rows, T]
            tok = ((kb + first_block(qb)) if window else kb
                   ) * tokens_per_block + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            qpos = qstart_ref[r] + q_off(qb) + _div(
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0), h)
            if window:
                # a later row of the block may see nothing of the first
                # block visited (it starts where the FIRST row's reach
                # does): a finite floor keeps its running max finite, and
                # the first block that holds a key in its reach wipes what
                # it summed
                s = jnp.where((tok <= qpos) & (tok < kvlen_ref[r])
                              & (tok > qpos - window), s, _OUT_OF_REACH)
            else:
                s = jnp.where((tok <= qpos) & (tok < kvlen_ref[r]), s,
                              NEG_INF)
            _flash_update(m_ref, l_ref, acc_ref, s, v)
            return carry

        jax.lax.fori_loop(0, n_blocks(qb), block, 0)

        out = _flash_normalize(l_ref, acc_ref)  # [rows, KV*Dv]
        out = jnp.where(bd_mask(dv), out, 0.0)
        folded = out[:, 0:dv]
        for kv in range(1, n_kv):
            folded = folded + out[:, kv * dv:(kv + 1) * dv]
        o_ref[0] = folded.reshape(block_q, h, dv).astype(o_ref.dtype)


# jitted so that the kernel body is traced once per shape, not once per
# enclosing program: 14 of the engine's 16 mixed programs share one shape
@functools.partial(jax.jit, static_argnames=(
    "page_size", "num_kv_heads", "num_decode", "decode_q", "block_q",
    "block_pages", "num_bufs", "interpret", "window"))
def ragged_paged_attention(
    q: jax.Array,  # [num_decode * decode_q + C, H, D] — leading rows, chunk
    k_pages: jax.Array,  # [P, ps, KV*D] (or int8 packed single-block rows)
    v_pages: jax.Array,
    tables: jax.Array,  # [num_decode + 1, W] int32 (last row = chunk pages)
    kv_lens: jax.Array,  # [num_decode + 1] int32 horizons incl. this step
    q_starts: jax.Array,  # [num_decode + 1] int32 first-query positions
    *,
    page_size: int,
    num_kv_heads: int,
    num_decode: int,
    decode_q: int = 1,
    block_q: int = 8,
    block_pages: int = DEFAULT_BLOCK_PAGES,
    num_bufs: int = DEFAULT_NUM_BUFS,
    interpret: bool = False,
    window: int = 0,
    sink=None,  # [H] float32: a learned logit a query head in the softmax
) -> jax.Array:
    """Mixed ragged batch: `num_decode` leading rows of `decode_q` query
    tokens each (one padded query block per row) plus ONE prefill chunk of C
    tokens tiled into blocks, all on one sequential grid. decode_q=1 is the
    plain mixed step; decode_q=K+1 makes each leading row a speculative
    verify window — the kernel needs no change because its mask is causal in
    absolute positions and clamped per-row by kv_lens, so a K+1-wide window
    with kv_len = q_start + K + 1 scores exactly like a mid-prefill row.
    `window` > 0 (static): a query sees itself and the window - 1 keys
    before it; KV blocks wholly below a query block's reach are skipped.
    window = 0 traces none of it: the kernel is the one without.
    `sink`: every row's softmax carries its head's learned logit in the
    denominator (`pallas_attention._flash_reset`); None traces none of it.
    Returns [num_decode * decode_q + C, H, Dv] (Dv: V's lanes a head, the
    V pool's own: D unless V's rows are narrower than K's)."""
    total, n_heads, head_dim = q.shape
    c = total - num_decode * decode_q
    assert c >= 1, "ragged batch needs a prefill chunk (use decode kernel)"
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
    width = tables.shape[1]
    assert tables.shape[0] == num_decode + 1, tables.shape
    block_pages = max(1, min(block_pages, width))
    num_bufs = max(2, num_bufs)
    # largest power-of-two divisor of c not exceeding the requested block
    # (chunks are page multiples, not necessarily block_q multiples); a
    # verify window must fit inside one padded query block, so the block
    # can't shrink below decode_q — the engine guarantees decode_q <= page
    # size <= chunk length, which keeps these two constraints compatible
    block_q = max(1, min(max(block_q, decode_q), c))
    while c % block_q != 0:
        block_q //= 2
    assert block_q >= decode_q, (block_q, decode_q, c)
    # every row of a block sees a key the block visits (its own at least)
    assert not window or window >= block_q, (window, block_q)
    n_chunk_blocks = c // block_q
    nbq = num_decode + n_chunk_blocks
    scale = 1.0 / (head_dim**0.5)
    rows = block_q * n_heads

    # leading rows each get their own zero-padded query block (decode_q real
    # tokens, the rest padding whose outputs are discarded); the chunk is
    # tiled block_q tokens per block
    nd = num_decode * decode_q
    q_dec = jnp.zeros((num_decode, block_q, n_heads, head_dim), q.dtype)
    if num_decode:
        q_dec = q_dec.at[:, :decode_q].set(
            q[:nd].reshape(num_decode, decode_q, n_heads, head_dim))
    q4 = jnp.concatenate(
        [q_dec,
         q[nd:].reshape(n_chunk_blocks, block_q, n_heads, head_dim)],
        axis=0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nbq,),
        in_specs=[
            pl.BlockSpec((1, block_q, n_heads, head_dim),
                         lambda qb, tb, kl, qs: (qb, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ] + _sink_specs(sink, rows, lambda qb, tb, kl, qs: (0, 0)),
        out_specs=pl.BlockSpec(
            (1, block_q, n_heads, v_dim),
            lambda qb, tb, kl, qs: (qb, 0, 0, 0),
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
        _ragged_kernel,
        page_size=page_size,
        table_width=width,
        block_pages=block_pages,
        block_q=block_q,
        num_bufs=num_bufs,
        num_decode=num_decode,
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
        out_shape=jax.ShapeDtypeStruct((nbq, block_q, n_heads, v_dim),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            # sequential on purpose: the DMA pipeline carries state across
            # grid steps (see module docstring)
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
      q_starts.astype(jnp.int32), q4, k_pages, v_pages,
      *_sink_rows(sink, block_q))
    return jnp.concatenate(
        [out[:num_decode, :decode_q].reshape(nd, n_heads, v_dim),
         out[num_decode:].reshape(c, n_heads, v_dim)], axis=0)
