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
Where it departs, it is to be small: the kernel is traced and lowered for
every mixed program (16 at warmup, ~1 s each on a serving host before
PR 26), so the page copies are a loop, `//` on traced integers is one
`lax.div`, and the wrapper is jitted so that programs that share shapes
share one trace:

- Grid is `(num_q_blocks, nk_max)` where the first `num_decode` query blocks
  are the decode slots (one real row each, padded to `block_q`) and the rest
  tile the prefill chunk `block_q` tokens at a time.
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

NaN-safety mirrors the house kernels: token 0 is unmasked for every row of
every sequence at its first KV block (`q_start >= 0`, `kv_len >= 1`), so the
running max is finite from the first `_flash_update` on.

Live KV only: a block's page copies never follow its table past the block's
horizon (a superblock's tail re-reads the last live page), so the work, the
bytes and the result depend on nothing a sequence does not own below its
kv_len — the property the mixed step pays for (the XLA composition it
replaced gathers max_num_seqs x max_seq_len whatever is live).

Hardware-validation gating follows the CHUNK_KERNEL convention:
`RAGGED_KERNEL_HW_VALIDATED` is True, so the dispatch in `attention.py`
follows the scoped backend (`auto` -> this kernel on a TPU). It was flipped
by PR 26 after on-chip parity at the benchmark cells' own shapes and after
both cells judged it as the default step (PERF.md section 6). With the flag
False the XLA composition serves every backend (counted in
dynamo_pallas_fallback_total); `DYNAMO_TPU_RAGGED_ATTENTION` overrides
either way.
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
    _v_ring,
    shared_kv,
)

# True since PR 26: on-chip parity at the benchmark cells' own shapes
# (ops/kernel_parity.py, `ragged_cell_*`) and both cells judged the kernel
# as the default mixed step (PERF.md section 6). Kept, with the
# `not_validated` route and DYNAMO_TPU_RAGGED_ATTENTION, for the parity
# tool and the tests until ROADMAP D1 removes the duplicate kernels.
RAGGED_KERNEL_HW_VALIDATED = True


def _div(x, n: int):
    """x // n for x >= 0, as ONE operation: `//` on a traced integer is
    Python's floor division, seven operations that truncation does not
    need here, and the kernel's size is paid at every trace and lowering."""
    return jax.lax.div(x, jnp.int32(n))


def _ragged_kernel(
    # scalar prefetch
    tables_ref,  # [R, W] int32 page tables (row R-1 = the prefill chunk's)
    kvlen_ref,  # [R] int32 attention horizon per sequence (incl. this step)
    qstart_ref,  # [R] int32 absolute position of the first query token
    # inputs
    q_ref,  # [1, BQ, H, D] VMEM block (one ragged query block)
    k_hbm,  # [P, ps, KVD] in ANY/HBM — manually DMA'd
    v_hbm,  # [P, ps, KVD]
    o_ref,  # [1, BQ, H, D]
    # scratch (persistent across the sequential grid)
    kbuf,  # [NBUF, SB, ps, KVD]
    vbuf,  # [NBUF, SB, ps, KVD]
    qbd_ref,  # [BQ*H, KVD] f32 — block-diagonal queries, built once per qb
    m_ref,  # [BQ*H, 128] f32
    l_ref,  # [BQ*H, 128] f32
    acc_ref,  # [BQ*H, KVD] f32
    ptr_ref,  # SMEM [4]: consumed count, issue cursor (qb, kb), issued count
    sem,  # DMA semaphores [NBUF, 2, SB]
    *,
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
):
    qb = pl.program_id(0)
    kb = pl.program_id(1)
    nq = pl.num_programs(0)
    tokens_per_block = block_pages * page_size
    h, d = q_ref.shape[2], q_ref.shape[3]
    group = h // n_kv
    rows = block_q * h
    kvd = n_kv * d

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
        # past the chunk end), and >= 1: see n_blocks
        r = seq_row(qq)
        hz = jnp.minimum(qstart_ref[r] + q_off(qq) + block_q, kvlen_ref[r])
        return jnp.maximum(hz, 1)

    def block_dma(qq, kk, slot, wait):
        """Start (or wait for) the 2 * block_pages page copies of KV block
        kk of query block qq into ring slot `slot`. A loop, not an unrolled
        list: the kernel is traced and lowered once per mixed program (16
        at warmup), and its size is set-up time."""
        r = seq_row(qq)
        # live KV only: a superblock's tail past the horizon re-reads the
        # last live page instead of following the table, so neither a
        # table entry past the horizon nor the page it names is ever read
        last = jnp.minimum(_div(horizon(qq) - 1, page_size), table_width - 1)

        def page(j, carry):
            # a wait needs the copy's shape and semaphore, not its source
            pg = 0 if wait else tables_ref[
                r, jnp.minimum(kk * block_pages + j, last)]
            # MLA keeps its latent row once: K alone is copied then
            for hbm, buf, which in (((k_hbm, kbuf, 0),) if shared else (
                    (k_hbm, kbuf, 0), (v_hbm, vbuf, 1))):
                c = pltpu.make_async_copy(
                    hbm.at[pg], buf.at[slot, j], sem.at[slot, which, j])
                c.wait() if wait else c.start()
            return carry

        jax.lax.fori_loop(0, block_pages, page, 0)

    def n_blocks(qq):
        # >= 1 (horizon is): every block owns at least one pipeline step —
        # breaking issue/consume pairing would corrupt the DMA slot parity
        return _div(horizon(qq) + tokens_per_block - 1, tokens_per_block)

    def issue_one():
        iq, ik = ptr_ref[1], ptr_ref[2]

        @pl.when(iq < nq)
        def _():
            block_dma(iq, ik, jax.lax.rem(ptr_ref[3], num_bufs), wait=False)
            ptr_ref[3] = ptr_ref[3] + 1
            nxt = ik + 1
            done = nxt >= n_blocks(iq)
            ptr_ref[1] = jnp.where(done, iq + 1, iq)
            ptr_ref[2] = jnp.where(done, 0, nxt)

    nb_q = n_blocks(qb)

    @pl.when((qb == 0) & (kb == 0))
    def _init():
        ptr_ref[0] = 0  # consumed-block count
        ptr_ref[1] = 0  # issue cursor: query block
        ptr_ref[2] = 0  # issue cursor: kv block within it
        ptr_ref[3] = 0  # issued-block count
        for _ in range(num_bufs - 1):
            issue_one()

    @pl.when(kb < nb_q)
    def _active():
        cnt = ptr_ref[0]
        cur = jax.lax.rem(cnt, num_bufs)
        issue_one()
        block_dma(qb, kb, cur, wait=True)
        ptr_ref[0] = cnt + 1

        def bd_mask():
            # only the first and the last KV block of a query block need it
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, kvd), 0)
            lane = jax.lax.broadcasted_iota(jnp.int32, (rows, kvd), 1)
            return _div(jax.lax.rem(row, h), group) == _div(lane, d)

        @pl.when(kb == 0)
        def _reset():
            _flash_reset(m_ref, l_ref, acc_ref)
            q = q_ref[0].astype(jnp.float32).reshape(rows, d) * scale
            qbd_ref[...] = jnp.where(bd_mask(), jnp.tile(q, (1, n_kv)), 0.0)

        k, v = _kv_block(kbuf, vbuf, cur, tokens_per_block, n_kv, d,
                         lane_width, quantized, shared)
        s = jax.lax.dot_general(
            qbd_ref[...].astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rows, T]
        tok = kb * tokens_per_block + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        r = seq_row(qb)
        qpos = qstart_ref[r] + q_off(qb) + _div(
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 0), h)
        s = jnp.where((tok <= qpos) & (tok < kvlen_ref[r]), s, NEG_INF)
        _flash_update(m_ref, l_ref, acc_ref, s, v)

        @pl.when(kb == nb_q - 1)
        def _finalize():
            out = _flash_normalize(l_ref, acc_ref)  # [rows, KVD]
            out = jnp.where(bd_mask(), out, 0.0)
            folded = out[:, 0:d]
            for kv in range(1, n_kv):
                folded = folded + out[:, kv * d:(kv + 1) * d]
            o_ref[0] = folded.reshape(block_q, h, d).astype(o_ref.dtype)


# jitted so that the kernel body is traced once per shape, not once per
# enclosing program: 14 of the engine's 16 mixed programs share one shape
@functools.partial(jax.jit, static_argnames=(
    "page_size", "num_kv_heads", "num_decode", "decode_q", "block_q",
    "block_pages", "num_bufs", "interpret"))
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
) -> jax.Array:
    """Mixed ragged batch: `num_decode` leading rows of `decode_q` query
    tokens each (one padded query block per row) plus ONE prefill chunk of C
    tokens tiled into blocks, all on one sequential grid. decode_q=1 is the
    plain mixed step; decode_q=K+1 makes each leading row a speculative
    verify window — the kernel needs no change because its mask is causal in
    absolute positions and clamped per-row by kv_lens, so a K+1-wide window
    with kv_len = q_start + K + 1 scores exactly like a mid-prefill row.
    Returns [num_decode * decode_q + C, H, D]."""
    total, n_heads, head_dim = q.shape
    c = total - num_decode * decode_q
    assert c >= 1, "ragged batch needs a prefill chunk (use decode kernel)"
    lane_width = k_pages.shape[2]
    quantized = k_pages.dtype == jnp.int8
    shared = shared_kv(v_pages)
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
    n_chunk_blocks = c // block_q
    nbq = num_decode + n_chunk_blocks
    nk_max = -(-width // block_pages)
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
        grid=(nbq, nk_max),
        in_specs=[
            pl.BlockSpec((1, block_q, n_heads, head_dim),
                         lambda qb, kb, tb, kl, qs: (qb, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, n_heads, head_dim),
            lambda qb, kb, tb, kl, qs: (qb, 0, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((num_bufs, block_pages, page_size, lane_width),
                       k_pages.dtype),
            _v_ring(shared, (num_bufs, block_pages, page_size, lane_width),
                    v_pages.dtype),
            pltpu.VMEM((rows, kvd), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, kvd), jnp.float32),
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
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nbq, block_q, n_heads, head_dim),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            # sequential on purpose: the DMA pipeline carries state across
            # grid steps (see module docstring)
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
      q_starts.astype(jnp.int32), q4, k_pages, v_pages)
    return jnp.concatenate(
        [out[:num_decode, :decode_q].reshape(nd, n_heads, head_dim),
         out[num_decode:].reshape(c, n_heads, head_dim)], axis=0)
