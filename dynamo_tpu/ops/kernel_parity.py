"""On-chip compile + parity check of every Pallas kernel.

Interpret mode proves a kernel's arithmetic; only Mosaic on a real chip
proves it lowers. This module compiles each kernel at a serving model's
head shapes — and the decode and ragged kernels at the benchmark cells' own
steps (`decode_cell_*`, `ragged_cell_*`: 32 slots of which 6 live, tables 128
wide, a 256-token chunk at positions 0 and 1536, 28/4 and 32/8 heads; the
decode kernel is handed context 0 for an empty slot, as `llama.decode_step`
hands it, and the ragged kernel `kernel_lens` as `llama.mixed_step` does;
since PR 45 also the ragged kernel at Falcon-H1's mixed step,
`ragged_cell_falcon_*`: 64 slots of which 13 live around 1,900 tokens, 20/4
heads, tables 384 / 399 wide, and at the Kimi cell's as it runs now,
`ragged_cell_kimi_*`: 2 live at 4,500 behind the 4,096-token prefix), and
every paged kernel at MLA's latent geometry as the Kimi-K2
cell runs it (`mla_*`: 64 heads on
one 640-lane row stored once, 64 slots, contexts of 8-10k, a chunk behind
an 8,192-token cached prefix) — and compares it with its XLA twin from
`ops/attention.py` (for `mla_*` the same sums against the one shared row,
written here: the twin's 64-fold repeat of the row does not fit); and the
Mamba-2 one-token state update over the live slots at the Nemotron cell's
state (`ssm_update_cell`: 64 slots of [64, 64, 128] float32, 27 live)
against `ops/ssm.step_every_slot`:

    python -m dynamo_tpu.ops.kernel_parity            # on the chip
    python -m dynamo_tpu.ops.kernel_parity --interpret  # CPU rehearsal

One JSON line per case: `compiled` (with max_abs_err), `parity_error`
(compiled, but disagrees with the twin or is non-finite) or `refused`
(lowering/compile raised; Mosaic's message is kept). The last line is the
summary, also written to `chiprun_out/kernel_parity.json`. Exit code 1 if
any case is not `compiled`.

Run it once per PR that touches a kernel, outside any timed window.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops import pallas_attention as pa
from dynamo_tpu.ops import ssm

# bf16 pools/queries with unit-normal values: outputs are convex averages
# of |v| <~ 4, the XLA twins round scores and probabilities to bf16
# (eps 2^-8) where the kernels accumulate in f32. 0.05 absolute is ~3x the
# worst disagreement that rounding alone explains and far below what a
# wrong mask, page or head mapping produces (O(1)).
TOLERANCE = 0.05

PAGE_SIZE = 16
HEAD_DIM = 128
NUM_POOL_PAGES = 96

OUT_PATH = os.path.join("chiprun_out", "kernel_parity.json")

# Nemotron-3-Nano's Mamba-2 state a slot: heads, head lanes, groups, state
# lanes (benchmarks/chip/configs/nemotron3-nano-w8a8-1chip)
SSM_STATE = (64, 64, 8, 128)

# (label, query heads, KV heads): qwen2.5-7b whole, and one tp=4 shard
SHAPES: Tuple[Tuple[str, int, int], ...] = (
    ("28q4kv", 28, 4),
    ("7q1kv", 7, 1),
)


def _pools(rng, n_kv: int, quantized: bool, pages: int = NUM_POOL_PAGES):
    n = pages * PAGE_SIZE
    kf = rng.normal(size=(n, n_kv, HEAD_DIM)).astype(np.float32)
    vf = rng.normal(size=(n, n_kv, HEAD_DIM)).astype(np.float32)
    if quantized:
        w = att.kv_lane_width(n_kv, HEAD_DIM, True)
        return (att.pack_kv_rows(jnp.asarray(kf), w).reshape(
                    pages, PAGE_SIZE, w),
                att.pack_kv_rows(jnp.asarray(vf), w).reshape(
                    pages, PAGE_SIZE, w))
    shape = (pages, PAGE_SIZE, n_kv * HEAD_DIM)
    return (jnp.asarray(kf.reshape(shape), jnp.bfloat16),
            jnp.asarray(vf.reshape(shape), jnp.bfloat16))


def _decode_tables(b: int = 8, pmax: int = 12):
    """Disjoint page tables with contexts that hit 1 token, mid-page,
    page-exact and table-full rows."""
    tables = np.zeros((b, pmax), np.int32)
    ctx = [1, 21, 96, 40, 7, 64, 33, pmax * PAGE_SIZE][:b]
    nxt = 1
    for i, c in enumerate(ctx):
        n = -(-c // PAGE_SIZE)
        tables[i, :n] = np.arange(nxt, nxt + n) % (NUM_POOL_PAGES - 1) + 1
        nxt += n
    return jnp.asarray(tables), jnp.asarray(ctx, jnp.int32)


def _case_decode(h: int, n_kv: int, quantized: bool, interpret: bool):
    rng = np.random.default_rng(9)
    kp, vp = _pools(rng, n_kv, quantized)
    bt, cl = _decode_tables()
    q = jnp.asarray(rng.normal(size=(bt.shape[0], h, HEAD_DIM)), jnp.bfloat16)
    ref = jax.jit(lambda *a: att.paged_attention_decode_xla(
        *a, page_size=PAGE_SIZE, num_kv_heads=n_kv, lane_blocks=1))
    ker = jax.jit(lambda *a: pa.paged_attention_decode(
        *a, page_size=PAGE_SIZE, num_kv_heads=n_kv, interpret=interpret))
    args = (q, kp, vp, bt, cl)
    return ker, ref, args


def _case_prefill(h: int, n_kv: int, s: int, interpret: bool):
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(s, h, HEAD_DIM)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(s, n_kv, HEAD_DIM)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(s, n_kv, HEAD_DIM)), jnp.bfloat16)
    sl = jnp.asarray(max(1, s - 5), jnp.int32)  # padded tail is masked
    ref = jax.jit(lambda q, k, v, sl: att.prefill_attention_xla(q, k, v, sl))
    ker = jax.jit(lambda q, k, v, sl: pa.prefill_attention(
        q, k, v, sl, interpret=interpret))
    return ker, ref, (q, k, v, sl)


def _chunk_pages(width: int = 32, used: int = 24):
    return jnp.asarray(list(range(40, 40 + used)) + [0] * (width - used),
                       jnp.int32)


def _case_chunk(h: int, n_kv: int, quantized: bool, interpret: bool):
    """A 256-token chunk starting mid-prompt at token 128 (page 8)."""
    rng = np.random.default_rng(5)
    kp, vp = _pools(rng, n_kv, quantized)
    q = jnp.asarray(rng.normal(size=(256, h, HEAD_DIM)), jnp.bfloat16)
    pages, start = _chunk_pages(), jnp.asarray(128, jnp.int32)
    ref = jax.jit(lambda *a: att.chunk_attention_xla(
        *a, page_size=PAGE_SIZE, num_kv_heads=n_kv))
    ker = jax.jit(lambda *a: pa.chunk_prefill_attention(
        *a, page_size=PAGE_SIZE, num_kv_heads=n_kv, interpret=interpret))
    return ker, ref, (q, kp, vp, pages, start)


def _case_ragged(h: int, n_kv: int, quantized: bool, decode_q: int,
                 interpret: bool):
    """8 leading rows (decode_q=1: decode slots; >1: speculative verify
    windows) plus one 256-token chunk, through the SAME descriptor
    construction the dispatcher uses (`attention.ragged_*_attention` with
    the kernel forced by env) against the XLA composition."""
    rng = np.random.default_rng(17)
    kp, vp = _pools(rng, n_kv, quantized)
    bt, cl = _decode_tables()
    b = bt.shape[0]
    q = jnp.asarray(rng.normal(size=(b * decode_q + 256, h, HEAD_DIM)),
                    jnp.bfloat16)
    pages, start = _chunk_pages(), jnp.asarray(128, jnp.int32)
    if decode_q == 1:
        def op(*a):
            return att.ragged_mixed_attention(
                *a, page_size=PAGE_SIZE, num_kv_heads=n_kv, num_decode=b)
        args = (q, kp, vp, bt, cl, pages, start)
    else:
        def op(*a):
            return att.ragged_verify_attention(
                *a, page_size=PAGE_SIZE, num_kv_heads=n_kv, num_verify=b,
                verify_width=decode_q)
        # window b's first query sits at position ctx-1; its K1 queries
        # need ctx-1+K1 tokens inside the table, so cap the full row
        pos = jnp.minimum(cl - 1, bt.shape[1] * PAGE_SIZE - decode_q)
        args = (q, kp, vp, bt, pos, pages, start)

    return (_forced(op, "pallas_interpret" if interpret else "pallas"),
            _forced(op, "xla"), args)


def _forced(op: Callable, backend: str) -> Callable:
    """`op` jitted with the attention backend scoped to `backend`. A fresh
    function object per backend: jit's trace cache is keyed on the
    function, and the scope is read at trace time."""
    fn = jax.jit(lambda *a: op(*a))

    def run(*a):
        with att.attention_context(backend, None):
            return fn(*a)
    return run


# The benchmark cells' mixed step (benchmarks/chip/configs/*.json: 32 slots,
# --max-seq-len 2048, --mixed-batch-tokens 256, page 16): heads as served,
# whole on one chip
CELL_SHAPES: Tuple[Tuple[str, int, int], ...] = (
    ("28q4kv", 28, 4),   # qwen2.5-7b-w8a8
    ("32q8kv", 32, 8),   # mixtral-8x7b-w8a8-1chip
)
CELL_SLOTS = 32
CELL_TABLE_WIDTH = 128
CELL_CHUNK = 256
CELL_POOL_PAGES = 640
# live slots (slot -> context length); the other 26 carry the engine's
# inactive-slot contract: a zero table and context_lens 1
CELL_LIVE = {0: 517, 3: 300, 4: 16, 9: 129, 17: 1999, 31: 800}


# (chunk start, width of the chunk's page table): a prompt's first chunk in
# the 256 bucket and the seventh in the 2048 bucket; the width is the
# bucket's pages plus chunk_pages - 1 trash slots
# (KVCacheSpec.page_table_width)
CELL_CHUNKS = ((0, 16 + 15), (1536, 128 + 15))


def _cell_batch(slots: int = CELL_SLOTS, width: int = CELL_TABLE_WIDTH,
                live=None, shrink: int = 1):
    """(tables, contexts, first free page) of a cell's decode batch: by
    default the chat cells', 32 slots of which 6 live, tables 128 wide. An
    interpret-mode rehearsal divides contexts and width by `shrink`."""
    tables = np.zeros((slots, width // shrink), np.int32)
    ctx = np.ones((slots,), np.int32)
    nxt = 1
    for slot, c in (CELL_LIVE if live is None else live).items():
        c = max(1, c // shrink)
        n = -(-c // PAGE_SIZE)
        tables[slot, :n] = np.arange(nxt, nxt + n)
        ctx[slot] = c
        nxt += n
    return tables, ctx, nxt


def _as_decode_step(ker, ref, args):
    """A decode case as `llama.decode_step` hands it over: the kernel gets
    context 0 for a slot whose table is all trash and writes zeros there;
    the twin keeps the engine's pin (context 1), and its rows for those
    slots are zeroed to match."""
    live = args[3][:, 0] > 0
    return (lambda q, kp, vp, bt, cl: ker(q, kp, vp, bt,
                                          jnp.where(live, cl, 0)),
            lambda *a: jnp.where(live[:, None, None], ref(*a), 0), args)


def _mixed_step_sides(n_kv: int, slots: int, interpret: bool, twin=None):
    """(kernel, twin) of a cell's mixed step as `llama.mixed_step` hands it
    over: the kernel gets `kernel_lens`, context 0 for a slot whose table is
    all trash, and writes zeros there; the twin (the XLA composition unless
    given) keeps the engine's pin (context 1), and its rows for those slots
    are zeroed to match."""
    def op(q, kp, vp, bt, cl, pg, st):
        return att.ragged_mixed_attention(
            q, kp, vp, bt, cl, pg, st, page_size=PAGE_SIZE,
            num_kv_heads=n_kv, num_decode=slots,
            kernel_lens=jnp.where(bt[:, 0] > 0, cl, 0))

    ref = _forced(op, "xla") if twin is None else jax.jit(twin)

    def zeroed(q, kp, vp, bt, *rest):
        keep = jnp.concatenate(
            [bt[:, 0] > 0, jnp.ones((q.shape[0] - slots,), bool)])
        return jnp.where(keep[:, None, None], ref(q, kp, vp, bt, *rest), 0)
    return _forced(op, "pallas_interpret" if interpret else "pallas"), zeroed


def _case_decode_cell(h: int, n_kv: int, interpret: bool):
    """The decode window's kernel over the chat cells' batch, bf16 pool."""
    rng = np.random.default_rng(28)
    kp, vp = _pools(rng, n_kv, False, pages=CELL_POOL_PAGES)
    tables, ctx, _ = _cell_batch()
    q = jnp.asarray(rng.normal(size=(CELL_SLOTS, h, HEAD_DIM)), jnp.bfloat16)
    ref = jax.jit(lambda *a: att.paged_attention_decode_xla(
        *a, page_size=PAGE_SIZE, num_kv_heads=n_kv, lane_blocks=1))
    ker = jax.jit(lambda *a: pa.paged_attention_decode(
        *a, page_size=PAGE_SIZE, num_kv_heads=n_kv, interpret=interpret))
    return _as_decode_step(
        ker, ref, (q, kp, vp, jnp.asarray(tables), jnp.asarray(ctx)))


def _case_ragged_cell(h: int, n_kv: int, p_start: int, width: int,
                      interpret: bool, batch=None):
    """A cell's own mixed step: by default the chat cells' (32 decode rows
    of which 6 live, tables 128 wide), else `batch` = (slots, decode table
    width, live slots, pool pages), shrunk 16-fold under the interpreter;
    one 256-token chunk at `p_start` over a table `width` wide, bf16 pool."""
    shrink = 16 if interpret and batch else 1
    slots, table, live, pool = batch or (
        CELL_SLOTS, CELL_TABLE_WIDTH, CELL_LIVE, CELL_POOL_PAGES)
    rng = np.random.default_rng(26 + p_start)
    kp, vp = _pools(rng, n_kv, False, pages=pool // shrink + 32)
    tables, ctx, nxt = _cell_batch(slots, table, live, shrink)
    p_start = p_start // shrink // PAGE_SIZE * PAGE_SIZE
    used = (p_start + CELL_CHUNK) // PAGE_SIZE
    assert nxt + used <= pool // shrink + 32
    pages = np.zeros((max(width // shrink, used),), np.int32)
    pages[:used] = np.arange(nxt, nxt + used)
    q = jnp.asarray(
        rng.normal(size=(slots + CELL_CHUNK, h, HEAD_DIM)), jnp.bfloat16)
    args = (q, kp, vp, jnp.asarray(tables), jnp.asarray(ctx),
            jnp.asarray(pages), jnp.asarray(p_start, jnp.int32))
    return (*_mixed_step_sides(n_kv, slots, interpret), args)


# Falcon-H1's mixed step (benchmarks/chip/configs/falcon-h1-34b-w8a8-1chip:
# 64 slots, --max-seq-len 6144 = decode tables 384 wide, ONE chunk table of
# 384 + 15, 20 / 4 heads of 128): 13 of 64 slots live around 1,900 tokens
# as the cell runs (PERF.md section 5), one context ending on a superblock
# (1,920 = 15 x 128), one a token past it, one of a single token
FALCON_SHAPE = ("20q4kv", 20, 4)
FALCON_BATCH = (64, 384, {
    0: 1900, 2: 1920, 7: 1921, 11: 128, 12: 1, 19: 2047, 23: 1664, 30: 777,
    31: 1900, 40: 3000, 47: 1536, 55: 2200, 63: 900}, 2048)
FALCON_CHUNK = (512, 384 + 15)


# ---- MLA's latent geometry (Kimi-K2 / DeepSeek-V3 cell, benchmarks/chip/
# configs/kimi-k2-w8a8-ep16-1chip.json): 64 query heads on ONE shared row of
# 640 lanes (512 + 64, padded), bf16, the row stored once — the V pool has no
# lanes and the kernels read V from the K rows. 64 slots, --max-seq-len
# 10240, 256-token chunks, an 8,192-token cached prefix: ISSUE 27's first
# sizes. The cell now runs the issue's fallback (4,096-token prefix, 6144
# positions: a table of 384 pages), which lies inside these shapes.
MLA_HEADS, MLA_LANES = 64, 640
MLA_SLOTS = 64
MLA_TABLE_WIDTH = 640
MLA_POOL_PAGES = 4096
# live slots (slot -> context); the rest are inactive (zero table, ctx 1)
MLA_LIVE = {0: 8600, 5: 8225, 6: 9984, 21: 8193, 40: 9000, 63: 16}
# (chunk start, chunk table width): a first chunk in the 256 bucket, and a
# tail's first chunk behind the cached prefix in the 10240 bucket
MLA_CHUNKS = ((0, 16 + 15), (8192, 640 + 15))
# the cell as it runs since ISSUE 27's fallback (PERF.md section 5): 2 of 64
# slots live at ~4,500 behind the 4,096-token prefix, decode tables of 384
# pages, the chunk's ONE table of 384 + 15, a tail's first chunk at 4,096
KIMI_LIVE = {5: 4500, 40: 4353}
KIMI_TABLE, KIMI_CHUNK = 384, (4096, 384 + 15)


def _mla_decode_twin(q, kp, bt, cl):
    """Absorbed MLA decode over the gathered rows, every head against the
    one shared row (what attention.paged_attention_decode_xla computes,
    without repeating the row 64 times: at this size that does not fit)."""
    b, pmax = bt.shape
    rows = kp[bt].reshape(b, pmax * PAGE_SIZE, MLA_LANES)
    scale = 1.0 / jnp.sqrt(jnp.float32(MLA_LANES)).astype(q.dtype)
    s = jnp.einsum("bhd,bsd->bhs", q * scale, rows)
    mask = jnp.arange(pmax * PAGE_SIZE)[None, None, :] < cl[:, None, None]
    s = jnp.where(mask, s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,bsd->bhd", p, rows)


def _mla_chunk_twin(q, kp, pages, start):
    c = q.shape[0]
    rows = kp[pages].reshape(pages.shape[0] * PAGE_SIZE, MLA_LANES)
    scale = 1.0 / jnp.sqrt(jnp.float32(MLA_LANES)).astype(q.dtype)
    s = jnp.einsum("chd,sd->hcs", q * scale, rows)
    mask = (jnp.arange(rows.shape[0])[None, None, :]
            <= start + jnp.arange(c)[None, :, None])
    s = jnp.where(mask, s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("hcs,sd->chd", p, rows)


def _mla_pool(rng, pages: int):
    kp = jnp.asarray(rng.normal(size=(pages, PAGE_SIZE, MLA_LANES)),
                     jnp.bfloat16)
    return kp, jnp.zeros((pages, PAGE_SIZE, 0), jnp.bfloat16)


def _mla_batch(shrink: int, live=None, width: int = MLA_TABLE_WIDTH):
    """(tables, contexts, first free page) of the cell's decode batch; an
    interpret-mode rehearsal divides the contexts by `shrink`."""
    return _cell_batch(MLA_SLOTS, width, MLA_LIVE if live is None else live,
                       shrink)


def _case_mla_decode(interpret: bool):
    """The decode window's kernel over the cell's batch."""
    shrink = 16 if interpret else 1
    rng = np.random.default_rng(27)
    tables, ctx, _ = _mla_batch(shrink)
    kp, vp = _mla_pool(rng, MLA_POOL_PAGES // shrink)
    q = jnp.asarray(rng.normal(size=(MLA_SLOTS, MLA_HEADS, MLA_LANES)),
                    jnp.bfloat16)
    ref = jax.jit(lambda q, kp, vp, bt, cl: _mla_decode_twin(q, kp, bt, cl))
    ker = jax.jit(lambda *a: pa.paged_attention_decode(
        *a, page_size=PAGE_SIZE, num_kv_heads=1, interpret=interpret))
    return _as_decode_step(
        ker, ref, (q, kp, vp, jnp.asarray(tables), jnp.asarray(ctx)))


def _mla_chunk_pages(first: int, p_start: int, width: int, shrink: int):
    p_start //= shrink
    used = (p_start + CELL_CHUNK) // PAGE_SIZE
    pages = np.zeros((max(width // shrink, used),), np.int32)
    pages[:used] = np.arange(first, first + used)
    return pages, p_start


def _case_mla_chunk(p_start: int, width: int, interpret: bool):
    """The chunk kernel: 256 tokens behind `p_start` cached ones."""
    shrink = 16 if interpret else 1
    rng = np.random.default_rng(28 + p_start)
    kp, vp = _mla_pool(rng, MLA_POOL_PAGES // shrink)
    pages, start = _mla_chunk_pages(1, p_start, width, shrink)
    q = jnp.asarray(rng.normal(size=(CELL_CHUNK, MLA_HEADS, MLA_LANES)),
                    jnp.bfloat16)
    ref = jax.jit(lambda q, kp, vp, pg, st: _mla_chunk_twin(q, kp, pg, st))
    ker = jax.jit(lambda *a: pa.chunk_prefill_attention(
        *a, page_size=PAGE_SIZE, num_kv_heads=1, interpret=interpret))
    return ker, ref, (q, kp, vp, jnp.asarray(pages),
                      jnp.asarray(start, jnp.int32))


def _case_mla_ragged(p_start: int, width: int, interpret: bool,
                     live=None, table: int = MLA_TABLE_WIDTH):
    """The cell's mixed step through the dispatcher: 64 decode rows of
    which 6 live (or `live`, over decode tables `table` wide) plus one
    256-token chunk at `p_start`."""
    shrink = 16 if interpret else 1
    rng = np.random.default_rng(29 + p_start)
    tables, ctx, nxt = _mla_batch(shrink, live, table)
    kp, vp = _mla_pool(rng, MLA_POOL_PAGES // shrink + 32)
    pages, start = _mla_chunk_pages(nxt, p_start, width, shrink)
    q = jnp.asarray(
        rng.normal(size=(MLA_SLOTS + CELL_CHUNK, MLA_HEADS, MLA_LANES)),
        jnp.bfloat16)

    def twin(q, kp, vp, bt, cl, pg, st):
        return jnp.concatenate([
            _mla_decode_twin(q[:MLA_SLOTS], kp, bt, cl),
            _mla_chunk_twin(q[MLA_SLOTS:], kp, pg, st)], axis=0)
    args = (q, kp, vp, jnp.asarray(tables), jnp.asarray(ctx),
            jnp.asarray(pages), jnp.asarray(start, jnp.int32))
    return (*_mixed_step_sides(1, MLA_SLOTS, interpret, twin), args)


def _case_mla_prefill(s: int, interpret: bool):
    """Full prefill of a short prompt in the absorbed form: 64 heads
    against one 640-lane row (prompts up to the chunk size take it)."""
    rng = np.random.default_rng(31)
    q = jnp.asarray(rng.normal(size=(s, MLA_HEADS, MLA_LANES)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(s, 1, MLA_LANES)), jnp.bfloat16)
    sl = jnp.asarray(max(1, s - 5), jnp.int32)
    ref = jax.jit(lambda q, k, v, sl: att.prefill_attention_xla(q, k, v, sl))
    ker = jax.jit(lambda q, k, v, sl: pa.prefill_attention(
        q, k, v, sl, interpret=interpret))
    return ker, ref, (q, k, k, sl)


def _case_ssm_update(interpret: bool):
    """y and the states after one token, side by side: a live row's are
    the twin's, an empty row's y is 0 and its state what it was."""
    b, live_n = (8, 3) if interpret else (64, 27)
    h, p, g, n = SSM_STATE
    rng = np.random.default_rng(7)
    live = np.zeros((b,), bool)
    live[rng.permutation(b)[:live_n]] = True
    live = jnp.asarray(live)
    args = (jnp.asarray(rng.normal(size=(b, h, p)), jnp.bfloat16),
            jnp.asarray(rng.uniform(1e-3, 1e-1, (b, h)), jnp.float32),
            -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, g, n)), jnp.bfloat16),
            jnp.asarray(rng.normal(size=(b, g, n)), jnp.bfloat16),
            jnp.asarray(rng.normal(size=(h,)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, h, p, n)), jnp.float32))

    def side_by_side(y, new):
        return jnp.concatenate([y.reshape(b, -1), new.reshape(b, -1)], axis=1)

    def ker(x, dt, *rest):
        return side_by_side(*ssm.update_live(
            x, dt, *rest, live, ssm.live_slots(live), interpret=interpret))

    def ref(*a):
        y, new = ssm.step_every_slot(*a, live)
        return side_by_side(jnp.where(live[:, None, None], y, 0.0), new)

    return jax.jit(ker), jax.jit(ref), args


def cases(interpret: bool) -> List[Tuple[str, Callable]]:
    out: List[Tuple[str, Callable]] = []
    for label, h, n_kv in SHAPES:
        def add(name, fn, *a):
            out.append((f"{name}/{label}",
                        functools.partial(fn, *a, interpret)))
        add("decode_bf16", _case_decode, h, n_kv, False)
        add("decode_int8kv", _case_decode, h, n_kv, True)
        add("prefill_s16", _case_prefill, h, n_kv, 16)
        add("prefill_s256", _case_prefill, h, n_kv, 256)
        add("chunk_bf16", _case_chunk, h, n_kv, False)
        add("chunk_int8kv", _case_chunk, h, n_kv, True)
        add("ragged_mixed_bf16", _case_ragged, h, n_kv, False, 1)
        add("ragged_mixed_int8kv", _case_ragged, h, n_kv, True, 1)
        add("ragged_verify_q5_bf16", _case_ragged, h, n_kv, False, 5)
    for label, h, n_kv in CELL_SHAPES:
        out.append((f"decode_cell_bf16/{label}",
                    functools.partial(_case_decode_cell, h, n_kv,
                                      interpret)))
        for p_start, width in CELL_CHUNKS:
            out.append((f"ragged_cell_p{p_start}_bf16/{label}",
                        functools.partial(_case_ragged_cell, h, n_kv,
                                          p_start, width, interpret)))
    label, h, n_kv = FALCON_SHAPE
    out.append((f"ragged_cell_falcon_p{FALCON_CHUNK[0]}_bf16/{label}",
                functools.partial(_case_ragged_cell, h, n_kv, *FALCON_CHUNK,
                                  interpret, FALCON_BATCH)))
    label = f"{MLA_HEADS}q1kv{MLA_LANES}"
    out.append((f"ragged_cell_kimi_p{KIMI_CHUNK[0]}_bf16/{label}",
                functools.partial(_case_mla_ragged, *KIMI_CHUNK, interpret,
                                  KIMI_LIVE, KIMI_TABLE)))
    out.append((f"mla_decode_cell_bf16/{label}",
                functools.partial(_case_mla_decode, interpret)))
    for p_start, width in MLA_CHUNKS:
        out.append((f"mla_chunk_p{p_start}_bf16/{label}",
                    functools.partial(_case_mla_chunk, p_start, width,
                                      interpret)))
        out.append((f"mla_ragged_cell_p{p_start}_bf16/{label}",
                    functools.partial(_case_mla_ragged, p_start, width,
                                      interpret)))
    for s_len in (16, 256):
        out.append((f"mla_prefill_s{s_len}/{label}",
                    functools.partial(_case_mla_prefill, s_len, interpret)))
    h, p, _, n = SSM_STATE
    out.append((f"ssm_update_cell/{h}h{p}p{n}n",
                functools.partial(_case_ssm_update, interpret)))
    return out


def run_case(name: str, build: Callable) -> Dict[str, object]:
    row: Dict[str, object] = {"case": name}
    try:
        ker, ref, args = build()
        want = np.asarray(ref(*args).astype(jnp.float32))
    except Exception as e:  # the XLA twin itself failed: not a kernel verdict
        row.update(outcome="reference_failed", error=_short(e))
        return row
    try:
        got = np.asarray(ker(*args).astype(jnp.float32))
    except Exception as e:
        row.update(outcome="refused", error=_short(e))
        return row
    err = float(np.max(np.abs(got - want)))
    finite = bool(np.all(np.isfinite(got)))
    row.update(max_abs_err=round(err, 5), tolerance=TOLERANCE,
               outcome="compiled" if finite and err < TOLERANCE
               else "parity_error")
    return row


def _short(e: BaseException, limit: int = 600) -> str:
    text = f"{type(e).__name__}: {e}"
    return text if len(text) <= limit else text[:limit] + " …"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="dynamo_tpu.ops.kernel_parity")
    p.add_argument("--interpret", action="store_true",
                   help="CPU rehearsal through the Pallas interpreter "
                        "(proves arithmetic, not Mosaic lowering)")
    args = p.parse_args(argv)

    from dynamo_tpu.utils.platform import init_backend

    platform = init_backend()
    if args.interpret != (platform == "cpu"):
        print(f"kernel_parity: --interpret is for the CPU and only the CPU "
              f"(platform {platform!r})", file=sys.stderr)
        return 2
    rows = []
    for name, build in cases(args.interpret):
        row = run_case(name, build)
        rows.append(row)
        print(json.dumps(row), flush=True)
    dev = jax.devices()[0]
    summary = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "interpret": args.interpret,
        "cases": len(rows),
        "compiled": sum(r["outcome"] == "compiled" for r in rows),
        "not_compiled": [r["case"] for r in rows
                         if r["outcome"] != "compiled"],
    }
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        json.dump({"summary": summary, "rows": rows}, f, indent=1)
    print(json.dumps(summary), flush=True)
    return 0 if not summary["not_compiled"] else 1


if __name__ == "__main__":
    sys.exit(main())
