"""Paged attention ops: XLA reference implementations + Pallas TPU dispatch.

These define the op contract used by the engine. The public entry points
(`paged_attention_decode`, `prefill_attention`) dispatch between the XLA
reference path (CPU tests, fallback) and the Pallas TPU kernels in
`dynamo_tpu.ops.pallas_attention`. The KV layout is paged — page_size
defaults to 16 for parity with the reference's SGLang flag `--page-size 16`
(/root/reference/examples/deploy/sglang/agg.yaml:38-39).

Backend selection: the engine scopes (backend, mesh, int8 lane blocking)
around every jit call with `attention_context()`, from
`EngineConfig.attention_backend` (`--attention-backend` in {auto, xla,
pallas, pallas_interpret}; `auto` is Pallas on a TPU and XLA elsewhere):
there is no other way to choose. What an op's trace then runs, and over
which mesh, is decided once, by `_route` (the dispatch section): under
tensor parallelism the kernels run inside `shard_map` over the (`data`,
`model`) axes; attention is head-parallel, so no collectives.

Layout (page-major, fused heads — one page is one contiguous DMA-able slab):
  k_pages, v_pages: [num_pages, page_size, num_kv_heads * head_dim]
  block_table:      [batch, max_pages_per_seq] int32 (page ids; 0 is the trash page)
  context_lens:     [batch] int32 — tokens in context INCLUDING the current one
The fused trailing KV*D axis keeps every page's bytes contiguous (the Pallas
decode kernel DMAs whole pages) and makes tensor-parallel sharding a plain
lane split (head h occupies lanes [h*D, (h+1)*D)).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

# (backend, mesh, kv_lane_blocks) bound by the engine around each jit call
# (incl. tracing), so attention config is per-engine, not process-global —
# two engines with different meshes/backends in one process (e.g. colocated
# disagg roles) never reconfigure each other. kv_lane_blocks is the
# tensor-parallel blocking of int8 KV page rows (see the int8 KV section).
_ATTN_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "dynamo_tpu_attn_ctx", default=(None, None, 1)
)

_VALID_BACKENDS = ("auto", "xla", "pallas", "pallas_interpret")
_KERNEL_BACKENDS = ("pallas", "pallas_interpret")


@contextlib.contextmanager
def attention_context(backend: Optional[str], mesh: Optional[Mesh],
                      kv_lane_blocks: int = 1):
    """Scope the attention backend + mesh (+ int8 KV lane blocking) for
    calls (and traces) within."""
    if backend is not None and backend not in _VALID_BACKENDS:
        raise ValueError(f"backend {backend!r} not in {_VALID_BACKENDS}")
    token = _ATTN_CTX.set((backend, mesh, kv_lane_blocks))
    try:
        yield
    finally:
        _ATTN_CTX.reset(token)


def _resolve_backend() -> str:
    b = _ATTN_CTX.get()[0] or "auto"
    if b == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return b


def _axis_size(mesh: Optional[Mesh], axis: str) -> int:
    return 1 if mesh is None else mesh.shape.get(axis, 1)


def _seq_parallel_mesh() -> Optional[Mesh]:
    """The scoped mesh when it carries a real `seq` (context-parallel) axis."""
    mesh = _ATTN_CTX.get()[1]
    return mesh if _axis_size(mesh, "seq") > 1 else None


def _mesh_for_shard_map() -> Optional[Mesh]:
    """The scoped mesh, when any axis actually needs sharding.

    Long-context ("seq") meshes are excluded — those route through
    dynamo_tpu.ops.ring_attention before backend dispatch, and the paged
    decode specs only know the (data, model) axes.
    """
    mesh = _ATTN_CTX.get()[1]
    if _axis_size(mesh, "seq") > 1 or (
            _axis_size(mesh, "model") == 1 and _axis_size(mesh, "data") == 1):
        return None
    return mesh


def repeat_kv(x: jax.Array, n_rep: int, axis: int) -> jax.Array:
    """GQA: repeat KV heads along `axis` to match the query head count."""
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=axis)


# ---------------------------------------------------------------- int8 KV --
# Quantized KV cache: pages store int8 values with a bf16 scale per
# (token, kv-head) PACKED INTO SPARE LANES of the same page row, so the
# pool stays ONE array — engine plumbing, transfer, and donation are
# untouched; only the lane width and dtype change.
#
# The row is blocked by tensor-parallel shard (`lane_blocks` = TP degree at
# allocation time) so a plain lane split over the `model` mesh axis hands
# every shard exactly its own heads' values AND scales:
#   [ block 0 | block 1 | ... ]   with each block =
#   [ (KV/tp)*D int8 values | 2*KV/tp int8 lanes = KV/tp bf16 scales | pad ]
# padded to a 128-lane multiple per block. Halves KV HBM footprint and
# stream (the binding constraint at the reference SLA's 4k ISL). Both the
# XLA gather paths and the Pallas decode/chunk kernels read this layout —
# the kernels dequantize in-VMEM after the superblock DMA (int8 halves the
# DMA bytes; the bf16 scale is rebuilt exactly via a 16-bit shift +
# same-width bitcast, see pallas_attention._dequant_rows).


def kv_lane_width(n_kv: int, head_dim: int, quantized: bool,
                  lane_blocks: int = 1) -> int:
    """Lane (last-dim) width of one KV page row."""
    if not quantized:
        return n_kv * head_dim
    if n_kv % lane_blocks != 0:
        raise ValueError(
            f"int8 KV lane blocking needs lane_blocks ({lane_blocks}) to "
            f"divide num_kv_heads ({n_kv})")
    kv_l = n_kv // lane_blocks
    block = -(-(kv_l * head_dim + 2 * kv_l) // 128) * 128
    return lane_blocks * block


def pack_kv_rows(x: jax.Array, lane_width: int,
                 lane_blocks: int = 1) -> jax.Array:
    """[T, KV, D] values -> [T, lane_width] int8 rows (see layout above)."""
    t, kv, d = x.shape
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=2)  # [T, KV]
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.bfloat16)
    q = jnp.clip(jnp.round(x32 / scale.astype(jnp.float32)[:, :, None]),
                 -127, 127).astype(jnp.int8)
    sc8 = jax.lax.bitcast_convert_type(scale, jnp.int8)  # [T, KV, 2]
    kv_l = kv // lane_blocks
    wl = lane_width // lane_blocks
    blocks = []
    for b in range(lane_blocks):
        row = jnp.concatenate(
            [q[:, b * kv_l:(b + 1) * kv_l].reshape(t, kv_l * d),
             sc8[:, b * kv_l:(b + 1) * kv_l].reshape(t, 2 * kv_l)],
            axis=1)
        blocks.append(jnp.pad(row, ((0, 0), (0, wl - row.shape[1]))))
    return jnp.concatenate(blocks, axis=1)


def unpack_kv_rows(rows: jax.Array, n_kv: int, head_dim: int,
                   dtype, lane_blocks: int = 1) -> jax.Array:
    """[..., lane_width] int8 rows -> [..., KV, D] dequantized values."""
    lead = rows.shape[:-1]
    kv_l = n_kv // lane_blocks
    kvd_l = kv_l * head_dim
    wl = rows.shape[-1] // lane_blocks
    qs, scs = [], []
    for b in range(lane_blocks):
        blk = rows[..., b * wl:(b + 1) * wl]
        qs.append(blk[..., :kvd_l].reshape(*lead, kv_l, head_dim))
        scs.append(blk[..., kvd_l:kvd_l + 2 * kv_l].reshape(*lead, kv_l, 2))
    q = jnp.concatenate(qs, axis=-2)
    sc8 = jnp.concatenate(scs, axis=-2)
    scale = jax.lax.bitcast_convert_type(sc8, jnp.bfloat16)  # [..., KV]
    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None]).astype(dtype)


def _kv_lane_blocks() -> int:
    """The int8 page-row lane blocking scoped by the engine (1 outside)."""
    return _ATTN_CTX.get()[2]


def _pool_kv_heads(k_pages: jax.Array, head_dim: int,
                   num_kv_heads) -> int:
    """KV-head count for a pool: lane width encodes it for bf16 pools;
    int8 pools (packed scale lanes) need the caller to say."""
    if k_pages.dtype == jnp.int8:
        if num_kv_heads is None:
            raise ValueError("int8 KV pools need explicit num_kv_heads")
        return num_kv_heads
    return k_pages.shape[-1] // head_dim


def _v_head_dim(v_pages, n_kv: int, head_dim: int) -> int:
    """Lanes a head of a V row. K and V rows need not be equally wide
    (MiMo-V2: keys 192 lanes a head, values 128): a bf16 V pool's rows are
    its heads' values and nothing else, so its own width says; an int8
    row's width holds scales too and a shared row is K's: `head_dim`."""
    if shared_kv(v_pages) or v_pages.dtype == jnp.int8:
        return head_dim
    return v_pages.shape[-1] // n_kv


def _softmax(scores: jax.Array, sink=None) -> jax.Array:
    """float32 softmax over the last axis. `sink` (broadcastable against
    scores[..., :1]): a learned logit that joins the max and the
    denominator and adds nothing to the numerator, so the probabilities of
    a row sum to less than one. None traces plain softmax."""
    s32 = scores.astype(jnp.float32)
    if sink is None:
        return jax.nn.softmax(s32, axis=-1)
    with jax.named_scope("attn_sink"):
        sink = sink.astype(jnp.float32)
        m = jnp.maximum(jnp.max(s32, axis=-1, keepdims=True), sink)
        p = jnp.exp(s32 - m)
        return p / (jnp.sum(p, axis=-1, keepdims=True) + jnp.exp(sink - m))


def _gather_kv(pages_pool: jax.Array, idx: jax.Array, n_kv: int,
               head_dim: int, dtype, lane_blocks=None) -> jax.Array:
    """Gather page rows by id and return [..., ps, KV, D] values
    (dequantizing int8 pools)."""
    rows = pages_pool[idx]
    if pages_pool.dtype == jnp.int8:
        if lane_blocks is None:
            lane_blocks = _kv_lane_blocks()
        return unpack_kv_rows(rows, n_kv, head_dim, dtype,
                              lane_blocks=lane_blocks)
    return rows.reshape(*rows.shape[:-1], n_kv, head_dim)


def shared_kv(v_pages) -> bool:
    """True when V is read from the K rows: an MLA model keeps its latent
    [c_kv | k_rope] row once (engine/kv_cache.py), so its V pool has no
    lanes and nothing is written to it. The compositions here gather K
    alone; the paged kernels copy K alone and use the block they hold for
    both products (their unused V operand is the K pool again: an HBM
    reference, nothing is moved). An MLA model whose V pool holds the
    sparse-attention indexer's key rows (KVCacheSpec.index_lanes) says so
    by handing the attention ops None for V: models/llama.py decides from
    the model's config, not from the pool's lane count."""
    return v_pages is None or v_pages.shape[-1] == 0


def write_kv_token(
    k_pages: jax.Array,
    v_pages: jax.Array,
    k_new: jax.Array,  # [B, KV, D]
    v_new: jax.Array,
    block_table: jax.Array,  # [B, Pmax]
    positions: jax.Array,  # [B] position being written (0-based)
    *,
    page_size: int,
):
    """Scatter one new token's K/V per sequence into its page.

    Inactive batch slots must carry block_table rows of zeros and position 0 so
    their writes land in the reserved trash page 0.
    """
    b, kv, d = k_new.shape
    page_idx = jnp.take_along_axis(
        block_table, (positions // page_size)[:, None], axis=1
    ).squeeze(1)  # [B]
    slot_idx = positions % page_size  # [B]
    def rows(new):
        if k_pages.dtype == jnp.int8:
            return pack_kv_rows(new, k_pages.shape[-1],
                                lane_blocks=_kv_lane_blocks())
        return new.reshape(b, -1)

    # advanced indexing over (page, slot) pairs -> rows of [lane_width]
    k_pages = k_pages.at[page_idx, slot_idx, :].set(rows(k_new), mode="drop")
    if not shared_kv(v_pages):
        v_pages = v_pages.at[page_idx, slot_idx, :].set(rows(v_new),
                                                        mode="drop")
    return k_pages, v_pages


def write_kv_prefill(
    k_pages: jax.Array,
    v_pages: jax.Array,
    k_new: jax.Array,  # [S, KV, D] padded to a multiple of page_size
    v_new: jax.Array,
    pages: jax.Array,  # [S // page_size] page ids for this sequence (0 pads)
    *,
    page_size: int,
):
    """Scatter a full (padded) prompt's K/V into its pages."""
    s, kv, d = k_new.shape
    n_pages = s // page_size
    def rows(new):
        if k_pages.dtype == jnp.int8:
            w = k_pages.shape[-1]
            return pack_kv_rows(new, w, lane_blocks=_kv_lane_blocks()
                                ).reshape(n_pages, page_size, w)
        return new.reshape(n_pages, page_size, -1)

    k_pages = k_pages.at[pages].set(rows(k_new), mode="drop")
    if not shared_kv(v_pages):
        v_pages = v_pages.at[pages].set(rows(v_new), mode="drop")
    return k_pages, v_pages


def _softcap(scores: jax.Array, logit_cap: float) -> jax.Array:
    """Gemma-2-style score capping: cap * tanh(x / cap). Applied BEFORE
    masking (tanh of the mask's -inf would be nan)."""
    if logit_cap and logit_cap > 0.0:
        return logit_cap * jnp.tanh(scores / logit_cap)
    return scores


def paged_attention_decode_xla(
    q: jax.Array,  # [B, H, D] — one query token per sequence
    k_pages: jax.Array,  # [P, ps, KV*D] (or int8 packed rows)
    v_pages: jax.Array,
    block_table: jax.Array,  # [B, Pmax]
    context_lens: jax.Array,  # [B]
    *,
    page_size: int,
    num_kv_heads=None,
    lane_blocks=None,
    window=None,  # traced scalar: attend only the last `window` positions
    logit_cap: float = 0.0,
    sink=None,  # [H] float32: a learned logit a head in the softmax
) -> jax.Array:
    """Reference paged decode attention (gather + masked softmax).

    XLA fuses the gather with the QK matmul reasonably well on TPU; the Pallas
    kernel avoids materialising the gathered KV in HBM entirely.
    """
    bsz, n_heads, head_dim = q.shape
    n_kv = _pool_kv_heads(k_pages, head_dim, num_kv_heads)
    pmax = block_table.shape[1]
    # gather pages: [B, Pmax, ps, KV, D] -> [B, KV, S, D]
    k = _gather_kv(k_pages, block_table, n_kv, head_dim, q.dtype,
                   lane_blocks).reshape(
        bsz, pmax * page_size, n_kv, head_dim
    ).transpose(0, 2, 1, 3)
    vd = _v_head_dim(v_pages, n_kv, head_dim)
    v = k if shared_kv(v_pages) else _gather_kv(
        v_pages, block_table, n_kv, vd, q.dtype, lane_blocks).reshape(
        bsz, pmax * page_size, n_kv, vd
    ).transpose(0, 2, 1, 3)
    k = repeat_kv(k, n_heads // n_kv, axis=1)
    v = repeat_kv(v, n_heads // n_kv, axis=1)
    scale = 1.0 / jnp.sqrt(head_dim).astype(q.dtype)
    scores = jnp.einsum("bhd,bhsd->bhs", q * scale, k)
    scores = _softcap(scores, logit_cap)
    span = jnp.arange(pmax * page_size)[None, None, :]
    mask = span < context_lens[:, None, None]
    if window is not None:
        # sliding window (gemma-2 local layers): a GLOBAL layer passes
        # window=0 through the same traced value — no lower bound then
        lower = jnp.where(window > 0, context_lens - window, 0)
        mask &= span >= lower[:, None, None]
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = _softmax(
        scores, None if sink is None else sink[None, :, None]).astype(q.dtype)
    return jnp.einsum("bhs,bhsd->bhd", probs, v)


def prefill_attention_xla(
    q: jax.Array,  # [S, H, D]
    k: jax.Array,  # [S, KV, D]
    v: jax.Array,
    seq_len,  # int or scalar array: true (unpadded) length
    *,
    window=None,
    logit_cap: float = 0.0,
    sink=None,  # [H] float32: a learned logit a head in the softmax
) -> jax.Array:
    """Causal self-attention over a single padded prompt."""
    s, n_heads, head_dim = q.shape
    n_kv = k.shape[1]
    k = repeat_kv(k, n_heads // n_kv, axis=1)
    v = repeat_kv(v, n_heads // n_kv, axis=1)
    scale = 1.0 / jnp.sqrt(head_dim).astype(q.dtype)
    scores = jnp.einsum("qhd,khd->hqk", q * scale, k)
    scores = _softcap(scores, logit_cap)
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    mask = (ki <= qi) & (ki < seq_len)
    if window is not None:
        mask &= jnp.where(window > 0, ki > qi - window, True)
    scores = jnp.where(mask[None], scores, jnp.finfo(scores.dtype).min)
    probs = _softmax(
        scores, None if sink is None else sink[:, None, None]).astype(q.dtype)
    return jnp.einsum("hqk,khd->qhd", probs, v)


def chunk_table_tail(chunk_tokens: int, page_size: int) -> int:
    """Trailing TRASH slots of a chunked prompt's page table, past its
    bucket's pages: the padded window of a prompt's last chunk may reach
    (chunk pages - 1) pages past the bucket, and its page slice must land
    on the trash page (engine/kv_cache.KVCacheSpec.page_table_width adds
    them; a program that is handed such a table takes them off again by
    its own chunk length: models/llama.prefill_chunk, mixed_step)."""
    return max(chunk_tokens, page_size) // page_size - 1


def chunk_attention(
    q: jax.Array,  # [C, H, D] — one prefill chunk's queries
    k_pages: jax.Array,  # [P, ps, KV*D]
    v_pages: jax.Array,
    pages: jax.Array,  # [Pbucket] page ids of THIS sequence (0-padded tail)
    start,  # scalar int32: absolute position of q[0]
    *,
    page_size: int,
    num_kv_heads=None,
    window=None,
    logit_cap: float = 0.0,
    sink=None,  # [H] float32: a learned logit a head in the softmax
) -> jax.Array:
    """Chunked-prefill attention: C chunk queries over the sequence's cached
    pages (prefix + the chunk itself, already written) with a causal mask in
    absolute positions.

    One gather of the sequence's pages serves ALL chunk rows (unlike the
    decode op, whose per-row tables would duplicate the prefix C times).

    Two implementations:
    - XLA: the gather feeds a masked-softmax attention; simple, correct
      everywhere, but materializes [H, C, S] scores per layer.
    - Pallas flash (bf16 pools): the decode kernel's superblock DMA ring
      with a query BLOCK per grid row — no score materialization, each KV
      byte fetched once per query block. The int8-KV dequant-in-chunk path
      has never run on the chip: it stays behind
      CHUNK_KERNEL_INT8_HW_VALIDATED, a counted demotion.
    """
    from dynamo_tpu.ops import pallas_attention as pa

    route = _route(
        "chunk attention", q.shape[1], q.shape[2],
        _pool_kv_heads(k_pages, q.shape[2], num_kv_heads), k_pages,
        window=window, logit_cap=logit_cap,
        int8_validated=pa.CHUNK_KERNEL_INT8_HW_VALIDATED,
        static_window_is_ragged=True)
    if route is None or (route.kernel and sink is not None):
        # a static window: the ragged kernel with no decode row is the
        # chunk kernel that masks below it (one windowed chunk kernel,
        # not two); and the one that carries a sink
        return ragged_mixed_attention(
            q, k_pages, v_pages, jnp.zeros((0, pages.shape[0]), jnp.int32),
            jnp.zeros((0,), jnp.int32), pages, start, page_size=page_size,
            num_kv_heads=num_kv_heads, num_decode=0, window=window,
            sink=sink)
    if not route.kernel:
        return chunk_attention_xla(
            q, k_pages, v_pages, pages, start, page_size=page_size,
            num_kv_heads=num_kv_heads, window=window, logit_cap=logit_cap,
            sink=sink)

    def call(q, kp, vp, pg, st):
        return pa.chunk_prefill_attention(
            q, kp, vp, pg, st, page_size=page_size,
            num_kv_heads=route.kv_heads, interpret=route.interpret)

    return _sharded(
        route, call,
        (q, k_pages, v_pages, pages, jnp.asarray(start, jnp.int32)),
        (_HEADS, _POOL, _POOL, P(None), P()))


def chunk_attention_xla(
    q: jax.Array,  # [C, H, D]
    k_pages: jax.Array,
    v_pages: jax.Array,
    pages: jax.Array,  # [Pbucket] page ids of THIS sequence (0-padded tail)
    start,  # scalar int32: absolute position of q[0]
    *,
    page_size: int,
    num_kv_heads=None,
    window=None,
    logit_cap: float = 0.0,
    sink=None,  # [H] float32: a learned logit a head in the softmax
) -> jax.Array:
    """Reference chunk attention (gather + masked softmax): the CPU/tier-1
    fallback for chunk_attention, and one leg of the ragged mixed step's XLA
    composition. GSPMD places the gather/einsums under a mesh."""
    c, n_heads, head_dim = q.shape
    n_kv = _pool_kv_heads(k_pages, head_dim, num_kv_heads)
    s_ctx = pages.shape[0] * page_size
    k = _gather_kv(k_pages, pages, n_kv, head_dim, q.dtype).reshape(
        s_ctx, n_kv, head_dim)
    vd = _v_head_dim(v_pages, n_kv, head_dim)
    v = k if shared_kv(v_pages) else _gather_kv(
        v_pages, pages, n_kv, vd, q.dtype).reshape(s_ctx, n_kv, vd)
    k = repeat_kv(k, n_heads // n_kv, axis=1)
    v = repeat_kv(v, n_heads // n_kv, axis=1)
    scale = 1.0 / jnp.sqrt(head_dim).astype(q.dtype)
    scores = jnp.einsum("chd,shd->hcs", q * scale, k)
    scores = _softcap(scores, logit_cap)
    qpos = start + jnp.arange(c)[None, :, None]
    kpos = jnp.arange(s_ctx)[None, None, :]
    mask = kpos <= qpos
    if window is not None:
        mask &= jnp.where(window > 0, kpos > qpos - window, True)
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = _softmax(
        scores, None if sink is None else sink[:, None, None]).astype(q.dtype)
    return jnp.einsum("hcs,shd->chd", probs, v)


def ragged_mixed_attention(
    q: jax.Array,  # [B + C, H, D] — B decode rows first, then one C-chunk
    k_pages: jax.Array,  # [P, ps, KV*D] (or int8 packed rows)
    v_pages: jax.Array,
    block_tables: jax.Array,  # [B, Pmax] decode page tables
    context_lens: jax.Array,  # [B] horizons incl. the token written this step
    p_pages: jax.Array,  # [Wp] the chunk's page ids (trash-padded tail)
    p_start,  # scalar int32: absolute position of the chunk's first token
    *,
    page_size: int,
    num_kv_heads=None,
    num_decode: int,
    window=None,
    logit_cap: float = 0.0,
    kernel_lens=None,  # [B] context_lens with 0 for a slot that holds nothing
    sink=None,  # [H] float32: a learned logit a head in the softmax
) -> jax.Array:
    """Mixed ragged-batch attention: B decode rows AND one prefill chunk in
    a single program (the RPA unification — see ops/ragged_attention.py).

    Decode rows attend their paged context through their block tables; the
    chunk's rows attend causally over its own page list. Inactive decode
    slots must carry context_lens >= 1 and zero tables (the engine's
    existing inactive-slot contract). `kernel_lens` is what the Pallas
    kernel is handed in place of `context_lens`, as in
    `paged_attention_decode`: it does nothing for a row at context 0 (no
    page copy, zeros out); the XLA composition keeps `context_lens`.

    On a kernel backend (`auto` on a TPU) the Pallas kernel runs, whose
    work follows the live KV; else the XLA composition — decode gather +
    chunk gather over the whole table.
    """
    n_kv = _pool_kv_heads(k_pages, q.shape[2], num_kv_heads)
    route = _route("ragged attention", q.shape[1], q.shape[2], n_kv,
                   k_pages, window=window, logit_cap=logit_cap)
    b = num_decode
    if route.kernel:
        # a decode row's one query sits at the end of its context
        return _ragged_kernel(
            route, q, k_pages, v_pages,
            _ragged_tables(
                block_tables,
                context_lens if kernel_lens is None else kernel_lens,
                p_pages, p_start, q.shape[0] - b,
                lens_of=lambda cl: cl,
                starts_of=lambda cl: jnp.maximum(cl - 1, 0)),
            sink=sink, page_size=page_size, num_decode=b, window=window or 0)
    # XLA composition: the decode gather and chunk gather reference paths,
    # concatenated — token-identical to the separate-program paths by
    # construction, which is what the mixed-step parity tests pin.
    if not b:  # a windowed chunk alone (chunk_attention)
        return chunk_attention_xla(
            q, k_pages, v_pages, p_pages, p_start, page_size=page_size,
            num_kv_heads=n_kv, window=window, logit_cap=logit_cap, sink=sink)
    dec = paged_attention_decode_xla(
        q[:b], k_pages, v_pages, block_tables, context_lens,
        page_size=page_size, num_kv_heads=n_kv,
        window=window, logit_cap=logit_cap, sink=sink)
    chk = chunk_attention_xla(
        q[b:], k_pages, v_pages, p_pages, p_start, page_size=page_size,
        num_kv_heads=n_kv, window=window, logit_cap=logit_cap, sink=sink)
    return jnp.concatenate([dec, chk], axis=0)


def ragged_verify_attention(
    q: jax.Array,  # [B*K1 + C, H, D] — B verify windows, then one C-chunk
    k_pages: jax.Array,  # [P, ps, KV*D] (or int8 packed rows)
    v_pages: jax.Array,
    block_tables: jax.Array,  # [B, Pmax] per-window page tables
    positions: jax.Array,  # [B] absolute position of each window's q[0]
    p_pages: jax.Array,  # [Wp] the chunk's page ids (trash-padded tail)
    p_start,  # scalar int32: absolute position of the chunk's first token
    *,
    page_size: int,
    num_kv_heads=None,
    num_verify: int,
    verify_width: int,
    window=None,
    logit_cap: float = 0.0,
) -> jax.Array:
    """Speculative verify windows as ragged rows: B windows of K1 = 1 + K
    query tokens each AND one prefill chunk in a single program — the spec-
    decode extension of ragged_mixed_attention. Window b's query j sits at
    absolute position `positions[b] + j` and attends causally over the
    window's pages (drafts' K/V already written, like verify_attention).

    Routed as ragged_mixed_attention: the Pallas kernel (each window = one
    padded query block, via decode_q=K1) or the XLA composition — verify
    gather + chunk gather. Inactive windows carry zero tables + position 0
    (trash-page rows, outputs discarded by the engine)."""
    n_kv = _pool_kv_heads(k_pages, q.shape[2], num_kv_heads)
    route = _route("ragged attention", q.shape[1], q.shape[2], n_kv,
                   k_pages, window=window, logit_cap=logit_cap)
    b, k1 = num_verify, verify_width
    if route.kernel:
        # window rows span [pos, pos + K1) so the horizon includes every
        # draft written this step
        return _ragged_kernel(
            route, q, k_pages, v_pages,
            _ragged_tables(
                block_tables, positions, p_pages, p_start,
                q.shape[0] - b * k1,
                lens_of=lambda ps: ps + k1, starts_of=lambda ps: ps),
            page_size=page_size, num_decode=b, decode_q=k1)
    # XLA composition: the verify gather and chunk gather reference paths,
    # concatenated — token-identical to the separate-program paths by
    # construction (what the mixed-spec parity tests pin).
    ver = verify_attention(
        q[:b * k1].reshape(b, k1, q.shape[1], q.shape[2]),
        k_pages, v_pages, block_tables, positions,
        page_size=page_size, num_kv_heads=n_kv,
        window=window, logit_cap=logit_cap)
    chk = chunk_attention_xla(
        q[b * k1:], k_pages, v_pages, p_pages, p_start, page_size=page_size,
        num_kv_heads=n_kv, window=window, logit_cap=logit_cap)
    return jnp.concatenate(
        [ver.reshape(b * k1, q.shape[1], q.shape[2]), chk], axis=0)


def verify_attention(
    q: jax.Array,  # [B, K1, H, D] — current token + K draft tokens per seq
    k_pages: jax.Array,  # [P, ps, KV*D]
    v_pages: jax.Array,
    block_table: jax.Array,  # [B, Pmax]
    positions: jax.Array,  # [B] absolute position of q[:, 0]
    *,
    page_size: int,
    num_kv_heads=None,
    window=None,
    logit_cap: float = 0.0,
) -> jax.Array:
    """Speculative-verification attention: query j of sequence b sits at
    absolute position `positions[b] + j` and attends causally over the
    sequence's cached pages (which already contain the draft tokens' K/V —
    the verify forward writes before attending, like prefill_chunk).

    The batched analogue of chunk_attention's XLA gather path: one page
    gather serves all K1 queries of a sequence. K1 is small (typically <=
    8), so the [B, H, K1, S] score tensor stays modest; spec decode targets
    low-batch latency where bandwidth, not score memory, is the limit.
    Inactive slots carry zero block tables + position 0: their queries
    attend only the trash page and are discarded by the engine.
    """
    b, k1, n_heads, head_dim = q.shape
    n_kv = _pool_kv_heads(k_pages, head_dim, num_kv_heads)
    w = block_table.shape[1]
    s_ctx = w * page_size
    k = _gather_kv(k_pages, block_table, n_kv, head_dim, q.dtype).reshape(
        b, s_ctx, n_kv, head_dim)
    v = k if shared_kv(v_pages) else _gather_kv(
        v_pages, block_table, n_kv, head_dim, q.dtype).reshape(
        b, s_ctx, n_kv, head_dim)
    k = repeat_kv(k, n_heads // n_kv, axis=2)
    v = repeat_kv(v, n_heads // n_kv, axis=2)
    scale = 1.0 / jnp.sqrt(head_dim).astype(q.dtype)
    scores = jnp.einsum("bqhd,bshd->bhqs", q * scale, k)
    scores = _softcap(scores, logit_cap)
    qpos = positions[:, None, None, None] + jnp.arange(k1)[None, None, :, None]
    spos = jnp.arange(s_ctx)[None, None, None, :]
    mask = spos <= qpos
    if window is not None:
        mask &= jnp.where(window > 0, spos > qpos - window, True)
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", probs, v)


# ------------------------------------------- learned sparse selection --
# DeepSeek-V3.2's sparse attention over an MLA cache (models/llama.py
# `_dsa_index` builds the operands): a lightning indexer scores every cached
# token of a query's sequence against the query,
#     I[t, s] = sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s]),
# the `topk` best-scoring tokens s <= t are selected, EXACTLY (in
# jax.lax.top_k's order: a total order on the scores, ties to the lower
# position), and the query's absorbed-form attention runs over those rows
# alone, gathered token by token from the pool. The sort that selects
# carries each position's PHYSICAL row with it (`_dsa_row_words`), so the
# gather needs no page-table lookup (PR 35: that lookup, a scalar gather,
# cost 7% of the docqa cell's device time). The indexer's keys live in the
# V pool an MLA model leaves empty (`idx_pages`), under the same page ids as
# the latent rows (`k_pages`).
# Plain XLA compositions: a kernel of their own is ROADMAP's.
# A program whose page table cannot address more than `topk` tokens never
# comes here (every token would be selected): the callers in models/llama.py
# decide that from shapes and keep today's kernels there.

# tests set this to a callable(kind, qpos, sel, valid): the selection of
# every traced call is handed to it (jax.debug.callback, in program order),
# `sel` as POSITIONS. None outside tests: no program carries a callback.
DSA_TAP = None

# bit 30 of the sort's second key: set where the score is -0.0
_NEG_ZERO = 1 << 30


def _dsa_scores(q_idx: jax.Array, w_idx: jax.Array, keys: jax.Array,
                spec: str) -> jax.Array:
    """Index scores, float32. q_idx [.., Hi, Di], w_idx [.., Hi] float32,
    keys [.., S, Di]; `spec` is the einsum of the q . k products with the
    index heads kept as axis -2 of the result."""
    dots = jnp.einsum(spec, q_idx, keys,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * w_idx[..., None], axis=-2)


def _dsa_row_words(pages: jax.Array, page_off, page_size: int,
                   layer_pages: int):
    """What the selecting sort carries for every position of a page table:
    pages [.., Wp] (flat ids inside the layer's slice of the pool, which
    starts at page `page_off` and holds `layer_pages`) -> (words, unpack).
    `words` are int32 [.., S]: dense elementwise work, a broadcast of the
    table over a page's slots and an iota, no gather. The first is the
    sort's second key and rises with the position, so equal scores keep
    top_k's order; `unpack(*words)` gives (positions, physical rows of the
    pool viewed as [P * page_size, D]) of whatever words the sort kept.

    One word where position and page id fit 30 bits together,
    [position | page - page_off] (the served cell: 15 + 13), so the sort
    has two operands as top_k's had; else two, [position] and [row]. The
    TPU compiler gives a STABLE sort whose payload is not an iota a third
    operand of its own (that iota), which is why stability is not used."""
    s = pages.shape[-1] * page_size
    pos = jnp.arange(s, dtype=jnp.int32)
    page = jnp.broadcast_to(
        pages[..., None], pages.shape + (page_size,)).reshape(
            pages.shape[:-1] + (s,))
    pbits = (layer_pages - 1).bit_length()
    if (s - 1).bit_length() + pbits <= 30:
        def unpack(word):
            sel = (word & (_NEG_ZERO - 1)) >> pbits
            page = (word & ((1 << pbits) - 1)) + page_off
            return sel, page * page_size + sel % page_size
        return ((pos << pbits) | (page - page_off),), unpack

    def unpack(word, rows):
        return word & (_NEG_ZERO - 1), rows
    return (jnp.broadcast_to(pos, page.shape),
            page * page_size + pos % page_size), unpack


def _dsa_select(scores: jax.Array, words, unpack, topk: int, kind: str,
                qpos):
    """scores [N, S] float32, -inf where a key may not be seen; `words`,
    `unpack` of `_dsa_row_words` ([S] or [N, S]) -> (rows [N, K] physical
    rows, valid [N, K]), K = min(topk, S). Exact, and jax.lax.top_k's to
    the row and its order: the K largest, ties to the lower position; a
    query with fewer than K visible keys gets them all and the rest (the
    first masked positions) flagged invalid. One sort of (-score, word):
    top_k orders +0.0 before -0.0 where jax.lax.sort holds them equal, so
    a -0.0 score raises a bit above the word's position."""
    k = min(topk, scores.shape[-1])
    with jax.named_scope("dsa_select"):
        neg_zero = (scores == 0) & jnp.signbit(scores)
        tie = words[0] | jnp.where(neg_zero, _NEG_ZERO, 0)
        rest = tuple(jnp.broadcast_to(w, scores.shape) for w in words[1:])
        key, *kept = jax.lax.sort((-scores, tie) + rest, dimension=1,
                                  is_stable=False, num_keys=2)
        valid = key[:, :k] < jnp.inf
        sel, rows = unpack(*(w[:, :k] for w in kept))
    if DSA_TAP is not None:
        jax.debug.callback(functools.partial(DSA_TAP, kind), qpos, sel,
                           valid, ordered=True)
    return rows, valid


def _dsa_attend(q: jax.Array, rows: jax.Array, valid: jax.Array
                ) -> jax.Array:
    """q [N, H, D] over each query's own gathered rows [N, K, D] (K and V
    both: the latent row) -> [N, H, D]. The generic ops' 1/sqrt(D) scale."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    sc = jnp.einsum("nhd,nkd->nhk", q * scale, rows,
                    preferred_element_type=jnp.float32)
    sc = jnp.where(valid[:, None, :], sc, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
    return jnp.einsum("nhk,nkd->nhd", probs, rows)


def _gather_rows(k_pages: jax.Array, rows: jax.Array) -> jax.Array:
    """Token-granular gather: physical rows [N, K] of the pool viewed as
    [P * page_size, D] -> [N, K, D]."""
    return k_pages.reshape((-1, k_pages.shape[-1]))[rows]


def dsa_decode_attention(
    q: jax.Array,  # [B, H, D] absorbed queries
    q_idx: jax.Array,  # [B, Hi, Di] indexer queries
    w_idx: jax.Array,  # [B, Hi] float32 head weights
    k_pages: jax.Array,  # [P, ps, D] latent rows
    idx_pages: jax.Array,  # [P, ps, Di] indexer keys
    block_table: jax.Array,  # [B, Pmax]
    context_lens: jax.Array,  # [B]
    *,
    page_size: int,
    topk: int,
    page_off=0,  # the table's ids lie in [page_off, page_off + layer_pages)
    layer_pages=None,  # default: the whole pool
) -> jax.Array:
    """One decode token a sequence: score the sequence's cached index keys,
    select, attend over the selected latent rows. An empty slot (context 1
    on the trash page) selects that one row, as the dense twin does."""
    b, pmax = block_table.shape
    s = pmax * page_size
    with jax.named_scope("dsa_indexer"):
        keys = idx_pages[block_table].reshape(b, s, idx_pages.shape[-1])
        scores = _dsa_scores(q_idx, w_idx, keys, "bhd,bsd->bhs")
        scores = jnp.where(jnp.arange(s)[None, :] < context_lens[:, None],
                           scores, -jnp.inf)
    rows, valid = _dsa_select(
        scores, *_dsa_row_words(block_table, page_off, page_size,
                                layer_pages or k_pages.shape[0]),
        topk, "decode", context_lens - 1)
    with jax.named_scope("dsa_sparse_attn"):
        return _dsa_attend(q, _gather_rows(k_pages, rows), valid)


def dsa_chunk_attention(
    q: jax.Array,  # [C, H, D] one sequence's consecutive queries
    q_idx: jax.Array,  # [C, Hi, Di]
    w_idx: jax.Array,  # [C, Hi] float32
    k_pages: jax.Array,
    idx_pages: jax.Array,
    pages: jax.Array,  # [Wp] page ids of the sequence (trash-padded tail)
    start,  # scalar int32: absolute position of q[0]
    *,
    page_size: int,
    topk: int,
    block_q: int = 32,
    page_off=0,  # as dsa_decode_attention's
    layer_pages=None,
    key_pages=None,  # leading entries of `pages` a key may lie in: all
) -> jax.Array:
    """A chunk's queries, each with its own selection among the positions
    at or before its own (the chunk's rows are already written). A block
    of `block_q` queries at a time: the float32 index products of a whole
    256-token chunk against 32k keys would be 2 GB.

    The selection (index keys gathered, scores, mask, the sort's words and
    so the sort) is built over the first `key_pages` entries of `pages`
    alone: the prompt bucket's pages, where the table carries a trash tail
    behind them (`chunk_table_tail`; the cell's 2,063-entry table: 32,768
    positions scored and sorted, not 33,008, which the TPU sorts as
    65,536). Exact for every REAL query: its position is under the
    prompt's length, the prompt fits its bucket, and the mask lets it see
    positions <= its own, so the tail held nothing it could see and what
    it selects, in which order, is what the whole table gave. A padded
    query past the prompt's end may select other rows than it did (past
    the bucket it sees every key of the extent and none behind it); its
    own rows lie on the trash page and its output is discarded."""
    c = q.shape[0]
    if key_pages is not None:
        pages = pages[:key_pages]
    s = pages.shape[0] * page_size
    block_q = max(1, min(block_q, c))
    while c % block_q:
        block_q //= 2
    with jax.named_scope("dsa_indexer"):
        keys = idx_pages[pages].reshape(s, idx_pages.shape[-1])
    qpos = jnp.asarray(start, jnp.int32) + jnp.arange(c, dtype=jnp.int32)
    # one sequence, one table: its words are built once, outside the map
    words, unpack = _dsa_row_words(pages, page_off, page_size,
                                   layer_pages or k_pages.shape[0])

    def block(args):
        qb, qib, wb, pos = args
        with jax.named_scope("dsa_indexer"):
            scores = _dsa_scores(qib, wb, keys, "qhd,sd->qhs")
            scores = jnp.where(jnp.arange(s)[None, :] <= pos[:, None],
                               scores, -jnp.inf)
        rows, valid = _dsa_select(scores, words, unpack, topk, "chunk", pos)
        with jax.named_scope("dsa_sparse_attn"):
            return _dsa_attend(qb, _gather_rows(k_pages, rows), valid)

    def blocks(x):
        return x.reshape((c // block_q, block_q) + x.shape[1:])

    out = jax.lax.map(block, (blocks(q), blocks(q_idx), blocks(w_idx),
                              blocks(qpos)))
    return out.reshape(q.shape)


# --------------------------------------------------------------- dispatch --


# Pallas -> XLA demotion visibility: every demotion gets ONE log line per
# (op, reason) plus a process-wide counter that
# observability/engine_metrics.py exports as dynamo_pallas_fallback_total.
# Gates run at TRACE time, so counts are per compiled shape, not per step —
# a nonzero count means some program is permanently off the kernel path.
_FALLBACK_COUNTS: dict = {}
_FALLBACK_LOGGED: set = set()


def _note_fallback(op: str, reason: str, detail: str = "") -> None:
    key = (op, reason)
    _FALLBACK_COUNTS[key] = _FALLBACK_COUNTS.get(key, 0) + 1
    if key not in _FALLBACK_LOGGED:
        _FALLBACK_LOGGED.add(key)
        import logging

        logging.getLogger("dynamo_tpu.ops").warning(
            "pallas %s demoted to the XLA path [%s]%s — counted in "
            "dynamo_pallas_fallback_total, logged once", op, reason,
            f": {detail}" if detail else "")


def _demote(backend: str, op: str, reason: str, detail: str = "") -> str:
    """Send `op` to the XLA path. When it had resolved to a kernel
    (`auto` on a TPU, or an explicit pallas*), that is a demotion and is
    counted; when it was XLA already, nothing happened."""
    if backend in _KERNEL_BACKENDS:
        _note_fallback(op, reason, detail)
    return "xla"


# Which implementation each op was TRACED with: {(op, impl): traces}, impl
# in {pallas, pallas_interpret, xla}. Like the fallback counts this is
# per compiled program, not per step. /worker/stats carries it so a smoke
# or a benchmark row can say what actually ran.
_IMPL_COUNTS: dict = {}


def _note_impl(op: str, impl: str) -> None:
    _IMPL_COUNTS[(op, impl)] = _IMPL_COUNTS.get((op, impl), 0) + 1


def attention_impl_counts() -> dict:
    return dict(_IMPL_COUNTS)


def pallas_fallback_counts() -> dict:
    """{(op, reason): trace-time demotion count}; exported by
    observability/engine_metrics.attach_engine_metrics."""
    return dict(_FALLBACK_COUNTS)


class _Route(NamedTuple):
    """Where one trace of an attention op goes (`_route` decides)."""
    backend: str  # "xla" | "pallas" | "pallas_interpret"
    mesh: Optional[Mesh]  # shard_map over it; None: call directly
    kv_heads: int  # KV heads one shard's call sees
    lane_blocks: int  # int8 lane blocks in one shard's page rows

    @property
    def kernel(self) -> bool:
        return self.backend in _KERNEL_BACKENDS

    @property
    def interpret(self) -> bool:
        return self.backend == "pallas_interpret"


def _route(op: str, n_heads: int, head_dim: int, n_kv: int, pool=None, *,
           window=None, logit_cap: float = 0.0, int8_validated: bool = True,
           static_window_is_ragged: bool = False,
           unsplit_keeps_backend: bool = False,
           shards_xla: bool = False) -> Optional[_Route]:
    """THE decision of which implementation one trace of `op` runs and over
    which mesh: every gate between the scoped backend and a kernel, in one
    order for all five ops, each demotion counted under the op's name and
    the outcome noted. `pool` is the K pool of a paged op, None for the
    whole-prompt prefill (whose flash kernel takes no window at all and has
    a head-dim gate where the paged kernels have a lane gate).

    Where the ops answer one gate differently, an argument says so:
    - `unsplit_keeps_backend` (decode, prefill): a mesh that cannot split
      the op (heads, or an int8 pool's lane blocks) is dropped and the
      backend kept: the op is traced whole and GSPMD places it. Default
      (chunk, ragged): such a mesh sends the op to the XLA composition.
    - `shards_xla` (decode): the XLA twin runs under the same shard_map as
      the kernel, so the mesh's gates are evaluated, and counted, on the
      XLA backend too, and a shape gate that sends the op to XLA keeps it.
    - `int8_validated` (chunk): False sends an int8 pool to XLA.
    - `static_window_is_ragged` (chunk): a kernel backend under a STATIC
      window returns None before the mesh and shape gates: the caller hands
      the chunk to the ragged op, whose own route runs them."""
    backend = _resolve_backend()
    paged = pool is not None
    quantized = paged and pool.dtype == jnp.int8
    if quantized and not int8_validated:
        backend = _demote(backend, op, "int8_not_validated",
                          "int8 dequant-in-chunk awaits its on-chip "
                          "parity verdict")
    # A window the paged kernels cannot take is a traced per-layer scalar
    # (the Gemma / Mistral / Phi-3 path: window 0 = a global layer through
    # the same value). A plain int is STATIC: the layer's kind is known
    # where the program is traced (ModelConfig.layer_types), the kernels
    # mask below it and visit no KV block wholly out of reach.
    windowed = bool(logit_cap) or (window is not None and not (
        paged and isinstance(window, int)))
    if windowed:
        backend = _demote(backend, op, "window_softcap",
                          "the kernel models neither sliding windows nor "
                          "score capping")
    if _seq_parallel_mesh() is not None:
        # long-context (seq) mesh: the pool is GSPMD-sharded on `model`,
        # and an unannotated pallas_call would force an all-gather of the
        # whole pool per step — the XLA gather path partitions cleanly
        backend = _demote(backend, op, "seq_mesh",
                          "sequence-parallel mesh shards the pool under "
                          "GSPMD")
    if not paged and head_dim % 128 != 0 and head_dim not in (32, 64, 192):
        # e.g. MLA's latent width (kv_lora_rank + rope = 576): no Mosaic
        # tiling for off-size trailing dims (192 over values of 128 lanes
        # compiles for a v5e and is held to the XLA twin on the chip)
        backend = _demote(backend, op, "head_dim",
                          f"no Mosaic tiling for head dim {head_dim}")
    if (static_window_is_ragged and backend in _KERNEL_BACKENDS
            and isinstance(window, int) and window):
        return None
    lb = _kv_lane_blocks() if quantized else 1
    mesh, tp, fits = None, 1, True
    if backend in _KERNEL_BACKENDS or shards_xla:
        # a traced per-layer `window` scalar can't be closed over by an
        # explicit shard_map body — GSPMD places the windowed op
        mesh = None if windowed else _mesh_for_shard_map()
        tp = _axis_size(mesh, "model")
        fits = n_kv % tp == 0 and n_heads % tp == 0
        if not fits:
            _note_fallback(op, "head_gate",
                           f"tp={tp} does not divide query heads "
                           f"({n_heads}) / KV heads ({n_kv})")
        if unsplit_keeps_backend and not (
                fits and (not quantized or lb % tp == 0)):
            # the lane split must hand each shard whole heads and whole
            # int8 layout blocks (weights replicated by sharding._fit_spec)
            mesh, tp, fits = None, 1, True
    if backend in _KERNEL_BACKENDS:
        # the TPU DMA constraint all paged kernels share: a 128-aligned
        # per-shard lane span (tp=8 over 8 KV heads of dim 64 is below a
        # lane tile). For int8 pools the VALUES span (the kernels slice
        # rows[:, :kvd] in-VMEM): the padded packed width always aligns.
        span = pool.shape[2] if paged and not quantized else n_kv * head_dim
        if fits and paged and (span // tp) % 128 != 0:
            _note_fallback(op, "lane_gate",
                           "per-shard KV*D lane dim not 128-aligned "
                           f"(KV*D={span}, tp={tp})")
            fits = False
        if quantized and lb != tp and (fits or not unsplit_keeps_backend):
            # the kernels read SINGLE-block rows: the shard_map split count
            # must equal the layout blocking (each shard then sees its own
            # [values | scales | pad] block). Engine-built configs always
            # match; mismatches (e.g. the head gate dropped the mesh) fall
            # back.
            _note_fallback(op, "int8_lane_blocks",
                           f"mesh TP ({tp}) != pool lane blocking ({lb})")
            fits = False
        if not fits:
            backend = "xla"
            if not shards_xla:
                mesh, tp = None, 1
    _note_impl(op, backend)
    return _Route(backend, mesh, n_kv // tp, lb // tp if quantized else 1)


# Heads (the fused KV*D lane axis of a pool row) shard on `model`: attention
# is embarrassingly parallel over them — no collectives inside.
_HEADS = P(None, "model", None)  # [rows, H, D]
_POOL = P(None, None, "model")  # [P, ps, KV*D]


def _sharded(route: _Route, call, args, in_specs, out_specs=_HEADS,
             sink=None):
    """`call(*args)` directly, or under shard_map over the route's mesh.
    `sink` [H], where given, rides as a last argument split by heads."""
    if sink is not None:
        args, in_specs = (*args, sink), (*in_specs, P("model"))
    if route.mesh is None:
        return call(*args)
    return jax.shard_map(call, mesh=route.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


def _ragged_tables(block_tables, rows, p_pages, p_start, chunk_len: int,
                   *, lens_of, starts_of):
    """The ragged kernel's unified descriptor set, (tabs, kv_lens,
    q_starts): one page-table row per decode slot (or verify window) plus
    a final row for the chunk, all zero-(trash-)padded to a common width.
    `rows` [B] is what a slot's horizon `lens_of(rows)` and first query
    position `starts_of(rows)` are read from."""
    b, pmax = block_tables.shape
    wp = p_pages.shape[0]
    tabs = jnp.zeros((b + 1, max(pmax, wp)), jnp.int32)
    tabs = tabs.at[:b, :pmax].set(block_tables.astype(jnp.int32))
    tabs = tabs.at[b, :wp].set(p_pages.astype(jnp.int32))
    rows = rows.astype(jnp.int32)
    st = jnp.asarray(p_start, jnp.int32)
    kv_lens = jnp.concatenate([lens_of(rows), (st + chunk_len).reshape(1)])
    q_starts = jnp.concatenate([starts_of(rows), st.reshape(1)])
    return tabs, kv_lens, q_starts


def _ragged_kernel(route: _Route, q, k_pages, v_pages, tables, *,
                   sink=None, **kw):
    from dynamo_tpu.ops import ragged_attention as ra

    def call(q, kp, vp, tb, kl, qs, *sk):
        return ra.ragged_paged_attention(
            q, kp, vp, tb, kl, qs, num_kv_heads=route.kv_heads,
            interpret=route.interpret, sink=sk[0] if sk else None, **kw)

    return _sharded(route, call, (q, k_pages, v_pages, *tables),
                    (_HEADS, _POOL, _POOL, P(None, None), P(None), P(None)),
                    sink=sink)


def paged_attention_decode(
    q: jax.Array,  # [B, H, D]
    k_pages: jax.Array,  # [P, ps, KV*D]
    v_pages: jax.Array,
    block_table: jax.Array,  # [B, Pmax]
    context_lens: jax.Array,  # [B]
    *,
    page_size: int,
    num_kv_heads=None,
    window=None,
    logit_cap: float = 0.0,
    kernel_lens=None,  # [B] context_lens with 0 for a slot that holds nothing
    sink=None,  # [H] float32: a learned logit a head in the softmax
) -> jax.Array:
    """`kernel_lens` is what the Pallas kernel is handed in place of
    `context_lens`: it does nothing for a slot at context 0 (no page copy,
    zeros out). The XLA twin keeps `context_lens`, where the engine pins an
    empty slot at context 1 so that no row is masked whole."""
    route = _route(
        "decode", q.shape[1], q.shape[2],
        _pool_kv_heads(k_pages, q.shape[2], num_kv_heads), k_pages,
        window=window, logit_cap=logit_cap, unsplit_keeps_backend=True,
        shards_xla=True)
    if route.kernel:
        from dynamo_tpu.ops import pallas_attention as pa

        def call(q, kp, vp, bt, cl, *sk):
            return pa.paged_attention_decode(
                q, kp, vp, bt, cl, page_size=page_size,
                num_kv_heads=route.kv_heads, interpret=route.interpret,
                window=window or 0, sink=sk[0] if sk else None)

        if kernel_lens is not None:
            context_lens = kernel_lens
    else:
        def call(q, kp, vp, bt, cl, *sk):
            return paged_attention_decode_xla(
                q, kp, vp, bt, cl, page_size=page_size,
                num_kv_heads=route.kv_heads, lane_blocks=route.lane_blocks,
                window=window, logit_cap=logit_cap,
                sink=sk[0] if sk else None)

    # the batch shards on `data` besides
    return _sharded(
        route, call, (q, k_pages, v_pages, block_table, context_lens),
        (P("data", "model", None), _POOL, _POOL, P("data", None),
         P("data")), P("data", "model", None), sink=sink)


def prefill_attention(
    q: jax.Array,  # [S, H, D]
    k: jax.Array,  # [S, KV, D]
    v: jax.Array,
    seq_len,  # int or scalar array: true (unpadded) length
    *,
    window=None,
    logit_cap: float = 0.0,
    sink=None,  # [H] float32: a learned logit a head in the softmax
) -> jax.Array:
    sp_mesh = _seq_parallel_mesh()
    if sp_mesh is not None:
        if window is not None or logit_cap or sink is not None:
            # the ring/Ulysses paths don't model windows/caps; the Engine
            # rejects --sp for sliding-window models before we ever get here
            raise ValueError(
                "sequence-parallel prefill does not support sliding-window/"
                "softcap models")
        return _seq_parallel_prefill(q, k, v, seq_len, sp_mesh)
    route = _route("prefill", q.shape[1], q.shape[2], k.shape[1],
                   window=window, logit_cap=logit_cap,
                   unsplit_keeps_backend=True)
    if not route.kernel:
        return prefill_attention_xla(q, k, v, seq_len, window=window,
                                     logit_cap=logit_cap,
                                     sink=sink)
    from dynamo_tpu.ops import pallas_attention as pa

    def call(q, k, v, sl, *sk):
        return pa.prefill_attention(q, k, v, sl, interpret=route.interpret,
                                    sink=sk[0] if sk else None)

    # Prefill is single-sequence: replicated over `data`, heads on `model`.
    return _sharded(route, call, (q, k, v, jnp.asarray(seq_len, jnp.int32)),
                    (_HEADS, _HEADS, _HEADS, P()), sink=sink)


def _seq_parallel_prefill(q, k, v, seq_len, sp_mesh: Mesh) -> jax.Array:
    """Long-context path: sequence sharded over the `seq` axis (the
    reference has no analogue — SURVEY.md §5). Strategy via
    DYNAMO_TPU_SP_STRATEGY: `ring` (default; ppermute neighbour hops, one
    ICI step per hop) or `ulysses` (all_to_all head/sequence exchange —
    fewer collectives, favors meshes with all-to-all bandwidth). Neither is
    a paged kernel's route: jnp collectives, counted as the flash kernel's
    `seq_mesh` demotion. The engine pads prompts to page_size multiples,
    not sp multiples, so pad here to the divisibility requirement and slice
    back (the tail past seq_len is masked inside either way)."""
    from dynamo_tpu.ops import ring_attention as ra

    strategy = os.environ.get("DYNAMO_TPU_SP_STRATEGY", "ring")
    if strategy not in ("ring", "ulysses"):
        raise ValueError(
            f"DYNAMO_TPU_SP_STRATEGY {strategy!r} not in "
            f"('ring', 'ulysses')")
    sp = _axis_size(sp_mesh, "seq")
    if strategy == "ulysses":
        # Ulysses' all_to_all splits the LOCAL head axis across `seq`:
        # per-model-shard query heads must divide by sp, else the
        # ring (which has no head requirement) serves the prompt
        local_h = q.shape[1] // _axis_size(sp_mesh, "model")
        if local_h % sp != 0:
            import logging

            logging.getLogger("dynamo_tpu.ops").warning(
                "ulysses needs local query heads (%d) divisible by "
                "the seq axis (%d); using ring attention", local_h, sp)
            strategy = "ring"
    fn = (ra.ulysses_prefill_attention if strategy == "ulysses"
          else ra.ring_prefill_attention)
    s = q.shape[0]
    pad = (-s) % sp
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
    _demote(_resolve_backend(), "prefill", "seq_mesh",
            "sequence-parallel prefill runs ring/Ulysses attention")
    _note_impl("prefill", strategy)
    out = fn(q, k, v, seq_len, sp_mesh)
    return out[:s] if pad else out
