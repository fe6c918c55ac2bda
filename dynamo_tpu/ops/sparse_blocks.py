"""Block-sparse attention over a paged GQA cache (MiniCPM4's InfLLM-v2, as
`minicpm_sala`'s `minicpm4` layers run it): past `dense_len` tokens of
context a query attends the blocks it SELECTS and nothing else.

The selection is parameter-free. With a mean pool of `2 * stride` tokens
every `stride` tokens (the page size: a pooled key is two consecutive pages)

    Kbar_g[j] = mean(k_g[stride j .. stride j + 2 stride - 1])
    p_h       = softmax_j(q_h . Kbar_g[j] / sqrt(D))      (whole kernels only)
    r_g[j]    = sum of p_h[j] over the query heads h of KV head g
    score_g[b] = max of r_g[j] over the pooled keys that overlap block b

and a query attends, for each KV head apart: the `init_blocks` leading
blocks, the `window_blocks` blocks that end with its own, and the `topk`
highest-scoring of the others (ties to the lower index).

Three pieces, each under a named scope the benchmark and the traces read:

- the POOLED KEYS ride a side array of one float32 row a (decode slot,
  logical page): the sum of the keys that page of the slot's sequence holds
  (`page_sums_*`, scope `sparse_pool_keys`). Pooled key j is (row j + row j
  + 1) / (2 stride). The array is indexed by the slot and the page's PLACE in
  the sequence, so a row's pooled keys lie side by side and the selection
  reads them with no gather (a row a physical page, gathered through the
  page table, cost 0.73 ms a layer-step at 16 slots x 3,072 pages on a v5e,
  more than the attention it steers: PERF.md section 6, PR 56). It needs no
  allocator, is written where the page's keys are written, and dies with
  the slot as the Lightning state does; it is NOT shared with a shared
  page (this model serves a prefix hit by recompute, which rewrites it). A
  decode row's entry is recomputed from the page itself after the page's
  write: a position written twice changes nothing.
- `select` (scopes `sparse_block_scores`, `sparse_block_select`): the
  membership mask [rows, KV, blocks] of every query, by comparisons against
  the top-k's last value and index (no scatter, no gather).
- the attention. A DECODE row's selection is an ordinary page table: a
  block is `block / page` whole pages, and only the last selected block, the
  query's own, is partial. `decode_attention` (scope `attn_sparse_blocks`)
  builds one table a (row, KV head) and calls `ops/attention.
  paged_attention_decode` on KV x B virtual rows, of which it keeps each
  row's own heads (no rotary: a page's place in the table carries nothing).
  A row at or under `dense_len` attends its own table as it is, the
  predicate traced. A CHUNK's queries each select their own blocks:
  `chunk_attention` (scope `attn_sparse_mask`) attends the context tile by
  tile under the membership mask, skipping a tile no query selected.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from dynamo_tpu.ops import attention as att

_HI = jax.lax.Precision.HIGHEST
# selection blocks a tile of the chunk's masked attention spans
TILE_BLOCKS = 16
# what a chunk's masked attention counts on the device (tiles of TILE_BLOCKS
# blocks): those it attended and those no query of the chunk had selected
CHUNK_STATS = ("chunk_blocks_visited", "chunk_blocks_skipped")


class Sizes(NamedTuple):
    """The selection's sizes, in tokens and blocks."""
    stride: int  # tokens between pooled keys (== the page size)
    block: int  # tokens a selection block
    topk: int
    init_blocks: int
    window_blocks: int
    dense_len: int  # contexts up to here attend densely

    @property
    def ratio(self) -> int:
        """Pooled keys a block spans."""
        return self.block // self.stride

    @property
    def picked(self) -> int:
        """Blocks a query past dense_len attends."""
        return self.init_blocks + self.window_blocks + self.topk


def sizes_of(cfg) -> Sizes:
    return Sizes(cfg.sparse_kernel_stride, cfg.sparse_block_size,
                 cfg.sparse_topk, cfg.sparse_init_blocks,
                 cfg.sparse_window_size // cfg.sparse_block_size,
                 cfg.sparse_dense_len)


def check_page_size(cfg, page_size: int) -> None:
    """A pooled key is two PAGES' sums: the stride is the page size."""
    if cfg.sparse_kernel_stride != page_size:
        raise ValueError(
            f"page_size={page_size} is not served for block-sparse "
            f"attention over pooled keys of stride "
            f"{cfg.sparse_kernel_stride}: a pooled key is the mean of two "
            "consecutive pages (set --page-size to the stride)")


# ------------------------------------------------------------ pooled keys --


def page_sums_token(sums: jax.Array, k_pages: jax.Array,
                    block_table: jax.Array, positions: jax.Array,
                    live: jax.Array, base, *, page_size: int) -> jax.Array:
    """After `write_kv_token`: the entry of the page each LIVE row wrote,
    recomputed from the page's keys up to the written position. sums [L *
    slots, J, KV*D] float32 (row `base` + b is decode row b's), k_pages [P,
    ps, KV*D], block_table [B, W], positions [B], live [B] bool (an empty
    decode slot may be a prompt's in flight: its entries are the chunks')."""
    with jax.named_scope("sparse_pool_keys"):
        b = positions.shape[0]
        at = positions // page_size
        page = jnp.take_along_axis(block_table, at[:, None], axis=1)[:, 0]
        rows = k_pages[page].astype(jnp.float32)  # [B, ps, KV*D]
        held = jnp.arange(page_size)[None, :] <= (positions % page_size)[:, None]
        row = jnp.where(live, base + jnp.arange(b), sums.shape[0])
        return sums.at[row, at].set(
            jnp.sum(jnp.where(held[..., None], rows, 0.0), axis=1),
            mode="drop")


def page_sums_prefill(sums: jax.Array, k_new: jax.Array, row, start,
                      n_valid, *, page_size: int, dtype) -> jax.Array:
    """Beside `write_kv_prefill`: the entries of the pages a chunk writes
    (from position `start`, a page boundary) in row `row` of `sums`, over
    its first `n_valid` rows (padding rows add nothing: the decode steps
    that fill the page later recompute it). k_new [S, KV, D] as the pool
    stores it (`dtype`)."""
    with jax.named_scope("sparse_pool_keys"):
        s = k_new.shape[0]
        rows = k_new.reshape(s, -1).astype(dtype).astype(jnp.float32)
        rows = jnp.where((jnp.arange(s) < n_valid)[:, None], rows, 0.0)
        return jax.lax.dynamic_update_slice(
            sums, rows.reshape(1, s // page_size, page_size, -1).sum(axis=2),
            (row, start // page_size, 0))


# -------------------------------------------------------------- selection --


def block_scores(q: jax.Array, sums: jax.Array, contexts: jax.Array,
                 sz: Sizes, n_kv: int) -> jax.Array:
    """score_g[b] of every row -> [R, KV, NB] float32, -inf where no whole
    pooled key overlaps the block. q [R, H, D]; sums [R or 1, J, KV*D], the
    sums rows of each row's pages in table order (one table for all rows
    where the leading extent is 1); contexts [R], each row's tokens in
    context including its own."""
    r, h, d = q.shape
    j = sums.shape[1]
    with jax.named_scope("sparse_block_scores"):
        qg = q.astype(jnp.float32).reshape(r, n_kv, h // n_kv, d)
        ks = sums.reshape(sums.shape[0], j, n_kv, d)
        # q . (page sum): pooled key jj is pages jj and jj + 1
        if sums.shape[0] == 1:
            # all rows' heads of a KV head as ONE row's heads, so that the
            # product has the decode rows' form (a batch of one): with the
            # rows as the product's rows the compiler relaid the whole array
            # of sums out pages-minor, 0.4 GB in and 0.4 GB out of every
            # chunk program (compiled for a described v5e)
            m = h // n_kv
            t = jnp.einsum(
                "rgmd,rjgd->rgmj",
                qg.transpose(1, 0, 2, 3).reshape(1, n_kv, r * m, d), ks,
                precision=_HI)
            t = t.reshape(n_kv, r, m, j).transpose(1, 0, 2, 3)
        else:
            t = jnp.einsum("rgmd,rjgd->rgmj", qg, ks, precision=_HI)
        s = (t[..., :-1] + t[..., 1:]) * (d ** -0.5 / (2 * sz.stride))
        # whole kernels only: stride jj + 2 stride <= n
        whole = (jnp.arange(j - 1)[None, :]
                 < (contexts // sz.stride - 1)[:, None])[:, None, None, :]
        s = jnp.where(whole, s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        rg = jnp.where(whole[:, :, 0], jnp.sum(p, axis=2), -jnp.inf)
        # block b: pooled keys ratio b - 1 .. ratio b + ratio - 1
        nb = -(-j // sz.ratio)
        rg = jnp.pad(rg, ((0, 0), (0, 0), (0, nb * sz.ratio - (j - 1))),
                     constant_values=-jnp.inf)
        own = rg.reshape(r, n_kv, nb, sz.ratio)
        before = jnp.concatenate(
            [jnp.full((r, n_kv, 1), -jnp.inf), own[:, :, :-1, -1]], axis=-1)
        return jnp.maximum(jnp.max(own, axis=-1), before)


def members(scores: jax.Array, contexts: jax.Array, sz: Sizes,
            picks: bool = False):
    """The blocks each row attends, [R, KV, NB] bool, from `block_scores`:
    up to dense_len every block up to its own; past it the forced blocks
    and the top-k of the others. `picks`: the top-k's block ids instead,
    [R, KV, topk] (of a row past dense_len: any order)."""
    with jax.named_scope("sparse_block_select"):
        nb = scores.shape[-1]
        b = jnp.arange(nb)[None, None, :]
        own = ((contexts - 1) // sz.block)[:, None, None]
        forced = (b < sz.init_blocks) | ((b > own - sz.window_blocks)
                                         & (b <= own))
        cand = (b <= own) & ~forced
        cs = jnp.where(cand, scores, -jnp.inf)
        vals, idx = jax.lax.top_k(cs, min(sz.topk, nb))
        if picks:
            return idx
        last, at = vals[..., -1:], idx[..., -1:]
        # top_k hands equal values back lower index first: what it took of
        # the last value's ties are those up to the last index
        took = cand & ((cs > last) | ((cs == last) & (b <= at)))
        dense = (contexts <= sz.dense_len)[:, None, None]
        return jnp.where(dense, b <= own, (forced & (b <= own)) | took)


def select(q, sums, contexts, sz: Sizes, n_kv: int) -> jax.Array:
    """`members` of `block_scores`."""
    return members(block_scores(q, sums, contexts, sz, n_kv), contexts, sz)


# ----------------------------------------------------------------- decode --


def decode_views(picked: jax.Array, tables: jax.Array, contexts: jax.Array,
                 sz: Sizes, page_size: int):
    """One page table and one length a (row, KV head) -> (tables [B, KV,
    Wv], lens [B], sparse [B] bool). A row past dense_len: the pages of the
    blocks it picked (`members(picks=True)`, any order), of its initial
    blocks and of its window's blocks in ascending order, the query's own
    block last and alone partial (no rotary: a page's place in the table
    carries nothing else); any other row: its own table's first Wv entries
    and its context. The pages are read off the table a BLOCK at a time."""
    b, n_kv, _ = picked.shape
    bp = sz.block // page_size  # pages a block
    w = tables.shape[1]
    nb = -(-w // bp)
    wv = max(sz.picked * bp, -(-sz.dense_len // page_size))
    own = (contexts - 1) // sz.block
    forced = jnp.concatenate([
        jnp.broadcast_to(jnp.arange(sz.init_blocks)[None], (b, sz.init_blocks)),
        own[:, None] - sz.window_blocks + 1 + jnp.arange(sz.window_blocks)],
        axis=1)
    ids = jnp.concatenate(
        [picked, jnp.broadcast_to(forced[:, None, :],
                                  (b, n_kv, forced.shape[1]))], axis=-1)
    by_block = jnp.pad(tables, ((0, 0), (0, nb * bp - w))).reshape(b, nb, bp)
    pages = jnp.take_along_axis(
        by_block[:, None], jnp.clip(ids, 0, nb - 1)[..., None], axis=2)
    pages = pages.reshape(b, n_kv, -1)
    pages = jnp.pad(pages, ((0, 0), (0, 0), (0, wv - pages.shape[-1])))
    whole = jnp.pad(tables[:, :wv], ((0, 0), (0, max(0, wv - w))))
    sparse = contexts > sz.dense_len
    lens = jnp.where(
        sparse, (sz.picked - 1) * sz.block + contexts - own * sz.block,
        contexts)
    return (jnp.where(sparse[:, None, None], pages, whole[:, None, :]),
            lens, sparse)


def decode_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     sums: jax.Array, tables: jax.Array,
                     context_lens: jax.Array, kernel_lens, sz: Sizes, *,
                     page_size: int, num_kv_heads: int) -> jax.Array:
    """One query a row, q [B, H, D], over its selected blocks (its whole
    context up to dense_len). `tables` [B, W] address the flat pools;
    `sums` [B, J, KV*D] are the rows' own page sums; `kernel_lens` as
    `paged_attention_decode`'s (0: the row holds nothing)."""
    b, h, d = q.shape
    kv = num_kv_heads
    picked = members(block_scores(q, sums, context_lens, sz, kv),
                     context_lens, sz, picks=True)
    with jax.named_scope("attn_sparse_blocks"):
        vt, lens, sparse = decode_views(picked, tables, context_lens, sz,
                                        page_size)
        live = (context_lens if kernel_lens is None else kernel_lens) > 0
        # a dense row's second .. KV-th virtual rows would repeat its
        # first: they are handed nothing and their heads read from it
        head0 = jnp.arange(kv)[None, :] == 0
        klens = jnp.where(live[:, None] & (sparse[:, None] | head0),
                          lens[:, None], 0)
        o = att.paged_attention_decode(
            jnp.broadcast_to(q[:, None], (b, kv, h, d)).reshape(b * kv, h, d),
            k_pages, v_pages, vt.reshape(b * kv, -1),
            jnp.broadcast_to(lens[:, None], (b, kv)).reshape(-1),
            page_size=page_size, num_kv_heads=kv,
            kernel_lens=klens.reshape(-1))
        o = o.reshape(b, kv, kv, h // kv, d)  # [row, table of, heads of, ..]
        own = jnp.stack([o[:, g, g] for g in range(kv)], axis=1)
        return jnp.where(sparse[:, None, None, None], own,
                         o[:, 0]).reshape(b, h, d)


# ------------------------------------------------------------------ chunk --


def masked_chunk_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, pages: jax.Array, start,
                           member: jax.Array, *, page_size: int, block: int,
                           num_kv_heads: int,
                           tile_blocks: int = TILE_BLOCKS):
    """C queries from position `start` over the sequence's pages, query c
    attending causally the rows of the blocks member[c] names -> (o [C, H,
    D], the tiles visited, the tiles skipped: no query of the chunk selected
    a block of theirs). Tile by tile with a running softmax; the tiles are
    `tile_blocks` blocks, up to the one that holds the last query."""
    c, h, d = q.shape
    kv = num_kv_heads
    tt = tile_blocks * block
    tp = tt // page_size
    tiles = -(-pages.shape[0] // tp)
    pages = jnp.pad(pages, (0, tiles * tp - pages.shape[0]))
    nb = member.shape[-1]  # of the sums' pages: those of the table's count
    member = jnp.pad(member, ((0, 0), (0, 0),
                              (0, max(0, tiles * tile_blocks - nb))))[
        ..., :tiles * tile_blocks]
    qg = (q * jnp.asarray(d ** -0.5, q.dtype)).reshape(c, kv, h // kv, d)
    qpos = start + jnp.arange(c)
    vd = att._v_head_dim(v_pages, kv, d)
    f32 = jnp.float32

    def tile(i, carry):
        m, l, acc = carry
        t = order[i]
        mem = jax.lax.dynamic_slice_in_dim(member, t * tile_blocks,
                                           tile_blocks, axis=2)
        k = jax.lax.dynamic_index_in_dim(keys, t, keepdims=False)
        v = jax.lax.dynamic_index_in_dim(values, t, keepdims=False)
        s = jnp.einsum("cgmd,tgd->gmct", qg, k, preferred_element_type=f32)
        kpos = t * tt + jnp.arange(tt)
        ok = (jnp.repeat(mem, block, axis=-1)
              & (kpos[None, :] <= qpos[:, None])[:, None, :])
        s = jnp.where(ok.transpose(1, 0, 2)[:, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        ref = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - ref[..., None])
        alpha = jnp.exp(m - ref)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "gmct,tgd->gmcd", p.astype(q.dtype), v,
            preferred_element_type=f32)
        return m_new, l, acc

    # the table's rows gathered ONCE, before the loop (25 MB a pool at
    # 49,152 tokens): a loop that read the pools themselves had both copied
    # whole, 1 GB each, in every program (compiled for a described v5e)
    keys = att._gather_kv(k_pages, pages, kv, d, q.dtype).reshape(
        tiles, tt, kv, d)
    values = att._gather_kv(v_pages, pages, kv, vd, q.dtype).reshape(
        tiles, tt, kv, vd)
    g, mq = kv, h // kv
    init = (jnp.full((g, mq, c), -jnp.inf, f32), jnp.zeros((g, mq, c), f32),
            jnp.zeros((g, mq, c, vd), f32))
    # the tiles some query selected a block of, in order, up to the one
    # that holds the last query: the loop walks those alone (no conditional
    # inside it: one around a read of the pools had them copied)
    last = jnp.minimum((start + c - 1) // tt, tiles - 1)
    wanted = (jnp.any(member.reshape(c, kv, -1, tile_blocks), axis=(0, 1, 3))
              & (jnp.arange(member.shape[-1] // tile_blocks) <= last))
    order = jnp.nonzero(wanted, size=wanted.shape[0], fill_value=0)[0]
    seen = jnp.sum(wanted, dtype=jnp.int32)
    skipped = (last + 1).astype(jnp.int32) - seen
    _, l, acc = jax.lax.fori_loop(0, seen, tile, init)
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return (o.transpose(2, 0, 1, 3).reshape(c, h, vd).astype(q.dtype),
            seen, skipped)


def chunk_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    sums: jax.Array, pages: jax.Array, start, sz: Sizes, *,
                    page_size: int, num_kv_heads: int):
    """A chunk's queries q [C, H, D] from position `start`, each over its
    own selected blocks (every block up to dense_len) -> (o, tiles visited,
    tiles skipped). `pages` [W] address the flat pools; `sums` [1, J,
    KV*D] are the sequence's own page sums."""
    contexts = start + 1 + jnp.arange(q.shape[0])
    member = select(q, sums, contexts, sz, num_kv_heads)
    with jax.named_scope("attn_sparse_mask"):
        return masked_chunk_attention(
            q, k_pages, v_pages, pages, start, member, page_size=page_size,
            block=sz.block, num_kv_heads=num_kv_heads)
