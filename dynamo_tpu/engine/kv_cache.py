"""Paged KV-cache: device-resident page pool + host-side page allocator.

The device arrays are `[num_layers, num_pages, page_size, lanes]`, one for K
and one for V — page-major with the KV heads fused into the trailing lane
axis, so one page is one contiguous slab the Pallas decode kernel moves with
a single DMA. K's rows have `num_kv_heads * head_dim` lanes and V's
`num_kv_heads * v_head_dim`: the same for most models, and narrower for V
where a model's values are (MiMo-V2: keys 192 lanes a head, values 128; a
head is never padded to the other's width). The fused axis is sharded over the `model` mesh axis
(dynamo_tpu.parallel.sharding.KV_SPEC): head h occupies lanes [h*D, (h+1)*D),
each tensor-parallel shard owns its local heads' lanes of every page, and the
decode loop never crosses ICI for cache reads.

An MLA model keeps ONE pool: its cache row is the shared latent
[c_kv | k_rope], which is both what queries score against and (its first
kv_lora_rank lanes) what they average, so the row lives once, in the K pool,
and the V pool is allocated with no lanes (`KVCacheSpec.v_from_k`; the
attention ops and kernels read V from the K rows they already hold). The
second array keeps the engine's (k_pages, v_pages) plumbing — donation,
transfer, tiering — one shape for every model.

An MLA model under a learned sparse selection (DeepSeek-V3.2's indexer,
`ModelConfig.index_topk`) keeps a SECOND kind of per-token row: the
indexer's key, `index_head_dim` lanes. It lives in the V pool
(`KVCacheSpec.index_lanes`), under the same page ids as the latent row, so
the allocator, prefix sharing, demotion, export / import and preemption
carry both rows of a token together. Attention still reads V from the K
rows: that is `v_from_k`, the spec's word, not the V pool's lane count.

A model whose layers are of more than one KIND (`ModelConfig.layer_types`:
full and sliding-window attention mixed) keeps a pool for EACH kind
(`models/llama.ByKind`): the full layers' pool is what `--num-pages` sizes,
addressed by the sequence's ordinary page table; a sliding layer needs only
the last `sliding_window` rows, the rows in flight and a page, whatever the
context, so its pool is sized for one RING of `KVCacheSpec.ring_pages`
pages a decode slot (`window_ring_pages`) and a sequence's second table is
that ring (`WindowRings`): logical page p lives in ring slot p % W, a page
out of every query's reach is handed back by being written over, and a
sequence never holds more than W pages a sliding layer. The kinds may differ
in KV heads (`KVCacheSpec.window_kv_heads`), so a ring's rows and a full
page's rows have lane counts of their own (MiMo-V2: 4 heads on the full
layers, 768 | 512 lanes for K | V; 8 on the sliding ones, 1,536 | 1,024).
Both pools ride the engine's (k_pages, v_pages) plumbing as one pytree each.

A HYBRID model (`ModelConfig.mixer_types`: state-space, expert and attention
layers, each layer one mixer; or every layer attention AND a state-space
mixer side by side) keeps a third thing a sequence owns beside pages and
rings: a STATE SLOT. The layers that attend own pages (the pool `--num-pages`
sizes has `num_layers` = those layers: a few of the first form's, ALL of the
second's); every layer with a Mamba-2 mixer keeps, for each decode slot, the
state S [H, P, N] float32 and the conv's last K-1 input rows
(`KVCacheSpec.ssm_shape` / `conv_shape`, `models/llama.StatePools`; one array
a layer, or where the layers run as one scan ONE array over (layer, slot):
`state_stacked`). A third form (`lfm2_moe`: every layer a gated short
convolution OR attention, then an FFN) keeps a slot WITHOUT a recurrence: the
convolution's last K-1 rows alone (`ssm_shape` empty; two rows of 2,048 lanes
a layer at the published kernel 3: 147 KB a slot over 18 layers, less than
one 16-token page of its KV). In the second form a sequence holds both in every layer, a
slot flat and pages by the token, and either store can be the one that
fills. The slot IS the decode slot: the engine
reserves it at admission before the first chunk (a chunked prompt's state
rides there between steps), decode row b updates slot b where it lies, and
the slot goes back at finish, abort and preemption. A state does not grow
with the context and is overwritten, not appended to: a prefix hit at block
b would need the state at b, which nothing keeps, so it is served as a miss;
a slot's first chunk (start 0) begins from zero whatever the slot held.

Page 0 is a reserved "trash" page: inactive batch slots point at it so the
full-batch decode step stays shape-static without masking scatter writes.

Page size defaults to 16 — parity with the reference's SGLang flag
(/root/reference/examples/deploy/sglang/agg.yaml:38-39).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models.config import FULL, SLIDING, ModelConfig


class OutOfPages(Exception):
    """KV pool exhausted — scheduler should defer admission."""


@dataclasses.dataclass
class KVCacheSpec:
    num_layers: int
    num_kv_heads: int
    num_pages: int
    page_size: int
    head_dim: int
    dtype: str = "bfloat16"  # "int8" -> packed-scale quantized rows
    # tensor-parallel blocking of int8 page rows: the row is laid out as
    # `lane_blocks` independent [values | scales | pad] blocks so a plain
    # lane split over the `model` mesh axis hands each shard its own heads'
    # values AND scales (see dynamo_tpu.ops.attention, int8 KV section)
    lane_blocks: int = 1
    # MLA: the latent row is stored once, in the K pool; the V pool has
    # no lanes and V is read from the K rows (see the module docstring)
    v_from_k: bool = False
    # lanes of the V pool of a v_from_k model: 0, or the width of the
    # sparse-attention indexer's key row (the second row kind a page holds)
    index_lanes: int = 0
    # a model whose layers are of more than one kind (module docstring):
    # num_layers / num_pages above are the FULL layers' pool; the sliding
    # layers' pool has window_layers x window_pages pages, of which a
    # sequence holds a ring of at most ring_pages. 0: one pool.
    window_layers: int = 0
    window_pages: int = 0
    ring_pages: int = 0
    # KV heads of the sliding layers' rows where they differ from the full
    # layers' (0: num_kv_heads), and the lanes a head of a V row where V is
    # narrower than K (0: head_dim)
    window_kv_heads: int = 0
    v_head_dim: int = 0
    # a hybrid model (module docstring): num_layers above counts the
    # layers that attend; each of its state_layers layers with a Mamba-2
    # mixer keeps, a decode slot, one state of ssm_shape (float32) and
    # conv_shape rows (the model's dtype). ssm_shape may be EMPTY: a gated
    # short convolution has no recurrence, and its slot holds conv_shape
    # rows alone. 0: no state. state_stacked: the states are ONE array
    # [state_layers, state_slots, ...] (the layers run as scans), not an
    # array a layer.
    state_layers: int = 0
    state_slots: int = 0
    ssm_shape: tuple = ()
    conv_shape: tuple = ()
    state_stacked: bool = False

    @staticmethod
    def from_model(
        cfg: ModelConfig, num_pages: int, page_size: int,
        kv_dtype: str = "auto", tensor_parallel: int = 1,
        window_slots: int = 0, window_ahead: int = 0,
        state_slots: int = 0,
    ) -> "KVCacheSpec":
        """`window_slots` / `window_ahead` (a model of kinds only): the
        sequences that may hold a ring at once and the tokens a step may
        write ahead of the oldest query in flight; they size the sliding
        layers' pool. `state_slots` (a hybrid model only): the decode
        slots, a state slot each."""
        if kv_dtype not in ("auto", "", "int8"):
            # only exactly "int8" takes the packed-scale quantized path;
            # any other narrow dtype would silently value-cast KV garbage
            raise ValueError(
                f"kv_cache_dtype must be 'auto' or 'int8', got {kv_dtype!r}")
        quantized = kv_dtype == "int8"
        if quantized and cfg.cache_index_dim:
            raise ValueError(
                "kv_cache_dtype=int8 with a sparse-attention indexer is not "
                "implemented: the indexer's key rows have no packed-scale "
                "layout")
        # cache geometry comes from the cache_* properties: MLA stores ONE
        # shared [c_kv | k_rope] latent row per token, classic attention
        # per-head K/V. MLA pools REPLICATE across the model axis (no lane
        # split), so their int8 rows are never TP-blocked.
        kv_heads, head_dim = cfg.cache_kv_heads, cfg.cache_head_dim
        kinds = {}
        if cfg.layer_types:
            if quantized or tensor_parallel > 1:
                raise ValueError(
                    "a model whose layers are of more than one kind "
                    "(layer_types) is served with bf16 KV on one chip a "
                    "replica: int8 rows and a lane split of the sliding "
                    "layers' rings are not implemented")
            ring = window_ring_pages(cfg.sliding_window, window_ahead,
                                     page_size)
            kinds = dict(
                window_layers=cfg.kind_layers(SLIDING), ring_pages=ring,
                # a ring a slot, and the trash page
                window_pages=window_slots * ring + 1,
                window_kv_heads=(cfg.kind_kv_heads(SLIDING)
                                 if cfg.kv_by_kind else 0),
                v_head_dim=(cfg.value_head_dim
                            if cfg.value_head_dim != head_dim else 0))
        if cfg.mixer_types:
            if quantized or tensor_parallel > 1:
                raise ValueError(
                    "a hybrid model (mixer_types) is served with bf16 KV on "
                    "one chip a replica: int8 rows, and heads and groups "
                    "of a state split over a model axis, are not "
                    "implemented")
            if state_slots <= 0:
                raise ValueError("a hybrid model needs state_slots")
            kinds = dict(  # the spec's fields of a hybrid model
                state_layers=cfg.state_layers, state_slots=state_slots,
                state_stacked=cfg.state_stacked,
                ssm_shape=(() if cfg.operator_ffn else (
                    cfg.mamba_num_heads, cfg.mamba_head_dim,
                    cfg.ssm_state_size)),
                conv_shape=(cfg.conv_kernel - 1,
                            cfg.hidden_size if cfg.operator_ffn
                            else cfg.mamba_conv_dim))
        blocks = 1 if cfg.is_mla else tensor_parallel
        if quantized and kv_heads % blocks != 0:
            raise ValueError(
                f"kv_cache_dtype=int8 needs tensor_parallel "
                f"({tensor_parallel}) to divide the cache KV-head count "
                f"({kv_heads}) — the packed-scale rows are blocked "
                f"per TP shard")
        return KVCacheSpec(
            num_layers=(cfg.paged_layers if cfg.mixer_types
                        else cfg.kind_layers(FULL) if kinds
                        else cfg.num_layers),
            **kinds,
            num_kv_heads=kv_heads,
            num_pages=num_pages,
            page_size=page_size,
            head_dim=head_dim,
            dtype=cfg.dtype if kv_dtype in ("auto", "") else kv_dtype,
            lane_blocks=blocks if quantized else 1,
            v_from_k=cfg.is_mla,
            index_lanes=cfg.cache_index_dim,
        )

    @property
    def quantized(self) -> bool:
        return self.dtype == "int8"

    @property
    def lane_width(self) -> int:
        from dynamo_tpu.ops.attention import kv_lane_width

        return kv_lane_width(self.num_kv_heads, self.head_dim,
                             self.quantized, self.lane_blocks)

    @property
    def shape(self):
        return (
            self.num_layers,
            self.num_pages,
            self.page_size,
            self.lane_width,
        )

    @property
    def v_shape(self):
        return self.shape[:3] + (self.v_lane_width,)

    @property
    def v_lane_width(self) -> int:
        if self.v_from_k:
            return self.index_lanes
        if self.v_head_dim:  # bf16 (from_model): the heads' values alone
            return self.num_kv_heads * self.v_head_dim
        return self.lane_width

    def kind_kv_heads(self) -> dict:
        """{kind: KV heads a row of that kind's pool holds}."""
        out = {"full": self.num_kv_heads}
        if self.window_layers:
            out["window"] = self.window_kv_heads or self.num_kv_heads
        return out

    def kind_lanes(self) -> dict:
        """{kind: {"k": lanes of a K row, "v": of a V row}}: what a row
        really holds, so a padded layout would show."""
        per = self.num_kv_heads
        return {kind: {"k": self.lane_width // per * n,
                       "v": self.v_lane_width // per * n}
                for kind, n in self.kind_kv_heads().items()}

    @property
    def window_shape(self):
        """The sliding layers' K pool; None with one pool."""
        if not self.window_layers:
            return None
        return (self.window_layers, self.window_pages, self.page_size,
                self.kind_lanes()["window"]["k"])

    @property
    def window_v_shape(self):
        """The sliding layers' V pool; None with one pool."""
        if not self.window_layers:
            return None
        return self.window_shape[:3] + (self.kind_lanes()["window"]["v"],)

    def bytes_per_token(self) -> int:
        """Bytes a token of CONTEXT costs in the paged pool (with pools by
        kind: in the full layers' pool, the one a context fills)."""
        return self.bytes_per_token_by_kind()["full"]

    def bytes_per_token_by_kind(self) -> dict:
        """{kind: bytes a cached token costs on the layers of that kind}:
        a context token on the full layers, a token within a ring's reach
        on the sliding ones, each the kind's layers x its own row (K lanes
        + V lanes; the kinds' KV heads, and so their rows, may differ). One
        entry where there is one pool."""
        size = jnp.dtype(self.dtype).itemsize
        layers = {"full": self.num_layers, "window": self.window_layers}
        return {kind: layers[kind] * (w["k"] + w["v"]) * size
                for kind, w in self.kind_lanes().items()}

    def bytes_per_slot(self) -> int:
        """Bytes one state slot costs over the layers that keep one (0
        without): what a hybrid model's sequence owns beside its pages,
        whatever its length. An empty ssm_shape costs nothing."""
        if not self.state_layers:
            return 0
        ssm = int(np.prod(self.ssm_shape)) * 4 if self.ssm_shape else 0
        return self.state_layers * (
            ssm
            + int(np.prod(self.conv_shape)) * jnp.dtype(self.dtype).itemsize)

    def page_table_width(self, bucket_tokens: int,
                         chunk_tokens: int) -> int:
        """Page-table width for a chunked (or unified ragged) prefill at
        this bucket: the bucket's pages plus (chunk_pages - 1) trailing
        TRASH slots. A chunk may start at any page boundary (cached
        prefixes are page-, not chunk-, aligned), so the final padded
        chunk window can extend past the bucket — its page slice must
        land on trash page 0, never clamp back onto real (possibly
        SHARED) pages. Mixed mode sizes chunk_tokens as
        max(prefill_chunk_tokens, mixed_batch_tokens): either path may
        advance the same inflight prompt (engine._mixed_step falls back
        to _advance_chunk when the decode batch empties), and both must
        fit one program's widest window.

        The tail's arithmetic is ops/attention.chunk_table_tail, and a
        chunk program takes the tail off again by it: no query of a
        prompt that fits the bucket can see a key there, so a chunk under
        a learned sparse selection scores and sorts the bucket's tokens,
        not the table's (ops/attention.dsa_chunk_attention `key_pages`)."""
        from dynamo_tpu.ops.attention import chunk_table_tail
        ps = self.page_size
        return bucket_tokens // ps + chunk_table_tail(chunk_tokens, ps)


def window_ring_pages(window: int, ahead_tokens: int, page_size: int) -> int:
    """Pages of a sliding layer's ring: those that can hold a key in reach
    of the oldest query in flight (ceil((window - 1) / page_size): the
    query's page and the window - 1 rows before it), those the step may
    write ahead of that query (a chunk, or the decode windows in flight),
    and one: so no page in reach is written over. 512 / 256 / 16 -> 49."""
    return (-(-(window - 1) // page_size) + -(-ahead_tokens // page_size)
            + 1)


def alloc_kv_pages(spec: KVCacheSpec, sharding=None):
    """Allocate zeroed K/V page pools (optionally with a NamedSharding):
    two arrays, with pools by kind two `ByKind` pairs of arrays, and for a
    hybrid model two `StatePools` (the attending layers' pool and an array
    a Mamba-2 layer over the state slots, or ONE over (layer, slot) where
    `state_stacked`)."""
    def put(shape):
        a = jnp.zeros(shape, dtype=jnp.dtype(spec.dtype))
        return a if sharding is None else jax.device_put(a, sharding)

    if spec.state_layers:
        from dynamo_tpu.models.llama import StatePools

        def states(shape, dtype):
            # a buffer of its own a layer (each is donated apart),
            # replicated: one chip a replica (from_model refuses the rest)
            def one(lead):
                a = jnp.zeros(lead + tuple(shape), dtype)
                return a if sharding is None else jax.device_put(
                    a, jax.sharding.NamedSharding(
                        sharding.mesh, jax.sharding.PartitionSpec()))
            if spec.state_stacked:  # one array over (layer, slot)
                return (one((spec.state_layers, spec.state_slots)),)
            return tuple(one((spec.state_slots,))
                         for _ in range(spec.state_layers))

        return (StatePools(put(spec.shape),
                           states(spec.ssm_shape, jnp.float32)
                           if spec.ssm_shape else ()),
                StatePools(put(spec.v_shape),
                           states(spec.conv_shape, jnp.dtype(spec.dtype))))
    if spec.window_layers:
        from dynamo_tpu.models.llama import ByKind

        return (ByKind(put(spec.shape), put(spec.window_shape)),
                ByKind(put(spec.v_shape), put(spec.window_v_shape)))
    return put(spec.shape), put(spec.v_shape)


class WindowRings:
    """The sliding layers' pages, host side: an allocator over their pool
    and, for each sequence that holds any (keyed by request id), its ring:
    ring slot i holds logical page p with p % W == i, the newest such p.
    A ring grows a page at a time until it has W and is then complete for
    the sequence's life: every further logical page is written over the
    page W before it, which no query can reach any more (`handed_back`
    counts those). The pool is sized for a ring a decode slot, so growing
    never fails while the engine keeps its slot count."""

    def __init__(self, num_pages: int, ring_pages: int):
        self.allocator = PageAllocator(num_pages)
        self.ring_pages = ring_pages
        self._rings: "dict[str, List[int]]" = {}
        self._logical: "dict[str, int]" = {}
        self.handed_back = 0

    def grow(self, key: str, logical_pages: int) -> bool:
        """Make `key`'s ring cover logical pages [0, logical_pages). True
        if the ring changed (its table row must be uploaded again)."""
        ring = self._rings.setdefault(key, [])
        before = self._logical.get(key, 0)
        if logical_pages <= before:
            return False
        self._logical[key] = logical_pages
        self.handed_back += (max(logical_pages, self.ring_pages)
                             - max(before, self.ring_pages))
        need = min(logical_pages, self.ring_pages) - len(ring)
        if need > 0:
            ring.extend(self.allocator.alloc(need))
        return need > 0

    def row(self, key: str) -> np.ndarray:
        """[W] int32: the ring as a table row (trash past what it holds)."""
        out = np.zeros((self.ring_pages,), np.int32)
        ring = self._rings.get(key, ())
        out[:len(ring)] = ring
        return out

    def release(self, key: str) -> None:
        self.allocator.free(self._rings.pop(key, []))
        self._logical.pop(key, None)

    def pages_held(self) -> int:
        return sum(len(r) for r in self._rings.values())

    def held_by(self, key: str) -> int:
        return len(self._rings.get(key, ()))


class PageAllocator:
    """Host-side free-list allocator over the device page pool.

    Pure-Python bookkeeping (no device sync) — the analogue of vLLM's block
    manager, kept intentionally simple: pages are identical, a sequence holds
    an ordered page list, and prefix-sharing/copy-on-write can layer on top
    (ref-counted pages are supported via `ref`)."""

    def __init__(self, num_pages: int):
        # page 0 reserved as trash
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._refs = np.zeros(num_pages, dtype=np.int32)
        self._refs[0] = 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise OutOfPages(f"need {n} pages, {len(self._free)} free")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def ref(self, pages: List[int]) -> None:
        for p in pages:
            assert self._refs[p] > 0
            self._refs[p] += 1

    def free(self, pages: List[int]) -> None:
        for p in pages:
            if p == 0:
                continue
            self._refs[p] -= 1
            if self._refs[p] == 0:
                self._free.append(p)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)


class PrefixCache:
    """Automatic prefix caching over the paged KV pool (vLLM-style).

    Full prompt pages are published under a rolling block-hash chain; a new
    request reuses the longest cached prefix (ref-counted pages shared
    across sequences — cached pages are immutable: only FULL pages are
    inserted, and decode/suffix writes always target later pages) and
    prefills only the suffix via the chunked-prefill path.

    The cache holds one reference per published page; eviction (LRU) only
    touches pages nothing else references, so live sequences are never
    disturbed. The reference stack gets this from its consumed engines
    (vLLM automatic prefix caching / SGLang radix cache); here it is a
    first-class allocator feature.
    """

    def __init__(self, allocator: PageAllocator, page_size: int):
        self.allocator = allocator
        self.page_size = page_size
        # block-hash -> page id, in LRU order (oldest first)
        self._map: "dict[bytes, int]" = {}
        # block-hash -> adapter namespace. The namespace already seeds the
        # hash chain (so _map alone can't recover it); this side map exists
        # for the memory-accounting plane's per-adapter split and carries
        # no cache semantics.
        self._ns: "dict[bytes, str]" = {}
        self.hits = 0
        self.misses = 0
        self.cached_tokens_served = 0
        # KVBM tiering bridge (dynamo_tpu.kvbm.manager.KVBM), attached by
        # the engine when a host tier is configured: evict() DEMOTES
        # sole-owned victims through it and lookup() misses consult the
        # lower tiers before giving up. None = classic destroy-on-evict.
        self.kvbm = None
        # KV event sink: callable(kind, [hash bytes], tier) feeding the
        # cluster event plane (kvbm/events.py); independent of tiering so
        # routing events flow even without a host pool.
        self.event_sink = None

    def _emit(self, kind: str, hashes, tier: str) -> None:
        if self.event_sink is None or not hashes:
            return
        try:
            self.event_sink(kind, list(hashes), tier)
        except Exception:  # the event plane must never break the engine
            import logging

            logging.getLogger("dynamo_tpu.kvbm").exception(
                "kv event sink failed")

    @staticmethod
    def _chain(prev: bytes, block) -> bytes:
        import hashlib

        h = hashlib.sha256(prev)
        h.update(np.asarray(block, dtype=np.int64).tobytes())
        return h.digest()

    def _hashes(self, tokens, n_blocks: int, namespace: str = ""):
        """Rolling block-hash chain. `namespace` seeds the chain root —
        multi-LoRA serving keys cached prefixes by (adapter, tokens), so
        two adapters (or an adapter and the base model) can NEVER share a
        KV prefix: their attention projections differ, so identical tokens
        produce different pages. The namespaced hashes flow through the
        KVBM tiers and the cluster KV event plane unchanged."""
        out, h = [], (b"root" if not namespace
                      else b"root|" + namespace.encode("utf-8"))
        for i in range(n_blocks):
            h = self._chain(h, tokens[i * self.page_size:
                                       (i + 1) * self.page_size])
            out.append(h)
        return out

    def lookup(self, prompt_tokens,
               namespace: str = "") -> "tuple[list[int], int]":
        """Longest cached prefix: returns (page_ids, n_tokens). The pages
        come back ref'd for the caller (the sequence now co-owns them).
        Always leaves >= 1 token uncached so the final-token logits are
        recomputed."""
        limit = (len(prompt_tokens) - 1) // self.page_size
        pages: "list[int]" = []
        hashes = self._hashes(prompt_tokens, limit, namespace)
        i = 0
        while i < limit:
            page = self._map.get(hashes[i])
            if page is not None:
                self._map[hashes[i]] = self._map.pop(hashes[i])  # LRU bump
                pages.append(page)
                i += 1
                continue
            if self.kvbm is None:
                break
            # consult the lower tiers for the rest of the chain; onboarded
            # pages come back with one cache-owned ref (exactly like
            # insert) and are republished here, so the caller-ref below
            # covers them too. Eviction is oldest-first, so a demoted run
            # can sit IN FRONT of blocks still on device — keep walking.
            got = self.kvbm.onboard_chain(hashes[i:])
            if not got:
                break
            for h2, p2 in got:
                self._map[h2] = p2
                self._ns[h2] = namespace
                pages.append(p2)
            i += len(got)
        if pages:
            self.allocator.ref(pages)
            self.hits += 1
            self.cached_tokens_served += len(pages) * self.page_size
        else:
            self.misses += 1
        return pages, len(pages) * self.page_size

    def has_prefix(self, prompt_tokens, namespace: str = "") -> bool:
        """True when lookup() would hit — WITHOUT taking references,
        bumping LRU order, or touching hit/miss statistics (admission
        grouping peeks to route cached prompts to the chunked path)."""
        if len(prompt_tokens) <= self.page_size:
            return False
        first = self._hashes(prompt_tokens, 1, namespace)[0]
        return first in self._map

    def insert(self, prompt_tokens, pages, namespace: str = "") -> None:
        """Publish a fully-prefilled prompt's FULL pages. Each newly
        published page gains a cache-owned reference."""
        n_full = len(prompt_tokens) // self.page_size
        fresh: "list[bytes]" = []
        for h, page in zip(self._hashes(prompt_tokens, n_full, namespace),
                           pages[:n_full]):
            if h in self._map:
                continue
            self.allocator.ref([page])
            self._map[h] = page
            self._ns[h] = namespace
            fresh.append(h)
        self._emit("stored", fresh, "device")

    def evictable(self) -> int:
        """Pages reclaimable right now (cache is the sole owner)."""
        return sum(1 for p in self._map.values()
                   if self.allocator._refs[p] == 1)

    def evict(self, n: int, protect=frozenset()) -> int:
        """Free up to n sole-owned pages, oldest first. Returns # evicted.

        With a KVBM attached the victims DEMOTE into the host tier (one
        batched device gather) before their device pages are freed; the
        host-pool-full remainder falls back to the classic plain free.
        `protect` hashes are never victims — the onboard path frees room
        for an incoming prefix by rotating OTHER prefixes down a tier,
        and must not evict blocks of the chain it is restoring."""
        if n <= 0:
            return 0
        victims = []
        for h, page in self._map.items():  # insertion order == LRU
            if self.allocator._refs[page] == 1 and h not in protect:
                victims.append((h, page))
                if len(victims) >= n:
                    break
        if self.kvbm is not None:
            self.kvbm.demote(victims)  # emits demoted/removed events
        else:
            self._emit("removed", [h for h, _ in victims], "none")
        for h, page in victims:
            del self._map[h]
            self._ns.pop(h, None)
            self.allocator.free([page])
        return len(victims)

    def pages_by_namespace(self) -> "dict[str, list[int]]":
        """Device pages the cache holds, grouped by adapter namespace
        ("" = base model) — the memory plane's per-adapter split."""
        out: "dict[str, list[int]]" = {}
        for h, page in self._map.items():
            out.setdefault(self._ns.get(h, ""), []).append(page)
        return out

    def stats(self) -> dict:
        return {
            "entries": len(self._map),
            "hits": self.hits,
            "misses": self.misses,
            "cached_tokens_served": self.cached_tokens_served,
        }


class SeqState:
    """Host-side state for one in-flight sequence (one decode slot)."""

    __slots__ = (
        "request_id", "slot", "pages", "num_tokens", "output_tokens",
        "max_tokens", "temperature", "top_p", "top_k", "stop_token_ids",
        "prompt_len", "logprobs", "prompt_ids",
        "req",  # originating GenRequest (preemption rebuilds a continuation)
        "guide",  # (mode, depth, bits) JSON-guide host mirror, or None
        "adapter_slot",  # LoRA device slot (0 = base) — pins the slot
        "token_wait",  # observability/timeline.TokenWait from the first token
    )

    def __init__(
        self,
        request_id: str,
        slot: int,
        pages: List[int],
        prompt_len: int,
        max_tokens: int,
        temperature: float = 0.0,
        top_p: float = 1.0,
        top_k: int = 0,
        stop_token_ids: Optional[List[int]] = None,
        logprobs: Optional[int] = None,
    ):
        self.request_id = request_id
        self.slot = slot
        self.pages = pages
        self.prompt_len = prompt_len
        self.num_tokens = prompt_len  # tokens whose KV is in cache
        self.output_tokens: List[int] = []
        self.max_tokens = max_tokens
        self.temperature = temperature
        self.top_p = top_p
        self.top_k = top_k
        self.stop_token_ids = stop_token_ids or []
        self.logprobs = logprobs
        self.guide = None
        self.adapter_slot = 0
        self.token_wait = None
        # prompt token ids, retained for the n-gram speculative proposer
        # (engine._propose_ngram fills it at slot installation)
        self.prompt_ids: List[int] = []

    def needs_page(self, page_size: int) -> bool:
        """Will the next decoded token spill onto a new page?"""
        return self.num_tokens >= len(self.pages) * page_size
