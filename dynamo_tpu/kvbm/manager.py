"""KVBM manager: the engine-side bridge between the device prefix cache and
the lower tiers (host RAM, disk, peer workers).

Wiring: `Engine` constructs a KVBM when `kvbm_host_blocks > 0` and attaches
it to its `PrefixCache`. From then on:

- `PrefixCache.evict` DEMOTES sole-owned victim pages through `demote()`
  (one batched device gather -> arena memcpy) instead of destroying them;
  pages the pool can't take fall back to a plain free.
- `PrefixCache.lookup` misses consult `onboard_chain()`: consecutive
  blocks found in the host tier (or a peer's, via the transfer plane) are
  restored with one padded scatter (`jax.device_put` + the engine's jitted
  page import), gated by the roofline restore-vs-recompute check.

Every device call here runs under the engine's `_exec_lock` — demote and
onboard only fire from `evict()`/`lookup()`, whose callers (admission,
page growth, KV import) all hold it.

Threading note: the `events` sink (kvbm/events.py) and the metrics
counters are touched from the scheduler thread; the host pool itself is
lock-protected because peer-serving threads read it concurrently.
"""

from __future__ import annotations

import logging
import threading
from typing import Callable, List, Optional, Tuple

import numpy as np

from dynamo_tpu.kvbm.cost_model import OnboardGate
from dynamo_tpu.kvbm.host_pool import DiskBlockTier, HostBlockPool

log = logging.getLogger("dynamo_tpu.kvbm")


def _pad_pow2(n: int) -> int:
    """Pad batched page gathers/scatters to a power of two so the eager
    gather and the jitted import compile O(log) distinct shapes, not one
    per prefix length."""
    b = 1
    while b < n:
        b *= 2
    return b


class KVBM:
    """Tiered KV block manager for one engine."""

    def __init__(self, engine, cfg=None):
        cfg = cfg or engine.cfg
        self.engine = engine
        spec = engine.kv_spec
        import jax.numpy as jnp

        self.block_shape = (spec.num_layers, spec.page_size, spec.lane_width)
        # the V pool's rows have their own width for an MLA model: none, or
        # the sparse-attention indexer's keys, which follow their pages
        self.v_block_shape = self.block_shape[:2] + (spec.v_lane_width,)
        self._np_dtype = np.dtype(jnp.dtype(spec.dtype))
        disk = None
        if getattr(cfg, "kvbm_disk_dir", None):
            disk = DiskBlockTier(cfg.kvbm_disk_dir,
                                 capacity_blocks=cfg.kvbm_disk_blocks)
        self.pool = HostBlockPool(cfg.kvbm_host_blocks, self.block_shape,
                                  self._np_dtype, disk=disk,
                                  v_block_shape=self.v_block_shape)
        self.gate = OnboardGate(
            mode=getattr(cfg, "kvbm_gate", "auto"),
            model_cfg=engine.model_cfg,
            block_nbytes=self.pool.block_nbytes,
            page_size=cfg.page_size,
            prefill_chunk_tokens=cfg.prefill_chunk_tokens or cfg.page_size,
        )
        # cluster plane hooks (set by the serving layer):
        # events(kind, [hash bytes], tier) -> None; kinds: stored | demoted
        # | removed. peer_fetch([hash bytes]) -> [(k, v)] consecutive-from-
        # the-start host-layout blocks pulled from a peer's host tier.
        self.events: Optional[Callable[[str, List[bytes], str], None]] = None
        self.peer_fetch: Optional[
            Callable[[List[bytes]], List[Tuple[np.ndarray, np.ndarray]]]
        ] = None
        self.tracer = None  # set by ServingContext; spans kvbm.offload/onboard
        # integrity sentinel (DYNAMO_TPU_INTEGRITY=full; docs/robustness.md
        # "Engine watchdog & quarantine"): CRC32 per demoted block, verified
        # at onboard — a mismatch (host-RAM/disk bit flip) drops the block
        # to a cache miss (recompute) instead of importing silent corruption
        # into the device pool. Peer-fetched blocks carry no local CRC and
        # skip verification.
        from dynamo_tpu.robustness.watchdog import integrity_mode

        self._checksum = integrity_mode() == "full"
        self._crc: dict = {}  # block hash -> crc32 at demote time
        self._lock = threading.Lock()  # counters only
        # counters behind the dynamo_kvbm_* metric series
        self.host_hits_total = 0        # lookups served >= 1 block from tiers
        self.host_hit_blocks_total = 0
        self.host_misses_total = 0      # lookup tails the tiers couldn't serve
        self.demoted_blocks_total = 0
        self.onboarded_blocks_total = 0
        self.peer_onboarded_blocks_total = 0
        self.removed_blocks_total = 0
        self.gate_recompute_total = 0   # onboards the cost gate refused

    # ------------------------------------------------------------- helpers --
    def _emit(self, kind: str, hashes: List[bytes], tier: str) -> None:
        if self.events is None or not hashes:
            return
        try:
            self.events(kind, list(hashes), tier)
        except Exception:  # the event plane must never break serving
            log.exception("kvbm event sink failed")

    def _span(self, name: str, **attrs):
        if self.tracer is None:
            from dynamo_tpu.observability import tracing as obs_tracing

            return obs_tracing.NOOP_SPAN
        return self.tracer.start_span(name, attributes=attrs)

    def _flight(self, event: str, **fields):
        """Tier moves land in the engine's flight ring: the KVBM runs on
        the engine thread (evict/onboard inside admission), so the note
        attaches to the very step record whose admission caused the move."""
        flight = getattr(self.engine, "flight", None)
        if flight is not None:
            flight.note(event, **fields)

    # -------------------------------------------------------------- demote --
    def demote(self, victims: List[Tuple[bytes, int]]) -> int:
        """Spill evicted sole-owned pages into the host tier. One padded
        device gather covers the whole victim batch; pages the pool cannot
        take (full-of-pinned, arena rejected) are reported `removed` and
        the caller frees them as before. Returns blocks demoted."""
        if not victims:
            return 0
        span = self._span("kvbm.offload", blocks=len(victims))
        try:
            import jax.numpy as jnp

            eng = self.engine
            pages = [p for _, p in victims]
            width = _pad_pow2(len(pages))
            idx = np.zeros((width,), np.int32)  # pad rows gather trash page 0
            idx[:len(pages)] = pages
            k = np.asarray(jnp.take(eng.k_pages, jnp.asarray(idx), axis=1))
            v = np.asarray(jnp.take(eng.v_pages, jnp.asarray(idx), axis=1))
            demoted, removed, dropped = [], [], []
            for i, (h, _) in enumerate(victims):
                ok, lru_removed = self.pool.put(h, k[:, i], v[:, i])
                dropped.extend(lru_removed)
                (demoted if ok else removed).append(h)
                if self._checksum and ok:
                    import zlib

                    self._crc[h] = zlib.crc32(
                        v[:, i].tobytes(),
                        zlib.crc32(k[:, i].tobytes()))
            if self._checksum:
                for h in removed + dropped:
                    self._crc.pop(h, None)
            with self._lock:
                self.demoted_blocks_total += len(demoted)
                self.removed_blocks_total += len(removed) + len(dropped)
            self._emit("demoted", demoted, "host")
            self._emit("removed", removed + dropped, "none")
            span.set_attributes({"demoted": len(demoted),
                                 "removed": len(removed) + len(dropped)})
            self._flight("kvbm_demote", blocks=len(demoted),
                         removed=len(removed) + len(dropped))
            return len(demoted)
        except Exception:
            log.exception("kvbm demote failed; pages freed undemoted")
            span.set_status("ERROR", "demote failed")
            return 0
        finally:
            span.end()

    def demote_all(self, prefix_cache) -> int:
        """Graceful-drain handoff: spill EVERY sole-owned prefix page into
        the host tier (prefix_cache.evict routes victims through demote()
        above, which publishes `demoted` events). Surviving workers keep
        routing on those blocks via the KV event index and onboard them
        over the cross-worker host-tier fetch — the departing worker's
        warm prefixes outlive the pod. Caller holds the engine exec lock.
        Returns pages demoted/evicted."""
        return prefix_cache.evict(prefix_cache.evictable())

    def _verify(self, h: bytes, k: np.ndarray, v: np.ndarray) -> bool:
        """Onboard-time CRC check (integrity=full). A mismatch means the
        block rotted in host RAM or on disk since demote: drop it from
        every tier (a cache miss — the prefix recomputes, correctly),
        count the fault on the watchdog, and never abort anything — the
        corruption was caught BEFORE it touched the device pool."""
        import zlib

        want = self._crc.get(h)
        if want is None:
            return True  # peer-fetched or pre-sentinel block: no claim
        got = zlib.crc32(v.tobytes(), zlib.crc32(k.tobytes()))
        if got == want:
            return True
        self._crc.pop(h, None)
        self.pool.drop(h)
        with self._lock:
            self.removed_blocks_total += 1
        self._emit("removed", [h], "none")
        self._flight("integrity_fault", sentinel="kv_checksum",
                     block=h.hex()[:16])
        wd = getattr(self.engine, "watchdog", None)
        if wd is not None:
            wd.record_integrity_fault("kv_checksum", [],
                                      block=h.hex()[:16])
        log.warning("kvbm checksum mismatch on block %s; dropped "
                    "(recompute)", h.hex()[:16])
        return False

    # ------------------------------------------------------------- onboard --
    def onboard_chain(self, hashes: List[bytes]) -> List[Tuple[bytes, int]]:
        """Restore the longest consecutive run of `hashes` available in the
        lower tiers back into the device pool. Returns [(hash, page_id)]
        with each new page holding ONE allocator ref (cache-owned, exactly
        like a freshly inserted prefix page); the caller republishes them
        in its hash map. Gated by the restore-vs-recompute check."""
        if not hashes:
            return []
        disk_drops: List[bytes] = []
        blocks: List[Tuple[bytes, np.ndarray, np.ndarray]] = []
        for h in hashes:
            got = self.pool.get(h, removed=disk_drops)
            if got is None:
                break
            if self._checksum and not self._verify(h, got[0], got[1]):
                break  # the chain must stay consecutive: stop before it
            blocks.append((h, got[0], got[1]))
        source = "host"
        if not blocks and self.peer_fetch is not None:
            blocks = self._fetch_from_peer(hashes)
            source = "peer"
        if disk_drops:
            for h in disk_drops:
                self._crc.pop(h, None)
            with self._lock:
                self.removed_blocks_total += len(disk_drops)
            self._emit("removed", disk_drops, "none")
        if not blocks:
            with self._lock:
                self.host_misses_total += 1
            return []
        eng = self.engine
        # cost gate FIRST — a refused onboard must not have demoted other
        # prefixes to make room for nothing
        if not self.gate.should_onboard(len(blocks)):
            with self._lock:
                self.gate_recompute_total += self.gate.skipped
                self.gate.skipped = 0
                self.host_misses_total += 1
            self._flight("kvbm_gate_recompute", blocks=len(blocks),
                         source=source)
            return []
        # make device room by rotating OTHER sole-owned cache entries down
        # a tier (they demote, not die — the incoming prefix is the hot
        # one); the chain's own hashes are protected from eviction, and
        # whatever room can't be made truncates the onboard
        free = eng.allocator.free_pages
        if len(blocks) > free and eng.prefix_cache is not None:
            eng.prefix_cache.evict(len(blocks) - free,
                                   protect=frozenset(hashes))
            free = eng.allocator.free_pages
        if len(blocks) > free:
            blocks = blocks[:free]
        if not blocks:
            with self._lock:
                self.host_misses_total += 1
            return []
        span = self._span("kvbm.onboard", blocks=len(blocks), source=source)
        try:
            import jax.numpy as jnp

            pages = eng.allocator.alloc(len(blocks))
            width = _pad_pow2(len(blocks))
            idx = np.zeros((width,), np.int32)  # pad rows scatter onto trash
            idx[:len(pages)] = pages
            k_new = np.zeros((self.block_shape[0], width) + self.block_shape[1:],
                             self._np_dtype)
            v_new = np.zeros((self.v_block_shape[0], width)
                             + self.v_block_shape[1:], self._np_dtype)
            for i, (_, kb, vb) in enumerate(blocks):
                k_new[:, i] = kb
                v_new[:, i] = vb
            eng.k_pages, eng.v_pages = eng._import(
                eng.k_pages, eng.v_pages, jnp.asarray(idx),
                jnp.asarray(k_new), jnp.asarray(v_new),
            )
            out = [(h, p) for (h, _, _), p in zip(blocks, pages)]
            with self._lock:
                self.host_hits_total += 1
                self.host_hit_blocks_total += len(out)
                self.onboarded_blocks_total += len(out)
                if source == "peer":
                    self.peer_onboarded_blocks_total += len(out)
            self._emit("stored", [h for h, _ in out], "device")
            span.set_attribute("onboarded", len(out))
            self._flight("kvbm_onboard", blocks=len(out), source=source)
            return out
        except Exception:
            log.exception("kvbm onboard failed; falling back to recompute")
            span.set_status("ERROR", "onboard failed")
            return []
        finally:
            span.end()

    def _fetch_from_peer(self, hashes: List[bytes]
                         ) -> List[Tuple[bytes, np.ndarray, np.ndarray]]:
        """Cross-worker onboard: pull the prefix blocks from a peer's host
        tier over the transfer plane instead of re-prefilling. Fetch
        failures mean recompute, never a request failure."""
        try:
            got = self.peer_fetch(hashes)
        except Exception as e:
            log.warning("kvbm peer fetch failed (%s); recomputing", e)
            return []
        out = []
        for h, (kb, vb) in zip(hashes, got):
            if (kb.shape != self.block_shape or kb.dtype != self._np_dtype
                    or vb.shape != self.v_block_shape):
                log.warning("kvbm peer block layout mismatch "
                            "(%s/%s vs %s/%s); recomputing",
                            kb.shape, kb.dtype, self.block_shape,
                            self._np_dtype)
                return []
            out.append((h, kb, vb))
        return out

    # --------------------------------------------------------------- stats --
    def notify_stored(self, hashes: List[bytes]) -> None:
        """PrefixCache.insert hook: freshly published device blocks."""
        self._emit("stored", hashes, "device")

    def stats(self) -> dict:
        with self._lock:
            out = {
                "host_hits_total": self.host_hits_total,
                "host_hit_blocks_total": self.host_hit_blocks_total,
                "host_misses_total": self.host_misses_total,
                "demoted_blocks_total": self.demoted_blocks_total,
                "onboarded_blocks_total": self.onboarded_blocks_total,
                "peer_onboarded_blocks_total": self.peer_onboarded_blocks_total,
                "removed_blocks_total": self.removed_blocks_total,
                "gate_recompute_total": self.gate_recompute_total,
            }
        out["host_pool"] = self.pool.stats()
        out["gate"] = self.gate.explain(1)
        return out
