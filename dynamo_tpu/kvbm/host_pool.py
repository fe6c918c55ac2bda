"""Bounded host-RAM block pool (+ optional disk tier) for demoted KV pages.

One block = one KV page's K and V slabs ([num_layers, page_size, lane_width]
each, the exact device-page layout of engine/kv_cache.py), keyed by the
PrefixCache's rolling block-hash digest so a demoted page round-trips back
onto the device bit-exactly for any KV dtype (bf16, fp32, packed int8 rows).

The arena is PREALLOCATED at construction — the steady-state demote path
only memcpys into it, never allocates, so host-RAM footprint is a config
knob (`kvbm_host_blocks * block_nbytes`), not a traffic function. Eviction
is LRU over unpinned entries; `pin`/`unpin` protect a block while a peer
worker streams it over the transfer plane (an LRU eviction mid-serve would
hand the peer another block's bytes).
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

log = logging.getLogger("dynamo_tpu.kvbm")


class DiskBlockTier:
    """Disk tier behind the host pool: blocks LRU-evicted from host RAM
    spill here (bounded by `capacity_blocks`); host-pool misses check it
    before giving up. One file per block: K bytes then V bytes, raw
    C-order — the shape/dtype contract lives in the owning pool."""

    def __init__(self, directory: str, capacity_blocks: int = 256):
        self.dir = directory
        self.capacity = capacity_blocks
        os.makedirs(directory, exist_ok=True)
        self._lock = threading.Lock()
        self._lru: Dict[bytes, str] = {}  # guarded_by: _lock — hash -> path, insertion order = LRU
        self.stored = 0
        self.hits = 0
        self.dropped = 0

    def _path(self, block_hash: bytes) -> str:
        return os.path.join(self.dir, block_hash.hex() + ".kv")

    def put(self, block_hash: bytes, k: np.ndarray, v: np.ndarray
            ) -> List[bytes]:
        """Store one block; returns the hashes DROPPED to make room."""
        dropped: List[bytes] = []
        path = self._path(block_hash)
        with self._lock:
            if block_hash in self._lru:
                self._lru[block_hash] = self._lru.pop(block_hash)
                return dropped
            while len(self._lru) >= self.capacity:
                old, old_path = next(iter(self._lru.items()))
                del self._lru[old]
                try:
                    os.remove(old_path)
                except OSError:
                    pass
                dropped.append(old)
                self.dropped += 1
        # the slow disk write runs with the lock RELEASED so concurrent
        # get()/put() on other blocks never stall behind it; the file is
        # content-addressed, so racing writers of the same hash produce
        # identical bytes and the capacity bound is soft by at most the
        # width of the race
        try:
            with open(path, "wb") as f:
                f.write(np.ascontiguousarray(k).view(np.uint8).tobytes())
                f.write(np.ascontiguousarray(v).view(np.uint8).tobytes())
        except OSError as e:
            log.warning("disk tier write failed for %s: %s",
                        block_hash.hex()[:12], e)
            return dropped
        with self._lock:
            if block_hash not in self._lru:
                self._lru[block_hash] = path
                self.stored += 1
        return dropped

    def get(self, block_hash: bytes, shape, dtype, v_shape=None
            ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        with self._lock:
            path = self._lru.get(block_hash)
            if path is None:
                return None
            self._lru[block_hash] = self._lru.pop(block_hash)  # LRU bump
        try:
            raw = open(path, "rb").read()
        except OSError:
            with self._lock:
                self._lru.pop(block_hash, None)
            return None
        # K's bytes, then V's (its own shape where the pools differ)
        half = int(np.prod(shape)) * np.dtype(dtype).itemsize
        k = np.frombuffer(raw[:half], dtype=np.uint8).view(dtype).reshape(shape)
        v = np.frombuffer(raw[half:], dtype=np.uint8).view(dtype).reshape(
            v_shape or shape)
        self.hits += 1
        return k.copy(), v.copy()

    def contains(self, block_hash: bytes) -> bool:
        with self._lock:
            return block_hash in self._lru

    def __len__(self) -> int:
        with self._lock:
            return len(self._lru)


class HostBlockPool:
    """Preallocated host-RAM KV block arena with LRU eviction and pinning."""

    def __init__(self, capacity_blocks: int, block_shape, dtype,
                 disk: Optional[DiskBlockTier] = None, v_block_shape=None):
        if capacity_blocks <= 0:
            raise ValueError("capacity_blocks must be > 0")
        self.capacity = capacity_blocks
        self.block_shape = tuple(block_shape)
        # the V pool's block: the K block's shape unless the model's V rows
        # have their own width (MLA: none at all, or the indexer's keys)
        self.v_block_shape = tuple(v_block_shape or block_shape)
        self.dtype = np.dtype(dtype)
        # one slab a pool, allocated once: a block's K is _arena[slot],
        # its V is _arena_v[slot]
        self._arena = np.empty((capacity_blocks,) + self.block_shape,
                               self.dtype)
        self._arena_v = np.empty((capacity_blocks,) + self.v_block_shape,
                                 self.dtype)
        self._free: List[int] = list(range(capacity_blocks - 1, -1, -1))  # guarded_by: _lock
        self._entries: Dict[bytes, int] = {}  # guarded_by: _lock — hash -> slot, dict order = LRU
        self._pins: Dict[bytes, int] = {}  # guarded_by: _lock
        self._lock = threading.Lock()
        self.disk = disk
        # counters (exposed as dynamo_kvbm_* series by the serving layer)
        self.hits = 0
        self.misses = 0
        self.stored = 0
        self.evicted_lru = 0
        self.rejected_full = 0

    @property
    def block_nbytes(self) -> int:
        return (int(np.prod(self.block_shape))
                + int(np.prod(self.v_block_shape))) * self.dtype.itemsize

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    # ---------------------------------------------------------------- write --
    def put(self, block_hash: bytes, k: np.ndarray, v: np.ndarray
            ) -> Tuple[bool, List[bytes]]:
        """Store one block (copy into the arena). Returns (stored, removed):
        `removed` lists hashes dropped from EVERY tier to make room (the
        event plane publishes them as gone). A full pool whose entries are
        all pinned rejects the put — the caller falls back to a plain free."""
        removed: List[bytes] = []
        with self._lock:
            if block_hash in self._entries:
                self._entries[block_hash] = self._entries.pop(block_hash)
                return True, removed
            slot = self._alloc_slot_locked(removed)
            if slot is None:
                self.rejected_full += 1
                return False, removed
            np.copyto(self._arena[slot], k, casting="no")
            np.copyto(self._arena_v[slot], v, casting="no")
            self._entries[block_hash] = slot
            self.stored += 1
        return True, removed

    def _alloc_slot_locked(self, removed: List[bytes]) -> Optional[int]:  # holds: _lock
        if self._free:
            return self._free.pop()
        # LRU-evict the oldest unpinned entry; spill it to disk if a tier
        # is configured (then only disk's own overflow is truly removed)
        for old, slot in self._entries.items():
            if self._pins.get(old, 0) > 0:
                continue
            del self._entries[old]
            self.evicted_lru += 1
            if self.disk is not None:
                removed.extend(self.disk.put(
                    old, self._arena[slot], self._arena_v[slot]))
            else:
                removed.append(old)
            return slot
        return None  # everything pinned

    # ----------------------------------------------------------------- read --
    def get(self, block_hash: bytes, removed: Optional[List[bytes]] = None
            ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Copy a block out (host RAM first, then the disk tier — a disk hit
        re-onboards into host RAM). None on miss. `removed`, when given,
        collects hashes a disk-promotion displaced out of every tier (the
        caller owes the event plane a `removed` for them)."""
        with self._lock:
            slot = self._entries.get(block_hash)
            if slot is not None:
                self._entries[block_hash] = self._entries.pop(block_hash)
                self.hits += 1
                return self._arena[slot].copy(), self._arena_v[slot].copy()
        if self.disk is not None:
            got = self.disk.get(block_hash, self.block_shape, self.dtype,
                                self.v_block_shape)
            if got is not None:
                self.hits += 1
                _, dropped = self.put(block_hash, got[0], got[1])  # re-promote
                if removed is not None:
                    removed.extend(dropped)
                return got
        with self._lock:
            self.misses += 1
        return None

    def contains(self, block_hash: bytes) -> bool:
        with self._lock:
            if block_hash in self._entries:
                return True
        return self.disk is not None and self.disk.contains(block_hash)

    # ------------------------------------------------------------ lifecycle --
    def pin(self, block_hash: bytes) -> bool:
        with self._lock:
            if block_hash not in self._entries:
                return False
            self._pins[block_hash] = self._pins.get(block_hash, 0) + 1
            return True

    def unpin(self, block_hash: bytes) -> None:
        with self._lock:
            n = self._pins.get(block_hash, 0) - 1
            if n <= 0:
                self._pins.pop(block_hash, None)
            else:
                self._pins[block_hash] = n

    def drop(self, block_hash: bytes) -> bool:
        with self._lock:
            slot = self._entries.pop(block_hash, None)
            if slot is None:
                return False
            self._free.append(slot)
            self._pins.pop(block_hash, None)
            return True

    def stats(self) -> dict:
        with self._lock:
            out = {
                "capacity_blocks": self.capacity,
                "used_blocks": len(self._entries),
                "block_nbytes": self.block_nbytes,
                "hits": self.hits,
                "misses": self.misses,
                "stored": self.stored,
                "evicted_lru": self.evicted_lru,
                "rejected_full": self.rejected_full,
            }
        if self.disk is not None:
            out["disk"] = {
                "used_blocks": len(self.disk),
                "capacity_blocks": self.disk.capacity,
                "hits": self.disk.hits,
                "dropped": self.disk.dropped,
            }
        return out
