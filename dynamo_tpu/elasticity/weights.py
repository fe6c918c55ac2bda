"""Hot weight swap: double-buffered sharded params with a version pointer.

The engine's jitted programs take ``params`` as an explicit per-call
operand, so serving a new weight version needs no recompile as long as the
incoming tree matches the live one leaf-for-leaf (same keys, shapes,
dtypes — a revision or requantize-in-kind, not an architecture change).
That makes a hitless rollout three well-separated phases:

  stage     load v2 host-side through the normal checkpoint path
            (models/loader.py), check HBM headroom against the memory
            plane, then ``device_put`` section-by-section onto the live
            leaves' exact shardings while v1 keeps serving.
  flip      swap the version pointer under ``engine._exec_lock`` — the
            lock serialises every device computation, so no step ever
            mixes versions. In ``finish`` mode a busy engine arms the
            flip instead: admissions hold, in-flight v1 streams run to
            completion, and the scheduler applies the swap at the first
            step boundary with an empty batch.
  rollback  the previous tree is retained on device (the second buffer)
            until ``commit`` or the next ``stage``, so a burn-gated
            rollback is the same O(1) pointer swap back.

KV isolation across the flip is namespace-based, not copy-based: the
engine seeds every prefix-cache / KVBM / KV-event hash chain with the
active version (``Engine._kv_namespace``), so v1 blocks can never verify
against v2 weights — they just age out like any cold prefix.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Dict, Optional

log = logging.getLogger("dynamo_tpu.elasticity")

# Override the device-reported free-HBM figure for the stage budget check
# (bytes). On backends that report no memory stats (CPU, some emulators)
# the check is skipped unless this forces a limit — which is exactly what
# the stage-abort chaos drills do.
HEADROOM_ENV = "DYNAMO_TPU_ROLLOUT_HEADROOM_BYTES"

# Fraction of the incoming tree's bytes demanded ON TOP of its own size
# before staging proceeds (transfer scratch, allocator slack). Default 0.05.
MARGIN_ENV = "DYNAMO_TPU_ROLLOUT_HEADROOM_MARGIN"

BASE_VERSION = "v0"


class StageError(RuntimeError):
    """Staging refused or aborted; the live version is untouched."""


def _tree_nbytes(params: Dict[str, Any]) -> int:
    total = 0
    for v in params.values():
        total += int(v.size) * int(v.dtype.itemsize)
    return total


def _section(key: str) -> str:
    """Top-level checkpoint section of a flat param key (progress unit for
    staging: 'layers.0.attn.wq' -> 'layers.0')."""
    parts = key.split(".")
    if parts[0] == "layers" and len(parts) > 1:
        return ".".join(parts[:2])
    return parts[0]


class WeightManager:
    """Owns the engine's weight version pointer and the staging buffer.

    Thread model: ``stage``/``flip``/``rollback``/``commit`` are called
    from HTTP threads; everything that swaps ``engine.params`` runs under
    ``engine._exec_lock`` (an RLock, so an armed flip applied from inside
    ``step()`` re-enters cleanly). ``self._lock`` guards the manager's own
    host-side bookkeeping against concurrent rollout requests.
    """

    def __init__(self, engine, version: str = BASE_VERSION):
        self.engine = engine
        self.version = version or BASE_VERSION
        self._lock = threading.Lock()
        # staged-but-not-flipped buffer: (version, sharded tree, nbytes)
        self._staged: Optional[tuple] = None
        # previous live tree retained for rollback: (version, tree)
        self._previous: Optional[tuple] = None
        # armed flip waiting for in-flight v1 streams to finish
        self._armed: Optional[str] = None
        self.flips_total = 0
        self.rollbacks_total = 0
        self.stage_aborts_total = 0
        self.last_stage_s = 0.0

    # ------------------------------------------------------------ queries --

    @property
    def namespace(self) -> str:
        """KV-hash namespace component for the ACTIVE version. The base
        version maps to "" so a never-rolled fleet hashes byte-identically
        to the pre-elasticity code (and to peers that never gained the
        subsystem)."""
        return "" if self.version == BASE_VERSION else self.version

    @property
    def admission_held(self) -> bool:
        """True while a ``finish``-mode flip is armed: new admissions wait
        in the pending queue so they land on the NEW version, while live
        v1 sequences run to completion."""
        return self._armed is not None

    @property
    def staged_version(self) -> Optional[str]:
        s = self._staged
        return s[0] if s else None

    @property
    def staged_nbytes(self) -> int:
        """Device bytes held by the staging buffer (and the retained
        rollback buffer) — the memory plane's double-buffer rows."""
        s = self._staged
        return s[2] if s else 0

    @property
    def previous_version(self) -> Optional[str]:
        p = self._previous
        return p[0] if p else None

    @property
    def previous_nbytes(self) -> int:
        p = self._previous
        return _tree_nbytes(p[1]) if p else 0

    def stats(self) -> dict:
        return {
            "version": self.version,
            "staged": self.staged_version,
            "staged_bytes": self.staged_nbytes,
            "previous": self.previous_version,
            "previous_bytes": self.previous_nbytes,
            "armed": self._armed,
            "flips_total": self.flips_total,
            "rollbacks_total": self.rollbacks_total,
            "stage_aborts_total": self.stage_aborts_total,
            "last_stage_s": round(self.last_stage_s, 3),
        }

    # ------------------------------------------------------------- budget --

    def _headroom_bytes(self) -> Optional[int]:
        """Free device bytes available for the staging buffer, or None if
        the backend reports nothing and no override forces a figure."""
        env = os.environ.get(HEADROOM_ENV, "")
        if env:
            return int(env)
        from dynamo_tpu.observability.memory import device_memory_stats

        free, known = 0, False
        for d in device_memory_stats():
            if d["bytes_limit"] > 0:
                known = True
                free += max(0, d["bytes_limit"] - d["bytes_in_use"])
        return free if known else None

    # -------------------------------------------------------------- stage --

    def stage(self, version: str, model_path: Optional[str] = None,
              seed: Optional[int] = None,
              quantization: Optional[str] = None) -> dict:
        """Load `version` host-side and double-buffer it into device HBM
        while the live version keeps serving. Raises StageError — with the
        live tree untouched and nothing resident — on version conflicts,
        tree mismatch, or insufficient headroom."""
        eng = self.engine
        cfg = eng.cfg
        t0 = time.monotonic()
        with self._lock:
            if not version:
                raise StageError("stage needs a non-empty version label")
            if version == self.version:
                raise StageError(f"version {version!r} is already live")
            if self._staged is not None:
                raise StageError(
                    f"a stage for {self._staged[0]!r} is already resident; "
                    "flip or abort it first")
            # staging claims the double buffer: the rollback window for
            # any PREVIOUS flip closes here (at most two trees resident)
            self._previous = None

        from dynamo_tpu.models.loader import load_or_init_params

        host = load_or_init_params(
            eng.model_cfg,
            model_path if model_path is not None else cfg.model_path,
            seed=seed if seed is not None else cfg.seed,
            quantization=quantization if quantization is not None
            else cfg.quantization,
        )
        live = eng.params
        missing = set(live) - set(host)
        extra = set(host) - set(live)
        if missing or extra:
            self._abort(version, "tree_mismatch")
            raise StageError(
                f"checkpoint tree for {version!r} does not match the live "
                f"model (missing={sorted(missing)[:3]}, "
                f"extra={sorted(extra)[:3]}): a hitless swap needs an "
                "identical architecture")
        for k in live:
            if (tuple(host[k].shape) != tuple(live[k].shape)
                    or host[k].dtype != live[k].dtype):
                self._abort(version, "leaf_mismatch")
                raise StageError(
                    f"leaf {k!r} differs from live ({host[k].shape}/"
                    f"{host[k].dtype} vs {live[k].shape}/{live[k].dtype})")

        incoming = _tree_nbytes(host)
        margin = float(os.environ.get(MARGIN_ENV, "0.05") or 0.05)
        need = int(incoming * (1.0 + margin))
        headroom = self._headroom_bytes()
        if headroom is not None and need > headroom:
            self._abort(version, "insufficient_hbm",
                        need=need, headroom=headroom)
            raise StageError(
                f"staging {version!r} needs {need} bytes "
                f"({incoming} tree + {margin:.0%} margin) but the memory "
                f"plane reports {headroom} free: aborting with the live "
                f"version untouched")

        # section-by-section device_put onto the live leaves' exact
        # shardings: same placement => same jit signature => no recompile.
        # A mid-transfer failure drops the partial dict and the live tree
        # never observed any of it.
        import jax

        staged: Dict[str, Any] = {}
        try:
            cur, cur_keys = None, 0
            for k in live:
                sec = _section(k)
                if sec != cur:
                    if cur is not None:
                        eng.flight.note("rollout_stage_section",
                                        version=version, section=cur,
                                        leaves=cur_keys)
                    cur, cur_keys = sec, 0
                staged[k] = jax.device_put(host[k], live[k].sharding)
                cur_keys += 1
        except Exception as e:
            staged.clear()
            self._abort(version, "device_put_failed", error=str(e))
            raise StageError(
                f"staging {version!r} failed during device transfer: {e}"
            ) from e

        self.last_stage_s = time.monotonic() - t0
        with self._lock:
            self._staged = (version, staged, incoming)
        eng.flight.note("rollout_staged", version=version,
                        bytes=incoming, seconds=round(self.last_stage_s, 3))
        log.info("staged weights %s: %.1f MiB in %.2fs (live %s untouched)",
                 version, incoming / 2**20, self.last_stage_s, self.version)
        return {"version": version, "bytes": incoming,
                "seconds": self.last_stage_s}

    def _abort(self, version: str, reason: str, **attrs) -> None:
        self.stage_aborts_total += 1
        self.engine.flight.note("rollout_stage_abort", version=version,
                                reason=reason, **attrs)
        log.warning("stage %s aborted (%s): live %s keeps serving",
                    version, reason, self.version)

    def restage_live(self) -> float:
        """Re-``device_put`` the LIVE tree onto its own shardings — the
        engine-resurrection path (robustness/watchdog.py): after a device
        fault every resident buffer is suspect, so the weights round-trip
        through host RAM and land on fresh device buffers.  Same
        section-by-section staging idiom as ``stage``, but leaf source is
        the live tree itself, so there is nothing to validate and no
        version change.  Caller holds ``engine._exec_lock``.  Returns the
        transfer seconds.  Any retained rollback/staging buffers are
        dropped — they are device-resident and therefore equally suspect."""
        import jax
        import numpy as np

        eng = self.engine
        t0 = time.monotonic()
        with self._lock:
            self._staged = None
            self._previous = None
            self._armed = None
        live = eng.params
        # np.asarray pulls a host copy first; device_put onto the leaf's
        # own sharding keeps the jit signatures byte-identical. Leaf by
        # leaf: a quantized weight is a (values, scales) pair, not an array
        eng.params = jax.tree_util.tree_map(
            lambda leaf: jax.device_put(np.asarray(leaf), leaf.sharding),
            live)
        dt = time.monotonic() - t0
        eng.flight.note("restage_live", version=self.version,
                        seconds=round(dt, 3))
        log.info("restaged live weights %s onto fresh device buffers "
                 "in %.2fs", self.version, dt)
        return dt

    def abort_stage(self) -> bool:
        """Drop a resident staging buffer without flipping."""
        with self._lock:
            if self._staged is None:
                return False
            version = self._staged[0]
            self._staged = None
            self._armed = None
        self._abort(version, "operator_abort")
        return True

    # --------------------------------------------------------------- flip --

    def flip(self, mode: str = "finish") -> dict:
        """Make the staged version live. With no in-flight sequences the
        pointer swaps immediately (under ``_exec_lock``, between steps).
        Otherwise:

        - ``finish``: arm the flip — admissions hold so new work queues
          for the new version, in-flight streams finish on the old one,
          and the scheduler applies the swap at the first empty-batch step
          boundary (``maybe_flip_locked``).
        - ``now``: swap immediately anyway. The caller has already moved
          in-flight streams elsewhere (drain-handoff: the HA frontend
          resumes them on a peer still serving the old version), so no
          live sequence crosses the flip.
        """
        if mode not in ("finish", "now"):
            raise ValueError(f"flip mode {mode!r} not in ('finish', 'now')")
        eng = self.engine
        with self._lock:
            if self._staged is None:
                raise StageError("no staged version to flip to")
            version = self._staged[0]
        with eng._exec_lock:
            if mode == "finish" and eng.seqs:
                with self._lock:
                    self._armed = version
                eng.flight.note("rollout_flip_armed", version=version,
                                live_seqs=len(eng.seqs))
                log.info("flip to %s armed: %d in-flight streams finish on "
                         "%s first (admissions held)",
                         version, len(eng.seqs), self.version)
                return {"version": version, "state": "armed",
                        "live_seqs": len(eng.seqs)}
            return self._flip_locked()

    def maybe_flip_locked(self) -> None:
        """Step-boundary hook (engine._step_locked, under _exec_lock):
        apply an armed flip once the last old-version stream is done."""
        if self._armed is None:
            return
        if self.engine.seqs:
            return
        self._flip_locked()

    def _flip_locked(self) -> dict:
        """The actual pointer swap. Caller holds ``engine._exec_lock``."""
        eng = self.engine
        with self._lock:
            version, tree, _ = self._staged
            self._previous = (self.version, eng.params)
            old = self.version
            eng.params = tree
            self.version = version
            self._staged = None
            self._armed = None
            self.flips_total += 1
        eng.flight.note("rollout_flip", version=version, previous=old)
        log.info("weight flip: %s -> %s (previous retained for rollback)",
                 old, version)
        return {"version": version, "state": "live", "previous": old}

    # ----------------------------------------------------------- rollback --

    def rollback(self) -> dict:
        """Swap back to the retained previous version (burn-gated fleet
        rollback path). O(1): the old tree never left HBM."""
        eng = self.engine
        with eng._exec_lock:
            with self._lock:
                if self._previous is None:
                    raise StageError(
                        "no previous version resident (already committed "
                        "or never flipped)")
                bad = self.version
                version, tree = self._previous
                eng.params = tree
                self.version = version
                self._previous = None
                self._staged = None
                self._armed = None
                self.rollbacks_total += 1
        eng.flight.note("rollout_rollback", version=version, rolled_back=bad)
        log.warning("weight rollback: %s -> %s", bad, version)
        return {"version": version, "state": "rolled_back",
                "rolled_back": bad}

    def commit(self) -> dict:
        """Drop the retained previous tree (drain-v1 complete): frees the
        double-buffer HBM and closes the rollback window."""
        with self._lock:
            dropped = self._previous[0] if self._previous else None
            self._previous = None
        if dropped is not None:
            self.engine.flight.note("rollout_commit", version=self.version,
                                    dropped=dropped)
            log.info("rollout committed at %s: dropped %s buffer",
                     self.version, dropped)
        return {"version": self.version, "dropped": dropped}
