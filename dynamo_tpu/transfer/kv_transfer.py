"""KV-cache handoff between prefill and decode workers.

Two data planes, selected by `--disaggregation-transfer-backend`
(mirroring /root/reference/examples/deploy/sglang/disagg.yaml:47-48):

- "ici": the handoff stays in device buffers. Two legs: (a) IN-PROCESS —
  colocated roles found via transfer.ici_registry move pages as jax.Arrays
  (XLA places a device-to-device copy; no host roundtrip); (b)
  CROSS-PROCESS — the prefill side stages the pages with a
  `jax.experimental.transfer` server (DeviceKVSource) and the decode side
  pulls them straight into its own device memory (DeviceKVClient). A pair
  that can do neither degrades to the TCP plane with a LOUD per-pair
  warning on the decode side.
- "dcn": cross-host — pages serialize to bytes and stream over the native
  transport (transfer.transport), with NIXL-style key rendezvous on the
  prefill worker's bootstrap port.

Wire schema (dcn): one message = JSON header (dtype/shape/n_tokens/first_token)
+ one message per tensor (k then v, raw bytes, C-order).
"""

from __future__ import annotations

import json
import logging
import threading
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from dynamo_tpu.transfer import transport

log = logging.getLogger("dynamo_tpu.kv_transfer")


def _tobytes(arr: np.ndarray) -> bytes:
    # bfloat16 has no numpy dtype string; ship raw bytes + jax dtype name
    return np.ascontiguousarray(arr).view(np.uint8).tobytes()


def _dtype_name(arr) -> str:
    return str(arr.dtype)


def _frombytes(data: bytes, dtype: str, shape) -> np.ndarray:
    if dtype == "bfloat16":
        import ml_dtypes

        np_dtype = np.dtype(ml_dtypes.bfloat16)
    else:
        np_dtype = np.dtype(dtype)
    return np.frombuffer(data, dtype=np_dtype).reshape(shape)


class KVSource:
    """Prefill-worker side: holds exported KV until the decode side pulls it.

    One accept thread serves the bootstrap port; each parked request is keyed
    by request_id. After a successful pull (or expiry) the engine's parked
    pages are released."""

    def __init__(self, engine, port: int = 0, parked_ttl_s: float = 120.0):
        self.engine = engine
        self.parked_ttl_s = parked_ttl_s
        self.listener = transport.Listener(port)
        self.port = self.listener.port
        self._stop = False
        self._thread = threading.Thread(target=self._serve, daemon=True,
                                        name="kv-source")
        self._thread.start()

    def close(self):
        self._stop = True
        self.listener.close()

    def _serve(self):
        last_expiry = 0.0
        while not self._stop:
            import time as _time

            now = _time.monotonic()
            if now - last_expiry > 10.0:
                # reclaim KV parked for peers that never pulled (crash / lost
                # ack) so failures can't bleed the page pool dry
                self.engine.expire_parked(self.parked_ttl_s)
                last_expiry = now
            try:
                conn, key = self.listener.accept(timeout_ms=500)
            except TimeoutError:
                continue
            except Exception:
                if self._stop:
                    return
                log.exception("kv-source accept failed")
                continue
            threading.Thread(
                target=self._handle, args=(conn, key), daemon=True
            ).start()

    def _handle(self, conn: transport.Connection, request_id: str):
        try:
            k, v, n_tokens = self.engine.export_kv(request_id)
            header = {
                "request_id": request_id,
                "n_tokens": n_tokens,
                "dtype": _dtype_name(k),
                "shape": list(k.shape),
                # the V pool's rows have a width of their own for an MLA
                # model (none, or the sparse-attention indexer's keys)
                "v_shape": list(v.shape),
            }
            conn.send_msg(json.dumps(header).encode())
            conn.send_msg(_tobytes(k))
            conn.send_msg(_tobytes(v))
            # wait for ack so pages outlive a mid-transfer failure
            ack = conn.recv_msg(max_len=64)
            if ack == b"OK":
                self.engine.release_parked(request_id)
        except KeyError:
            try:
                conn.send_msg(json.dumps({"error": "unknown request"}).encode())
            except Exception:
                pass
        except Exception:
            log.exception("kv transfer for %s failed", request_id)
        finally:
            conn.close()


def fetch_kv(host: str, port: int, request_id: str
             ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Decode-worker side: pull one sequence's KV. Returns (k, v, n_tokens)."""
    conn = transport.connect(host, port, request_id)
    try:
        header = json.loads(conn.recv_msg(max_len=1 << 16))
        if "error" in header:
            raise KeyError(f"prefill side: {header['error']}")
        k = _frombytes(conn.recv_msg(), header["dtype"], header["shape"])
        v = _frombytes(conn.recv_msg(), header["dtype"],
                       header.get("v_shape", header["shape"]))
        conn.send_msg(b"OK")
        return k, v, header["n_tokens"]
    finally:
        conn.close()


# ----------------------------------------------------------- KVBM host tier --
# Cross-worker onboard (dynamo_tpu.kvbm): on a disagg or failover miss a
# worker pulls demoted prefix BLOCKS from a peer's host tier over this same
# TCP plane instead of re-prefilling them. One connection per pull; the key
# namespace ("kvbm") keeps it off the per-request parked-KV protocol above.

KVBM_KEY = "kvbm"


class HostTierSource:
    """Serves a worker's KVBM host-tier blocks to pulling peers.

    Wire: peer connects with key "kvbm", sends one JSON message
    {"blocks": [hex hash, ...]}; the source answers a JSON header
    {"found": n, "shape": [...], "dtype": "..."} for the longest
    consecutive-from-the-start run it holds, then n (k, v) raw-byte
    message pairs. Blocks are copied out of the pool under its lock, so
    concurrent demotes/LRU evictions can't tear a served block."""

    def __init__(self, kvbm, port: int = 0):
        self.kvbm = kvbm
        self.listener = transport.Listener(port)
        self.port = self.listener.port
        self._stop = False
        self._thread = threading.Thread(target=self._serve, daemon=True,
                                        name="kvbm-host-tier")
        self._thread.start()

    def close(self):
        self._stop = True
        self.listener.close()

    def _serve(self):
        while not self._stop:
            try:
                conn, key = self.listener.accept(timeout_ms=500)
            except TimeoutError:
                continue
            except Exception:
                if self._stop:
                    return
                log.exception("kvbm host-tier accept failed")
                continue
            if key != KVBM_KEY:
                conn.close()
                continue
            threading.Thread(target=self._handle, args=(conn,),
                             daemon=True).start()

    def _handle(self, conn: transport.Connection):
        try:
            req = json.loads(conn.recv_msg(max_len=1 << 20))
            hashes = [bytes.fromhex(h) for h in req.get("blocks", [])]
            blocks = []
            for h in hashes:
                got = self.kvbm.pool.get(h)
                if got is None:
                    break
                blocks.append(got)
            header = {"found": len(blocks)}
            if blocks:
                header["shape"] = list(blocks[0][0].shape)
                header["v_shape"] = list(blocks[0][1].shape)
                header["dtype"] = _dtype_name(blocks[0][0])
            conn.send_msg(json.dumps(header).encode())
            for k, v in blocks:
                conn.send_msg(_tobytes(k))
                conn.send_msg(_tobytes(v))
        except Exception:
            log.exception("kvbm host-tier pull failed")
        finally:
            conn.close()


def fetch_host_blocks(host: str, port: int, hashes_hex
                      ) -> "list[Tuple[np.ndarray, np.ndarray]]":
    """Pull host-tier blocks from a peer. Returns the consecutive-from-the-
    start run the peer held, as (k, v) numpy pairs in host-pool layout."""
    conn = transport.connect(host, port, KVBM_KEY)
    try:
        conn.send_msg(json.dumps({"blocks": list(hashes_hex)}).encode())
        header = json.loads(conn.recv_msg(max_len=1 << 16))
        out = []
        for _ in range(int(header.get("found", 0))):
            k = _frombytes(conn.recv_msg(), header["dtype"], header["shape"])
            v = _frombytes(conn.recv_msg(), header["dtype"],
                           header.get("v_shape", header["shape"]))
            out.append((k, v))
        return out
    finally:
        conn.close()


# ------------------------------------------------------- device-buffer plane --
# Cross-PROCESS leg of the "ici" backend: when prefill and decode engines
# are colocated on one slice but in different processes (the reference's
# standard disagg topology — separate pods,
# /root/reference/examples/deploy/sglang/disagg.yaml:47-52), the parked KV
# streams through `jax.experimental.transfer` — the decode side pulls the
# prefill side's device buffers directly (no np.asarray readback, no JSON
# byte pump). The in-process registry path remains the fastest leg; the TCP
# (dcn) plane remains the cross-slice fallback.


def _uuid64(key: str) -> int:
    """63-bit pull id. The decode side never derives this — it uses the
    `transfer_uuid` from the stage descriptor — so the key carries a
    per-stage nonce (see DeviceKVSource.stage)."""
    import hashlib

    return int.from_bytes(
        hashlib.sha256(key.encode()).digest()[:8], "big") >> 1


_XFER_LOCK = threading.Lock()
_XFER_SERVER = None


def _transfer_server():
    """Process-wide jax transfer server, started lazily.

    Lazy on purpose: (a) starting two servers in ONE process crashes the
    local bulk-transport factory (jaxlib streaming.cc CHECK), and in-process
    handoffs never need a server; (b) worker startup shouldn't pay the
    socket setup unless disagg device transfer is actually used.
    Bind host comes from DYNAMO_TPU_TRANSFER_BIND (default 0.0.0.0 — the
    advertised wildcard is substituted with the worker's URL host by the
    decode side)."""
    global _XFER_SERVER
    with _XFER_LOCK:
        if _XFER_SERVER is None:
            import os

            import jax
            from jax.experimental import transfer as jxfer

            bind = os.environ.get("DYNAMO_TPU_TRANSFER_BIND", "0.0.0.0")
            client = jax.devices()[0].client
            _XFER_SERVER = jxfer.start_transfer_server(
                client, f"{bind}:0", [f"{bind}:0"])
        return _XFER_SERVER


class DeviceKVSource:
    """Prefill side: stages a parked sequence's KV for a remote device pull.

    Staging is LAZY (the decode side's /disagg/stage RPC, not the prefill
    response): an eager await_pull would pin a gathered KV copy in device
    memory for every request whose peer then pulls over TCP instead — an
    HBM leak, since the transfer server has no un-await. Pages are released
    by the decode side's /disagg/release RPC (or the TTL sweep).

    Stage-then-crash peers are contained three ways:
    - outstanding stages are CAPPED (`max_staged`), counting BOTH live
      stages and expired-but-never-released ones: an un-pulled gather
      stays pinned in the transfer server (it has no un-await), so its
      slot is only freed by /disagg/release — making the cap a true hard
      bound on server-pinned HBM. Past the cap, stage() refuses and the
      peer degrades to the TCP plane.
    - a TTL sweep demotes expired entries to the leaked ledger (loudly),
      so operators see stage-then-crash peers in logs and /worker/stats;
      a late re-stage for a leaked request RESURRECTS the original
      coordinates instead of pinning a second gather.
    - each stage derives its pull uuid from a fresh NONCE, so a re-stage
      after release can never re-issue await_pull for a uuid the server
      has already seen (whose behavior is undefined — a jaxlib CHECK
      could kill the process rather than raise).
    A duplicate stage() for a request that is still staged returns the
    ORIGINAL coordinates instead of staging again (the peer retried the
    RPC or lost the response; the arrays are consumed by whichever pull
    lands first). The whole stage body runs under one lock: concurrent
    duplicate RPCs must not race past the ledger check and double-pin
    (the export gather is milliseconds; stage RPCs are per-request)."""

    def __init__(self, engine, staged_ttl_s: float = 120.0,
                 max_staged: int = 64):
        self.engine = engine
        self.staged_ttl_s = staged_ttl_s
        self.max_staged = max_staged
        self._warned = False
        self._lock = threading.Lock()
        # request_id -> (monotonic ts, descriptor dict, (k, v) array refs)
        self._staged: Dict[str, tuple] = {}
        # expired un-released stages: the transfer server still pins their
        # gathers, so they keep holding cap slots until /disagg/release
        self._leaked: Dict[str, tuple] = {}

    @property
    def eligible(self) -> bool:
        """Cheap pre-check advertised in the prefill response: v1 pulls
        single-device buffers, so a TP-sharded KV pool never stages (and
        never pays the export gather only to discard it)."""
        return len(self.engine.k_pages.sharding.device_set) == 1

    def counts(self) -> tuple:
        """(live, leaked) under ONE lock and sweep — a two-property read
        could sweep between them and count an expiring entry twice. The
        sweep on read keeps expiry observable in /worker/stats and
        /metrics even when no new stage traffic arrives."""
        import time as _time

        with self._lock:
            self._sweep_locked(_time.monotonic())
            return len(self._staged), len(self._leaked)

    @property
    def staged_count(self) -> int:
        return self.counts()[0]

    @property
    def leaked_count(self) -> int:
        """Expired un-released stages whose gathers the transfer server
        still pins (surfaced in /worker/stats for operators)."""
        return self.counts()[1]

    def _sweep_locked(self, now: float) -> None:
        dead = [rid for rid, (ts, _, _) in self._staged.items()
                if now - ts > self.staged_ttl_s]
        for rid in dead:
            self._leaked[rid] = self._staged.pop(rid)
        if dead:
            log.warning(
                "%d staged KV gather(s) expired un-pulled (%s): their "
                "device copies stay pinned in the transfer server (no "
                "un-await) and keep holding stage slots until "
                "/disagg/release", len(dead), ", ".join(dead[:5]))

    def mark_released(self, request_id: str) -> None:
        """Decode side released the request (post-pull): forget the stage."""
        with self._lock:
            self._staged.pop(request_id, None)
            self._leaked.pop(request_id, None)

    def stage(self, request_id: str) -> Optional[dict]:
        if not self.eligible:
            return None
        import secrets
        import time as _time

        now = _time.monotonic()
        with self._lock:
            self._sweep_locked(now)
            hit = self._staged.get(request_id)
            if hit is not None:
                return dict(hit[1])
            leaked = self._leaked.pop(request_id, None)
            if leaked is not None:
                # the peer came back after the TTL: its gather is still
                # pinned and pullable — resurrect rather than double-pin
                self._staged[request_id] = (now, leaked[1], leaked[2])
                return dict(leaked[1])
            if len(self._staged) + len(self._leaked) >= self.max_staged:
                log.warning(
                    "staged-KV cap reached (%d live + %d leaked); refusing "
                    "stage for %s — peer will use the TCP plane",
                    len(self._staged), len(self._leaked), request_id)
                return None
            k, v, _ = self.engine.export_kv_device(request_id)
            uid = _uuid64(f"{request_id}:{secrets.token_hex(8)}")
            try:
                srv = _transfer_server()
                srv.await_pull(uid, [k, v])
            except Exception as e:  # backend without transfer-server support
                if not self._warned:
                    self._warned = True
                    log.warning(
                        "device-buffer KV staging unavailable (%s); this "
                        "prefill worker will serve KV over the TCP plane", e)
                return None
            desc = {
                "transfer_address": srv.address(),
                "transfer_uuid": uid,
                "kv_shape": list(k.shape),
                "kv_dtype": str(k.dtype),
            }
            self._staged[request_id] = (now, desc, (k, v))
            return dict(desc)


class DeviceKVClient:
    """Decode side: pulls staged KV into local device memory."""

    def __init__(self):
        self._conns: Dict[str, object] = {}
        self._lock = threading.Lock()

    def pull(self, address: str, uuid: int, shape, dtype: str):
        import jax
        from jax.sharding import SingleDeviceSharding

        srv = _transfer_server()
        with self._lock:
            conn = self._conns.get(address)
            if conn is None:
                conn = srv.connect(address)
                self._conns[address] = conn
        sds = jax.ShapeDtypeStruct(
            tuple(shape), jnp_dtype(dtype),
            sharding=SingleDeviceSharding(jax.devices()[0]))
        k, v = conn.pull(uuid, [sds, sds])
        return k, v


def jnp_dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


class ICIHandoff:
    """Colocated prefill/decode engines on one slice: device-to-device copy.

    export_kv_device/import_kv operate on jax.Arrays; when both engines share
    devices XLA turns the gather+scatter into on-device copies (ICI for
    cross-chip shards) with no host bounce. The serving path reaches this
    via transfer.ici_registry when `--disaggregation-transfer-backend ici`
    finds the routed prefill engine in-process."""

    def __init__(self, prefill_engine, decode_engine):
        self.src = prefill_engine
        self.dst = decode_engine

    def transfer(self, req, first_token: int) -> None:
        k, v, _ = self.src.export_kv_device(req.request_id)
        self.dst.import_kv(req, first_token, k, v)
        self.src.release_parked(req.request_id)
