"""ctypes binding + on-demand build of the native transport library.

The shared object is compiled once, keyed by the source's content hash, into
the program's build home (`utils/platform.build_home()`: inside the checkout,
git-ignored; `DYNAMO_TPU_BUILD_DIR` places it elsewhere, e.g. an image
layer). g++ is in the image; pybind11 is not, hence the plain C ABI. A build
failure degrades to `lib = None`; the transfer layer then uses its
pure-Python socket fallback with identical wire format, so functionality
never depends on a compiler.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

log = logging.getLogger("dynamo_tpu.native")

_CSRC = os.path.join(os.path.dirname(__file__), "csrc")
_SRC = os.path.join(_CSRC, "dynamo_transport.cpp")
_ROUTER_SRC = os.path.join(_CSRC, "dynamo_router.cpp")
_lock = threading.Lock()
_lib = None
_tried = False
_router_lib = None
_router_tried = False


def _build_dir() -> str:
    from dynamo_tpu.utils.platform import build_home

    d = os.environ.get("DYNAMO_TPU_BUILD_DIR",
                       os.path.join(build_home(), "native"))
    os.makedirs(d, exist_ok=True)
    return d


def _build(src: str, stem: str) -> str:
    """Compile `src` (if needed) into the cache dir; return the .so path."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so_path = os.path.join(_build_dir(), f"lib{stem}_{digest}.so")
    if os.path.exists(so_path):
        return so_path
    # per-process tmp name: concurrent first-start compiles (colocated
    # workers) must not interleave writes into one .tmp — whoever's
    # os.replace lands last wins, both outputs are identical
    tmp = f"{so_path}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-Wall",
        src, "-o", tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so_path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return so_path


def build_library() -> str:
    """Compile (if needed) and return the transport .so path."""
    return _build(_SRC, "dynamo_transport")


def get_lib():
    """The loaded native library, or None if unavailable."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            path = build_library()
            lib = ctypes.CDLL(path)
            lib.dt_listen.argtypes = [ctypes.c_uint16,
                                      ctypes.POINTER(ctypes.c_uint16)]
            lib.dt_listen.restype = ctypes.c_int
            lib.dt_accept.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
            lib.dt_accept.restype = ctypes.c_int
            lib.dt_connect.argtypes = [ctypes.c_char_p, ctypes.c_uint16,
                                       ctypes.c_char_p]
            lib.dt_connect.restype = ctypes.c_int
            lib.dt_send_msg.argtypes = [ctypes.c_int, ctypes.c_void_p,
                                        ctypes.c_int64]
            lib.dt_send_msg.restype = ctypes.c_int
            lib.dt_recv_len.argtypes = [ctypes.c_int]
            lib.dt_recv_len.restype = ctypes.c_int64
            lib.dt_recv_into.argtypes = [ctypes.c_int, ctypes.c_void_p,
                                         ctypes.c_int64]
            lib.dt_recv_into.restype = ctypes.c_int
            lib.dt_close.argtypes = [ctypes.c_int]
            lib.dt_key_len.restype = ctypes.c_int
            _lib = lib
            log.info("native transport loaded: %s", path)
        except Exception as e:
            log.warning("native transport unavailable (%s); python fallback", e)
            _lib = None
        return _lib


def get_router_lib():
    """The native router-core library, or None if unavailable."""
    global _router_lib, _router_tried
    with _lock:
        if _router_tried:
            return _router_lib
        _router_tried = True
        try:
            lib = ctypes.CDLL(_build(_ROUTER_SRC, "dynamo_router"))
            lib.dr_pick.argtypes = [
                ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_char_p),
                ctypes.POINTER(ctypes.c_double),
                ctypes.c_int,
            ]
            lib.dr_pick.restype = ctypes.c_int
            lib.dr_hash64.argtypes = [ctypes.c_char_p]
            lib.dr_hash64.restype = ctypes.c_uint64
            _router_lib = lib
            log.info("native router core loaded")
        except Exception as e:
            log.warning("native router unavailable (%s); python fallback", e)
            _router_lib = None
        return _router_lib
