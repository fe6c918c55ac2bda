"""Backend selection: one strict, in-process initialisation.

The program is written for a TPU. `init_backend()` is the only way an entry
point touches the device: once, in the process that will own the chip, with
no probe child and no retry (a chip belongs to one process — a probe that
initialises it first takes it from its own parent). The CPU is a choice
somebody made by exporting `JAX_PLATFORMS=cpu` (tests, local development),
never something the program falls back to.

`build_home()` is where everything the program builds at run time lives
(JAX's persistent compile cache, the native `.so`s): one fixed directory
inside the checkout, git-ignored, so a second process — or a second run on
the same disk — finds what the first one built.
"""

from __future__ import annotations

import logging
import os

log = logging.getLogger("dynamo_tpu.platform")

PLATFORMS_ENV = "JAX_PLATFORMS"
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build_home() -> str:
    """`<checkout>/.dynamo_cache`: never derived from `~`, a temp name, a
    pid or a clock — the path is part of the compile cache's key."""
    return os.path.join(_CHECKOUT, ".dynamo_cache")


def force_cpu(num_virtual_devices: int | None = None) -> None:
    """Tests' helper: pin this process to the CPU backend, optionally split
    into N virtual devices for mesh tests. Call before the first backend
    touch."""
    if num_virtual_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{num_virtual_devices}"
            ).strip()
    os.environ[PLATFORMS_ENV] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def init_backend() -> str:
    """Initialise the JAX backend in this process and return its platform:
    `"tpu"`, or `"cpu"` when `JAX_PLATFORMS=cpu` was set explicitly. Anything
    else — no accelerator, an accelerator that is not a TPU, a platform list
    that fails to initialise — exits non-zero naming what JAX found."""
    import jax

    wanted = os.environ.get(PLATFORMS_ENV, "").strip().lower()
    cpu_on_purpose = wanted == "cpu"  # the CPU and nothing else
    if wanted and "cpu" not in wanted.split(","):
        # the loader stages full-precision checkpoints on the host before
        # quantizing (models/loader.py), which needs JAX's CPU backend
        # beside the accelerator; the default platform stays the first
        jax.config.update("jax_platforms", f"{wanted},cpu")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(
            f"dynamo_tpu: JAX could not initialise its backend "
            f"({PLATFORMS_ENV}={wanted or '<unset>'}): {e}") from e
    platform = devices[0].platform
    if cpu_on_purpose:
        log.warning(
            "running on the CPU because %s=cpu is set: fine for tests and "
            "local development, and no time measured here says anything "
            "about a TPU", PLATFORMS_ENV)
        return "cpu"
    if platform != "tpu":
        raise SystemExit(
            f"dynamo_tpu: no TPU — JAX's default backend is {platform!r} "
            f"({len(devices)} x {devices[0].device_kind}). Set "
            f"{PLATFORMS_ENV}=cpu to run on the CPU on purpose.")
    log.info("backend tpu: %d x %s", len(devices), devices[0].device_kind)
    return "tpu"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a place a later process
    can find again, and return it. Where `JAX_COMPILATION_CACHE_DIR` is set
    JAX reads it itself and nothing is set in code; otherwise the cache
    lives under `build_home()`. Every entry point that compiles calls this
    before its first jit."""
    placed = os.environ.get(COMPILE_CACHE_ENV)
    if placed:
        return placed
    import jax

    path = os.path.join(build_home(), "jax-comp-cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
