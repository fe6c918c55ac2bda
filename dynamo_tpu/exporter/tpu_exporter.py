"""TPU hardware metrics exporter — the DCGM analogue.

The reference's Grafana dashboard reads per-device hardware series from the
DCGM exporter installed by the GPU Operator (DCGM_FI_DEV_GPU_UTIL /
DCGM_FI_DEV_POWER_USAGE, /root/reference/examples/dgdr/trtllm/
grafana-dynamo-dashboard-configmap.yaml:604,617). This exporter feeds the
same dashboard slots for TPUs:

    tpu_tensorcore_utilization   (gauge, %, per device)  <- duty-cycle proxy
    tpu_hbm_memory_usage_bytes   (gauge, bytes, per device)
    tpu_hbm_memory_total_bytes   (gauge, bytes, per device)
    tpu_power_usage_watts        (gauge, W, per device; label
                                  source="modeled"|"measured")

Sources, in order of preference:
1. `jax.local_devices()[i].memory_stats()` — live HBM numbers on TPU
   backends (bytes_in_use / bytes_limit).
2. A pluggable sampler hook (`set_sampler`) so engine processes can push
   real utilization from profiler data.
3. On an explicit CPU run (`JAX_PLATFORMS=cpu`) devices are labelled
   kind="cpu" and report zeros.

Runs in-process inside an engine worker via `attach_to_registry` — the
worker owns the chips, so only it can read them. The standalone process
(`python -m dynamo_tpu.exporter`) is for nodes whose chips no worker holds.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Dict, List, Optional

from dynamo_tpu.serving.metrics import Gauge, Registry

log = logging.getLogger("dynamo_tpu.exporter")


def _chip_of(dev):
    """(kind label, modeled board power in W or None) for one device, from
    the one chip table (profiler/systems.py). A CPU device (tests, local
    development) is labelled "cpu" and draws a modeled 0 W; a TPU that is
    not in the table keeps its own device_kind as the label and gets NO
    modeled power series — never another chip's number."""
    from dynamo_tpu.profiler.systems import chip_for_device_kind

    if dev.platform == "cpu":
        return "cpu", 0.0
    kind = getattr(dev, "device_kind", "") or ""
    chip = chip_for_device_kind(kind)
    if chip is None:
        return kind.lower() or dev.platform, None
    return chip.name, chip.tdp_w


Sample = Dict[str, float]  # {"util_pct", "hbm_used", "hbm_total", "power_w"}
Sampler = Callable[[], Dict[int, Sample]]


def engine_busy_sampler(engine) -> Sampler:
    """Utilization from engine step accounting: fraction of wall time spent
    inside device compute (prefill + decode) since the last sample. The mesh
    is SPMD, so every local device reports the same duty cycle."""
    last = {"busy": 0.0, "wall": time.monotonic()}

    def sample() -> Dict[int, Sample]:
        import jax

        m = engine.metrics
        busy = float(m.prefill_time_s + m.decode_time_s)
        now = time.monotonic()
        d_busy, d_wall = busy - last["busy"], now - last["wall"]
        last["busy"], last["wall"] = busy, now
        util = max(0.0, min(100.0, 100.0 * d_busy / d_wall)) if d_wall > 0 else 0.0
        return {dev.id: {"util_pct": util} for dev in jax.local_devices()}

    return sample


class TpuMetricsExporter:
    """Collects per-device samples into Prometheus gauges."""

    def __init__(self, registry: Optional[Registry] = None):
        self.registry = registry or Registry()
        r = self.registry
        self.util = Gauge(
            "tpu_tensorcore_utilization",
            "TensorCore utilization percent per device", r,
        )
        self.hbm_used = Gauge(
            "tpu_hbm_memory_usage_bytes", "HBM bytes in use per device", r
        )
        self.hbm_total = Gauge(
            "tpu_hbm_memory_total_bytes", "HBM capacity bytes per device", r
        )
        self.power = Gauge(
            "tpu_power_usage_watts", "Estimated chip power draw per device", r
        )
        self._sampler: Optional[Sampler] = None
        self._lock = threading.Lock()

    def set_sampler(self, sampler: Optional[Sampler]) -> None:
        """Install a live utilization source (e.g. engine step accounting)."""
        with self._lock:
            self._sampler = sampler

    def collect_once(self) -> int:
        """Sample all local devices; returns number of devices exported."""
        import jax

        try:
            devices = jax.local_devices()
        except Exception as e:  # backend failed to initialise
            log.warning("no JAX devices visible: %s", e)
            return 0

        with self._lock:
            sampler = self._sampler
        pushed: Dict[int, Sample] = {}
        if sampler is not None:
            try:
                pushed = sampler()
            except Exception as e:
                log.warning("sampler failed: %s", e)

        for dev in devices:
            idx = dev.id
            kind, tdp = _chip_of(dev)
            labels = {"device": str(idx), "kind": kind}
            used = total = 0.0
            try:
                stats = dev.memory_stats() or {}
                used = float(stats.get("bytes_in_use", 0))
                total = float(
                    stats.get("bytes_limit", stats.get("bytes_reservable_limit", 0))
                )
            except Exception:
                pass
            sample = pushed.get(idx, {})
            util = float(sample.get("util_pct", 0.0))
            self.util.set(util, **labels)
            self.hbm_used.set(float(sample.get("hbm_used", used)), **labels)
            self.hbm_total.set(float(sample.get("hbm_total", total)), **labels)
            # power: a real measurement when the sampler pushed one, else a
            # model (idle floor + utilization-proportional dynamic power).
            # The source label lets dashboards/alerts tell them apart rather
            # than treating the model as hardware truth.
            if "power_w" in sample:
                power, source = sample["power_w"], "measured"
            elif tdp is None:
                continue  # chip not in the table: no model to apply
            else:
                power = tdp * (0.25 + 0.75 * util / 100.0)
                source = "modeled"
            # drop the opposite-source series on flip, or sum() over the
            # metric double-counts a frozen stale variant
            other = "modeled" if source == "measured" else "measured"
            self.power.remove(source=other, **labels)
            self.power.set(float(power), source=source, **labels)
        return len(devices)

    def run_forever(self, interval_s: float = 10.0,
                    stop: Optional[threading.Event] = None) -> None:
        stop = stop or threading.Event()
        while not stop.is_set():
            self.collect_once()
            stop.wait(interval_s)


def attach_to_registry(registry: Registry, interval_s: float = 10.0
                       ) -> TpuMetricsExporter:
    """Spawn a background collector exporting into an existing registry
    (used by engine workers so /metrics carries hardware series too)."""
    exp = TpuMetricsExporter(registry)
    t = threading.Thread(
        target=exp.run_forever, args=(interval_s,), daemon=True,
        name="tpu-exporter",
    )
    t.start()
    return exp
