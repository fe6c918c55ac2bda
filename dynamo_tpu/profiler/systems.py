"""TPU system catalog for the SLA profiler.

Plays the role of aiconfigurator's `aicSystem: a100_sxm` GPU profiles
(/root/reference/examples/dgdr/trtllm/dgdr.yaml:28-31): a small table of
per-chip peak numbers plus slice topologies, from public TPU spec sheets.
Numbers are peak/datasheet values; the roofline model applies utilization
factors (MFU, achievable-bandwidth fraction) on top.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Tuple

GiB = 1024**3


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    bf16_flops: float          # peak FLOP/s per chip (MXU, bf16)
    hbm_bytes: float           # HBM capacity per chip
    hbm_bw: float              # HBM bandwidth per chip, bytes/s
    ici_link_bw: float         # one-direction ICI bandwidth per link, bytes/s
    ici_links: int             # ICI links per chip (torus degree)
    chips_per_host: int = 4    # chips attached to one host VM (pod slices)
    # board power used ONLY by the exporter's modeled tpu_power_usage_watts
    # series (label source="modeled"); an estimate, not a datasheet peak
    tdp_w: float = 0.0

    @property
    def ici_bisection_bw(self) -> float:
        """Per-chip aggregate one-way ICI bandwidth (all links)."""
        return self.ici_link_bw * self.ici_links


# Source: Google Cloud TPU documentation, one page per generation
# (cloud.google.com/tpu/docs/v4, /v5e, /v5p, /v6e): peak bf16 FLOP/s, HBM
# capacity and HBM bandwidth per chip as published there (v5e: 197
# TFLOP/s, 16 GB, 819 GB/s). ICI numbers are per-link planning values from
# the same pages' interconnect totals.
CHIPS: Dict[str, ChipSpec] = {
    "v4": ChipSpec("v4", 275e12, 32 * GiB, 1.2e12, 4.5e10, 6, 4, 170.0),
    "v5e": ChipSpec("v5e", 197e12, 16 * GiB, 8.19e11, 4.5e10, 4, 8, 170.0),
    "v5p": ChipSpec("v5p", 459e12, 95 * GiB, 2.765e12, 9.0e10, 6, 4, 350.0),
    "v6e": ChipSpec("v6e", 918e12, 32 * GiB, 1.64e12, 9.0e10, 4, 8, 200.0),
}


@dataclasses.dataclass(frozen=True)
class SystemSpec:
    name: str
    chip: ChipSpec
    num_chips: int

    @property
    def total_flops(self) -> float:
        return self.chip.bf16_flops * self.num_chips

    @property
    def total_hbm_bw(self) -> float:
        return self.chip.hbm_bw * self.num_chips


def _mk(family: str, n: int) -> SystemSpec:
    return SystemSpec(f"{family}-{n}", CHIPS[family], n)


# Named slice shapes available to DGDR profilingConfig.tpuSystem. Mirrors the
# staged configs in BASELINE.json (v5e-8, v5e-16, v5p-64).
SYSTEMS: Dict[str, SystemSpec] = {
    s.name: s
    for s in [
        _mk("v5e", 1), _mk("v5e", 4), _mk("v5e", 8), _mk("v5e", 16),
        _mk("v5e", 32), _mk("v5e", 64), _mk("v5e", 128), _mk("v5e", 256),
        _mk("v5p", 4), _mk("v5p", 8), _mk("v5p", 16), _mk("v5p", 32),
        _mk("v5p", 64), _mk("v5p", 128),
        _mk("v6e", 1), _mk("v6e", 4), _mk("v6e", 8), _mk("v6e", 16),
        _mk("v6e", 32), _mk("v6e", 64), _mk("v6e", 256),
        _mk("v4", 8), _mk("v4", 16), _mk("v4", 32), _mk("v4", 64),
    ]
}

_SYSTEM_RE = re.compile(r"^(v\d+[ep]?)-(\d+)$")


# jax `device.device_kind` strings -> chip catalog names. THE table: the
# benchmark, the live MFU/MBU exposition (observability/engine_metrics.py),
# the KVBM cost gate (kvbm/cost_model.py), the hardware exporter and
# chip_smoke.py all map hardware through it. A v5e reports itself as
# "TPU v5 lite"; the short forms cover `DYNAMO_TPU_CHIP`-style names.
_DEVICE_KIND_PATTERNS = (
    (r"v5 ?lite|v5e", "v5e"), (r"v5p|v5 ?pod", "v5p"),
    (r"v6e|v6 ?lite|trillium", "v6e"), (r"v4", "v4"),
)


def chip_for_device_kind(kind: str) -> "ChipSpec | None":
    """Map a jax `device_kind` string onto the chip catalog; None when it
    names no chip in the table (a CPU, or a TPU nobody has entered yet).
    Callers that need a peak use `require_chip`."""
    kind = (kind or "").lower()
    for pat, name in _DEVICE_KIND_PATTERNS:
        if re.search(pat, kind):
            return CHIPS[name]
    return None


def require_chip(kind: str) -> ChipSpec:
    """`chip_for_device_kind` for callers that need a peak number: a device
    that is not in the table is an error, never a default."""
    chip = chip_for_device_kind(kind)
    if chip is None:
        raise KeyError(
            f"device kind {kind!r} is not in the chip table "
            f"(profiler/systems.py CHIPS: {sorted(CHIPS)}); add its "
            f"datasheet peaks there before using them")
    return chip


def get_system(name: str) -> SystemSpec:
    """Look up a system, accepting any `<family>-<nchips>` string."""
    if name in SYSTEMS:
        return SYSTEMS[name]
    m = _SYSTEM_RE.match(name.strip().lower())
    if m and m.group(1) in CHIPS:
        return SystemSpec(name, CHIPS[m.group(1)], int(m.group(2)))
    raise KeyError(
        f"unknown TPU system {name!r}; known: {sorted(SYSTEMS)} "
        f"or any '<family>-<chips>' with family in {sorted(CHIPS)}"
    )


def valid_tp_sizes(system: SystemSpec) -> Tuple[int, ...]:
    """Tensor-parallel degrees that tile the slice (powers of two)."""
    out = []
    tp = 1
    while tp <= system.num_chips:
        if system.num_chips % tp == 0:
            out.append(tp)
        tp *= 2
    return tuple(out)
