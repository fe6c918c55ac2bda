"""Restore-vs-recompute gate for KV onboarding.

A host-tier hit is only worth taking when restoring the pages
(host->device DMA + one scatter dispatch) beats recomputing them (a
chunked-prefill pass over the same tokens). Both sides come from the
serving roofline (`profiler/roofline.py`): recompute is compute-bound
prefill FLOPs plus a dispatch overhead per chunk; restore is bytes over
the host<->device link plus one dispatch. On real models restore wins by
an order of magnitude — the reason KV offload exists — but the gate keeps
degenerate cases (tiny prompts on fast chips, a crawling disk tier)
honest instead of hard-coding "always onboard".
"""

from __future__ import annotations

import os
from typing import Optional

from dynamo_tpu.profiler import roofline

# host<->device staging bandwidth (bytes/s). TPU hosts stream HBM over
# PCIe-class links; 8 GB/s is the conservative planning number, overridable
# per deployment (DYNAMO_TPU_KVBM_H2D_GBPS).
DEFAULT_H2D_BYTES_S = 8e9
# fixed cost of one host->device scatter dispatch / one prefill-chunk
# dispatch (same constant family as roofline.DISPATCH_OVERHEAD_S)
TRANSFER_OVERHEAD_S = 0.0005


def _h2d_bytes_s() -> float:
    try:
        return float(os.environ.get("DYNAMO_TPU_KVBM_H2D_GBPS", "0")) * 1e9 \
            or DEFAULT_H2D_BYTES_S
    except ValueError:
        return DEFAULT_H2D_BYTES_S


class OnboardGate:
    """Decides whether to restore N cached blocks or recompute them.

    mode: "auto" (roofline compare) | "always" | "never". `chip_flops`
    defaults to the detected chip's peak when the engine runs on TPU and
    to the v5e planning number elsewhere (CPU tests/dev boxes — where the
    real recompute is far SLOWER than the model assumes, so auto remains
    conservative in the onboard direction)."""

    def __init__(self, mode: str = "auto", model_cfg=None,
                 block_nbytes: int = 0, page_size: int = 16,
                 prefill_chunk_tokens: int = 256,
                 chip_flops: Optional[float] = None,
                 bytes_per_s: Optional[float] = None):
        if mode not in ("auto", "always", "never"):
            raise ValueError(f"kvbm_gate must be auto|always|never, "
                             f"got {mode!r}")
        self.mode = mode
        self.model_cfg = model_cfg
        self.block_nbytes = block_nbytes
        self.page_size = page_size
        self.chunk_tokens = max(prefill_chunk_tokens, page_size)
        self.chip_flops = chip_flops or _detect_chip_flops()
        self.bytes_per_s = bytes_per_s or _h2d_bytes_s()
        self.skipped = 0  # onboards refused (recompute was cheaper)

    def restore_seconds(self, n_blocks: int) -> float:
        return roofline.kvbm_restore_seconds(
            n_blocks * self.block_nbytes, self.bytes_per_s,
            overhead_s=TRANSFER_OVERHEAD_S)

    def recompute_seconds(self, n_blocks: int) -> float:
        n_tokens = n_blocks * self.page_size
        n_chunks = max(1, -(-n_tokens // self.chunk_tokens))
        return roofline.kvbm_recompute_seconds(
            self.model_cfg, n_tokens, self.chip_flops, n_dispatches=n_chunks)

    def should_onboard(self, n_blocks: int) -> bool:
        if n_blocks <= 0 or self.mode == "never":
            if self.mode == "never" and n_blocks > 0:
                self.skipped += 1
            return False
        if self.mode == "always" or self.model_cfg is None:
            return True
        ok = self.restore_seconds(n_blocks) <= self.recompute_seconds(n_blocks)
        if not ok:
            self.skipped += 1
        return ok

    def explain(self, n_blocks: int) -> dict:
        return {
            "n_blocks": n_blocks,
            "restore_s": round(self.restore_seconds(n_blocks), 6),
            "recompute_s": round(self.recompute_seconds(max(n_blocks, 1)), 6)
            if self.model_cfg is not None else None,
            "mode": self.mode,
        }


def _detect_chip_flops() -> float:
    """Peak bf16 FLOP/s of the chip actually serving, for the recompute
    side of the gate, from the one chip table (profiler/systems.py). An
    unknown TPU is an error; a CPU process (tests, local development) has
    no peak of its own and plans for the v5e the repo is measured on."""
    import jax

    from dynamo_tpu.profiler import systems

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return systems.CHIPS["v5e"].bf16_flops
    return systems.require_chip(dev.device_kind).bf16_flops
