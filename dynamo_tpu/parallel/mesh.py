"""Device-mesh construction for TPU slices.

The reference exposes tensor parallelism as an engine CLI flag (`--tp N`,
/root/reference/examples/deploy/sglang/agg.yaml:40-41) and data parallelism as
K8s `replicas`. Here `--tp` maps to the size of the `model` mesh axis laid out
over ICI; `data` is the in-engine batch axis; `expert` is the MoE axis
(BASELINE.json config #5). Multi-host slices extend the same mesh over DCN —
XLA places collectives on ICI within a host-connected slice automatically when
the mesh axis ordering matches the physical device order
(`jax.experimental.mesh_utils.create_device_mesh`).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

log = logging.getLogger("dynamo_tpu.mesh")

AXES = ("data", "expert", "model")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    tensor_parallel: int = 1  # `model` axis (intra-slice ICI)
    data_parallel: int = 1  # `data` axis
    expert_parallel: int = 1  # `expert` axis (MoE)

    @property
    def num_devices(self) -> int:
        return self.tensor_parallel * self.data_parallel * self.expert_parallel


def build_mesh(
    cfg: MeshConfig = MeshConfig(),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a (data, expert, model) mesh.

    The `model` axis is innermost so tensor-parallel collectives ride the
    fastest ICI links (nearest-neighbour on the torus).
    """
    explicit = devices is not None
    devices = list(devices if explicit else jax.devices())
    n = cfg.num_devices
    if n > len(devices):
        raise ValueError(
            f"mesh needs {n} devices (dp={cfg.data_parallel} x "
            f"ep={cfg.expert_parallel} x tp={cfg.tensor_parallel}), "
            f"only {len(devices)} available"
        )
    shape = (cfg.data_parallel, cfg.expert_parallel, cfg.tensor_parallel)
    return Mesh(_device_grid(shape, devices[:n], explicit), AXES)


def _device_grid(shape, devices, explicit: bool) -> np.ndarray:
    """Devices arranged as `shape` in the order that puts the innermost
    mesh axis on the fastest ICI links. A topology error is an error —
    except in the one documented case: the caller handed over its OWN
    device list (colocated disagg roles on disjoint sub-meshes of one
    slice) and that subset is not a contiguous cuboid of the torus, so no
    topology-aware order exists for it. Then the caller's order is used,
    and said so."""
    from jax.experimental import mesh_utils

    try:
        return mesh_utils.create_device_mesh(shape, devices=devices)
    except (AssertionError, NotImplementedError) as e:
        if not explicit:
            raise
        log.warning(
            "no topology-aware order for the explicit device subset %s "
            "(%s); using the order given", [d.id for d in devices], e)
        return np.array(devices).reshape(shape)


def single_device_mesh() -> Mesh:
    return build_mesh(MeshConfig())


LONG_CONTEXT_AXES = ("seq", "model")


def build_long_context_mesh(
    sequence_parallel: int,
    tensor_parallel: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """("seq", "model") mesh for ring/Ulysses long-context prefill.

    `seq` is outermost so each ring hop (ppermute neighbour) is one ICI step;
    `model` stays innermost for the usual TP collectives. Used by the
    long-context prefill path (dynamo_tpu.ops.ring_attention), which the
    reference has no analogue for (SURVEY.md §5).
    """
    explicit = devices is not None
    devices = list(devices if explicit else jax.devices())
    n = sequence_parallel * tensor_parallel
    if n > len(devices):
        raise ValueError(
            f"long-context mesh needs {n} devices (sp={sequence_parallel} x "
            f"tp={tensor_parallel}), only {len(devices)} available"
        )
    shape = (sequence_parallel, tensor_parallel)
    return Mesh(_device_grid(shape, devices[:n], explicit), LONG_CONTEXT_AXES)
