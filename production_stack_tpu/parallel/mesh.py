"""Device-mesh construction for the serving engine.

TPU-first parallelism: a single logical ``jax.sharding.Mesh`` with named axes

    ("data", "stage", "seq", "tensor", "expert")

- ``data``   replica data parallelism (whole-model replicas within one process;
             cross-pod replica DP is the router's job, as in the reference's
             replicaCount + load balancing — SURVEY.md §2.9).
- ``stage``, ``seq``  nothing shards over them any more (pipeline stages
             and ring prefill were removed): ``LLMEngine`` refuses a size
             above 1, and the axes go with the next change to the mesh
             (ROADMAP D19).
- ``tensor`` tensor parallelism over ICI (reference passes
             --tensor-parallel-size through to vLLM).
- ``expert`` expert parallelism for MoE layers.

Axes of size 1 cost nothing: XLA inserts no collectives for them, so the
same model code runs unchanged from 1 chip to a multi-host pod.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_DATA = "data"
AXIS_STAGE = "stage"
AXIS_SEQ = "seq"
AXIS_TENSOR = "tensor"
AXIS_EXPERT = "expert"

MESH_AXES = (AXIS_DATA, AXIS_STAGE, AXIS_SEQ, AXIS_TENSOR, AXIS_EXPERT)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape; -1 on data axis means "use all remaining devices"."""

    data: int = 1
    stage: int = 1
    seq: int = 1
    tensor: int = -1
    expert: int = 1

    def resolved(self, n_devices: int) -> "MeshConfig":
        sizes = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}
        unknown = [k for k, v in sizes.items() if v == -1]
        if len(unknown) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {unknown}")
        if unknown:
            known = math.prod(v for v in sizes.values() if v != -1)
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {known}"
                )
            sizes[unknown[0]] = n_devices // known
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(
                f"mesh {sizes} does not use all {n_devices} devices"
            )
        return MeshConfig(**sizes)

    @property
    def shape(self) -> tuple[int, ...]:
        return (self.data, self.stage, self.seq, self.tensor, self.expert)


def build_mesh(
    config: MeshConfig | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build the 5-axis logical mesh over the given (default: all) devices.

    ``jax.experimental.mesh_utils`` orders the devices so that the tensor
    axis — the most communication-hungry — lands on ICI-adjacent chips. A
    topology it cannot lay the mesh on raises: a naive reshape of the
    device list would run, slowly, over the wrong links.
    """
    devices = list(devices if devices is not None else jax.devices())
    config = config or MeshConfig()
    fixed = [s for s in config.shape if s != -1]
    if -1 not in config.shape and math.prod(fixed) < len(devices):
        # fully specified mesh smaller than the host's device count: use a
        # prefix of the devices (tests pin small meshes on 8-dev CPU hosts)
        devices = devices[: math.prod(fixed)]
    config = config.resolved(len(devices))
    from jax.experimental import mesh_utils

    device_array = mesh_utils.create_device_mesh(
        config.shape, devices=np.asarray(devices)
    )
    if jax.process_count() > 1:
        # multi-controller: a mesh that omits any process's devices leaves
        # that process with ZERO addressable shards — even "replicated"
        # outputs are unfetchable there and its replay loop dies. Fail at
        # construction, where the shape error is obvious.
        procs = {d.process_index for d in np.asarray(device_array).flat}
        if procs != set(range(jax.process_count())):
            raise ValueError(
                f"mesh {config.shape} covers processes {sorted(procs)} but "
                f"the group has {jax.process_count()} — every controller "
                "process must own a slice of the mesh (use -1 on the data "
                "axis to absorb all devices)"
            )
    return Mesh(device_array, MESH_AXES)


def local_mesh() -> Mesh:
    """Single-process mesh over all visible devices, all on the tensor axis."""
    return build_mesh(MeshConfig())
