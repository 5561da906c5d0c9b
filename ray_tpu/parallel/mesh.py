"""Device mesh construction for dp/fsdp/sp/tp/ep parallelism.

The reference has no native model-parallel layout (SURVEY §2.4: TP/PP arrive
via user libraries; Ray only gang-schedules).  Here the mesh IS the
framework's communication backend: axes map onto ICI dimensions so that
tensor-parallel collectives ride the fastest links, fsdp next, data-parallel
outermost (possibly spanning DCN between slices).

Axis order (outer → inner): ("stage", "data", "fsdp", "seq", "expert",
"tensor").  "tensor" is innermost = most bandwidth-hungry (per-layer
all-reduces), matching the scaling-book recipe of putting TP on the
shortest ICI rings; "stage" (pipeline parallelism) is outermost — stages
exchange only activation boundaries, the lowest-bandwidth traffic, and
often span slices/DCN.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("stage", "data", "fsdp", "seq", "expert", "tensor")


@dataclass
class MeshConfig:
    """Sizes per axis; -1 means "absorb all remaining devices"."""

    data: int = -1
    fsdp: int = 1
    seq: int = 1
    expert: int = 1
    tensor: int = 1
    stage: int = 1

    def resolve(self, n_devices: int) -> tuple[int, ...]:
        sizes = [self.stage, self.data, self.fsdp, self.seq, self.expert,
                 self.tensor]
        fixed = 1
        wild = None
        for i, s in enumerate(sizes):
            if s == -1:
                if wild is not None:
                    raise ValueError("only one mesh axis may be -1")
                wild = i
            else:
                fixed *= s
        if wild is not None:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {fixed}")
            sizes[wild] = n_devices // fixed
        if int(np.prod(sizes)) != n_devices:
            raise ValueError(
                f"mesh {dict(zip(AXES, sizes))} != {n_devices} devices")
        return tuple(sizes)


def mesh_shape_for(n_devices: int, config: MeshConfig | None = None):
    return (config or MeshConfig()).resolve(n_devices)


def create_mesh(config: MeshConfig | None = None,
                devices: list | None = None) -> Mesh:
    """Build the framework mesh.  On real TPU slices jax orders devices by
    ICI coordinates, so reshaping the flat device list keeps neighboring
    mesh indices physically adjacent (contiguous rings per axis)."""
    devices = devices if devices is not None else jax.devices()
    shape = mesh_shape_for(len(devices), config)
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        # A shape the slice's ICI topology cannot host raises here; a
        # plain reshape would hide it behind non-adjacent rings.
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        # CPU/virtual devices carry no coordinates: plain reshape.
        dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, AXES)


def local_mesh() -> Mesh:
    """Single-process mesh over whatever devices exist (1 on the dev chip,
    8 on the virtual-CPU test platform)."""
    n = len(jax.devices())
    if n == 1:
        return create_mesh(MeshConfig(data=1))
    # Default split: fsdp over everything (ZeRO-3-style) for tests.
    return create_mesh(MeshConfig(data=1, fsdp=n))
