"""Device-mesh construction with named parallelism axes.

This replaces the reference's NCCL process-group bootstrap
(`python/ray/train/torch/config.py:69` `_setup_torch_process_group`): on TPU
the "process group" is a `jax.sharding.Mesh` whose axes carry the parallelism
strategy, and collectives are XLA ops riding ICI (see SURVEY.md §2.6).

Canonical axis names (outer → inner, DCN-slowest to ICI-fastest):

  dp    data parallel (pure replication of params)
  fsdp  fully-sharded data parallel (params sharded along it; ZeRO analogue)
  pp    pipeline stages
  sp    sequence/context parallel (ring attention)
  tp    tensor parallel (megatron-style)
  ep    expert parallel (MoE)

``create_device_mesh`` orders axes so that tp/sp land on the
fastest-adjacent ICI dimensions of the physical torus.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

AXIS_ORDER = ("dp", "fsdp", "pp", "sp", "ep", "tp")


def create_mesh(
    axes: Dict[str, int],
    devices: Optional[Sequence] = None,
    allow_split_physical_axes: bool = True,
) -> Mesh:
    """Build a Mesh from {axis_name: size}; size -1 means "all remaining".

    Axes are laid out in AXIS_ORDER so the innermost (tp) axis maps to
    physically adjacent chips — XLA collectives on it then ride the
    shortest ICI links, the analogue of NVLink-island-first placement.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    sizes = dict(axes)
    unknown = [k for k, v in sizes.items() if v == -1]
    if len(unknown) > 1:
        raise ValueError("at most one axis may be -1")
    known = int(np.prod([v for v in sizes.values() if v != -1])) or 1
    if unknown:
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        sizes[unknown[0]] = n // known
    total = int(np.prod(list(sizes.values()))) if sizes else 1
    if total != n:
        raise ValueError(
            f"mesh axes {sizes} require {total} devices, have {n}"
        )
    names = [a for a in AXIS_ORDER if a in sizes]
    extra = [a for a in sizes if a not in AXIS_ORDER]
    names += extra
    shape = [sizes[a] for a in names]
    # Topology-aware on a TPU (a plain reshape elsewhere).  An error here is
    # a layout the physical torus cannot carry; a row-major guess in its
    # place would hide that, so it propagates.
    dev_array = mesh_utils.create_device_mesh(
        shape, devices=devices,
        allow_split_physical_axes=allow_split_physical_axes,
    )
    return Mesh(dev_array, tuple(names))


def single_device_mesh(axis: str = "dp") -> Mesh:
    return Mesh(np.asarray(jax.devices()[:1]), (axis,))


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


def local_device_count() -> int:
    return jax.local_device_count()
