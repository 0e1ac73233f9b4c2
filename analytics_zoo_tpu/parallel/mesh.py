"""Device-mesh construction: the TPU-native replacement for the reference's
five communication backends (SURVEY.md §2.4; reference: Spark block-manager
AllReduce at zoo/.../pipeline/api/keras/models/Topology.scala:1203-1206, Gloo at
pyzoo/zoo/orca/learn/horovod/horovod_ray_runner.py:119, DDP-gloo at
pyzoo/zoo/orca/learn/pytorch/torch_runner.py:136-140).

One mesh, named axes, XLA collectives over ICI/DCN. Axis conventions:

* ``dp``   — data parallel (gradient psum rides ICI; across hosts, DCN)
* ``fsdp`` — parameter/optimizer sharding (ZeRO-style, all_gather/reduce_scatter)
* ``tp``   — tensor parallel (matmul sharding)
* ``sp``   — sequence/context parallel (ring attention / all-to-all)

Axes of size 1 are free; estimators default to pure DP but every train step is
jitted over the full mesh so tp/sp/fsdp can be enabled by config alone.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_ORDER = ("dp", "fsdp", "tp", "sp")
# optional axes appended to the mesh only when requested: pipeline stages
# (parallel/pipeline_parallel.py) and MoE experts (expert_parallel.py)
OPTIONAL_AXES = ("pp", "ep")


def resolve_axis_sizes(n_devices: int, axes: Dict[str, int]) -> Dict[str, int]:
    """Resolve ``-1`` wildcards so that the product of axis sizes == n_devices.

    At most one axis may be -1. Missing canonical axes get size 1;
    unknown axis names raise (a silently-dropped axis previously crashed
    later with an opaque reshape error).
    """
    unknown = set(axes) - set(AXIS_ORDER) - set(OPTIONAL_AXES)
    if unknown:
        raise ValueError(
            f"unknown mesh axes {sorted(unknown)} — known: "
            f"{AXIS_ORDER + OPTIONAL_AXES}")
    sizes = {a: int(axes.get(a, 1)) for a in AXIS_ORDER}
    for a in OPTIONAL_AXES:
        if a in axes:
            sizes[a] = int(axes[a])
    wild = [a for a, v in sizes.items() if v == -1]
    if len(wild) > 1:
        raise ValueError(f"at most one mesh axis may be -1, got {wild}")
    fixed = math.prod(v for v in sizes.values() if v != -1)
    if wild:
        if n_devices % fixed != 0:
            raise ValueError(
                f"cannot fill axis {wild[0]}: {n_devices} devices not divisible "
                f"by fixed product {fixed}")
        sizes[wild[0]] = n_devices // fixed
    elif fixed != n_devices:
        raise ValueError(
            f"mesh axes {sizes} use {fixed} devices but {n_devices} available")
    return sizes


def create_mesh(axes: Optional[Dict[str, int]] = None,
                devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a named-axis Mesh over all (or given) devices.

    ``mesh_utils.create_device_mesh`` lays the axes out along the physical
    ICI topology on TPU devices (virtual/CPU devices have none: it reshapes
    them in id order). A topology it cannot map raises — a silently
    reshaped mesh would put dp neighbours on distant chips.
    """
    devices = list(devices if devices is not None else jax.devices())
    sizes = resolve_axis_sizes(len(devices), axes or {"dp": -1})
    # drop trailing size-1 axes? No — keep all four so PartitionSpecs are
    # stable; optional pp/ep axes append only when requested
    names = AXIS_ORDER + tuple(a for a in OPTIONAL_AXES if a in sizes)
    shape = tuple(sizes[a] for a in names)
    from jax.experimental import mesh_utils
    return Mesh(mesh_utils.create_device_mesh(shape, devices=devices), names)


def data_sharding(mesh: Mesh, ndim: int, batch_axes: Tuple[str, ...] = ("dp", "fsdp")
                  ) -> NamedSharding:
    """Sharding for a host batch: leading dim split across dp (and fsdp, which
    acts as an extra data axis for activations when ZeRO-sharding params)."""
    axes: Tuple = (batch_axes,) + (None,) * (ndim - 1)
    return NamedSharding(mesh, P(*axes))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def local_mesh_devices(mesh: Mesh) -> List[jax.Device]:
    pid = jax.process_index()
    return [d for d in mesh.devices.flat if d.process_index == pid]


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape.get(name, 1)


def parse_mesh_axes(spec: str) -> Dict[str, int]:
    """Parse a ``ZOO_MESH_AXES`` string — ``"dp=2,fsdp=2,tp=2"`` (one axis
    may be ``-1`` to absorb the remaining devices) — into the axes dict
    ``create_mesh``/``init_orca_context`` take. Validates axis names
    against the canonical + optional sets so a typo fails here, not as an
    opaque reshape error later."""
    axes: Dict[str, int] = {}
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"ZOO_MESH_AXES entry {part!r} is not name=size "
                "(expected e.g. 'dp=2,fsdp=2,tp=2')")
        name, _, size = part.partition("=")
        name = name.strip()
        if name not in AXIS_ORDER + OPTIONAL_AXES:
            raise ValueError(
                f"ZOO_MESH_AXES axis {name!r} unknown — known: "
                f"{AXIS_ORDER + OPTIONAL_AXES}")
        axes[name] = int(size)
    if not axes:
        raise ValueError(f"ZOO_MESH_AXES {spec!r} names no axes")
    return axes


def batch_divisor(mesh: Mesh) -> int:
    """Global batch must be a multiple of this (the TPU analogue of the
    reference's node_num*core_num rule, pyzoo/zoo/tfpark/tf_dataset.py:135-149)."""
    return mesh_axis_size(mesh, "dp") * mesh_axis_size(mesh, "fsdp")
