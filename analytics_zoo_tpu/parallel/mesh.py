"""Device mesh discovery and construction.

This replaces the reference's entire cluster bootstrap (NNContext /
SparkContext / Engine.init, reference: zoo/.../common/NNContext.scala:132-206):
on TPU the "cluster" is the device mesh, and the communication backend is
XLA collectives over ICI (intra-slice) and DCN (cross-slice) — there is no
Spark shuffle to configure.

Axis convention (superset of the reference's data-parallel-only world,
SURVEY §2.10):
  data   — data parallelism (gradient psum; the reference's AllReduce)
  fsdp   — parameter/optimizer sharding (ZeRO-style), rides ICI
  tensor — tensor/model parallelism within layers
  seq    — sequence/context parallelism (ring attention)
  expert — expert parallelism (MoE)
  pipe   — pipeline parallelism stages
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("data", "fsdp", "tensor", "seq", "expert", "pipe")


def create_mesh(axes: Optional[Dict[str, int]] = None,
                devices=None) -> Mesh:
    """Build a Mesh over ``devices`` with named axis sizes.

    With no arguments: all local devices on one ``data`` axis — the
    reference's data-parallel topology.  Axis sizes of -1 absorb the
    remaining devices.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    axes = dict(axes or {"data": n})
    # resolve a single -1 wildcard
    known = math.prod(v for v in axes.values() if v != -1)
    for k, v in axes.items():
        if v == -1:
            axes[k] = n // known
    total = math.prod(axes.values())
    if total != n:
        raise ValueError(
            f"Mesh axes {axes} need {total} devices, have {n}")
    names = tuple(axes.keys())
    shape = tuple(axes.values())
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, names)


def data_sharding(mesh: Mesh, batch_axes: Sequence[str] = ("data", "fsdp")):
    """NamedSharding for a batch: leading dim split over the data-ish axes
    present in the mesh, rest replicated."""
    present = tuple(a for a in batch_axes if a in mesh.axis_names
                    and mesh.shape[a] > 1)
    spec = P(present if present else None)
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def dp_size(mesh: Mesh) -> int:
    size = 1
    for a in ("data", "fsdp"):
        if a in mesh.axis_names:
            size *= mesh.shape[a]
    return size


_DEFAULT_MESH: Optional[Mesh] = None
_ACTIVE_MESH: Optional[Mesh] = None


def set_default_mesh(mesh: Optional[Mesh]):
    global _DEFAULT_MESH
    _DEFAULT_MESH = mesh


class active_mesh:
    """Context manager marking the mesh a Trainer is tracing/executing
    under, so mesh-aware layers (ring attention) see the mesh passed to
    ``compile(mesh=...)`` rather than only the process default."""

    def __init__(self, mesh: Optional[Mesh]):
        self.mesh = mesh

    def __enter__(self):
        global _ACTIVE_MESH
        self._prev = _ACTIVE_MESH
        _ACTIVE_MESH = self.mesh
        return self.mesh

    def __exit__(self, *exc):
        global _ACTIVE_MESH
        _ACTIVE_MESH = self._prev
        return False


def get_active_mesh() -> Optional[Mesh]:
    """The mesh of the currently-executing Trainer (if inside one),
    else the process default — WITHOUT auto-creating one."""
    return _ACTIVE_MESH if _ACTIVE_MESH is not None else _DEFAULT_MESH


def get_step_mesh() -> Optional[Mesh]:
    """The mesh of the currently-executing Trainer step, or None outside
    one — NO fallback to the process default: code that lays work out
    over the mesh (the flash kernel's shard_map) must only do so where
    the arrays really are laid out on it, and a serving trace on its own
    device is not."""
    return _ACTIVE_MESH


def get_default_mesh() -> Mesh:
    global _DEFAULT_MESH
    if _DEFAULT_MESH is None:
        _DEFAULT_MESH = create_mesh()
    return _DEFAULT_MESH
