"""Sharded serving: replica GROUPS over device sub-meshes.

Every serving layer below this module equates "replica" with "one
device", so a model exceeding one chip's HBM cannot serve at all.  A
:class:`ShardGroupSet` generalizes the ``ReplicaSet`` contract to M
replica *groups*: each group is a pjit executable over a sub-mesh
carved from the local device set, the model's weight tree sharded
across the group's devices by a declarative rule table
(:mod:`analytics_zoo_tpu.parallel.sharding`).

Compile-once / place-everywhere survives the generalization intact —
that is the point of building this on the ``ReplicaSet`` hooks rather
than beside them.  The sharded forward is lowered and compiled ONCE
per padded signature (on group 0's sub-mesh), the executable is
serialized to the persistent store, and every other group rehydrates
the same bytes with only the :class:`DeviceAssignment` rewritten to
span the group's devices — a ``(1, group_size)`` assignment (one
replica, ``group_size`` partitions) instead of the single-device
``(1, 1)``.  A second group, a second process, and a pager fault-in
all instantiate with ZERO compile events.

Scheduling, health probing, elasticity, and in-flight accounting are
inherited: the coalescer's least-outstanding-work scheduler picks
among *groups* exactly as it picked among devices, because a group IS
a replica to every caller (``ShardGroup`` subclasses ``Replica``;
``group.device`` is the group's first device for anything that wants
one device, e.g. log labels).

Bit-exactness: with the default (and recommended) column rules —
every matched weight sharded along its LAST axis — XLA partitions the
forward as all-gather + full local contraction, which performs the
identical float operations in the identical order as the unsharded
program, so 1-group-of-N output is bit-identical to single-device
(test_serving_shardgroup pins this).  Contraction-dim (row) sharding
instead lowers to partial-dot + psum, whose float add order differs:
supported, but NOT bit-exact — choose it for memory, not for the
oracle.

The mesh spec (``normalize_mesh_spec``) is a small JSON-safe dict so
it rides the deploy envelope end to end: ``InferenceModel(mesh=...)``,
``ModelRegistry.deploy(..., mesh=...)``, the pager's rebuild recipe,
and the fleet artifact's ``mesh`` section all build the identical
sharded executable from the identical spec — and the spec's canonical
form is folded into the execstore fingerprint, so two deploys
differing only in mesh shape or partition rules can never serve each
other's entries.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability import profile as _profile
from ..observability.log import get_logger as _get_logger
from ..parallel.mesh import AXES as _MESH_AXES
from ..parallel.sharding import (fsdp_tree, replicated_tree,
                                 tensor_parallel_tree)
from ..pipeline.inference.serving import Replica, ReplicaSet

_slog = _get_logger("zoo.shardgroup")

_STRATEGIES = ("tp", "tensor", "fsdp", "replicate")


def normalize_mesh_spec(spec) -> Dict[str, Any]:
    """Validate and canonicalize a deploy-spec ``mesh`` section.

    Accepted keys::

        axes:          {axis_name: size} — the sub-mesh each group
                       spans; group size = product of sizes.  Axis
                       names must come from parallel.mesh.AXES.
        groups:        "all" (default) — as many groups as the device
                       set holds — or an explicit int >= 1.
        strategy:      "tp" (default) | "tensor" | "fsdp" | "replicate"
        rules:         {param-path regex: axis index} for tp — when
                       omitted, the default column rules shard every
                       >=2-D weight's LAST axis (bit-exact, see module
                       docstring).
        fsdp_min_size: replicate params smaller than this (fsdp only).

    Returns a plain-dict canonical form (sorted keys via
    :func:`mesh_spec_canonical`) that is BOTH the build input and the
    fingerprint component — there is no second interpretation to
    drift."""
    if not isinstance(spec, dict):
        raise ValueError(f"mesh spec must be a dict, got {type(spec).__name__}")
    unknown = set(spec) - {"axes", "groups", "strategy", "rules",
                           "fsdp_min_size"}
    if unknown:
        raise ValueError(f"unknown mesh spec keys: {sorted(unknown)}")
    axes_in = spec.get("axes") or {"tensor": 1}
    if not isinstance(axes_in, dict) or not axes_in:
        raise ValueError("mesh spec 'axes' must be a non-empty dict")
    axes: Dict[str, int] = {}
    for name, size in axes_in.items():
        if name not in _MESH_AXES:
            raise ValueError(
                f"unknown mesh axis {name!r} (choose from {_MESH_AXES})")
        size = int(size)
        if size < 1:
            raise ValueError(f"mesh axis {name!r} size must be >= 1")
        axes[name] = size
    groups = spec.get("groups", "all")
    if groups != "all":
        groups = int(groups)
        if groups < 1:
            raise ValueError("mesh spec 'groups' must be >= 1 or 'all'")
    strategy = spec.get("strategy", "tp")
    if strategy not in _STRATEGIES:
        raise ValueError(f"unknown sharding strategy {strategy!r} "
                         f"(choose from {_STRATEGIES})")
    rules = spec.get("rules") or None
    if rules is not None:
        if not isinstance(rules, dict):
            raise ValueError("mesh spec 'rules' must map regex -> axis index")
        rules = {str(k): int(v) for k, v in rules.items()}
    return {"axes": axes, "groups": groups, "strategy": strategy,
            "rules": rules,
            "fsdp_min_size": int(spec.get("fsdp_min_size", 2 ** 14))}


def group_size(spec: Dict[str, Any]) -> int:
    """Devices per group: the product of the spec's axis sizes."""
    n = 1
    for s in spec["axes"].values():
        n *= int(s)
    return n


def mesh_spec_canonical(spec: Dict[str, Any]) -> str:
    """The spec's canonical JSON — the execstore fingerprint component
    (sorted keys, no whitespace variance) AND the ``--by-mesh`` meta."""
    return json.dumps(spec, sort_keys=True, separators=(",", ":"))


def carve_groups(devices, spec: Dict[str, Any]
                 ) -> List[Tuple[Tuple, Mesh]]:
    """Carve ``devices`` into replica groups: consecutive runs of
    ``group_size`` devices, each wrapped in a Mesh shaped by the
    spec's axes.  Leftover devices (count not divisible) stay idle —
    logged, never silently half-grouped."""
    devs = list(devices)
    gsize = group_size(spec)
    if gsize > len(devs):
        raise ValueError(
            f"mesh spec needs {gsize} devices per group but only "
            f"{len(devs)} are available")
    n_groups = len(devs) // gsize
    if spec["groups"] != "all":
        if spec["groups"] > n_groups:
            raise ValueError(
                f"mesh spec asks for {spec['groups']} groups of "
                f"{gsize} but only {len(devs)} devices are available")
        n_groups = spec["groups"]
    leftover = len(devs) - n_groups * gsize
    if leftover and spec["groups"] == "all":
        _slog.info("shardgroup_devices_idle", idle=leftover,
                   group_size=gsize, groups=n_groups)
    names = tuple(spec["axes"])
    shape = tuple(spec["axes"][n] for n in names)
    out = []
    for g in range(n_groups):
        gdevs = tuple(devs[g * gsize:(g + 1) * gsize])
        out.append((gdevs, Mesh(np.asarray(gdevs).reshape(shape), names)))
    return out


def _column_tree(params, mesh: Mesh, axis: str = "tensor"):
    """The default rule table: shard every >=2-D param along its LAST
    axis when divisible by the tensor-axis size, replicate the rest.
    Last-axis (column) splits keep the partitioned program gather-only
    — the bit-exact layout (module docstring)."""
    if axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return replicated_tree(params, mesh)
    n = mesh.shape[axis]

    def rule(p):
        shape = np.shape(p)
        if len(shape) >= 2 and shape[-1] % n == 0:
            spec = [None] * len(shape)
            spec[-1] = axis
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(rule, params)


def spec_tree_for(params, mesh: Mesh, spec: Dict[str, Any]):
    """Resolve the spec's strategy + rule table into a NamedSharding
    tree for ``params`` on ``mesh``."""
    strategy = spec["strategy"]
    if strategy == "replicate":
        return replicated_tree(params, mesh)
    if strategy == "fsdp":
        return fsdp_tree(params, mesh, axis="fsdp",
                         min_size=spec["fsdp_min_size"])
    # tp / tensor
    if spec["rules"]:
        return tensor_parallel_tree(params, mesh, spec["rules"])
    return _column_tree(params, mesh)


class ShardGroup(Replica):
    """One replica group: a tuple of devices, the Mesh spanning them,
    and the group's sharded copy of the params.  IS-A ``Replica`` so
    the scheduler, health probing, elasticity, and per-replica
    counters apply unchanged — ``device`` is the group's first device
    for anything that wants a single device (log labels, backend
    access)."""

    __slots__ = ("devices", "mesh", "in_sharding")

    def __init__(self, index: int, devices: Tuple, mesh: Mesh,
                 params_flat: List):
        super().__init__(index, devices[0], params_flat)
        self.devices = tuple(devices)
        self.mesh = mesh
        # batch inputs are replicated across the group: every device
        # holds the full padded batch, the weights carry the sharding
        self.in_sharding = NamedSharding(mesh, P())

    def __repr__(self):
        return (f"ShardGroup({self.index}, {len(self.devices)} devices, "
                f"healthy={self.healthy}, active={self.active})")


class ShardGroupSet(ReplicaSet):
    """M replica groups over device sub-meshes — the ``ReplicaSet``
    contract with "device" generalized to "group" (see module
    docstring for the full design).  Constructed with a normalized
    mesh spec; everything else (store protocol, scheduler, health,
    elasticity) is inherited behavior."""

    def __init__(self, fn, params, mesh_spec, devices=None, **kw):
        self._mesh_spec = normalize_mesh_spec(mesh_spec)
        self._spec_canonical = mesh_spec_canonical(self._mesh_spec)
        super().__init__(fn, params, devices=devices, **kw)

    # ---- placement-unit hooks ----
    def _carve_units(self, devices) -> List:
        devs = list(devices) if devices else list(jax.local_devices())
        if not devs:
            raise ValueError("ShardGroupSet needs at least one device")
        return carve_groups(devs, self._mesh_spec)

    @staticmethod
    def _unit_devices(unit) -> Tuple:
        return unit[0]

    def _make_jit(self, units):
        # outputs replicate across the group — serving returns whole
        # batches to the host, and a replicated output disassembles
        # into identical per-device shards (dispatch() takes shard 0)
        _, mesh0 = units[0]
        return jax.jit(self._fn,
                       out_shardings=NamedSharding(mesh0, P()))

    def _place_params(self, params, unit):
        gdevs, mesh = unit
        return jax.device_put(
            params, spec_tree_for(params, mesh, self._mesh_spec))

    def _make_replica(self, index: int, unit, placed) -> ShardGroup:
        gdevs, mesh = unit
        return ShardGroup(index, gdevs, mesh,
                          jax.tree_util.tree_leaves(placed))

    def _input_sharding(self):
        return self.groups[0].in_sharding

    def _fp_parts(self) -> Tuple:
        # the canonical mesh spec rotates the store key whenever the
        # mesh shape, group layout, or partition rules change — the
        # PR 14 discipline (sampling config in the fingerprint),
        # applied to layout
        return ("shardgroup-forward", self._spec_canonical)

    def _store_meta(self) -> Dict[str, Any]:
        return {"kind": "shardgroup-forward",
                "mesh": {"axes": dict(self._mesh_spec["axes"]),
                         "strategy": self._mesh_spec["strategy"],
                         "group_size": self.group_size}}

    def span_labels(self, replica) -> Dict[str, Any]:
        # a "replica" here IS a group — label both so dashboards keyed
        # on either name resolve, and traces show which group served
        return {"replica": replica.index, "group": replica.index}

    def _place_serialized(self, ser: bytes, group: ShardGroup):
        """Rehydrate onto one GROUP: a ``(1, group_size)`` device
        assignment — one replica, ``group_size`` partitions spanning
        the group's devices — instead of the base class's ``(1, 1)``.
        Still a load, never a compile: zero ``backend_compile`` events
        (``test_warm_store_second_set_zero_compiles`` counts)."""
        return self._load_serialized(ser, group.devices)

    # ---- identity / introspection ----
    @property
    def groups(self) -> Tuple[ShardGroup, ...]:
        return self.replicas

    @property
    def group_size(self) -> int:
        return len(self.replicas[0].devices)

    @property
    def mesh_spec(self) -> Dict[str, Any]:
        return self._mesh_spec

    # ---- dispatch ----
    def dispatch(self, replica: ShardGroup, batched, spans=(),
                 key: Optional[Tuple] = None):
        """Upload one exactly-bucket-sized host batch to the group
        (replicated across its devices) and run the group's sharded
        executable; returns the DEVICE result tree (fetch via
        :func:`fetch_rows`).  Mirrors ``ReplicaSet.dispatch`` with the
        raw single-device ``execute`` swapped for ``execute_sharded``
        + shard reassembly — outputs are replicated (``_make_jit``
        pins ``out_shardings``), so each output is rebuilt from its
        per-device shards with the group's mesh."""
        if key is None:
            key = self._key(batched)
        exe = self._exes[key][replica.index]
        for s in spans:
            s.phase_start("device_put")
        in_sh = replica.in_sharding
        dev_x = [jax.device_put(a, in_sh)
                 for a in jax.tree_util.tree_leaves(batched)]
        _profile.note_transfer("h2d")
        args = replica.params_flat + dev_x
        kept = self._kept[key]
        if kept is not None:
            args = [args[i] for i in kept]
        for s in spans:
            s.phase_start("execute")
        results = exe.execute_sharded(args)
        shards_per_out = results.disassemble_into_single_device_arrays()
        out_sh = NamedSharding(replica.mesh, P())
        outs = [jax.make_array_from_single_device_arrays(
                    av.shape, out_sh, shards)
                for av, shards in zip(self._out_avals[key],
                                      shards_per_out)]
        return jax.tree_util.tree_unflatten(self._out_tree[key], outs)

    # ---- stats ----
    def stats(self) -> Dict[str, Any]:
        out = super().stats()
        out.update({
            "groups": len(self.replicas),
            "group_size": self.group_size,
            "group_dispatches": {g.index: g.dispatches
                                 for g in self.replicas},
            "mesh_axes": dict(self._mesh_spec["axes"]),
        })
        return out
