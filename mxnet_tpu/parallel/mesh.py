"""Mesh helpers: the ONE place device meshes and mesh-axis plumbing
come from.

Every parallel module (dp/zero/tp/pp/sp/embedding, the planner) builds
its mesh through these constructors and imports `shard_map`/`pcast`
from here — a mesh axis name used anywhere in the package is declared in
AXIS_NAMES, and `axis_size`/`data_axis` replace the ad-hoc `mesh.shape[name]` /
`mesh.axis_names[0]` lookups that used to be copied per module.
"""
from __future__ import annotations

import numpy as np

from jax import shard_map  # noqa: F401  (re-export)
from jax.lax import pcast  # noqa: F401  (re-export)

# canonical axis vocabulary (docs/PLANNER.md): data-parallel batch axis,
# megatron/tensor axis, pipeline-stage axis, sequence axis, expert axis.
# Aliases map the short spellings the shard_map modules historically
# used onto the canonical names.
AXIS_NAMES = ("data", "model", "pipe", "sp", "ep")
AXIS_ALIASES = {"dp": "data", "tp": "model", "pp": "pipe"}


def build_mesh(axis_sizes: dict, devices=None):
    """Build a Mesh with named axes, e.g. {'data': 4, 'model': 2}.

    Axis order follows dict order; total size must divide the device count.
    This is the TPU-native analog of choosing ctx=[gpu(0)..gpu(n)] — the mesh
    IS the device list, and shardings replace per-device executor replicas.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    names = tuple(axis_sizes.keys())
    sizes = tuple(int(axis_sizes[n]) for n in names)
    total = int(np.prod(sizes))
    if total > len(devices):
        raise ValueError(
            f"mesh {axis_sizes} needs {total} devices, have {len(devices)}")
    arr = np.array(devices[:total]).reshape(sizes)
    return Mesh(arr, names)


def data_parallel_mesh(n=None, devices=None):
    """1-D data-parallel mesh over n (default: all) devices."""
    import jax
    if devices is None:
        devices = jax.devices()
    if n is None:
        n = len(devices)
    return build_mesh({"data": n}, devices)


def single_axis_mesh(axis_name, n=None, devices=None):
    """1-D mesh over one named axis — what the shard_map building blocks
    (tp/pp/sp and their tests/examples) construct instead of an inline
    ``Mesh(np.array(devices), (name,))``."""
    import jax
    if devices is None:
        devices = jax.devices()
    if n is None:
        n = len(devices)
    return build_mesh({str(axis_name): n}, list(devices))


def axis_size(mesh, axis_name, default=None):
    """Size of a named mesh axis; `default` (when given) instead of a
    KeyError for an absent axis, so callers can treat a 1-D data mesh as
    {'model': 1, 'pipe': 1} without special-casing."""
    name = AXIS_ALIASES.get(axis_name, axis_name)
    for n, s in zip(mesh.axis_names, mesh.devices.shape):
        if n == name or n == axis_name:
            return int(s)
    if default is not None:
        return int(default)
    raise KeyError(f"mesh {tuple(mesh.axis_names)} has no axis "
                   f"{axis_name!r}")


def data_axis(mesh):
    """The batch-sharding axis of a mesh: 'data' when present, else the
    leading axis (the historical 1-D convention)."""
    return "data" if "data" in mesh.axis_names else mesh.axis_names[0]


# One canonical mesh per device tuple so Parameters, Module executors and
# split_and_load all agree on the mesh object (shardings compare equal).
_MESH_CACHE: dict = {}


def mesh_for_devices(devices):
    key = tuple(devices)
    mesh = _MESH_CACHE.get(key)
    if mesh is None:
        mesh = data_parallel_mesh(len(devices), list(devices))
        _MESH_CACHE[key] = mesh
    return mesh


def mesh_for_contexts(ctx_list):
    """The cached 1-D data mesh over the jax devices of a context list —
    the TPU-native meaning of ctx=[gpu(0)..gpu(n-1)] everywhere a context
    list is accepted (Module, gluon initialize/split_and_load)."""
    return mesh_for_devices([c.jax_device() for c in ctx_list])


def mesh_descriptor(mesh):
    """JSON-safe description of a mesh: {axis_name: size}. Recorded in
    checkpoint TOPOLOGY.json so a restore at a different device count
    can tell (and log) what it is resharding from; also the Plan's
    mesh-shape spelling (parallel/planner.py)."""
    return {str(n): int(s)
            for n, s in zip(mesh.axis_names, mesh.devices.shape)}


def mesh_from_descriptor(desc, devices=None):
    """Inverse of mesh_descriptor: build (and cache) the mesh a
    descriptor names. The cache key includes the axis layout, so a
    dp4×tp2 mesh and a dp8 mesh over the same devices coexist."""
    import jax
    if devices is None:
        devices = jax.devices()
    items = tuple((str(k), int(v)) for k, v in desc.items())
    key = (tuple(devices), items)
    mesh = _MESH_CACHE.get(key)
    if mesh is None:
        mesh = build_mesh(dict(items), list(devices))
        _MESH_CACHE[key] = mesh
    return mesh


def current_topology(mesh=None):
    """JSON-safe snapshot of this process's device topology (checkpoint
    TOPOLOGY.json): device/process counts plus the mesh axes when one is
    given."""
    import jax
    d = {"device_count": int(jax.device_count()),
         "local_device_count": int(jax.local_device_count()),
         "process_count": int(jax.process_count()),
         "process_index": int(jax.process_index())}
    if mesh is not None:
        d["mesh_axes"] = mesh_descriptor(mesh)
    return d


def replicated_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P())


def batch_sharding(mesh, batch_axis=0):
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = [None] * batch_axis + [data_axis(mesh)]
    return NamedSharding(mesh, P(*spec))


def put_replicated(data, mesh):
    """Commit host/any-device data to the mesh, replicated."""
    import jax
    data = getattr(data, "_data", data)
    if not isinstance(data, jax.Array):
        data = np.asarray(data)
    return jax.device_put(data, replicated_sharding(mesh))


def put_batch_sharded(data, mesh, batch_axis=0):
    """Commit host/any-device data to the mesh, sharded on the batch axis."""
    import jax
    data = getattr(data, "_data", data)
    if not isinstance(data, jax.Array):
        data = np.asarray(data)
    n = axis_size(mesh, data_axis(mesh))
    if data.shape[batch_axis] % n != 0:
        raise ValueError(
            f"batch axis {batch_axis} of shape {tuple(data.shape)} must be "
            f"divisible by the {n}-way data axis")
    return jax.device_put(data, batch_sharding(mesh, batch_axis))
