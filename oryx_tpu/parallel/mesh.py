"""Mesh construction and sharding specs.

The framework's standard mesh has one axis, ``data``, over which examples
(users, points, ratings) are sharded; factor/parameter matrices are either
replicated or row-sharded over the same axis. Multi-axis meshes (e.g.
{data, model}) are supported by config: oryx.batch.compute.mesh is an
object of axis-name -> size.
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Mapping

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"

# Per-thread device-subset override: hyperparameter candidates train
# concurrently on disjoint sub-meshes (MLUpdate.java:256-288 runs them as
# parallel Spark jobs; here each candidate thread scopes its own devices).
_scope = threading.local()


@contextlib.contextmanager
def device_scope(devices):
    """Restrict mesh construction in this thread to `devices`."""
    prev = getattr(_scope, "devices", None)
    _scope.devices = list(devices)
    try:
        yield
    finally:
        _scope.devices = prev


def scoped_devices() -> list:
    """Devices visible to mesh construction in this thread."""
    devs = getattr(_scope, "devices", None)
    return list(devs) if devs is not None else list(jax.devices())


def partition_devices(groups: int) -> list[list]:
    """Split the local devices into `groups` disjoint contiguous subsets
    (empty-safe: at most one group per device). Contiguity keeps each
    sub-mesh on neighboring ICI links."""
    devices = scoped_devices()
    groups = max(1, min(groups, len(devices)))
    per = len(devices) // groups
    return [devices[g * per : (g + 1) * per] for g in range(groups)]


def get_mesh(spec: Mapping[str, int] | None = None, devices=None) -> Mesh:
    """Build a Mesh over the thread's scoped devices (all local devices
    unless a device_scope is active). Default: one 'data' axis."""
    devices = scoped_devices() if devices is None else devices
    if not spec:
        return Mesh(np.asarray(devices), (DATA_AXIS,))
    names = tuple(spec.keys())
    sizes = tuple(int(s) for s in spec.values())
    want = math.prod(sizes)
    if want > len(devices):
        raise ValueError(f"mesh {dict(spec)} needs {want} devices, have {len(devices)}")
    arr = np.asarray(devices[:want]).reshape(sizes)
    return Mesh(arr, names)


def shard_rows(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """First array dim sharded over `axis`, rest replicated."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_layout(arr) -> str:
    """Where an array's data actually is: 'dev0:(r, c) dev1:(r, c) ...'
    from its addressable shards (logged by the sharded paths, so a run can
    show the data is not all on device 0)."""
    return " ".join(
        f"dev{s.device.id}:{tuple(s.data.shape)}" for s in arr.addressable_shards
    )


def pad_to_multiple(n: int, multiple: int) -> int:
    """Smallest m >= n with m % multiple == 0 (shard-evenly helper)."""
    return ((n + multiple - 1) // multiple) * multiple


def mesh_from_config(config) -> Mesh | None:
    """Mesh per oryx.batch.compute.mesh: explicit axis spec, or all local
    devices on one 'data' axis when several are present, else None
    (single device: skip sharding machinery entirely)."""
    spec = config.get("oryx.batch.compute.mesh", None)
    if spec is None:
        if len(scoped_devices()) > 1:
            return get_mesh()
        return None
    return get_mesh(spec)
