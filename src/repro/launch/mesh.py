"""Production mesh definitions.

Single pod = one TPU v5e pod slice, 16×16 = 256 chips, axes (data, model).
Multi-pod = 2 pods = 512 chips, axes (pod, data, model): the ``pod`` axis is
the *among-device* axis — the paper's device boundary.  Training replicates
across it (gradient all-reduce = the only pod-crossing collective); serving
crosses it with query offloading (client pod -> server pod ppermute).

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

V5E_PEAK_FLOPS = 197e12      # bf16 FLOP/s per chip
V5E_HBM_BW = 819e9           # bytes/s per chip
V5E_ICI_BW = 50e9            # bytes/s per link (~per-direction)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices=None):
    """Every mesh of this package is built here, with ``Auto`` axes: the
    sharding code states layouts as ``NamedSharding`` placements and
    ``with_sharding_constraint`` hints and leaves propagation to the
    compiler (GSPMD).  ``jax.make_mesh`` defaults to ``Explicit`` axes,
    under which a ``lax.scan`` carry must keep its sharded type exactly."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Whatever fits the local devices — CPU tests, the e2e example and
    one host's chips."""
    n = len(jax.devices())
    mp = min(model_parallel, n)
    return make_mesh((n // mp, mp), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def data_axes(mesh) -> Tuple[str, ...]:
    """Axes carrying the batch dimension (pod + data when multi-pod)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_axis_size(mesh) -> int:
    """Total device count along the batch-carrying axes."""
    sizes = mesh_axis_sizes(mesh)
    n = 1
    for a in data_axes(mesh):
        n *= sizes[a]
    return n


def batch_spec(mesh):
    """PartitionSpec entry for a leading batch/frame axis laid out along the
    mesh's data axes (None when the mesh has no data axes)."""
    dp = data_axes(mesh)
    if not dp:
        return None
    return dp if len(dp) > 1 else dp[0]


def mesh_fingerprint(mesh) -> Tuple:
    """Hashable identity of a mesh for executable-cache keys: axis names,
    axis sizes, and the physical device assignment.  Two Mesh objects over
    the same devices in the same layout share executables; reconnecting
    after failover with the same mesh therefore never retraces."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))
