"""Device-mesh management.

The mesh is the TPU analogue of the reference's device list
(``Module(context=[gpu(0)..gpu(N)])``) plus its comm topology
(``src/kvstore/gpu_topology.h`` link-matrix spanning trees) — except the
topology work is XLA's job; we only name axes and pick shapes.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
import numpy as onp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["set_mesh", "get_mesh", "current_mesh", "default_mesh",
           "device_mesh", "batch_sharded_over", "batch_shards",
           "per_batch_shard"]


class _MeshState(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None


_STATE = _MeshState()


def set_mesh(mesh: Optional[Mesh]) -> None:
    """Install the process-wide mesh used by kvstore('tpu'), Trainer and
    shard_batch."""
    _STATE.mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return _STATE.mesh


class current_mesh:
    """Context manager scoping a mesh."""

    def __init__(self, mesh: Mesh):
        self._mesh = mesh
        self._prev = None

    def __enter__(self):
        self._prev = _STATE.mesh
        _STATE.mesh = self._mesh
        return self._mesh

    def __exit__(self, *a):
        _STATE.mesh = self._prev
        return False


def device_mesh(shape: Optional[Sequence[int]] = None,
                axis_names: Sequence[str] = ("dp",),
                devices=None) -> Mesh:
    """Build a named mesh over devices.

    ``device_mesh()`` → 1-D data-parallel mesh over all local devices;
    ``device_mesh((4, 2), ("dp", "tp"))`` → 2-D dp×tp mesh.  On real slices
    jax orders devices along ICI rings so neighbouring mesh coordinates are
    physical neighbours (what gpu_topology.h's Kernighan-Lin clustering
    approximated for PCIe).
    """
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devices),)
    arr = onp.array(devices).reshape(tuple(shape))
    return Mesh(arr, tuple(axis_names))


class _BatchAxis(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.axis: Optional[str] = None


_BATCH_AXIS = _BatchAxis()


@contextlib.contextmanager
def batch_sharded_over(mesh: Optional[Mesh], axis: str = "dp"):
    """Declare, while a program is being traced, that its batch is sharded
    over ``mesh[axis]`` by GSPMD (jit over arrays with a NamedSharding).

    The compiler partitions composed XLA ops on its own, but not a Mosaic
    (Pallas TPU) kernel: "Mosaic kernels cannot be automatically
    partitioned".  Inside such a program a kernel has to be wrapped in a
    ``shard_map`` by hand, and only the code that laid the batch out knows
    over which axis.  ``DataParallelStep`` opens this scope around its
    forward+backward trace; the kernel entry points in ``ops/`` go through
    :func:`per_batch_shard`.  ``mesh=None`` (one device) is a no-op."""
    prev = _BATCH_AXIS.mesh, _BATCH_AXIS.axis
    _BATCH_AXIS.mesh, _BATCH_AXIS.axis = mesh, axis
    try:
        yield
    finally:
        _BATCH_AXIS.mesh, _BATCH_AXIS.axis = prev


def batch_shards() -> int:
    """Into how many shards the declared scope splits a batch (1 outside
    :func:`batch_sharded_over`)."""
    mesh, axis = _BATCH_AXIS.mesh, _BATCH_AXIS.axis
    return 1 if mesh is None else mesh.shape[axis]


def per_batch_shard(fn, operands, replicated=(), summed=None):
    """``fn(*operands)``, run once per batch shard of the declared scope.

    Every operand and every output is split on its leading (batch or row)
    dim, except the operand positions in ``replicated`` (whole on every
    shard) and the outputs flagged in ``summed`` — one bool per element of
    the tuple ``fn`` returns: a flagged output is a per-shard partial sum
    (a backward's per-channel reduction), summed over the axis and
    returned whole.  ``None`` operands pass through.  Outside
    :func:`batch_sharded_over`, or over an axis of one device, this is a
    plain call."""
    mesh, axis = _BATCH_AXIS.mesh, _BATCH_AXIS.axis
    if mesh is None or mesh.shape[axis] == 1:
        return fn(*operands)
    present = [i for i, o in enumerate(operands) if o is not None]

    def body(*args):
        full = [None] * len(operands)
        for i, a in zip(present, args):
            full[i] = a
        out = fn(*full)
        if summed is None:
            return out
        return tuple(lax.psum(o, axis) if s else o
                     for o, s in zip(out, summed))

    in_specs = tuple(P() if i in replicated else P(axis) for i in present)
    out_specs = P(axis) if summed is None else \
        tuple(P() if s else P(axis) for s in summed)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(
                             *[operands[i] for i in present])


def default_mesh() -> Mesh:
    """The installed mesh, or a fresh all-device dp mesh."""
    m = get_mesh()
    if m is None:
        m = device_mesh()
        set_mesh(m)
    return m
