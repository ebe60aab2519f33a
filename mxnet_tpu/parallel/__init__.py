"""Parallelism: device meshes, collectives, SPMD train steps, ring attention.

TPU-native replacement for the reference's entire distributed stack
(SURVEY.md §2.3): KVStore comm trees (``src/kvstore/comm.h``,
``comm_tree.h``), NCCL (``kvstore_nccl.h``), and the ps-lite parameter
server (``kvstore_dist.h``) all collapse into **XLA collectives over an ICI
mesh** expressed with ``jax.sharding`` + ``shard_map``:

* reduce/broadcast of gradients  → ``lax.psum`` (inserted by GSPMD or
  explicit in shard_map)
* parameter-server key sharding  → parameter/optimizer-state sharding
  annotations (ZeRO-style), no RPC
* the scheduler/role bootstrap   → ``jax.distributed.initialize``
* topology-aware reduce trees (gpu_topology.h Kernighan-Lin) → not needed:
  XLA routes collectives on the ICI torus.

Axis convention: ``dp`` (data), ``tp`` (tensor/model), ``pp`` (pipeline),
``sp`` (sequence/context).  The reference only has dp (+ device placement);
tp/pp/sp are capabilities the TPU build adds (SURVEY.md §2.3 rows TP/PP/SP).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
import numpy as onp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import (  # noqa: F401
    current_mesh, default_mesh, device_mesh, get_mesh, set_mesh,
)
from .collectives import (  # noqa: F401
    allreduce, all_gather, all_gather_unpad, flatten_pad, padded_size,
    pmean, ppermute, psum, reduce_scatter, reduce_scatter_padded,
    unflatten,
)
from .data_parallel import DataParallelStep  # noqa: F401
from .elastic import ElasticContext, kv_retry  # noqa: F401
from . import chaos  # noqa: F401
from . import compression  # noqa: F401
from .ring_attention import (  # noqa: F401
    blockwise_attention, ring_attention, ring_attention_sharded)
from .pipeline import (pipeline_apply, pipeline_train_step,  # noqa: F401
                       PipelineTrainer)
from .moe import moe_ffn_init, moe_ffn_apply, moe_ffn_ref  # noqa: F401

__all__ = [
    "Mesh", "NamedSharding", "P",
    "current_mesh", "default_mesh", "device_mesh", "get_mesh", "set_mesh",
    "allreduce", "all_gather", "all_gather_unpad", "flatten_pad",
    "padded_size", "pmean", "ppermute", "psum", "reduce_scatter",
    "reduce_scatter_padded", "unflatten",
    "DataParallelStep", "ElasticContext", "kv_retry", "chaos",
    "compression",
    "ring_attention", "ring_attention_sharded",
    "blockwise_attention", "shard_batch", "replicate", "initialize",
    "pipeline_apply",
    "pipeline_train_step",
    "PipelineTrainer",
    "moe_ffn_init",
    "moe_ffn_apply",
    "moe_ffn_ref",
]


def _dist_is_initialized():
    """``jax.distributed.is_initialized`` across jax versions (the public
    accessor only exists on newer clients; older ones expose the live
    coordination client on the private global state)."""
    fn = getattr(jax.distributed, "is_initialized", None)
    if fn is not None:
        return bool(fn())
    try:
        from jax._src import distributed as _dist
        return _dist.global_state.client is not None
    except Exception:
        return False


def initialize(coordinator_address=None, num_processes=None, process_id=None,
               initialization_timeout=None):
    """Multi-host bootstrap (reference: ps-lite scheduler roles via
    DMLC_PS_ROOT_URI etc., docs/faq/distributed_training.md:254; here the
    jax coordination service).

    Arguments default from the env contract set by ``tools/launch.py``
    (MXNET_TPU_COORDINATOR_ADDRESS / _NUM_PROCESSES / _PROCESS_ID), the
    role the reference's DMLC_* env played."""
    import os
    if _dist_is_initialized():
        return  # idempotent: mxnet_tpu auto-joins at import when the
                # launcher env is set (see mxnet_tpu/__init__.py)
    if coordinator_address is None:
        coordinator_address = os.environ.get(
            "MXNET_TPU_COORDINATOR_ADDRESS")
    if num_processes is None and "MXNET_TPU_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["MXNET_TPU_NUM_PROCESSES"])
    if process_id is None and "MXNET_TPU_PROCESS_ID" in os.environ:
        process_id = int(os.environ["MXNET_TPU_PROCESS_ID"])
    if initialization_timeout is None and "MXNET_TPU_INIT_TIMEOUT" in os.environ:
        initialization_timeout = int(os.environ["MXNET_TPU_INIT_TIMEOUT"])
    kw = {}
    if initialization_timeout is not None:
        kw["initialization_timeout"] = initialization_timeout
    if coordinator_address is not None:
        kw["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kw["num_processes"] = num_processes
    if process_id is not None:
        kw["process_id"] = process_id
    if "MXNET_TPU_HEARTBEAT_TIMEOUT" in os.environ:
        # failure-detection latency knob (reference: ps-lite
        # PS_HEARTBEAT_TIMEOUT, docs/faq/env_var.md DMLC heartbeat family)
        kw["heartbeat_timeout_seconds"] = int(
            os.environ["MXNET_TPU_HEARTBEAT_TIMEOUT"])
    if os.environ.get("MXNET_TPU_RECOVERABLE", "") in ("1", "true"):
        # survive peer failure instead of fail-fast: the kvstore's
        # num_dead_node() liveness view stays queryable after a worker
        # dies (reference get_num_dead_node semantics — survivors keep
        # running; fail-fast remains the default, matching round-3's
        # hard-failure contract).
        jax.config.update("jax_enable_recoverability", True)
    jax.distributed.initialize(**kw)


def shard_batch(x, mesh: Optional[Mesh] = None, axis: str = "dp"):
    """Place a host batch onto the mesh, sharded along its leading dim —
    the analogue of `DataParallelExecutorGroup.decide_slices` + `_load_data`
    scatter (reference executor_group.py:282-304,451), done by sharding
    annotation instead of explicit per-GPU copies."""
    from ..ndarray import NDArray
    from ..ndarray.ndarray import _wrap
    mesh = mesh or get_mesh()
    if mesh is None:
        return x
    val = x._data if isinstance(x, NDArray) else x
    spec = P(axis, *([None] * (val.ndim - 1)))
    target = NamedSharding(mesh, spec)
    if getattr(val, "sharding", None) == target:
        return x  # pre-placed (e.g. DevicePrefetchIter(mesh=...)): no-op
    out = jax.device_put(val, target)
    return _wrap(out, x.context) if isinstance(x, NDArray) else out


def replicate(x, mesh: Optional[Mesh] = None):
    """Replicate a value across the mesh (parameter broadcast — the
    reference's kvstore Broadcast / comm.h broadcast path)."""
    from ..ndarray import NDArray
    from ..ndarray.ndarray import _wrap
    mesh = mesh or get_mesh()
    if mesh is None:
        return x
    val = x._data if isinstance(x, NDArray) else x
    out = jax.device_put(val, NamedSharding(mesh, P()))
    return _wrap(out, x.context) if isinstance(x, NDArray) else out
