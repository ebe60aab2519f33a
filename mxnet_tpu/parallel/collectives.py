"""Collectives: the communication vocabulary.

Replaces the reference's comm implementations (``src/kvstore/comm.h``
CommCPU/CommDevice reduce+broadcast, ``comm_tree.h`` tree allreduce,
``kvstore_nccl.h`` NCCL) with XLA collectives.  Two call modes:

* **inside shard_map/pmap trace**: thin wrappers over ``jax.lax`` psum /
  all_gather / ppermute — collectives ride ICI, overlap scheduled by XLA.
* **eager, global-view arrays**: JAX arrays are *global*; a sum over the
  batch axis of a dp-sharded array already is the all-reduced value, so the
  eager ``allreduce`` re-replicates the (already-global) value instead of
  communicating — semantic parity with kvstore push/pull without a second
  comm path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["psum", "pmean", "all_gather", "reduce_scatter", "ppermute",
           "allreduce", "flatten_pad", "unflatten", "padded_size",
           "reduce_scatter_padded", "all_gather_unpad",
           "zero_sharded_update", "resolve_shard_optimizer"]


def _is_traced(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _unwrap(x):
    from ..ndarray import NDArray
    return x._data if isinstance(x, NDArray) else x


def _rewrap(val, like):
    from ..ndarray import NDArray
    from ..ndarray.ndarray import _wrap
    if isinstance(like, NDArray):
        return _wrap(val, like.context)
    return val


def psum(x, axis_name: str = "dp"):
    """All-reduce-sum across a named mesh axis (use under shard_map/pmap).
    The reference's KVStore push+pull sum (kvstore_local.h:184) in one op."""
    val = _unwrap(x)
    return _rewrap(lax.psum(val, axis_name), x)


def pmean(x, axis_name: str = "dp"):
    val = _unwrap(x)
    return _rewrap(lax.pmean(val, axis_name), x)


def all_gather(x, axis_name: str = "dp", axis: int = 0, tiled: bool = True):
    val = _unwrap(x)
    return _rewrap(lax.all_gather(val, axis_name, axis=axis, tiled=tiled), x)


def reduce_scatter(x, axis_name: str = "dp", scatter_dimension: int = 0):
    val = _unwrap(x)
    return _rewrap(
        lax.psum_scatter(val, axis_name, scatter_dimension=scatter_dimension,
                         tiled=True), x)


# ---------------------------------------------------------------------------
# ZeRO-style flat shard layout (arxiv 2004.13336: weight-update sharding)
#
# Cross-replica sharding of the optimizer state divides each leaf evenly
# across the ``dp`` axis.  Natural weight shapes almost never divide by
# the axis size (a (1000,) bias on 8 chips), so every sharded leaf lives
# in a canonical FLAT layout: ``reshape(-1)`` then zero-pad to the next
# multiple of the axis size.  The same layout math serves the eager
# global-view path (sharding annotations, GSPMD inserts the collectives)
# and the explicit shard_map path (``reduce_scatter_padded`` /
# ``all_gather_unpad`` below).
# ---------------------------------------------------------------------------

def padded_size(n: int, axis_size: int) -> int:
    """Smallest multiple of ``axis_size`` >= n (and >= axis_size, so a
    scalar leaf still gives every replica one element)."""
    return max(1, -(-int(n) // int(axis_size))) * int(axis_size)


def flatten_pad(x, axis_size: int):
    """Flatten to 1-D and zero-pad so the length divides ``axis_size``.

    Works on eager arrays and on tracers (inside jit the pad is a fused
    concat).  Zero padding is numerics-neutral for every update rule in
    ``optimizer/``: the pad region of the weight/state is zero, gradients
    there are zero, and ``wd * 0 == 0`` — whatever garbage the update
    computes in the pad lanes is dropped by ``unflatten``.
    """
    val = _unwrap(x)
    flat = val.reshape(-1)
    pad = padded_size(flat.shape[0], axis_size) - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


def unflatten(flat, shape):
    """Undo ``flatten_pad``: drop the pad lanes, restore ``shape``."""
    val = _unwrap(flat)
    n = 1
    for d in shape:
        n *= int(d)
    return val[:n].reshape(shape)


SHARD_OFF = (False, None, 0, "0", "off")
SHARD_FORCED = (True, 1, "1", "on")


def resolve_shard_optimizer(knob, mesh):
    """The ``dp`` extent a ``shard_optimizer`` knob shards the optimizer
    state over on ``mesh``; 0 keeps the replicated update.  The one rule
    for ``DataParallelStep`` and ``Trainer``: ``"auto"`` shards where
    the mesh has a ``dp`` axis larger than 1; ``True`` takes any ``dp``
    axis (size 1 is a no-op layout, handy on one device); without a
    ``dp`` axis there is nothing to shard over."""
    if knob in SHARD_OFF:
        return 0
    if knob != "auto" and knob not in SHARD_FORCED:
        raise ValueError("shard_optimizer must be True/False/'auto', "
                         "got %r" % (knob,))
    if mesh is None or "dp" not in mesh.axis_names:
        return 0
    n = int(mesh.shape["dp"])
    return 0 if knob == "auto" and n <= 1 else n


def reduce_scatter_padded(x, axis_name: str = "dp", axis_size: int = None,
                          dtype=None):
    """Flat reduce-scatter with uneven-leaf padding (use under
    shard_map).  Flattens ``x``, zero-pads to a multiple of
    ``axis_size`` and psum-scatters — each replica gets the fully
    reduced 1/N slice of the flat leaf.  ``axis_size`` must be the
    static size of ``axis_name`` (shard_map callers know their mesh;
    the pad amount must be a trace-time constant).

    ``dtype`` is the narrow-wire variant (compressed gradient
    collectives, docs/PERF.md): the operand is explicitly cast to the
    wire dtype BEFORE the scatter, so the collective moves 1-2 bytes
    per element instead of 4.  The reduction then accumulates in the
    wire dtype — callers must guarantee headroom (chunk-scaled
    quantized values, or a float wire like bf16/fp8 where saturation
    is the documented rounding), and the matching gather side must
    spell its widening cast explicitly on the operand
    (``all_gather_unpad(shard.astype(orig_dtype), ...)``) — the
    num-collective-dtype lint contract."""
    if axis_size is None:
        raise ValueError("reduce_scatter_padded needs the static "
                         "axis_size (the pad width is shape math)")
    flat = flatten_pad(x, axis_size)
    if dtype is not None:
        flat = flat.astype(dtype)
    return lax.psum_scatter(flat, axis_name, scatter_dimension=0,
                            tiled=True)


def all_gather_unpad(shard, shape, axis_name: str = "dp"):
    """Inverse of ``reduce_scatter_padded``: gather the flat shards from
    every replica, drop the padding, restore the natural ``shape``."""
    val = _unwrap(shard)
    flat = lax.all_gather(val, axis_name, axis=0, tiled=True)
    return unflatten(flat, shape)


def zero_sharded_update(step_fn, w, g, state_leaves, t, lr, *, shape,
                        mp, axis_size, shard, repl, compress=None,
                        corrupt=None):
    """One weight's ZeRO-sharded optimizer update (arxiv 2004.13336),
    shared by ``DataParallelStep`` and the Trainer's ``_FusedUpdate``
    so the numerics live in exactly one place.

    The gradient is flattened/padded and CONSTRAINED to the dp-sharded
    layout ``shard`` — when its producer is the global-batch mean,
    GSPMD lowers the (all-reduce, slice) pair to a reduce-scatter; a
    replicated producer makes it a free local slice.  ``step_fn`` then
    runs on the local 1/N flat shard only, and the updated weight is
    constrained back to ``repl`` (replicated), which lowers to an
    all-gather in the WORKING dtype — under ``mp`` the fp32 master
    (state leaf 0, sharded) is updated and the half-width weight
    re-quantized from it before the gather.  State leaves arrive and
    leave dp-sharded.  Returns ``(new_weight, new_state_leaves)``.

    ``compress`` (``"int8"``/``"fp8"``) narrows the gradient wire
    (compression.py, docs/PERF.md): the LAST state leaf is the
    error-feedback residual — the step consumes exactly
    ``dequantize(quantize(grad + residual))`` and the new residual
    (the exact quantization error) leaves dp-sharded with the rest of
    the state, so it re-shards and checkpoints like any ZeRO leaf.
    ``corrupt`` is the ``grad_compress_corrupt`` chaos operand
    (traced scalar) threaded into the dequantize."""
    import jax
    from ..optimizer.optimizer import pin_update_dtypes
    wsc = jax.lax.with_sharding_constraint
    residual = None
    if compress:
        residual, state_leaves = state_leaves[-1], state_leaves[:-1]

    def narrow_wire(g_flat):
        # error-feedback compressed leg: what crosses the (emulated)
        # narrow wire is dequantize(quantize(comp)); the exact error
        # becomes the next step's residual leaf
        from .compression import compress_decompose
        comp = g_flat + residual.astype(g_flat.dtype)
        v, new_res = compress_decompose(comp, compress, corrupt=corrupt)
        return wsc(v, shard), wsc(new_res.astype(residual.dtype), shard)

    if mp:
        g32 = wsc(flatten_pad(g.astype(jnp.float32), axis_size), shard)
        new_res = []
        if compress:
            g32, res_leaf = narrow_wire(g32)
            new_res = [res_leaf]
        master, rest = state_leaves[0], state_leaves[1:]
        res = step_fn(master, g32, t, lr, *rest)
        new_master, new_rest = pin_update_dtypes(res, master, rest)
        new_master = wsc(new_master, shard)
        half = wsc(new_master.astype(w.dtype), repl)
        return (unflatten(half, shape),
                [new_master] + [wsc(s, shard) for s in new_rest] + new_res)
    gg = wsc(flatten_pad(g, axis_size), shard)
    new_res = []
    if compress:
        gg, res_leaf = narrow_wire(gg)
        new_res = [res_leaf]
    wflat = wsc(flatten_pad(w, axis_size), shard)
    res = step_fn(wflat, gg, t, lr.astype(w.dtype), *state_leaves)
    new_wflat, new_st = pin_update_dtypes(res, wflat, state_leaves)
    return (unflatten(wsc(new_wflat, repl), shape),
            [wsc(s, shard) for s in new_st] + new_res)


def ppermute(x, perm, axis_name: str = "dp"):
    """Neighbour exchange on the ICI ring — the building block of ring
    attention and pipeline parallelism."""
    val = _unwrap(x)
    return _rewrap(lax.ppermute(val, axis_name, perm), x)


def allreduce(x, axis_name: str = "dp"):
    """Gradient all-reduce with call-mode dispatch (see module docstring).

    Inside a shard_map/pmap trace → real ``lax.psum``.  Eagerly on global
    arrays → identity-with-replication: the global value already includes
    every shard's contribution (global-view semantics), matching what the
    reference's push+pull round-trip produces.
    """
    val = _unwrap(x)
    if _is_traced(val):
        try:
            return _rewrap(lax.psum(val, axis_name), x)
        except NameError:
            return x  # traced under plain jit (no named axis): global value
    from .mesh import get_mesh
    mesh = get_mesh()
    if mesh is None or mesh.size == 1:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    return _rewrap(jax.device_put(val, NamedSharding(mesh, P())), x)
