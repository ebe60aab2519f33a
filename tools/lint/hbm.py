"""Static per-chip HBM footprint estimator.

Pure shape arithmetic (stdlib only — no jax, no numpy): given the leaf
specs of a jitted train step — parameter shapes/dtypes, optimizer
state-leaf multiplicity, the dp-axis size and the layout each leaf
lives in (replicated vs the ZeRO flat zero-padded dp-sharded layout of
``parallel/collectives.py``) — compute the bytes ONE chip holds.  The
padding math mirrors ``collectives.padded_size`` exactly, so the
estimate agrees with the runtime ``optimizer_state_bytes_per_chip``
gauges (cross-checked in ``tests/test_hbm_estimator.py``).

Consumers: ``DataParallelStep.hbm_estimate()`` journals a
``hbm/estimate`` telemetry event per jitted program (rendered by
``tools/parse_log.py``); ``serve.buckets.validate_menu`` sizes a bucket
menu's batch buffers with ``dtype_itemsize``.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Sequence, Tuple

REPLICATED = "replicated"
DP_SHARDED = "dp_sharded"      # flat zero-padded, sharded over the dp axis

_ITEMSIZE = {
    "float64": 8, "int64": 8, "uint64": 8, "complex64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool": 1, "bool_": 1,
}


def dtype_itemsize(dtype) -> int:
    """Itemsize of a dtype given by name ('float32', 'bf16'-style names
    fall back to trailing-bit-count parsing); unknown names raise."""
    name = str(dtype)
    if name in _ITEMSIZE:
        return _ITEMSIZE[name]
    digits = ""
    for ch in reversed(name):
        if ch.isdigit():
            digits = ch + digits
        else:
            break
    if digits and int(digits) % 8 == 0:
        return int(digits) // 8
    raise ValueError("unknown dtype %r" % (dtype,))


def padded_size(n: int, axis_size: int) -> int:
    """Smallest multiple of ``axis_size`` >= n (and >= axis_size) — the
    flat zero-padded ZeRO leaf length.  Must stay identical to
    ``mxnet_tpu.parallel.collectives.padded_size``."""
    return max(1, -(-int(n) // int(axis_size))) * int(axis_size)


def _numel(shape: Sequence[int]) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def leaf_bytes_per_chip(shape: Sequence[int], dtype, layout: str,
                        axis_size: int = 1) -> int:
    """Bytes ONE chip holds for a leaf of ``shape``/``dtype``.

    ``replicated`` leaves cost their full natural size everywhere;
    ``dp_sharded`` leaves live flat zero-padded and each chip holds
    ``padded_size(numel, axis_size) / axis_size`` elements."""
    isz = dtype_itemsize(dtype)
    if layout == REPLICATED or axis_size <= 1:
        return _numel(shape) * isz
    if layout != DP_SHARDED:
        raise ValueError("unknown layout %r" % (layout,))
    return padded_size(_numel(shape), axis_size) * isz // int(axis_size)


def estimate_step_hbm(params: Iterable, *, axis_size: int = 1,
                      state_leaves: int = 0,
                      shard_optimizer: bool = False,
                      multi_precision: bool = False,
                      activations: Iterable = ()) -> Dict[str, int]:
    """Per-chip HBM estimate for one fused train step.

    ``params``: iterable of ``(shape, dtype)`` or ``(shape, dtype,
    trainable)`` tuples (trainable defaults True).  Parameters are
    replicated (the dp layout this codebase trains in).

    ``state_leaves``: elementwise optimizer state leaves per trainable
    param (SGD+momentum: 1, Adam: 2).  Under ``multi_precision``,
    half-width (itemsize < 4) weights carry an fp32 master as an extra
    leaf and their state leaves are fp32 — mirroring
    ``DataParallelStep``.  ``shard_optimizer`` puts every state leaf in
    the flat padded dp-sharded layout (structured/non-elementwise state
    that falls back replicated at runtime is not modeled — pass
    per-leaf calls to :func:`leaf_bytes_per_chip` for exotic slots).

    ``activations``: ``(shape, dtype)`` batch leaves, sharded over dp on
    their leading axis.

    Returns ``{"params_bytes", "opt_state_bytes", "activation_bytes",
    "total_bytes"}`` — all per chip.
    """
    layout = DP_SHARDED if shard_optimizer else REPLICATED
    p_bytes = 0
    s_bytes = 0
    for entry in params:
        shape, dtype = entry[0], entry[1]
        trainable = entry[2] if len(entry) > 2 else True
        p_bytes += leaf_bytes_per_chip(shape, dtype, REPLICATED, axis_size)
        if not trainable:
            continue
        mp_active = multi_precision and dtype_itemsize(dtype) < 4
        state_dtype = "float32" if mp_active else dtype
        n_leaves = state_leaves + (1 if mp_active else 0)
        s_bytes += n_leaves * leaf_bytes_per_chip(shape, state_dtype,
                                                  layout, axis_size)
    a_bytes = 0
    for shape, dtype in activations:
        full = _numel(shape) * dtype_itemsize(dtype)
        a_bytes += full // max(1, int(axis_size))
    return {"params_bytes": p_bytes, "opt_state_bytes": s_bytes,
            "activation_bytes": a_bytes,
            "total_bytes": p_bytes + s_bytes + a_bytes}
