"""Compressed gradient collectives for the ZeRO wire (int8 / fp8-e4m3).

At large dp extents the gradient reduce-scatter is the step's dominant
inter-chip traffic (arxiv 2004.13336's communication analysis).  This
module narrows that wire: the flat zero-padded gradient layout the ZeRO
path already reduce-scatters (``collectives.zero_sharded_update``) is
quantized per chunk — symmetric max-abs scaling, one f32 scale per
``CHUNK`` elements riding along as a tiny side tensor — to a 1-byte
payload (``int8`` round-to-nearest, or ``fp8`` via
``ml_dtypes.float8_e4m3fn`` where available, scale+clamp emulation
otherwise), then dequantized and accumulated in f32 on the local shard.
The quantization error is NOT dropped: an error-feedback residual
(1-bit-Adam lineage) is carried as an extra dp-sharded state leaf and
added to the next step's gradient, so the systematic bias of naive
quantization cancels and convergence provably tracks the uncompressed
step (the bench's loss-parity gate measures exactly this).

Honesty note on the wire: under GSPMD the gradient's reduction is
lowered from a sharding constraint inside one jitted program, so the
quantize → reduce-scatter → dequantize sequence here is a
numerics-exact EMULATION of the narrow wire — the update consumes
exactly ``dequantize(quantize(grad + residual))`` and the residual
carries the exact error, while the wire-byte accounting
(:func:`wire_bytes` / :func:`scale_bytes`) is schedule arithmetic, the
same discipline as the ZeRO layout's ``reduce_scatter_bytes`` journal.
The explicit narrow-dtype collective spelling lives in
``collectives.reduce_scatter_padded(dtype=...)`` for shard_map-level
callers.  See docs/PERF.md "Compressed gradient collectives".

The legacy 2-bit kvstore compression (reference
``gradient_compression.h``) lives here too as jnp-pure helpers —
``mxnet_tpu.gradient_compression`` is a deprecation shim re-exporting
them for the kvstore dist path.
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["MODES", "CHUNK", "INT8_MAX", "FP8_MAX", "fp8_wire_dtype",
           "num_chunks", "quantize_chunked", "dequantize_chunked",
           "compress_decompose", "wire_bytes", "scale_bytes",
           "wire_ratio", "resolve_grad_compression", "journal_decision",
           "quantize_2bit", "dequantize_2bit", "pack_2bit", "unpack_2bit"]

# the compressed wire modes DataParallelStep/Trainer accept (besides
# None/"off")
MODES = ("int8", "fp8")

CHUNK = 256          # elements per max-abs scale chunk
INT8_MAX = 127.0     # symmetric int8 code range
FP8_MAX = 448.0      # float8_e4m3fn finite max
_SCALE_EPS = 1e-30   # all-zero chunks quantize through a tiny scale


def fp8_wire_dtype():
    """The fp8-e4m3 storage dtype, or None when ml_dtypes lacks it (the
    quantizer then emulates fp8 as scale+clamp: same range mapping and
    saturation, mantissa rounding elided — documented in PERF.md)."""
    try:
        import ml_dtypes
        return jnp.dtype(ml_dtypes.float8_e4m3fn)
    except (ImportError, AttributeError, TypeError):
        return None


def num_chunks(n):
    """Scale-tensor length for an ``n``-element flat gradient."""
    return -(-int(n) // CHUNK)


def quantize_chunked(flat, mode):
    """Quantize a flat f32 gradient to the narrow wire layout.

    Returns ``(q, scales)``: ``q`` of shape ``(num_chunks, CHUNK)`` in
    the wire dtype (int8 codes, fp8 values, or f32 scale+clamp
    emulation), ``scales`` of shape ``(num_chunks,)`` in f32 — the side
    tensor that rides the wire next to the payload.  The tail chunk is
    zero-padded; zeros survive the round-trip exactly, so
    :func:`dequantize_chunked` slices the pad back off losslessly.
    """
    if mode not in MODES:
        raise ValueError("grad compression mode must be one of %s, got %r"
                         % (MODES, mode))
    x = flat.astype(jnp.float32).reshape(-1)
    pad = (-x.shape[0]) % CHUNK
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), jnp.float32)])
    rows = x.reshape(-1, CHUNK)
    amax = jnp.max(jnp.abs(rows), axis=1)
    qmax = INT8_MAX if mode == "int8" else FP8_MAX
    scales = jnp.maximum(amax / qmax, _SCALE_EPS)
    y = jnp.clip(rows / scales[:, None], -qmax, qmax)
    if mode == "int8":
        q = jnp.round(y).astype(jnp.int8)
    else:
        fp8 = fp8_wire_dtype()
        q = y.astype(fp8) if fp8 is not None else y
    return q, scales


def dequantize_chunked(q, scales, n, corrupt=None):
    """Inverse of :func:`quantize_chunked`: f32 flat gradient of length
    ``n``.  ``corrupt`` is the ``grad_compress_corrupt`` chaos seam — a
    traced scalar multiplied into chunk 0's scale (1.0 when the fault
    is not armed, non-finite when it fires), so a garbled wire scale
    surfaces as exactly the non-finite/drift signal NumericsSanitizer
    polices."""
    scales = scales.astype(jnp.float32)
    if corrupt is not None:
        scales = scales.at[0].set(scales[0] * corrupt)
    vals = q.astype(jnp.float32) * scales[:, None]
    return vals.reshape(-1)[: int(n)]


def compress_decompose(comp, mode, corrupt=None):
    """Error-feedback decomposition of one flat compensated gradient
    ``comp = grad + residual``: returns ``(v, new_residual)`` where
    ``v = dequantize(quantize(comp))`` is what crosses the wire (the
    value the optimizer step consumes) and ``new_residual = comp - v``
    is the exact quantization error carried to the next step as a
    dp-sharded ZeRO state leaf.  Both come back in ``comp``'s dtype so
    the update path stays drift-free."""
    q, scales = quantize_chunked(comp, mode)
    v32 = dequantize_chunked(q, scales, comp.shape[0], corrupt=corrupt)
    comp32 = comp.astype(jnp.float32)
    return v32.astype(comp.dtype), (comp32 - v32).astype(comp.dtype)


# ---------------------------------------------------------------------------
# wire-byte arithmetic (schedule accounting, same discipline as the
# ZeRO layout's reduce_scatter_bytes journal)
# ---------------------------------------------------------------------------

def wire_bytes(n, mode=None):
    """Gradient PAYLOAD bytes on the reduce-scatter wire for an
    ``n``-element flat f32 gradient: 4 B/elem uncompressed, 1 B/elem on
    the int8/fp8 wire.  The scale side tensor is accounted separately
    (:func:`scale_bytes`) — it is the "tiny side tensor" of the wire
    layout, not part of the gradient payload the 4x ratio is quoted
    against."""
    n = int(n)
    if mode in (None, "", "off"):
        return 4 * n
    if mode not in MODES:
        raise ValueError("unknown compression mode %r" % (mode,))
    return n          # int8 and fp8 are both 1-byte payloads


def scale_bytes(n, mode=None):
    """Bytes of the f32 max-abs scale side tensor (0 uncompressed)."""
    if mode in (None, "", "off"):
        return 0
    return 4 * num_chunks(n)


def wire_ratio(n, mode):
    """f32 payload bytes / compressed payload bytes (4.0 for int8/fp8)."""
    return wire_bytes(n, None) / float(wire_bytes(n, mode))


def resolve_grad_compression(knob, shard_n=0):
    """The wire mode — "" (uncompressed) or a ``MODES`` member — a
    ``grad_compression`` knob gives an update sharded ``shard_n`` ways.
    The one rule for ``DataParallelStep`` and ``Trainer``.  Compression
    IS the narrow ZeRO wire: with the sharded update off or over one
    replica there is no gradient reduce-scatter to narrow, and a valid
    mode quietly disables."""
    if knob in (None, False, "", 0, "0", "off"):
        return ""
    if knob not in MODES:
        raise ValueError("grad_compression must be one of %s or None, "
                         "got %r" % (MODES, knob))
    return knob if shard_n >= 2 else ""


def journal_decision(requested, mode, shard_n, params, dtype):
    """One ``compress/decision`` journal record + the byte gauges for a
    knob that asked for a mode: what the wire will carry per step vs
    the f32 baseline for ``params`` gradient elements (schedule
    arithmetic, the same discipline as reduce_scatter_bytes)."""
    from .. import telemetry
    base = wire_bytes(params, None)
    wire = wire_bytes(params, mode or None)
    scale = scale_bytes(params, mode or None)
    telemetry.gauge("compression.bytes_saved", max(0, base - wire - scale))
    telemetry.gauge("compression.scale_bytes", scale)
    telemetry.event(
        "compress", "decision", mode=mode or "off",
        requested=str(requested), path="forced" if mode else "disabled",
        dp=int(shard_n), params=int(params), dtype=dtype,
        wire_bytes=int(wire), scale_bytes=int(scale), f32_bytes=int(base),
        ratio=round(base / float(wire), 3) if wire else 1.0)


# ---------------------------------------------------------------------------
# legacy 2-bit kvstore compression (reference gradient_compression.h),
# jnp-pure — re-exported by the mxnet_tpu.gradient_compression shim
# ---------------------------------------------------------------------------

def quantize_2bit(data, residual, threshold):
    """Quantize (data + residual) to {-t, 0, +t}; return (q, new_residual).

    ``q`` is the dequantized value actually transmitted; ``new_residual``
    carries the error forward (reference gradient_compression-inl.h
    quantize_2bit kernel semantics)."""
    d = data + residual
    q = jnp.where(d >= threshold, threshold,
                  jnp.where(d <= -threshold, -threshold, 0.0))
    return q, d - q


def dequantize_2bit(q, threshold):
    """Identity on already-dequantized values (kept for API symmetry)."""
    return q


def pack_2bit(q, threshold):
    """Pack quantized values into the 2-bit wire format: uint32 words,
    16 codes each (code 0 → 0, 1 → +t, 2 → -t).  Returns (packed uint32
    array, original size)."""
    flat = jnp.ravel(q)
    n = flat.shape[0]
    codes = jnp.where(flat > 0, 1, jnp.where(flat < 0, 2, 0)).astype(
        jnp.uint32)
    pad = (-n) % 16
    codes = jnp.concatenate(
        [codes, jnp.zeros((pad,), jnp.uint32)]) if pad else codes
    codes = codes.reshape(-1, 16)
    shifts = jnp.arange(16, dtype=jnp.uint32) * 2
    packed = jnp.bitwise_or.reduce(codes << shifts, axis=1)
    return packed, n


def unpack_2bit(packed, n, threshold, shape=None):
    """Inverse of :func:`pack_2bit` → float32 values in {-t, 0, +t}."""
    shifts = jnp.arange(16, dtype=jnp.uint32) * 2
    codes = (packed[:, None] >> shifts) & jnp.uint32(3)
    flat = codes.reshape(-1)[:n]
    out = jnp.where(flat == 1, threshold,
                    jnp.where(flat == 2, -threshold, 0.0)).astype(jnp.float32)
    return out.reshape(shape) if shape is not None else out
