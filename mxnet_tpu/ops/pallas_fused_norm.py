"""Fused BatchNorm→residual-add→ReLU epilogue Pallas kernels (fwd+bwd).

Round-5 profiling of the ResNet-50 bf16 train step attributed ~13% of
device time to the UNFUSED BN-apply/residual/ReLU elementwise chains at
the end of every residual unit: XLA emits them as separate loop fusions
that re-read the conv output and the skip tensor from HBM on a step that
is already HBM-bandwidth-bound.  The fused epilogue makes the chain what
it algorithmically is — ONE read of (x, residual) + one write forward,
one read of (x, y, ct) + two writes backward — with the per-channel
dscale/dshift reductions riding the same pass in VMEM scratch.

The kernel works on the folded form the BatchNorm op already computes
(`ops/nn.py _bn_apply`): per-channel fp32 ``scale = rsqrt(var+eps)*gamma``
and ``shift = beta - mean*scale`` vectors, so the epilogue itself is

    y = relu(x * scale[c] + shift[c] + residual)

Layout: the channel axis and everything minor to it collapse into the
lane dimension (``cols = C * trail``, scale/shift repeated per ``trail``)
and the leading dims become rows — no transposes for NCHW or NHWC.
Reference role: ``src/operator/nn/batch_norm`` + the CUDNN fused
AddRelu epilogue (batch_norm add_relu fusion) the reference enables on
GPU for exactly this chain.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import context as _context
from .pallas_attention import per_batch_shard

__all__ = ["fused_scale_shift_add_relu", "fused_bn_add_relu_epilogue",
           "pallas_epilogue_fwd", "pallas_epilogue_bwd"]

_BLOCK_ROWS = 256
_BLOCK_COLS = 512
# fwd holds x/r/y, bwd x/y/ct/dx/dr blocks as f32 working values; budget
# well under the ~16 MB VMEM with room for double buffering
_VMEM_BUDGET = 6 * 1024 * 1024


def _epi_fwd_kernel(x_ref, s_ref, t_ref, r_ref, y_ref):
    x = x_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)
    y = x * s_ref[...] + t_ref[...] + r
    y_ref[...] = jnp.maximum(y, 0.0).astype(y_ref.dtype)


def _epi_bwd_kernel(x_ref, s_ref, y_ref, ct_ref, dx_ref, dr_ref,
                    ds_ref, dt_ref, ds_acc, dt_acc, *, n_rblocks):
    import jax.experimental.pallas as pl

    ri = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    ct = ct_ref[...].astype(jnp.float32)
    # the ReLU mask recomputes from y (y > 0 iff the pre-ReLU value was
    # positive), so the boolean mask is never materialized in HBM.  The
    # compare runs in fp32: v5e has no bf16 vector compare
    g = jnp.where(y_ref[...].astype(jnp.float32) > 0, ct, 0.0)
    dx_ref[...] = (g * s_ref[...]).astype(dx_ref.dtype)
    dr_ref[...] = g.astype(dr_ref.dtype)

    @pl.when(ri == 0)
    def _init():
        ds_acc[...] = jnp.zeros_like(ds_acc)
        dt_acc[...] = jnp.zeros_like(dt_acc)

    ds_acc[...] += jnp.sum(g * x, axis=0, keepdims=True)
    dt_acc[...] += jnp.sum(g, axis=0, keepdims=True)

    @pl.when(ri == n_rblocks - 1)
    def _flush():
        ds_ref[...] = ds_acc[...]
        dt_ref[...] = dt_acc[...]


def _pad2d(x, block_r, block_c):
    R, C = x.shape
    pr = (-R) % block_r
    pc = (-C) % block_c
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x, R + pr, C + pc


def _pick_blocks_heuristic(rows, cols, n_bufs):
    """Hand-derived (block_r, block_c): the v5e defaults halved until the
    f32 working set of ``n_bufs`` blocks fits the VMEM budget; None when
    even the minimum tile does not.  Pure — the autotuner's search
    anchors on this and its candidates are pruned by the same budget."""
    block_r = min(_BLOCK_ROWS, max(8, -(-rows // 8) * 8))
    block_c = min(_BLOCK_COLS, max(128, -(-cols // 128) * 128))
    while block_r > 8 and block_r * block_c * 4 * n_bufs > _VMEM_BUDGET:
        block_r //= 2
    if block_r * block_c * 4 * n_bufs > _VMEM_BUDGET:
        return None
    return block_r, block_c


def _pick_blocks(rows, cols, n_bufs, quiet=False):
    """(block_r, block_c) for an instance: the autotuner's cost table
    when it has this (rows, cols) shape, else the heuristic.  The table
    key drops ``n_bufs`` — one entry serves fwd (3 bufs) and bwd (5),
    validated at the conservative 5-buf working set, so both passes run
    the SAME measured blocks.  ``quiet``: the routing check in
    ``_fssar_fwd`` censuses the decision ONCE; the fwd/bwd kernel
    entries re-read it quietly (no double counters, never a second
    search).  With no table and no ``MXNET_AUTOTUNE`` opt-in this is
    exactly ``_pick_blocks_heuristic`` (bit-identical default,
    regression-tested)."""
    from .. import tune as _tune
    tuned = _tune.table_blocks("fused_norm", (int(rows), int(cols)),
                               "float32", quiet=quiet)
    if tuned is not None:
        return tuned
    return _pick_blocks_heuristic(rows, cols, n_bufs)


def pallas_epilogue_fwd(x2d, s_row, t_row, r2d, interpret=False,
                        block_r=None, block_c=None):
    """x2d/r2d (R, C); s_row/t_row (1, C) f32 → y (R, C) in x's dtype.
    Explicit ``block_r``/``block_c`` bypass the picker (the autotune
    search times candidate configs through these)."""
    import jax.experimental.pallas as pl

    R, C = x2d.shape
    if block_r is None or block_c is None:
        block_r, block_c = _pick_blocks(R, C, 3, quiet=True)
    # clamp to the padded extents (the attention/LN kernels do the
    # same): an oversize block — a caller's or a stale table's — must
    # only cost its own tile, never padding R/C up to it
    block_r = min(block_r, max(8, -(-R // 8) * 8))
    block_c = min(block_c, max(128, -(-C // 128) * 128))
    xp, Rp, Cp = _pad2d(x2d, block_r, block_c)
    rp, _, _ = _pad2d(r2d, block_r, block_c)
    # scale/shift pad with ZEROS so padded columns emit relu(0) == 0
    sp, _, _ = _pad2d(s_row, 1, block_c)
    tp, _, _ = _pad2d(t_row, 1, block_c)
    y = pl.pallas_call(
        _epi_fwd_kernel,
        grid=(Cp // block_c, Rp // block_r),
        in_specs=[
            pl.BlockSpec((block_r, block_c), lambda ci, ri: (ri, ci)),
            pl.BlockSpec((1, block_c), lambda ci, ri: (0, ci)),
            pl.BlockSpec((1, block_c), lambda ci, ri: (0, ci)),
            pl.BlockSpec((block_r, block_c), lambda ci, ri: (ri, ci)),
        ],
        out_specs=pl.BlockSpec((block_r, block_c), lambda ci, ri: (ri, ci)),
        out_shape=jax.ShapeDtypeStruct((Rp, Cp), x2d.dtype),
        interpret=interpret,
        name="bn_add_relu_fwd",
    )(xp, sp, tp, rp)
    return y[:R, :C]


def pallas_epilogue_bwd(x2d, s_row, y2d, ct2d, interpret=False,
                        block_r=None, block_c=None):
    """→ (dx (R,C) x-dtype, dr (R,C) x-dtype, ds (1,C) f32, dt (1,C) f32)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, C = x2d.shape
    if block_r is None or block_c is None:
        block_r, block_c = _pick_blocks(R, C, 5, quiet=True)
    block_r = min(block_r, max(8, -(-R // 8) * 8))
    block_c = min(block_c, max(128, -(-C // 128) * 128))
    xp, Rp, Cp = _pad2d(x2d, block_r, block_c)
    yp, _, _ = _pad2d(y2d, block_r, block_c)
    # padded cotangent rows/cols are zero → no dx/dr/ds/dt contribution
    ctp, _, _ = _pad2d(ct2d, block_r, block_c)
    sp, _, _ = _pad2d(s_row, 1, block_c)
    n_rblocks = Rp // block_r
    dx, dr, ds, dt = pl.pallas_call(
        functools.partial(_epi_bwd_kernel, n_rblocks=n_rblocks),
        grid=(Cp // block_c, n_rblocks),
        in_specs=[
            pl.BlockSpec((block_r, block_c), lambda ci, ri: (ri, ci)),
            pl.BlockSpec((1, block_c), lambda ci, ri: (0, ci)),
            pl.BlockSpec((block_r, block_c), lambda ci, ri: (ri, ci)),
            pl.BlockSpec((block_r, block_c), lambda ci, ri: (ri, ci)),
        ],
        out_specs=[
            pl.BlockSpec((block_r, block_c), lambda ci, ri: (ri, ci)),
            pl.BlockSpec((block_r, block_c), lambda ci, ri: (ri, ci)),
            pl.BlockSpec((1, block_c), lambda ci, ri: (0, ci)),
            pl.BlockSpec((1, block_c), lambda ci, ri: (0, ci)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, Cp), x2d.dtype),
            jax.ShapeDtypeStruct((Rp, Cp), x2d.dtype),
            jax.ShapeDtypeStruct((1, Cp), jnp.float32),
            jax.ShapeDtypeStruct((1, Cp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_c), jnp.float32),
                        pltpu.VMEM((1, block_c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="bn_add_relu_bwd",
    )(xp, sp, yp, ctp)
    return dx[:R, :C], dr[:R, :C], ds[:, :C], dt[:, :C]


def _jnp_epilogue(x2d, scale, shift, r2d):
    y = (x2d.astype(jnp.float32) * scale + shift
         + r2d.astype(jnp.float32))
    return jnp.maximum(y, 0.0).astype(x2d.dtype)


@jax.custom_vjp
def fused_scale_shift_add_relu(x2d, scale, shift, r2d):
    """relu(x * scale + shift + residual) over 2D (rows, cols) operands
    with per-COLUMN fp32 scale/shift (cols,) — the BN epilogue in folded
    form.  Pallas kernels on TPU (one read + one write forward; the
    backward emits dx, dresidual AND the per-column dscale/dshift
    reductions in a single pass), jnp composition elsewhere."""
    return _fssar_fwd(x2d, scale, shift, r2d)[0]


def _fssar_fwd(x2d, scale, shift, r2d):
    s_row = scale.astype(jnp.float32).reshape(1, -1)
    t_row = shift.astype(jnp.float32).reshape(1, -1)
    # graftlint: disable-next=retrace-shape-branch -- kernel-vs-dense
    # choice is per-shape trace-time specialization by design
    if not _context.on_tpu(x2d) or \
            _pick_blocks(x2d.shape[0], x2d.shape[1], 5) is None:
        y = _jnp_epilogue(x2d, s_row, t_row, r2d)
        return y, (x2d, scale, shift, r2d, None)
    y = per_batch_shard(pallas_epilogue_fwd, (x2d, s_row, t_row, r2d),
                        replicated=(1, 2))
    return y, (x2d, scale, shift, r2d, y)


def _fssar_bwd(res, ct):
    x2d, scale, shift, r2d, y = res
    if y is None:
        _, vjp = jax.vjp(
            lambda x, s, t, r: _jnp_epilogue(
                x, s.astype(jnp.float32).reshape(1, -1),
                t.astype(jnp.float32).reshape(1, -1), r),
            x2d, scale, shift, r2d)
        return vjp(ct)
    s_row = scale.astype(jnp.float32).reshape(1, -1)
    # the per-column dscale/dshift reductions are partial sums per shard
    dx, dr, ds, dt = per_batch_shard(
        pallas_epilogue_bwd, (x2d, s_row, y, ct), replicated=(1,),
        summed=(False, False, True, True))
    return (dx, ds.reshape(scale.shape).astype(scale.dtype),
            dt.reshape(shift.shape).astype(shift.dtype),
            dr.astype(r2d.dtype))


fused_scale_shift_add_relu.defvjp(_fssar_fwd, _fssar_bwd)


def fused_bn_add_relu_epilogue(data, scale, shift, residual, axis):
    """ND entry: ``relu(data * scale[c] + shift[c] + residual)`` with the
    per-channel vectors broadcast on ``axis``.  Collapses the channel
    axis and everything minor to it into the lane (column) dimension —
    ``cols = C * trail`` with scale/shift repeated per trailing element —
    so NCHW and NHWC both route to the 2D kernel without a transpose."""
    # graftlint: disable-next=retrace-shape-branch -- shape validation:
    # raises on mismatch, no per-shape code paths
    if residual.shape != data.shape:
        raise ValueError("residual shape %r must match data shape %r"
                         % (residual.shape, data.shape))
    shape = data.shape
    axis = axis % data.ndim
    lead = 1
    for d in shape[:axis]:
        lead *= d
    trail = 1
    for d in shape[axis + 1:]:
        trail *= d
    cols = shape[axis] * trail
    s32 = scale.astype(jnp.float32)
    t32 = shift.astype(jnp.float32)
    if trail > 1:
        # differentiable broadcast: the (cols,) cotangent sums back over
        # the trailing repeat automatically
        s32 = jnp.repeat(s32, trail)
        t32 = jnp.repeat(t32, trail)
    out = fused_scale_shift_add_relu(
        data.reshape(lead, cols), s32, t32, residual.reshape(lead, cols))
    return out.reshape(shape)
