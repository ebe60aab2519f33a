"""Fused LayerNorm Pallas kernels for TPU (forward AND backward).

A profile of the BERT-base train step on an earlier machine showed the
XLA-composed LayerNorm chains at ~38% of device time — each of the 25 LN
sites expands into separate convert/subtract/reduce fusions that re-read
the (B, S, C) activation several times in fp32.  The fused kernels make
LN what it algorithmically is: ONE read + one write forward (stats in
fp32 on the fly), two reads + one write backward, with dgamma/dbeta
accumulated across row blocks in VMEM scratch.

Reference role: ``src/operator/nn/layer_norm.cc`` (the reference ships a
hand-written fused CPU/GPU LayerNorm for the same reason).

Layout: rows = every leading dim collapsed, C = the normalized (last)
axis rides the lanes.  Kernels require axis=-1; the generic jnp path in
``ops/nn.py`` remains the fallback (other axes, CPU, interpret tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import context as _context
from .pallas_attention import per_batch_shard

__all__ = ["fused_layer_norm", "pallas_layer_norm_fwd",
           "pallas_layer_norm_bwd"]

_BLOCK_ROWS = 512


def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, mu_ref, rs_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)            # (block, C)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    g = g_ref[...].astype(jnp.float32)            # (1, C)
    b = b_ref[...].astype(jnp.float32)
    y_ref[...] = (xc * rstd * g + b).astype(y_ref.dtype)
    mu_ref[...] = mu
    rs_ref[...] = rstd


def _ln_bwd_kernel(x_ref, g_ref, mu_ref, rs_ref, ct_ref,
                   dx_ref, dg_ref, db_ref, dg_acc, db_acc, *, n_blocks):
    import jax.experimental.pallas as pl

    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    ct = ct_ref[...].astype(jnp.float32)
    mu = mu_ref[...]                              # (block, 1) fp32
    rstd = rs_ref[...]
    xhat = (x - mu) * rstd
    g = g_ref[...].astype(jnp.float32)
    ctg = ct * g
    m1 = jnp.mean(ctg, axis=-1, keepdims=True)
    m2 = jnp.mean(ctg * xhat, axis=-1, keepdims=True)
    dx_ref[...] = ((ctg - m1 - xhat * m2) * rstd).astype(dx_ref.dtype)

    @pl.when(i == 0)
    def _init():
        dg_acc[...] = jnp.zeros_like(dg_acc)
        db_acc[...] = jnp.zeros_like(db_acc)

    dg_acc[...] += jnp.sum(ct * xhat, axis=0, keepdims=True)
    db_acc[...] += jnp.sum(ct, axis=0, keepdims=True)

    @pl.when(i == n_blocks - 1)
    def _flush():
        dg_ref[...] = dg_acc[...]
        db_ref[...] = db_acc[...]


def _pad_rows(x, block):
    n = x.shape[0]
    pad = (-n) % block
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, n + pad


def pallas_layer_norm_fwd(x2d, gamma, beta, eps, block_rows=_BLOCK_ROWS,
                          interpret=False):
    """x2d (N, C) → (y (N, C), mu (N, 1) f32, rstd (N, 1) f32).

    y's dtype follows jnp promotion over (x, gamma, beta) — identical to
    the composed ``(x-mu)*rstd*gamma+beta`` expression, so mixed-dtype
    (bf16 data, f32 affine) models see the same dtypes either path."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, C = x2d.shape
    out_dtype = jnp.result_type(x2d.dtype, gamma.dtype, beta.dtype)
    # keep the block a multiple of 8 sublanes (padding handles the tail)
    block = min(block_rows, max(8, -(-N // 8) * 8))
    xp, Np = _pad_rows(x2d, block)
    grid = (Np // block,)
    g2 = gamma.reshape(1, C)
    b2 = beta.reshape(1, C)
    y, mu, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, C), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, C), out_dtype),
            jax.ShapeDtypeStruct((Np, 1), jnp.float32),
            jax.ShapeDtypeStruct((Np, 1), jnp.float32),
        ],
        interpret=interpret,
        name="layernorm_fwd",
    )(xp, g2, b2)
    return y[:N], mu[:N], rstd[:N]


def pallas_layer_norm_bwd(x2d, gamma, mu, rstd, ct2d,
                          block_rows=_BLOCK_ROWS, interpret=False):
    """→ (dx (N, C) in x's dtype, dgamma (C,) f32, dbeta (C,) f32)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, C = x2d.shape
    block = min(block_rows, max(8, -(-N // 8) * 8))
    xp, Np = _pad_rows(x2d, block)
    # padded cotangent rows are zero, so they add nothing to dg/db and
    # their dx rows are sliced away
    ctp, _ = _pad_rows(ct2d, block)
    mup, _ = _pad_rows(mu, block)
    rsp, _ = _pad_rows(rstd, block)
    n_blocks = Np // block
    dx, dg, db = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, n_blocks=n_blocks),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
            pl.BlockSpec((block, C), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, C), x2d.dtype),
            jax.ShapeDtypeStruct((1, C), jnp.float32),
            jax.ShapeDtypeStruct((1, C), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, C), jnp.float32),
                        pltpu.VMEM((1, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="layernorm_bwd",
    )(xp, gamma.reshape(1, C), mup, rsp, ctp)
    return dx[:N], dg.reshape(C), db.reshape(C)


# bwd holds x, ct and dx blocks as f32 in VMEM (3 * block * C * 4B) plus
# small per-row/per-channel operands; budget well under the ~16 MB VMEM
_VMEM_BUDGET = 6 * 1024 * 1024


def _pick_block_rows(C):
    """Largest multiple-of-8 row block whose bwd working set fits the
    VMEM budget; None when even 8 rows do not fit (fall back to XLA)."""
    rows = _VMEM_BUDGET // (3 * 4 * C)
    rows = min(_BLOCK_ROWS, (rows // 8) * 8)
    return rows if rows >= 8 else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layer_norm(data, gamma, beta, eps=1e-5):
    """Last-axis LayerNorm with fused TPU kernels (jnp fallback off-TPU
    and for channel sizes past the VMEM budget).  Output dtype follows
    jnp promotion over (data, gamma, beta), like the composed form.

    Being a ``custom_vjp``, this supports reverse-mode only — forward-
    mode autodiff (jvp/hessians) raises.  That is why the LayerNorm op
    routes here only when ``MXNET_FUSED_LAYERNORM=1`` (opt-in): the
    fused kernels cut the LN HLO families ~4x in isolation but measured
    wall-clock-neutral on the BERT step (the step is bound elsewhere),
    so jvp-compatibility wins by default."""
    return _fln_fwd(data, gamma, beta, eps)[0]


def _jnp_ln(data, gamma, beta, eps):
    x32 = data.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    xc = x32 - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    out = (xc * lax.rsqrt(var + eps)).astype(data.dtype)
    return out * gamma + beta


def _fln_fwd(data, gamma, beta, eps):
    C = data.shape[-1]
    block = _pick_block_rows(C)
    if not _context.on_tpu(data) or block is None:
        out = _jnp_ln(data, gamma, beta, eps)
        return out, (data, gamma, beta, None, None)
    shape = data.shape
    x2d = data.reshape(-1, C)
    y, mu, rstd = per_batch_shard(
        lambda x, g, b: pallas_layer_norm_fwd(x, g, b, eps,
                                              block_rows=block),
        (x2d, gamma, beta), replicated=(1, 2))
    return y.reshape(shape), (data, gamma, beta, mu, rstd)


def _fln_bwd(eps, res, ct):
    data, gamma, beta, mu, rstd = res
    shape = data.shape
    C = shape[-1]
    if mu is None:
        _, vjp = jax.vjp(lambda d, g, b: _jnp_ln(d, g, b, eps),
                         data, gamma, beta)
        return vjp(ct)
    block = _pick_block_rows(C)
    # dgamma/dbeta are partial sums per shard
    dx2, dg, db = per_batch_shard(
        lambda x, g, mu, rs, ct: pallas_layer_norm_bwd(
            x, g, mu, rs, ct, block_rows=block),
        (data.reshape(-1, C), gamma, mu, rstd, ct.reshape(-1, C)),
        replicated=(1,), summed=(False, True, True))
    return (dx2.reshape(shape), dg.astype(gamma.dtype),
            db.astype(beta.dtype))


fused_layer_norm.defvjp(_fln_fwd, _fln_bwd)
