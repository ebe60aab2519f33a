"""Neural-network core operators.

Reference: ``src/operator/nn/`` (~29k LoC: convolution, fully_connected,
batch_norm, layer_norm, pooling, softmax, dropout, …) plus the cuDNN/MKLDNN
backends it dispatches to (SURVEY.md §2.2 rows 5-7).  TPU-native: every
kernel is a lax/jnp composition lowered by XLA onto the MXU (convs/matmuls)
with elementwise epilogues fused — the role cuDNN algorithm selection plays
on GPU is played by XLA autotuning here, for free.

Layout note: the public API keeps the reference's NCHW default; XLA's layout
assignment re-tiles for the TPU's native layouts internally.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register, alias

from jax.ad_checkpoint import checkpoint_name as _remat_name


# ---------------------------------------------------------------------------
# dense / conv
# ---------------------------------------------------------------------------

@register("FullyConnected", aliases=("fully_connected",))
def fully_connected(data, weight, bias=None, num_hidden: int = 0,
                    no_bias: bool = False, flatten: bool = True):
    """Reference src/operator/nn/fully_connected-inl.h: y = x·Wᵀ + b."""
    # graftlint: disable-next=retrace-shape-branch -- rank dispatch is
    # trace-time specialization by design (reference FC flatten rule)
    x = data.reshape(data.shape[0], -1) if flatten and data.ndim > 2 else data
    y = jnp.matmul(x, weight.T)
    if bias is not None and not no_bias:
        y = y + bias
    return y


def _conv_dn(ndim: int):
    if ndim == 1:
        return ("NCH", "OIH", "NCH")
    if ndim == 2:
        return ("NCHW", "OIHW", "NCHW")
    return ("NCDHW", "OIDHW", "NCDHW")


def _tup(v, n):
    if v is None:
        return (1,) * n
    if isinstance(v, int):
        return (v,) * n
    t = tuple(v)
    return t if len(t) == n else t + (t[-1],) * (n - len(t))


@register("Convolution", aliases=("convolution", "Convolution_v1"))
def convolution(data, weight, bias=None, kernel=(), stride=None, dilate=None,
                pad=None, num_filter: int = 0, num_group: int = 1,
                no_bias: bool = False, cudnn_tune=None, cudnn_off: bool = False,
                workspace: int = 1024, layout=None):
    """Reference src/operator/nn/convolution-inl.h → lax.conv_general_dilated
    (XLA conv lowers directly onto the MXU systolic array)."""
    n = len(kernel) if kernel else data.ndim - 2
    strides = _tup(stride, n)
    dil = _tup(dilate, n)
    pads = _tup(pad, n) if pad is not None else (0,) * n
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=strides,
        padding=[(p, p) for p in pads],
        rhs_dilation=dil,
        dimension_numbers=_conv_dn(n),
        feature_group_count=num_group,
    )
    out = _remat_name(out, "conv_out")
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * n)
    return out


@register("Deconvolution", aliases=("deconvolution",))
def deconvolution(data, weight, bias=None, kernel=(), stride=None, dilate=None,
                  pad=None, adj=None, target_shape=None, num_filter: int = 0,
                  num_group: int = 1, no_bias: bool = True, cudnn_tune=None,
                  cudnn_off: bool = False, workspace: int = 512, layout=None):
    """Transposed convolution (reference deconvolution-inl.h) via
    lax.conv_transpose with IO-swapped kernel."""
    n = len(kernel) if kernel else data.ndim - 2
    strides = _tup(stride, n)
    pads = _tup(pad, n) if pad is not None else (0,) * n
    dil = _tup(dilate, n)
    k = tuple(kernel)
    # grad-of-conv formulation: conv_general_dilated with lhs_dilation
    pad_cfg = [(d * (kk - 1) - p, d * (kk - 1) - p) for kk, p, d in zip(k, pads, dil)]
    if adj is not None:
        pad_cfg = [(lo, hi + a) for (lo, hi), a in zip(pad_cfg, _tup(adj, n))]
    # weight layout in MXNet deconv: (in_channels, out_channels/group, *k)
    w = jnp.flip(weight, axis=tuple(range(2, 2 + n)))
    if num_group > 1:
        cin, cog = w.shape[0], w.shape[1]
        w = w.reshape((num_group, cin // num_group, cog) + w.shape[2:])
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((num_group * cog, cin // num_group) + w.shape[3:])
    else:
        w = jnp.swapaxes(w, 0, 1)
    out = lax.conv_general_dilated(
        data, w,
        window_strides=(1,) * n,
        padding=pad_cfg,
        lhs_dilation=strides,
        rhs_dilation=dil,
        dimension_numbers=_conv_dn(n),
        feature_group_count=num_group,
    )
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * n)
    return out


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------

@register("Pooling", aliases=("pooling", "Pooling_v1"))
def pooling(data, kernel=(), pool_type: str = "max", global_pool: bool = False,
            stride=None, pad=None, pooling_convention: str = "valid",
            cudnn_off: bool = False, p_value=None, count_include_pad=None,
            layout=None):
    """Reference src/operator/nn/pooling-inl.h via lax.reduce_window."""
    n = data.ndim - 2
    if global_pool:
        ax = tuple(range(2, data.ndim))
        if pool_type == "max":
            return jnp.max(data, axis=ax, keepdims=True)
        return jnp.mean(data, axis=ax, keepdims=True)
    k = _tup(kernel, n)
    s = _tup(stride, n) if stride is not None else k
    p = _tup(pad, n) if pad is not None else (0,) * n
    dims = (1, 1) + k
    strides = (1, 1) + s
    if pooling_convention == "full":
        # ceil-mode: pad high side enough that ceil-div windows fit
        pads = [(0, 0), (0, 0)]
        for i in range(n):
            in_sz = data.shape[2 + i] + 2 * p[i]
            out_sz = -(-(in_sz - k[i]) // s[i]) + 1  # ceil
            needed = (out_sz - 1) * s[i] + k[i] - in_sz
            pads.append((p[i], p[i] + max(needed, 0)))
    else:
        pads = [(0, 0), (0, 0)] + [(pp, pp) for pp in p]
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, dims, strides, pads)
    if pool_type in ("avg", "sum"):
        summed = lax.reduce_window(data, 0.0, lax.add, dims, strides, pads)
        if pool_type == "sum":
            return summed
        if count_include_pad is None or count_include_pad:
            denom = 1
            for kk in k:
                denom *= kk
            return summed / denom
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, 0.0, lax.add, dims, strides, pads)
        return summed / counts
    if pool_type == "lp":
        pv = p_value or 2
        powed = lax.reduce_window(jnp.abs(data) ** pv, 0.0, lax.add, dims, strides, pads)
        return powed ** (1.0 / pv)
    raise ValueError("unknown pool_type %r" % pool_type)


@register("UpSampling")
def upsampling(*data, scale: int = 1, sample_type: str = "nearest",
               num_args: int = 1, num_filter: int = 0, multi_input_mode: str = "concat",
               workspace: int = 512):
    x = data[0]
    n, c, h, w = x.shape
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
    else:  # bilinear
        out = jax.image.resize(x, (n, c, h * scale, w * scale), method="bilinear")
    return out


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def _bn_stats(data, moving_mean, moving_var, axis, batch):
    """Per-channel (mean, var): the moving statistics, or with ``batch``
    the one-pass batch statistics :func:`batch_norm` documents."""
    if not batch:
        return moving_mean, moving_var
    ax = tuple(i for i in range(data.ndim) if i != (axis % data.ndim))
    mean = jnp.mean(data, axis=ax, dtype=jnp.float32)
    sq = jnp.mean(jnp.square(data), axis=ax, dtype=jnp.float32)
    # clamp: fp32 cancellation on a large-mean/low-variance channel can
    # drive E[x²]−E[x]² slightly negative → rsqrt NaN
    var = jnp.maximum(sq - jnp.square(mean), 0.0)
    # under backward-mirror remat the (tiny) per-channel stats are saved
    # so the bwd recompute never re-reduces the big activation tensor
    mean = _remat_name(mean.astype(data.dtype), "bn_stats")
    var = _remat_name(var.astype(data.dtype), "bn_stats")
    return mean, var


def _bn_fold(data, mean, var, gamma, beta, eps, fix_gamma, axis):
    """(mean, var, gamma, beta) folded in fp32 into per-channel
    (scale, shift) vectors, and the shape that broadcasts them on
    ``axis`` of ``data``."""
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    shape = [1] * data.ndim
    shape[axis % data.ndim] = data.shape[axis % data.ndim]
    scale = lax.rsqrt(var.astype(jnp.float32) + eps) * g.astype(jnp.float32)
    shift = beta.astype(jnp.float32) - mean.astype(jnp.float32) * scale
    return scale, shift, tuple(shape)


def _bn_apply(data, mean, var, gamma, beta, eps, fix_gamma, axis):
    """Normalize + affine, the part shared by BatchNorm / SyncBatchNorm.

    Folds (mean, var, gamma, beta) into per-channel scale/shift vectors in
    fp32, then applies ONE bf16-width elementwise pass ``x*scale + shift``.
    On TPU this matters: the naive ``(x-m)*rsqrt(v+eps)*g + b`` chain keeps
    wide intermediates alive, while scale/shift is a single fused
    multiply-add over the (HBM-bandwidth-bound) activation tensor.
    """
    scale, shift, shp = _bn_fold(data, mean, var, gamma, beta, eps,
                                 fix_gamma, axis)
    out = data * scale.astype(data.dtype).reshape(shp) \
        + shift.astype(data.dtype).reshape(shp)
    return out, lax.stop_gradient(mean), lax.stop_gradient(var)


@register("BatchNorm", num_outputs=3, needs_training=True,
          aliases=("batch_norm", "BatchNorm_v1"))
def batch_norm(data, gamma, beta, moving_mean, moving_var,
               eps: float = 1e-3, momentum: float = 0.9,
               fix_gamma: bool = True, use_global_stats: bool = False,
               output_mean_var: bool = False, axis: int = 1,
               cudnn_off: bool = False, training: bool = True):
    """Reference src/operator/nn/batch_norm-inl.h.

    Returns (out, batch_mean, batch_var); the moving-average update is done
    by the caller (Gluon layer) — functional style, so the same kernel works
    eagerly and under jit (aux-state updates become extra jit outputs).

    TPU note: statistics use the one-pass ``E[x²] − E[x]²`` form with fp32
    accumulators.  ``jnp.var`` would be a two-pass algorithm (mean first,
    then a second full read of ``(x-mean)²``) — the extra pass cannot fuse
    into the convolution that produced ``data``, and profiling shows it
    costs ~10% of a ResNet-50 train step on a bandwidth-bound v5e chip.
    One-pass lets XLA fuse BOTH reductions into the producing conv.
    """
    mean, var = _bn_stats(data, moving_mean, moving_var, axis,
                          training and not use_global_stats)
    return _bn_apply(data, mean, var, gamma, beta, eps, fix_gamma, axis)


@register("_contrib_BatchNormAddRelu", num_outputs=3, needs_training=True,
          aliases=("BatchNormAddRelu",))
def batch_norm_add_relu(data, residual, gamma, beta, moving_mean, moving_var,
                        eps: float = 1e-3, momentum: float = 0.9,
                        fix_gamma: bool = True,
                        use_global_stats: bool = False,
                        output_mean_var: bool = False, axis: int = 1,
                        cudnn_off: bool = False, training: bool = True):
    """BatchNorm → residual add → ReLU, the tail of a ResNet residual unit
    (reference: the cuDNN ``BatchNormAddRelu`` op MXNet enables on GPU).

    Statistics are computed exactly as :func:`batch_norm` (one-pass
    E[x²]−E[x]² in fp32, clamped, remat-named).  The normalize/affine is
    folded into per-channel fp32 scale/shift, and
    ``relu(x*scale + shift + residual)`` is plain ``jax.numpy`` on the ND
    tensor, accumulated in fp32 with ONE cast to ``data.dtype`` at the
    end: XLA runs it as one fusion in the layout the convolutions already
    use, and JAX differentiates it.  Returns (out, batch_mean,
    batch_var) like BatchNorm; the moving-average update stays with the
    caller."""
    # graftlint: disable-next=retrace-shape-branch -- shape validation:
    # raises on mismatch, no per-shape code paths
    if residual.shape != data.shape:
        raise ValueError("residual shape %r must match data shape %r"
                         % (residual.shape, data.shape))
    mean, var = _bn_stats(data, moving_mean, moving_var, axis,
                          training and not use_global_stats)
    scale, shift, shp = _bn_fold(data, mean, var, gamma, beta, eps,
                                 fix_gamma, axis)
    out = (data.astype(jnp.float32) * scale.reshape(shp)
           + shift.reshape(shp) + residual.astype(jnp.float32))
    out = jnp.maximum(out, 0.0).astype(data.dtype)
    return out, lax.stop_gradient(mean), lax.stop_gradient(var)


def _bound_axis_names():
    """Mapped-context axis names currently in scope."""
    from jax._src.core import get_axis_env
    return tuple(get_axis_env().axis_sizes)


@register("_contrib_SyncBatchNorm", num_outputs=3, needs_training=True,
          aliases=("SyncBatchNorm",))
def sync_batch_norm(data, gamma, beta, moving_mean, moving_var,
                    eps: float = 1e-3, momentum: float = 0.9,
                    fix_gamma: bool = True, use_global_stats: bool = False,
                    output_mean_var: bool = False, ndev: int = 1,
                    key: str = "dp", training: bool = True):
    """Cross-device BatchNorm (reference src/operator/contrib/sync_batch_norm).

    The reference's only cross-device op: workers exchange batch statistics
    before normalizing.  TPU-native this is a ``lax.pmean`` of (mean, E[x²])
    over the data-parallel mesh axis named ``key`` — when called inside a
    mapped context (shard_map/pjit step); standalone (no mapped axes bound)
    it degrades to local BatchNorm, matching ndev=1 semantics.  Calling it
    inside a mapped context whose axes do NOT include ``key`` is an error —
    silently falling back to per-device stats is the one failure this op
    exists to prevent.
    """
    ax = tuple(i for i in range(data.ndim) if i != 1)
    if use_global_stats or not training:
        mean, var = moving_mean, moving_var
    else:
        mean = jnp.mean(data, axis=ax, dtype=jnp.float32)
        sq = jnp.mean(jnp.square(data), axis=ax, dtype=jnp.float32)
        bound = _bound_axis_names()
        if key in bound:
            mean = lax.pmean(mean, key)
            sq = lax.pmean(sq, key)
        elif bound:
            raise ValueError(
                "SyncBatchNorm key=%r is not a bound mesh axis (bound: %r);"
                " pass key=<your data-parallel axis name>" % (key, bound))
        var = jnp.maximum(sq - jnp.square(mean), 0.0).astype(data.dtype)
        mean = mean.astype(data.dtype)
    return _bn_apply(data, mean, var, gamma, beta, eps, fix_gamma, axis=1)


@register("LayerNorm", aliases=("layer_norm",))
def layer_norm(data, gamma, beta, axis: int = -1, eps: float = 1e-5,
               output_mean_var: bool = False):
    # graftlint: disable-next=retrace-shape-branch -- kernel-vs-dense
    # choice is per-shape trace-time specialization by design
    if axis in (-1, data.ndim - 1) and not output_mean_var \
            and os.environ.get("MXNET_FUSED_LAYERNORM", "") == "1":
        # opt-in fused Pallas kernels (one read + one write fwd, fused
        # bwd with in-VMEM dgamma/dbeta accumulation).  Not the default:
        # custom_vjp breaks forward-mode autodiff, and on the BERT bench
        # the fused path measured wall-clock-neutral (the step is bound
        # by gemms/attention/optimizer, not LN) — see
        # pallas_layernorm.fused_layer_norm.
        from .pallas_layernorm import fused_layer_norm
        return fused_layer_norm(data, gamma, beta, float(eps))
    if jnp.dtype(data.dtype).itemsize < 4:
        # low-precision inputs: one-pass E[x^2]-E[x]^2 stats in fp32 —
        # both reductions fuse into a single read of x (jnp.var's
        # two-pass form re-reads it) and the backward reduces over x
        # once.  The fp32 accumulator has ~2^16 more mantissa headroom
        # than the bf16 values, so the cancellation is benign HERE —
        # fp32 inputs keep the two-pass form below precisely because it
        # is not (values ~1e4 with std ~1 would cancel to garbage).
        x32 = data.astype(jnp.float32)
        mean = jnp.mean(x32, axis=axis, keepdims=True)
        msq = jnp.mean(x32 * x32, axis=axis, keepdims=True)
        var = jnp.maximum(msq - mean * mean, 0.0)
        out = ((x32 - mean) * lax.rsqrt(var + eps)).astype(data.dtype)
    else:
        mean = jnp.mean(data, axis=axis, keepdims=True)
        var = jnp.var(data, axis=axis, keepdims=True)
        out = (data - mean) * lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("GroupNorm")
def group_norm(data, gamma, beta, num_groups: int = 1, eps: float = 1e-5,
               output_mean_var: bool = False):
    n, c = data.shape[:2]
    x = data.reshape((n, num_groups, c // num_groups) + data.shape[2:])
    ax = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=ax, keepdims=True)
    var = jnp.var(x, axis=ax, keepdims=True)
    x = (x - mean) * lax.rsqrt(var + eps)
    x = x.reshape(data.shape)
    shape = (1, c) + (1,) * (data.ndim - 2)
    return x * gamma.reshape(shape) + beta.reshape(shape)


@register("InstanceNorm")
def instance_norm(data, gamma, beta, eps: float = 1e-3):
    ax = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.var(data, axis=ax, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = (1, data.shape[1]) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("LRN", aliases=("lrn",))
def lrn(data, nsize: int = 5, alpha: float = 1e-4, beta: float = 0.75, knorm: float = 2.0):
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(padded[:, i:i + data.shape[1]] for i in range(nsize))
    return data / jnp.power(knorm + (alpha / nsize) * acc, beta)


# ---------------------------------------------------------------------------
# activations / softmax
# ---------------------------------------------------------------------------

@register("Activation", aliases=("activation",))
def activation(data, act_type: str = "relu"):
    if act_type == "relu":
        return jax.nn.relu(data)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    if act_type == "gelu":
        # the reference exposes gelu via LeakyReLU(act_type='gelu'); also
        # accepted here so Dense(activation='gelu') composes directly
        return jax.nn.gelu(data, approximate=False)
    if act_type == "silu":
        return jax.nn.silu(data)
    if act_type == "relu2":
        # squared ReLU (So et al. arXiv:2109.08668)
        return jnp.square(jax.nn.relu(data))
    raise ValueError("unknown act_type %r" % act_type)


@register("LeakyReLU")
def leaky_relu(data, gamma=None, act_type: str = "leaky", slope: float = 0.25,
               lower_bound: float = 0.125, upper_bound: float = 0.334):
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "prelu":
        g = gamma
        # graftlint: disable-next=retrace-shape-branch -- rank dispatch
        # is trace-time specialization by design (per-channel broadcast)
        shape = (1, -1) + (1,) * (data.ndim - 2) if data.ndim > 1 else (-1,)
        return jnp.where(data > 0, data, g.reshape(shape) * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        a, s = 1.6732632423543772, 1.0507009873554805
        return s * jnp.where(data > 0, data, a * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":  # eval-mode deterministic slope
        return jnp.where(data > 0, data, (lower_bound + upper_bound) / 2 * data)
    raise ValueError("unknown act_type %r" % act_type)


@register("softmax")
def softmax(data, length=None, axis: int = -1, temperature=None,
            dtype=None, use_length: bool = False):
    x = data / temperature if temperature else data
    if length is not None and use_length:
        idx = jnp.arange(x.shape[axis])
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        mask = idx.reshape(shape) < jnp.expand_dims(length, axis)
        x = jnp.where(mask, x, -jnp.inf)
    out = jax.nn.softmax(x, axis=axis)
    if length is not None and use_length:
        out = jnp.where(mask, out, 0.0)
    return out.astype(jnp.dtype(dtype)) if dtype else out


@register("log_softmax")
def log_softmax(data, axis: int = -1, temperature=None, dtype=None,
                use_length: bool = False):
    x = data / temperature if temperature else data
    out = jax.nn.log_softmax(x, axis=axis)
    return out.astype(jnp.dtype(dtype)) if dtype else out


@register("softmin")
def softmin(data, axis: int = -1, temperature=None, dtype=None):
    return softmax(-data, axis=axis, temperature=temperature, dtype=dtype)


@register("SoftmaxActivation")
def softmax_activation(data, mode: str = "instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, multi_output,
                        use_ignore, preserve_shape, normalization, out_grad, smooth_alpha):
    axis = 1 if (multi_output and data.ndim > 2) else -1
    return jax.nn.softmax(data, axis=axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _softmax_output_core(data, label, grad_scale, ignore_label, use_ignore,
                         normalization, smooth_alpha, batch_size):
    return jax.nn.softmax(data, axis=-1)


def _smo_fwd(data, label, grad_scale, ignore_label, use_ignore,
             normalization, smooth_alpha, batch_size):
    out = jax.nn.softmax(data, axis=-1)
    return out, (out, label)


def _smo_bwd(grad_scale, ignore_label, use_ignore, normalization,
             smooth_alpha, batch_size, res, g):
    # reference mshadow SoftmaxGrad/SmoothSoftmaxGrad + the normalization
    # ladder of softmax_output-inl.h:187-242
    out, label = res
    k = out.shape[-1]
    li = label.astype(jnp.int32)
    oh = jax.nn.one_hot(li, k, dtype=out.dtype)
    if smooth_alpha:
        # target gets p-1+alpha; the rest p - alpha/(K-1)
        target = (1.0 - smooth_alpha) * oh \
            + (smooth_alpha / max(k - 1, 1)) * (1.0 - oh)
        dx = out - target.astype(out.dtype)
    else:
        dx = out - oh
    valid = None
    if use_ignore:
        valid = (label != ignore_label)
        dx = dx * valid[:, None].astype(dx.dtype)
    scale = jnp.asarray(grad_scale, jnp.float32)
    if normalization == "batch":
        # divide by the TRUE batch size (reference kBatch uses
        # label.size(0)), not the flattened N*positions row count the
        # multi_output path hands this kernel
        scale = scale / batch_size
    elif normalization == "valid":
        # reference kValid: non-ignored count under use_ignore, else the
        # full label count (softmax_output-inl.h:194)
        if valid is not None:
            scale = scale / jnp.maximum(
                jnp.sum(valid.astype(jnp.float32)), 1.0)
        else:
            scale = scale / max(int(label.shape[0]), 1)
    dx = (dx.astype(jnp.float32) * scale).astype(out.dtype)
    return (dx, jnp.zeros_like(label))


_softmax_output_core.defvjp(_smo_fwd, _smo_bwd)


@register("SoftmaxOutput", aliases=("softmax_output", "Softmax"))
def softmax_output(data, label, grad_scale: float = 1.0, ignore_label: float = -1.0,
                   multi_output: bool = False, use_ignore: bool = False,
                   preserve_shape: bool = False, normalization: str = "null",
                   out_grad: bool = False, smooth_alpha: float = 0.0):
    """Reference src/operator/softmax_output-inl.h: forward = softmax; the
    *backward* ignores the incoming head-grad and produces (p - target)
    via custom_vjp, honoring grad_scale, use_ignore/ignore_label,
    normalization ('null'|'batch'|'valid') and smooth_alpha label
    smoothing (mshadow SoftmaxGrad/SmoothSoftmaxGrad)."""
    knobs = (float(grad_scale), float(ignore_label), bool(use_ignore),
             str(normalization), float(smooth_alpha), int(data.shape[0]))
    # graftlint: disable-next=retrace-shape-branch -- rank dispatch is
    # trace-time specialization by design (reference multi-output rule)
    if data.ndim > 2 and multi_output:
        # (N, C, ...) softmax over C with per-position labels
        x = jnp.moveaxis(data, 1, -1)
        flat = x.reshape(-1, x.shape[-1])
        out = _softmax_output_core(flat, label.reshape(-1), *knobs)
        out = jnp.moveaxis(out.reshape(x.shape), -1, 1)
        return out
    x = data.reshape(data.shape[0], -1)
    out = _softmax_output_core(x, label.reshape(-1), *knobs)
    return out.reshape(data.shape) if preserve_shape else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _svm_output_core(data, label, margin, reg, use_linear):
    return data


def _svm_fwd(data, label, margin, reg, use_linear):
    return data, (data, label)


def _svm_bwd(margin, reg, use_linear, res, g):
    # like SoftmaxOutput, the head grad is IGNORED: the op IS the loss head
    # (reference svm_output.cc L1_SVM/L2_SVM kernels)
    x, label = res
    k = jax.nn.one_hot(label.astype(jnp.int32), x.shape[-1],
                       dtype=x.dtype) > 0
    if use_linear:      # L1-SVM: +-reg on margin violations
        at_k = -(margin > x).astype(x.dtype) * reg
        off_k = (margin > -x).astype(x.dtype) * reg
    else:               # L2-SVM (default): linear-in-violation magnitude
        at_k = jnp.where(margin > x, 2.0 * (margin - x), 0.0) * -reg
        off_k = jnp.where(margin > -x, -2.0 * (margin + x), 0.0) * -reg
    dx = jnp.where(k, at_k, off_k).astype(x.dtype)
    return dx, jnp.zeros_like(label)


_svm_output_core.defvjp(_svm_fwd, _svm_bwd)


@register("SVMOutput", aliases=("svm_output",))
def svm_output(data, label, margin: float = 1.0,
               regularization_coefficient: float = 1.0,
               use_linear: bool = False):
    """Reference src/operator/svm_output.cc: forward = identity; backward
    replaces the head grad with the hinge-loss gradient (L2-SVM by
    default, L1-SVM with ``use_linear``), scaled by
    ``regularization_coefficient``."""
    x = data.reshape(data.shape[0], -1)
    out = _svm_output_core(x, label.reshape(-1), float(margin),
                           float(regularization_coefficient),
                           bool(use_linear))
    return out.reshape(data.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _kl_sparse_core(data, moving_avg, sparseness_target, penalty, momentum,
                    has_ma):
    return data


def _klsr_fwd(data, moving_avg, sparseness_target, penalty, momentum,
              has_ma):
    return data, (data, moving_avg, has_ma)


def _klsr_bwd(sparseness_target, penalty, momentum, has_ma, res, g):
    x, moving_avg, _ = res
    rho = sparseness_target
    avg = jnp.mean(x, axis=0)                         # per-unit activation
    # momentum applies only against a caller-carried running average; a
    # fresh call uses the batch average directly (a zero-initialized ma
    # would shrink the denominator 10x and explode the penalty)
    ma = momentum * moving_avg + (1.0 - momentum) * avg if has_ma else avg
    # dead units (avg == 0) must not emit -rho/0 = -inf gradients
    eps = 1e-6
    ma = jnp.clip(ma, eps, 1.0 - eps)
    kl = penalty * (-rho / ma + (1.0 - rho) / (1.0 - ma))
    return (g + jnp.broadcast_to(kl, x.shape).astype(x.dtype),
            jnp.zeros_like(moving_avg))


_kl_sparse_core.defvjp(_klsr_fwd, _klsr_bwd)


@register("IdentityAttachKLSparseReg",
          aliases=("identity_attach_KL_sparse_reg",))
def identity_attach_kl_sparse_reg(data, moving_avg=None,
                                  sparseness_target: float = 0.1,
                                  penalty: float = 0.001,
                                  momentum: float = 0.9):
    """Reference src/operator/identity_attach_KL_sparse_reg.cc: forward is
    identity; backward adds the KL-divergence sparseness penalty
    ``penalty * (-rho/ma + (1-rho)/(1-ma))``.  ``ma`` is the
    momentum-blend of a caller-carried running average with the batch
    average when ``moving_avg`` is supplied (the reference's aux state,
    which the caller updates as ``momentum*ma + (1-momentum)*batch_avg``
    between steps), or simply the batch average when it is not; the
    denominator is clamped away from 0/1 so dead units cannot emit
    infinite gradients."""
    x = data.reshape(data.shape[0], -1)
    has_ma = moving_avg is not None
    if not has_ma:
        moving_avg = jnp.zeros((x.shape[-1],), x.dtype)
    out = _kl_sparse_core(x, moving_avg, float(sparseness_target),
                          float(penalty), float(momentum), has_ma)
    return out.reshape(data.shape)


@register("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    oh = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1], dtype=data.dtype)
    return -jnp.sum(oh * logp)


# ---------------------------------------------------------------------------
# dropout (RNG op)
# ---------------------------------------------------------------------------

@register("Dropout", needs_rng=True, needs_training=True, aliases=("dropout",))
def dropout(key, data, p: float = 0.5, mode: str = "training", axes=(),
            cudnn_off: bool = True, training: bool = True):
    """Reference src/operator/nn/dropout-inl.h (scaled/inverted dropout)."""
    if not training and mode != "always":
        return data
    if p <= 0.0:
        return data
    shape = list(data.shape)
    if axes:
        for a in axes:
            shape[a] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, tuple(shape)).astype(data.dtype)
    return data * mask / keep


# ---------------------------------------------------------------------------
# embedding / sequence ops
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _take_rows_grad_summed_in_f32(weight, idx):
    """``weight[idx]`` whose gradient sums the rows of one id in float32
    BEFORE the one cast to the weight's dtype.  Autodiff's scatter-add adds
    in the cotangent's dtype: with bfloat16 and text-like ids — the most
    frequent token 1,300 times in 16,384 — a row's running sum soon
    outgrows what 8 bits of mantissa can add a single term to (17.7% off in
    the Frobenius norm on the chip, PERF.md PR 26).  The ids are sorted,
    equal ids summed as one segment, and each segment's sum lands in its
    row once."""
    return jnp.take(weight, idx, axis=0)


def _take_rows_fwd(weight, idx):
    return jnp.take(weight, idx, axis=0), (weight, idx)


def _take_rows_bwd(res, g):
    import numpy as onp
    from ..parallel.mesh import batch_shards, per_batch_shard
    weight, idx = res
    table, dtype = weight.shape, weight.dtype

    def summed(idx, g):
        ids = idx.reshape(-1)
        n = ids.shape[0]
        order = jnp.argsort(ids)
        ids = jnp.take(ids, order)
        rows = jnp.take(g.reshape(n, -1).astype(jnp.float32), order, axis=0)
        segment = jnp.cumsum(jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             (ids[1:] != ids[:-1]).astype(jnp.int32)]))
        sums = jax.ops.segment_sum(rows, segment, num_segments=n,
                                   indices_are_sorted=True)
        # segment s is the id of its first row; segments past the last one
        # point outside the table and are dropped
        first = jnp.full((n,), table[0], jnp.int32).at[segment].min(ids)
        return (jnp.zeros(table, dtype).at[first].set(
            sums.astype(dtype), mode="drop"),)

    # under a batch GSPMD shards, each shard sorts and sums its own ids
    # and the tables are added across shards, as autodiff's were (a sort
    # over the whole batch would gather every shard's rows on every chip);
    # ids that do not split (one row of positions) are summed whole
    # graftlint: disable-next=retrace-shape-branch -- the layout of the ids
    # is static per program: one trace a shape is what is meant
    if idx.ndim and idx.shape[0] % batch_shards() == 0:
        (grad,) = per_batch_shard(summed, (idx, g), summed=(True,))
    else:
        (grad,) = summed(idx, g)
    return grad, onp.zeros(idx.shape, jax.dtypes.float0)


_take_rows_grad_summed_in_f32.defvjp(_take_rows_fwd, _take_rows_bwd)


@register("Embedding")
def embedding(data, weight, input_dim: int = 0, output_dim: int = 0,
              dtype="float32", sparse_grad: bool = False):
    idx = jnp.clip(data.astype(jnp.int32), 0, weight.shape[0] - 1)
    if jnp.dtype(weight.dtype).itemsize < 4:
        # a half-width table: the rows of one id are summed in float32
        return _take_rows_grad_summed_in_f32(weight, idx)
    return jnp.take(weight, idx, axis=0)


def _embedding_sparse_vjp_factory(static_kwargs):
    """With sparse_grad=True the weight gradient is delivered as a
    parts-backed RowSparseNDArray — (unique batch ids, summed cotangent
    rows) — so backward cost scales with the batch, not the vocabulary
    (reference: Embedding sparse_grad + row_sparse kernels in
    src/operator/tensor/indexing_op.cc)."""
    if not static_kwargs.get("sparse_grad"):
        return None

    def hook(in_values, outs_ct):
        import numpy as onp
        from ..ndarray.sparse import RowSparseNDArray, dedup_rows
        ids, weight = in_values[0], in_values[1]
        ct = outs_ct[0]
        if ct is None:
            return (None, None)
        flat_ids = onp.asarray(ids).astype(onp.int64).ravel()
        flat_ids = onp.clip(flat_ids, 0, weight.shape[0] - 1)
        ct_rows = onp.asarray(ct).reshape(flat_ids.size, -1)
        uniq, summed = dedup_rows(flat_ids, ct_rows)
        summed = summed.reshape((uniq.size,) + tuple(weight.shape[1:]))
        return (None, RowSparseNDArray.from_parts(summed, uniq,
                                                  weight.shape))
    return hook


embedding._sparse_vjp_factory = _embedding_sparse_vjp_factory


@register("SequenceMask")
def sequence_mask(data, sequence_length=None, use_sequence_length: bool = False,
                  value: float = 0.0, axis: int = 0):
    """Reference src/operator/sequence_mask: data is (T, N, ...) (axis=0) or
    (N, T, ...) (axis=1)."""
    if not use_sequence_length or sequence_length is None:
        return data
    T = data.shape[axis]
    idx = jnp.arange(T)
    if axis == 0:
        shape = (T, 1) + (1,) * (data.ndim - 2)
        lshape = (1, -1) + (1,) * (data.ndim - 2)
    else:
        shape = (1, T) + (1,) * (data.ndim - 2)
        lshape = (-1, 1) + (1,) * (data.ndim - 2)
    mask = idx.reshape(shape) < sequence_length.reshape(lshape)
    return jnp.where(mask, data, value)


@register("SequenceLast")
def sequence_last(data, sequence_length=None, use_sequence_length: bool = False,
                  axis: int = 0):
    if not use_sequence_length or sequence_length is None:
        return jnp.take(data, -1, axis=axis)
    last = (sequence_length.astype(jnp.int32) - 1)
    if axis == 0:
        return jnp.take_along_axis(
            data, last.reshape((1, -1) + (1,) * (data.ndim - 2)), axis=0)[0]
    return jnp.take_along_axis(
        data, last.reshape((-1, 1) + (1,) * (data.ndim - 2)), axis=1)[:, 0]


@register("SequenceReverse")
def sequence_reverse(data, sequence_length=None, use_sequence_length: bool = False,
                     axis: int = 0):
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(data, axis=0)
    T = data.shape[0]
    idx = jnp.arange(T).reshape(-1, 1)
    L = sequence_length.astype(jnp.int32).reshape(1, -1)
    rev = jnp.where(idx < L, L - 1 - idx, idx)
    return jnp.take_along_axis(data, rev.reshape(rev.shape + (1,) * (data.ndim - 2)), axis=0)


@register("slice_channel", num_outputs=0, aliases=("SliceChannel",))
def slice_channel(data, num_outputs: int = 1, axis: int = 1, squeeze_axis: bool = False):
    parts = jnp.split(data, num_outputs, axis=axis)
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=axis) for p in parts]
    return tuple(parts)


# ---------------------------------------------------------------------------
# losses as ops (reference loss/output group)
# ---------------------------------------------------------------------------

@register("LinearRegressionOutput", aliases=("linear_regression_output",))
def linear_regression_output(data, label, grad_scale: float = 1.0):
    return data  # forward identity; grad is (data-label) — handled by Gluon L2Loss


@register("MAERegressionOutput")
def mae_regression_output(data, label, grad_scale: float = 1.0):
    return data


@register("LogisticRegressionOutput", aliases=("logistic_regression_output",))
def logistic_regression_output(data, label, grad_scale: float = 1.0):
    return jax.nn.sigmoid(data)


# ---------------------------------------------------------------------------
# decoder-block ops: RMS norm, rotary embedding on part of a head, causal
# convolutions over the sequence, and the cross-entropy against a tied
# embedding taken in blocks of its rows
# ---------------------------------------------------------------------------

def _gated_group_rms_norm(data, gamma, gate, eps, groups):
    x32 = data.astype(jnp.float32)
    if gate is not None:
        x32 = x32 * jax.nn.silu(gate.astype(jnp.float32))
    parts = x32.reshape(x32.shape[:-1] + (groups, -1))
    inv = lax.rsqrt(jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
    return ((parts * inv).reshape(x32.shape)
            * gamma.astype(jnp.float32)).astype(data.dtype)


@register("RMSNorm", aliases=("rms_norm",))
def rms_norm(data, gamma, gate=None, axis: int = -1, eps: float = 1e-5,
             groups: int = 1):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over ``axis``; statistics in
    fp32 whatever the input, one cast out.  With ``groups`` > 1 the mean
    is taken over each of that many equal parts of the LAST axis on its
    own (``gamma`` still one gain a channel); with ``gate`` the input is
    ``x * silu(gate)`` first — the gate BEFORE the norm, as a Mamba-2
    mixer's output norm has it."""
    if gate is not None or groups > 1:
        # recomputed in the backward: what autodiff would keep are three
        # float32 copies of the input, six times its bfloat16 bytes
        return jax.checkpoint(functools.partial(
            _gated_group_rms_norm, eps=eps, groups=groups))(data, gamma,
                                                            gate)
    x32 = data.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(x32 * x32, axis=axis, keepdims=True) + eps)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    return (x32 * inv * gamma.astype(jnp.float32).reshape(shape)).astype(
        data.dtype)


@register("l2_normalize")
def l2_normalize(data, scale=None, axis: int = -1, eps: float = 1e-12):
    """``sqrt(d) x / |x|`` over ``axis`` of width d (a unit RMS vector),
    times the learned ``scale`` where one is given: one scalar per entry
    of axis -3 (a head of a (B, H, S, D) tensor)."""
    x32 = data.astype(jnp.float32)
    d = data.shape[axis]
    out = x32 * lax.rsqrt(jnp.sum(x32 * x32, axis=axis, keepdims=True)
                          + eps) * (d ** 0.5)
    if scale is not None:
        out = out * scale.astype(jnp.float32).reshape(-1, 1, 1)
    return out.astype(data.dtype)


def yarn_ramp(rotary_dim, theta, original_length, beta_fast=32.0,
              beta_slow=1.0):
    """YaRN's blend a frequency (Peng et al., arXiv:2309.00071, as the
    ``transformers`` rule has it), (rotary_dim / 2,) float32 in [0, 1]: 0
    where dimension i turns more than ``beta_fast`` times over
    ``original_length`` positions (kept as trained), 1 where fewer than
    ``beta_slow`` (interpolated), linear between.  With r =
    ``rotary_dim``: ``corr(n) = r ln(original_length / (2 pi n)) /
    (2 ln theta)``, ``low = max(floor(corr(beta_fast)), 0)``, ``high =
    min(ceil(corr(beta_slow)), r - 1)``, ``ramp_i = clip((i - low) /
    (high - low), 0, 1)``."""
    import numpy as onp

    def corr(turns):
        return rotary_dim * math.log(original_length / (turns * 2 * math.pi)
                                     ) / (2 * math.log(theta))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001           # the rule's own guard against 0 / 0
    return onp.clip((onp.arange(rotary_dim // 2, dtype="float32") - low)
                    / (high - low), 0, 1)


@register("rotary_embedding")
def rotary_embedding(data, positions=None, rotary_dim: int = 0,
                     theta: float = 10000.0, rope_type: str = "default",
                     factor: float = 1.0, original_length: int = 0,
                     beta_fast: float = 32.0, beta_slow: float = 1.0,
                     attention_factor: float = 1.0):
    """Rotary position embedding on the first ``rotary_dim`` of the D
    dimensions of a (B, H, S, D) tensor (0: all of them), the rest passed
    through.  Rotate-half pairing: dimension i turns with i + rotary_dim/2
    by the angle ``t * freq_i``, ``freq_i = theta ** (-2 i / rotary_dim)``;
    t is the token's
    place in the row, from 0, or where ``positions`` (B, S) is given the
    token's own position id (ids may repeat: a row that holds a sequence
    twice).

    ``rope_type="yarn"``: a model trained to ``original_length`` positions
    and stretched ``factor`` times — ``freq_i (1 - ramp_i) + freq_i /
    factor * ramp_i`` with ``yarn_ramp``'s blend, and cos and sin times
    ``attention_factor`` (the rule's scale on the scores, carried by q
    and k)."""
    from .. import telemetry
    if rope_type not in ("default", "yarn"):
        raise ValueError("rope_type=%r is neither default nor yarn"
                         % (rope_type,))
    r = rotary_dim or data.shape[-1]
    half = r // 2
    # trace-time census, once a traced shape, like attention_dispatch's
    telemetry.inc("rotary.rule.%s" % rope_type)
    telemetry.event("rotary", rope_type, rule=rope_type, rotary_dim=int(r),
                    factor=float(factor), theta=float(theta))
    if positions is None:
        pos = jnp.arange(data.shape[-2], dtype=jnp.float32)
    else:
        pos = positions.astype(jnp.float32)[:, None, :]      # (B, 1, S)
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / r)
    if rope_type == "yarn":
        ramp = yarn_ramp(r, theta, original_length, beta_fast, beta_slow)
        freq = freq * (1.0 - ramp) + freq / factor * ramp
    ang = pos[..., None] * freq[None, :]                     # (.., S, r/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if attention_factor != 1.0:
        cos, sin = cos * attention_factor, sin * attention_factor
    x32 = data.astype(jnp.float32)
    x1, x2, rest = x32[..., :half], x32[..., half:r], x32[..., r:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
        axis=-1).astype(data.dtype)


@register("causal_conv1d")
def causal_conv1d(data, weight, bias=None, groups: int = 1):
    """Causal convolution over the sequence of a (B, S, C) tensor,
    left-padded with zeros so that the output at t reads inputs t-K+1..t.
    ``weight`` is (C_out, C_in / groups, K) as ``Conv1D``'s; tap K-1
    meets the input at t; ``bias`` (C_out,) where one is given.
    ``groups == C`` is the depthwise case (a multiply-add per tap);
    otherwise one (C/g x C/g) product per group and tap, accumulated in
    fp32."""
    b, s, c = data.shape
    c_out, c_in_g, k = weight.shape
    out = None
    for j in range(k):
        shift = k - 1 - j
        xs = data if shift == 0 else \
            jnp.pad(data, ((0, 0), (shift, 0), (0, 0)))[:, :s]
        if c_in_g == 1 and c_out == c:
            term = xs.astype(jnp.float32) * weight[:, 0, j].astype(
                jnp.float32)
        else:
            wj = weight[:, :, j].reshape(groups, c_out // groups, c_in_g)
            term = jnp.einsum(
                "bsgi,goi->bsgo", xs.reshape(b, s, groups, c_in_g), wj,
                preferred_element_type=jnp.float32).reshape(b, s, c_out)
        out = term if out is None else out + term
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(data.dtype)


def vocab_block_rows(vocab, target):
    """The largest divisor of ``vocab`` that is at most ``target``: the
    rows of the embedding one block of logits covers."""
    target = max(1, min(int(target), vocab))
    return next(r for r in range(target, 0, -1) if vocab % r == 0)


def token_block_positions(seq, vocab, block_rows):
    """The positions of a row one block of TOKENS covers: the largest
    divisor of ``seq`` whose (B, positions, V) logits are no more than the
    (B * seq, block_rows) a block of vocabulary rows holds."""
    return vocab_block_rows(seq, seq * block_rows // vocab)


def _tied_ce_token_blocks(hidden, label, scale, weight, block_rows, grads):
    """The cross-entropy and the log-sum-exp of every position of
    ``hidden`` (B, S, D), a block of positions against ALL rows of
    ``weight`` at a time — a block's log-sum-exp is whole while its logits
    are still there.  With ``grads`` also the gradients of
    ``sum(scale * ce)``: dh in float32, each block written once, and dW
    summed over the blocks in ONE float32 (V, D) accumulator that the loop
    carries."""
    b, s, _ = hidden.shape
    v = weight.shape[0]
    s_blk = token_block_positions(s, v, block_rows)

    def block(x, i):
        return lax.dynamic_slice_in_dim(x, i * s_blk, s_blk, axis=1)

    def body(dw, i):
        h, lab = block(hidden, i), block(label, i)
        logits = lax.dot_general(h, weight, (((2,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
        picked = jnp.take_along_axis(
            logits, jnp.clip(lab, 0, v - 1)[..., None], axis=-1)[..., 0]
        ce = lse - jnp.where((lab >= 0) & (lab < v), picked, 0.0)
        if not grads:
            return dw, (ce, lse)
        col = lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        dl = (jnp.exp(logits - lse[..., None])
              - (col == lab[..., None]).astype(jnp.float32)
              ) * block(scale, i)[..., None]
        dl = dl.astype(hidden.dtype)
        dh = lax.dot_general(dl, weight, (((2,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        dw = dw + lax.dot_general(dl, h, (((0, 1), (0, 1)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return dw, (ce, lse, dh)

    dw, out = lax.scan(
        body, jnp.zeros(weight.shape if grads else (), jnp.float32),
        jnp.arange(s // s_blk))
    # (blocks, B, s_blk, ...) -> (B, S, ...)
    out = tuple(jnp.moveaxis(x, 0, 1).reshape((b, s) + x.shape[3:])
                for x in out)
    return out + (dw,) if grads else out


def _tied_ce_per_shard(hidden, weight, label, scale, block_rows, grads):
    """``_tied_ce_token_blocks`` once a shard of the batch where the
    program being traced has one sharded (the batch axis is never merged
    with the sequence's, so a shard's rows stay its own): each shard sums
    its own dW over its blocks and the shards' sums are added ONCE —
    left to the partitioner the whole (V, D) accumulator would be reduced
    over the mesh every block."""
    from .pallas_attention import per_batch_shard
    return per_batch_shard(
        functools.partial(_tied_ce_token_blocks, block_rows=block_rows,
                          grads=grads),
        [hidden, label, scale, weight], replicated=(3,),
        summed=(False, False, False, True) if grads else (False, False))


def _tied_ce_recompute(hidden, weight, label, lse, g, block_rows):
    """dh and dW of ``sum(g * ce)`` over (N, D) tokens with every logit
    formed a SECOND time, a block of ``block_rows`` vocabulary rows
    against all tokens at a time: each block's part of dW is whole when
    the block is done, dh is summed over the blocks in float32."""
    from .. import telemetry
    telemetry.inc("tied_ce.path.recompute")
    v, d = weight.shape
    blocks = weight.reshape(v // block_rows, block_rows, d)

    def body(dh, xs):
        i, w = xs
        logits = lax.dot_general(hidden, w, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        col = i * block_rows + lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        dl = (jnp.exp(logits - lse[:, None])
              - (col == label[:, None]).astype(jnp.float32)) * g[:, None]
        dl = dl.astype(hidden.dtype)
        dw = lax.dot_general(dl, hidden, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        dh = dh + lax.dot_general(dl, w, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return dh, dw.astype(weight.dtype)

    dh, dw = lax.scan(body, jnp.zeros(hidden.shape, jnp.float32),
                      (jnp.arange(v // block_rows), blocks))
    return dh.astype(hidden.dtype), dw.reshape(v, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _tied_ce(hidden, weight, label, scale, block_rows):
    return scale * _tied_ce_per_shard(hidden, weight, label, scale,
                                      block_rows, grads=False)[0]


def _tied_ce_fwd(hidden, weight, label, scale, block_rows):
    # trace time: once a differentiated shape, as attention.kernel.*
    from .. import telemetry
    telemetry.inc("tied_ce.path.forward_grads")
    ce, lse, dh, dw = _tied_ce_per_shard(hidden, weight, label, scale,
                                         block_rows, grads=True)
    # held until the backward in the dtypes of the gradients they become
    return scale * ce, (hidden, weight, label, scale, ce, lse,
                        dh.astype(hidden.dtype), dw.astype(weight.dtype))


def _tied_ce_bwd(block_rows, res, g):
    """The forward's gradients times the cotangent where that is ONE
    number for every position (the mean of a batch, a scaled loss) —
    tested on the device, since no trace can know it; any other cotangent
    takes the recomputing loop, so every gradient is exact."""
    hidden, weight, label, scale, ce, lse, dh_u, dw_u = res
    g = g.astype(jnp.float32)
    g0 = g.reshape(-1)[0]

    def scaled():
        return ((dh_u * g0).astype(hidden.dtype),
                (dw_u * g0).astype(weight.dtype))

    def recomputed():
        dh, dw = _tied_ce_recompute(
            hidden.reshape(-1, hidden.shape[-1]), weight, label.reshape(-1),
            lse.reshape(-1), (g * scale).reshape(-1), block_rows)
        return dh.reshape(hidden.shape), dw

    dh, dw = lax.cond(jnp.all(g == g0), scaled, recomputed)
    import numpy as onp
    return dh, dw, onp.zeros(label.shape, jax.dtypes.float0), g * ce


_tied_ce.defvjp(_tied_ce_fwd, _tied_ce_bwd)


@register("tied_softmax_cross_entropy")
def tied_softmax_cross_entropy(hidden, weight, label, scale=None,
                               block_rows: int = 8192):
    """``scale`` times the per-position softmax cross-entropy of
    ``hidden @ weight.T`` against ``label`` — the head TIED to the (V, D)
    embedding ``weight`` — without the (N, V) logits, in THREE products a
    training step: under differentiation the forward takes a block of
    tokens against all V rows, so that a block's log-sum-exp is whole
    while its logits are there, and makes the block's gradients on the
    spot (``softmax - onehot`` times ``scale``, rounded to the hidden
    states' dtype; dh written once; dW summed in one float32 (V, D)
    accumulator); the backward only multiplies them by the cotangent.
    That needs the cotangent to be ONE number for every position, which
    the backward tests on the device: put per-position weights (a mask
    over ignored labels, a sample weight, one over a row's count) in
    ``scale`` (...), and the mean of a batch or a scaled loss leaves it
    so.  Any other cotangent takes a second loop over blocks of
    ``weight``'s rows that forms the logits again (a fourth product):
    exact, and a quarter slower.  Without differentiation only the loss
    is computed.  ``block_rows`` is the budget of one block of logits: a
    block of tokens — as many positions of every row as divide the last
    leading axis of ``hidden`` — holds at most ``N * block_rows`` of
    them, a block of the second loop the largest divisor of V at most
    ``block_rows`` rows against all N tokens.  ``hidden`` is (..., D),
    ``label`` (...) integer ids (float ids are cast); a label outside
    0..V-1 (say -1) picks no logit: give such positions the ``scale``
    0."""
    lead = hidden.shape[:-1]
    shape = (math.prod(lead[:-1]), lead[-1]) if lead else (1, 1)
    scale = jnp.ones(shape, jnp.float32) if scale is None else \
        jnp.broadcast_to(scale, lead).astype(jnp.float32).reshape(shape)
    loss = _tied_ce(hidden.reshape(shape + hidden.shape[-1:]), weight,
                    label.reshape(shape).astype(jnp.int32), scale,
                    vocab_block_rows(weight.shape[0], block_rows))
    return loss.reshape(lead)


@register("_contrib_cca_qkv", num_outputs=3, aliases=("cca_qkv",))
def cca_qkv(q_lat, k_lat, v_now, v_prev, conv0_weight, conv1_weight,
            k_scale, num_heads: int = 1, num_kv_heads: int = 1,
            rotary_dim: int = 0, theta: float = 10000.0):
    """Compressed convolutional attention's q, k, v from the latent
    projections of a (B, S, ·) sequence, as (B, heads, S, d) operands for
    ``flash_attention``:

    * ``z = [q_lat ; k_lat]`` through two causal convolutions over the
      sequence: depthwise (``conv0_weight`` (C, 1, K0)), then grouped with
      one group a head (``conv1_weight`` (C, d, K1));
    * the q-k mean: ``mq`` = (q_lat + its key-value head's k_lat) / 2 per
      query head, ``mk`` = the mean of ``mq`` over the query heads of a
      key-value head; ``q = conv_q + mq``, ``k = conv_k + mk``;
    * q and k L2-normalised to ``sqrt(d)`` per head, k times the learned
      ``k_scale`` (one a key-value head), then rotary on the first
      ``rotary_dim`` dimensions;
    * v head 0 is ``v_now`` (from h_t), head 1 ``v_prev`` shifted one
      step (from h_(t-1), zero at t = 0), and so on in pairs."""
    b, s, cq = q_lat.shape
    d = cq // num_heads
    group = num_heads // num_kv_heads
    z = jnp.concatenate([q_lat, k_lat], axis=-1)
    z = causal_conv1d(z, conv0_weight, groups=z.shape[-1])
    z = causal_conv1d(z, conv1_weight, groups=num_heads + num_kv_heads)
    q32 = q_lat.astype(jnp.float32).reshape(b, s, num_kv_heads, group, d)
    k32 = k_lat.astype(jnp.float32).reshape(b, s, num_kv_heads, 1, d)
    mq = (q32 + k32) * 0.5
    mk = jnp.mean(mq, axis=3)
    q = z[..., :cq].astype(jnp.float32) + mq.reshape(b, s, cq)
    k = z[..., cq:].astype(jnp.float32) + mk.reshape(b, s, -1)

    def heads(x, n):
        return x.reshape(b, s, n, d).transpose(0, 2, 1, 3)

    q = rotary_embedding(l2_normalize(heads(q, num_heads)),
                         rotary_dim=rotary_dim, theta=theta)
    k = rotary_embedding(l2_normalize(heads(k, num_kv_heads), k_scale),
                         rotary_dim=rotary_dim, theta=theta)
    v_prev = jnp.pad(v_prev, ((0, 0), (1, 0), (0, 0)))[:, :s]
    half = num_kv_heads // 2
    v = jnp.concatenate([heads(v_now, half), heads(v_prev, half)], axis=1)
    dtype = q_lat.dtype
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)
