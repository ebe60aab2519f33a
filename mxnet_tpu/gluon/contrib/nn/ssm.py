"""State-space layers as Gluon blocks: the Mamba-2 mixer (Dao & Gu,
arXiv:2405.21060) round ``ops.ssm.ssd_chunk_scan``, and Kimi Delta
Attention (Kimi Linear, arXiv:2510.26692) round
``ops.delta_rule.delta_rule_chunked``."""
from __future__ import annotations

import numpy as onp

from .... import initializer
from ...block import HybridBlock
from ...nn import Dense, RMSNorm

__all__ = ["Mamba2Mixer", "KimiDeltaAttention"]


class _TimeStepBias(initializer.Initializer):
    """``softplus^-1(dt0)`` with ``dt0`` log-uniform in ``[dt_min,
    dt_max]``, floored at ``dt_floor``: a fresh mixer's time steps cover
    the range (the Mamba-2 reference initialisation)."""

    def __init__(self, dt_min, dt_max, dt_floor):
        super().__init__(dt_min=dt_min, dt_max=dt_max, dt_floor=dt_floor)

    def _init_weight(self, name, arr):
        kw = self._kwargs
        dt0 = onp.exp(initializer._host_rng().uniform(
            onp.log(kw["dt_min"]), onp.log(kw["dt_max"]), arr.shape))
        dt0 = onp.maximum(dt0, kw["dt_floor"])
        self._set(arr, dt0 + onp.log(-onp.expm1(-dt0)))


class Mamba2Mixer(HybridBlock):
    """The Mamba-2 mixer of a (B, S, units) sequence::

        [z | xBC | dt] = u W_in          inner + (inner + 2 G N) + H wide
        xBC  = silu(conv(xBC))           causal depthwise, with bias
        x, B, C = xBC as (H, P), (G, N), (G, N)
        y    = scan(x, softplus(dt + dt_bias), -exp(a_log), B, C) + D x
        out  = GroupRMSNorm(y * silu(z)) W_out

    with ``inner = num_heads * head_dim`` (H heads of P), ``num_groups``
    (G) groups sharing B and C of ``state_size`` (N), the scan in chunks
    of ``chunk_size`` (``ops.ssm.ssd_chunk_scan``: the state is float32
    and starts at zero), the norm over G groups of ``inner / G`` with the
    gate before it.  No bias but the convolution's.  ``a_log`` starts at
    ``log(1..H)``, ``D`` at 1, ``dt_bias`` so that the time steps are
    log-uniform in ``dt_range``, floored at ``dt_floor``; the three stay
    float32 under ``cast`` (one scalar a head each, exponentiated)."""

    def __init__(self, units, num_heads, head_dim, state_size, num_groups=1,
                 conv_kernel=4, chunk_size=128, epsilon=1e-5,
                 dt_range=(1e-3, 1e-1), dt_floor=1e-4, **kwargs):
        super().__init__(**kwargs)
        inner = num_heads * head_dim
        bc = num_groups * state_size
        self._split = (inner, inner + bc)
        self._shape = (num_heads, head_dim, num_groups, state_size)
        self._chunk = chunk_size
        with self.name_scope():
            self.in_proj = Dense(2 * inner + 2 * bc + num_heads,
                                 flatten=False, use_bias=False,
                                 in_units=units, prefix="in_")
            self.conv_weight = self.params.get(
                "conv_weight", shape=(inner + 2 * bc, 1, conv_kernel),
                init=initializer.Normal(conv_kernel ** -0.5))
            self.conv_bias = self.params.get(
                "conv_bias", shape=(inner + 2 * bc,), init="zeros")
            self.a_log = self.params.get(
                "a_log", shape=(num_heads,), init=initializer.Constant(
                    onp.log(onp.arange(1, num_heads + 1)).tolist()))
            self.d_skip = self.params.get("d_skip", shape=(num_heads,),
                                          init="ones")
            self.dt_bias = self.params.get(
                "dt_bias", shape=(num_heads,),
                init=_TimeStepBias(dt_range[0], dt_range[1], dt_floor))
            self.norm = RMSNorm(epsilon=epsilon, in_channels=inner,
                                groups=num_groups, prefix="norm_")
            self.out_proj = Dense(units, flatten=False, use_bias=False,
                                  in_units=inner, prefix="out_")

    def cast(self, dtype):
        super().cast(dtype)
        for p in (self.a_log, self.d_skip, self.dt_bias):
            p.cast("float32")

    def hybrid_forward(self, F, x, conv_weight, conv_bias, a_log, d_skip,
                       dt_bias):
        heads, head_dim, groups, state = self._shape
        inner, to_c = self._split                 # xBC: x | B | C
        b, s = x.shape[0], x.shape[1]

        def part(t, begin, end):
            return F.slice_axis(t, axis=-1, begin=begin, end=end)

        zxbcdt = self.in_proj(x)
        channels = conv_weight.shape[0]
        z = part(zxbcdt, 0, inner)
        xbc = part(zxbcdt, inner, inner + channels)
        dt = part(zxbcdt, inner + channels, None)
        xbc = F.Activation(F.causal_conv1d(xbc, conv_weight, conv_bias,
                                           groups=channels),
                           act_type="silu")
        y = F.ssd_chunk_scan(
            part(xbc, 0, inner).reshape(b, s, heads, head_dim), dt, a_log,
            part(xbc, inner, to_c).reshape(b, s, groups, state),
            part(xbc, to_c, None).reshape(b, s, groups, state),
            d_skip, dt_bias, chunk=self._chunk)
        return self.out_proj(self.norm(y.reshape(b, s, inner), z))


class KimiDeltaAttention(HybridBlock):
    """Kimi Delta Attention over a (B, S, units) sequence ``u`` (the
    layer's normed input): the gated delta rule with a decay a channel,
    ``num_heads`` heads of ``head_dim`` (``ops.delta_rule``'s
    ``kimi_delta_attention`` has the equations)::

        [q | k | v | f | b | h] = u W_proj     4 H d + 2 H wide, no bias
        q, k, v = silu(conv(q | k | v))        causal depthwise, no bias
        g       = lower_bound sigmoid(exp(a_log) (f + dt_bias))
        o       = delta rule(q / |q|, k / |k|, v, g, sigmoid(b))
        out     = (RMSNorm_head(o) * sigmoid(h)) W_out

    in chunks of ``chunk_size`` (float32 state from zero), the heads in
    groups one after another (``ops.delta_rule._HEAD_GROUPS``).  The taps
    start N(0, 1 / conv_kernel) as ``Mamba2Mixer``'s, ``a_log`` at ``log``
    of 1..16 spread over the heads, ``dt_bias`` so that ``softplus`` of it
    is log-uniform in [1e-3, 1e-1], floored at 1e-4 (the
    flash-linear-attention ``kda`` initialisation); the two stay float32
    under ``cast``."""

    def __init__(self, units, num_heads, head_dim, conv_kernel=4,
                 lower_bound=-5.0, chunk_size=64, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        width = num_heads * head_dim
        self._core = dict(heads=num_heads, head_dim=head_dim,
                          lower_bound=float(lower_bound), chunk=chunk_size,
                          eps=epsilon)
        with self.name_scope():
            self.proj = Dense(4 * width + 2 * num_heads, flatten=False,
                              use_bias=False, in_units=units, prefix="proj_")
            self.conv_weight = self.params.get(
                "conv_weight", shape=(3 * width, 1, conv_kernel),
                init=initializer.Normal(conv_kernel ** -0.5))
            self.a_log = self.params.get(
                "a_log", shape=(num_heads,), init=initializer.Constant(
                    onp.log(onp.linspace(1, 16, num_heads)).tolist()))
            self.dt_bias = self.params.get(
                "dt_bias", shape=(width,),
                init=_TimeStepBias(1e-3, 1e-1, 1e-4))
            self.out_norm_gamma = self.params.get(
                "out_norm_gamma", shape=(width,), init="ones")
            self.out = Dense(units, flatten=False, use_bias=False,
                             in_units=width, prefix="out_")

    def cast(self, dtype):
        super().cast(dtype)
        for p in (self.a_log, self.dt_bias):
            p.cast("float32")

    def hybrid_forward(self, F, x, conv_weight, a_log, dt_bias,
                       out_norm_gamma):
        return self.out(F.kimi_delta_attention(
            self.proj(x), conv_weight, a_log, dt_bias, out_norm_gamma,
            **self._core))
