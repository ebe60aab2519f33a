"""The gated delta rule with a decay a channel (Kimi Delta Attention, Kimi
Linear arXiv:2510.26692; Gated DeltaNet arXiv:2412.06464), computed in
chunks.  On a TPU a shape ``pallas_delta.delta_dispatch`` accepts runs as
the Pallas kernels of ``ops/pallas_delta.py``, forward and backward; every
other call runs the chunked form here, plain ``jax.numpy`` / ``lax``,
which is also the kernels' reference.

The recurrence, one head (state ``S`` (d_k, d_v) float32, ``alpha_t =
exp(g_t)`` a vector of d_k, ``beta_t`` a scalar in (0, 1))::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale                                  S_(-1) = 0

Written ``S_t = Diag(alpha_t) S_(t-1) + k_t u_t^T`` with ``u_t = beta_t
(v_t - (Diag(alpha_t) S_(t-1))^T k_t)``, a chunk of C steps that starts
from ``S_0`` is (``G_t`` the cumulative sum of ``g`` inside the chunk,
``Gamma_t = exp(G_t)``, ``A_ti = sum_c k_tc k_ic exp(G_tc - G_ic)``)::

    intra    (I + Diag(beta) A_strict) [U~ | W] = Diag(beta) [V | Gamma o K]
    states   S_next = Diag(Gamma_C) S_0 + (K o exp(G_C - G))^T (U~ - W S_0)
    output   O = (Gamma o Q) S_0 + (Q K^T decayed, i <= t) (U~ - W S_0)

the first a unit lower-triangular C x C solve a chunk (the WY / UT form),
all chunks at once; only the second runs chunk after chunk (``lax.scan``
over a float32 (B, H, d_k, d_v) state); the third is all chunks at once
again from the states the scan emits.

The decays are never formed as ``exp(G_t) * exp(-G_i)``: ``-G`` reaches
``C |g|`` (320 at C = 64 and the gate's bound -5) and ``exp`` overflows
float32 past 88.7.  The C x C matrices are built a SUB-CHUNK of keys at a
time (``_SUBCHUNK``, 16): key i of sub-chunk b is scaled by ``exp(E_b -
G_i)`` <= 1 and query t by ``exp(G_t - E_b)``, ``E_b`` the cumulative
decay at the end of sub-chunk b.  For t past sub-chunk b that factor is
<= 1; for t inside it, at most ``16 |g|`` (80 at the bound -5), so
nothing overflows as long as every ``g >= -80 / 16``; queries before
sub-chunk b are never formed.  An underflow to zero happens only where the
exact product is below float32's range.

The chunked form is differentiated by JAX: the chunk states (chunks x H x
d_k x d_v float32, 134 MB a layer at 4,096 tokens and 32 heads of 128)
and the C x C matrices are its residuals.  ``kimi_delta_attention`` runs
the rule a group of heads at a time under ``jax.checkpoint``, so they
live only through a group's backward and nothing of them is kept across
a step.  The three
phases are ``jax.named_scope``s (``delta.intra``,
``delta.states``, ``delta.output``; the kernels' calls ``delta.fwd`` and
``delta.bwd``) in every device operation's ``op_name``.  A traced call
bumps ``linear_attention.scan.kernel`` or ``linear_attention.scan.chunked``
and writes a ``linear_attention.scan`` event with its ``path``, once, as
``ssm.scan.*``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .. import context as _context
from .registry import register

__all__ = ["delta_rule_chunked", "delta_rule_recurrent", "kimi_delta_attention"]

_EXP_ROOM = 80.0        # the largest decay a sub-chunk may span: < 88.7
_SUBCHUNK = 16          # keys a block of the C x C matrices: the gate's
#                         bound -5 spans exp(80) over it
_HEAD_GROUPS = 4        # kimi_delta_attention's heads in this many groups:
#                         one Ling delta layer's forward and backward hold
#                         0.86 GB of temporaries so on a v5e, 2.64 in one


def _chunked(q, k, v, g, beta, chunk, scale):
    """The rule on (B, S, H, d) inputs whose S is a whole number of
    chunks; returns o (B, S, H, d_v) float32."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    nc, subchunk = s // chunk, _SUBCHUNK
    f32 = jnp.float32

    def chunks(t):                  # (B, S, H, ...) -> (B, H, nc, C, ...)
        t = t.astype(f32).reshape((b, nc, chunk, h) + t.shape[3:])
        return jnp.moveaxis(t, 3, 1)

    qc, kc, vc, gc = chunks(q) * scale, chunks(k), chunks(v), chunks(g)
    bc = chunks(beta)[..., None]                       # (B, H, nc, C, 1)

    with jax.named_scope("delta.intra"):
        cum = jnp.cumsum(gc, axis=-2)                  # G_t, <= 0
        rows = jnp.arange(chunk)
        a_qk, a_kk = [], []
        for sb in range(chunk // subchunk):
            lo, hi = sb * subchunk, (sb + 1) * subchunk
            # E_b: the product of the two factors does not depend on it,
            # so neither does the gradient — held constant, autodiff does
            # not form two large terms that cancel
            end = lax.stop_gradient(cum[..., hi - 1:hi, :])
            keys = kc[..., lo:hi, :] * jnp.exp(end - cum[..., lo:hi, :])
            after = jnp.exp(cum[..., lo:, :] - end)    # <= exp(_EXP_ROOM)
            seen = rows[lo:, None] >= rows[None, lo:hi]
            below = seen & (rows[lo:, None] > rows[None, lo:hi])

            def block(x, keep):
                out = jnp.einsum("...td,...id->...ti", x[..., lo:, :] * after,
                                 keys, preferred_element_type=f32)
                out = jnp.where(keep, out, 0.0)
                return jnp.pad(out, [(0, 0)] * (out.ndim - 2)
                               + [(lo, 0), (0, 0)])
            a_qk.append(block(qc, seen))
            a_kk.append(block(kc, below))
        a_qk = jnp.concatenate(a_qk, axis=-1)          # (.., C, C), i <= t
        a_kk = jnp.concatenate(a_kk, axis=-1)          # i < t
        decay = jnp.exp(cum)                           # Gamma_t <= 1
        system = jnp.eye(chunk, dtype=f32) + bc * a_kk
        solved = lax.linalg.triangular_solve(
            system, bc * jnp.concatenate([vc, kc * decay], axis=-1),
            left_side=True, lower=True, unit_diagonal=True)
        u_free, w = solved[..., :dv], solved[..., dv:]
        last = cum[..., -1:, :]                        # G_C
        to_end = kc * jnp.exp(last - cum)              # K o exp(G_C - G)

    with jax.named_scope("delta.states"):
        def step(state, xs):
            uf, wc, ke, keep = xs
            new = jnp.exp(keep)[..., 0, :, None] * state + jnp.einsum(
                "...td,...tv->...dv", ke, uf - jnp.einsum(
                    "...td,...dv->...tv", wc, state,
                    preferred_element_type=f32),
                preferred_element_type=f32)
            return new, state                          # emits S_0 a chunk

        def by_chunk(t):
            return jnp.moveaxis(t, 2, 0)

        _, starts = lax.scan(
            step, jnp.zeros((b, h, dk, dv), f32),
            (by_chunk(u_free), by_chunk(w), by_chunk(to_end), by_chunk(last)))
        starts = jnp.moveaxis(starts, 0, 2)            # (B, H, nc, dk, dv)

    with jax.named_scope("delta.output"):
        u = u_free - jnp.einsum("...td,...dv->...tv", w, starts,
                                preferred_element_type=f32)
        out = jnp.einsum("...td,...dv->...tv", qc * decay, starts,
                         preferred_element_type=f32) \
            + jnp.einsum("...ti,...iv->...tv", a_qk, u,
                         preferred_element_type=f32)
    return jnp.moveaxis(out, 1, 3).reshape(b, s, h, dv)


@register("_contrib_delta_rule_chunked", aliases=("delta_rule_chunked",))
def delta_rule_chunked(q, k, v, g, beta, chunk: int = 64, scale=None,
                       lower_bound=None):
    """The gated delta rule with a decay a channel, in chunks of ``chunk``
    steps (the module's docstring has the recurrence and the chunked
    form).

    ``q``, ``k`` (B, S, H, d_k), ``v`` (B, S, H, d_v), ``g`` (B, S, H, d_k)
    the decay in log space (``alpha = exp(g)``, every ``g`` in ``[-5,
    0]``: a sub-chunk of 16 steps spans at most exp(80)), ``beta``
    (B, S, H).  ``scale`` multiplies
    the queries (``d_k ** -0.5`` where None).  The state starts at zero
    and is float32; so is the arithmetic.  Returns o (B, S, H, d_v) in
    ``v``'s dtype.  A sequence that is no multiple of ``chunk`` is padded
    inside — steps with ``g = 0`` and ``beta = 0`` leave the state as it
    is — and cut again; one shorter than a chunk is a chunk of its length
    rounded up to whole sub-chunks.  ``chunk`` is a multiple of 16.
    ``lower_bound``: the bound the caller's gate keeps ``g`` above,
    checked against the sub-chunk's room and written into the event.  On
    a TPU a shape ``pallas_delta.delta_dispatch`` accepts (heads of 128
    lanes) runs as Pallas kernels; the result, the dtype contract and the
    gradients are the same."""
    from .. import telemetry
    from . import pallas_delta

    s, h, dk = q.shape[1:]
    chunk = min(int(chunk), s + -s % _SUBCHUNK)
    if chunk % _SUBCHUNK:
        raise ValueError("chunk %d is no multiple of the sub-chunk %d"
                         % (chunk, _SUBCHUNK))
    if lower_bound is not None and -lower_bound * _SUBCHUNK > _EXP_ROOM:
        raise ValueError("a sub-chunk of %d steps at g >= %g spans more "
                         "than exp(%g)" % (_SUBCHUNK, lower_bound, _EXP_ROOM))
    scale = float(dk ** -0.5 if scale is None else scale)
    pad = -s % chunk
    path = pallas_delta.delta_dispatch(s + pad, chunk, h, dk, q.dtype,
                                       on_tpu=_context.on_tpu(q),
                                       v_dim=v.shape[-1])
    # trace time: once a traced shape, as ssm.scan.*
    telemetry.inc("linear_attention.scan.%s" % path)
    telemetry.event("linear_attention.scan", path, path=path,
                    seq_len=int(s), chunk=chunk, subchunk=_SUBCHUNK,
                    heads=int(h), head_dim=int(dk),
                    lower_bound=None if lower_bound is None
                    else float(lower_bound))
    if pad:
        def padded(t):
            return jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        q, k, v, g, beta = (padded(t) for t in (q, k, v, g, beta))
    if path == "kernel":
        out = pallas_delta.delta_rule_kernels(q, k, v, g, beta, chunk, scale)
    else:
        out = _chunked(q, k, v, g, beta, chunk, scale)
    return out[:, :s].astype(v.dtype)


def delta_rule_recurrent(q, k, v, g, beta, scale=None):
    """The same rule a token at a time (``lax.scan`` over t, float32):
    the plain form the chunked one is tested against.  Shapes and
    arguments as ``delta_rule_chunked``'s."""
    f32 = jnp.float32
    dk, dv = q.shape[-1], v.shape[-1]
    scale = dk ** -0.5 if scale is None else scale

    def step(state, xs):
        qt, kt, vt, gt, bt = xs                        # (B, H, ...)
        state = jnp.exp(gt)[..., None] * state
        erase = jnp.einsum("bhd,bhdv->bhv", kt, state)
        state = state + bt[..., None, None] * kt[..., None] \
            * (vt - erase)[..., None, :]
        return state, jnp.einsum("bhdv,bhd->bhv", state, qt * scale)

    xs = tuple(jnp.moveaxis(t.astype(f32), 1, 0) for t in (q, k, v, g, beta))
    _, out = lax.scan(step, jnp.zeros(q.shape[:1] + (q.shape[2], dk, dv), f32),
                      xs)
    return jnp.moveaxis(out, 0, 1).astype(v.dtype)


def _kda_mix(qkv, f, beta, gate, conv_weight, a_log, dt_bias, norm_gamma,
             heads, head_dim, lower_bound, chunk, eps):
    """``kimi_delta_attention`` for ``heads`` heads: ``qkv`` (B, S, 3 H d)
    and ``f`` (B, S, H d) from the projection, ``beta`` and ``gate``
    (B, S, H) before their sigmoids; returns (B, S, H d) float32."""
    from .nn import causal_conv1d

    b, s = qkv.shape[:2]
    width = heads * head_dim
    f32 = jnp.float32
    with jax.named_scope("kda.inputs"):
        qkv = jax.nn.silu(causal_conv1d(qkv, conv_weight,
                                        groups=3 * width).astype(f32))
        q, k, v = (qkv[..., i * width:(i + 1) * width].reshape(
            b, s, heads, head_dim) for i in range(3))
        q, k = (t * lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
                for t in (q, k))
        g = lower_bound * jax.nn.sigmoid(
            jnp.exp(a_log.astype(f32))[:, None]
            * (f.astype(f32) + dt_bias.astype(f32)).reshape(
                b, s, heads, head_dim))
    out = delta_rule_chunked(q, k, v, g, jax.nn.sigmoid(beta.astype(f32)),
                             chunk=chunk, lower_bound=lower_bound)
    with jax.named_scope("kda.output"):
        out = out * lax.rsqrt(jnp.mean(out * out, -1, keepdims=True) + eps) \
            * norm_gamma.astype(f32).reshape(heads, head_dim)
        out = out * jax.nn.sigmoid(gate.astype(f32))[..., None]
    return out.reshape(b, s, width)


@register("_contrib_kimi_delta_attention",
          aliases=("kimi_delta_attention",))
def kimi_delta_attention(proj, conv_weight, a_log, dt_bias, norm_gamma,
                         heads: int = 1, head_dim: int = 1,
                         lower_bound: float = -5.0, chunk: int = 64,
                         eps: float = 1e-6):
    """What a Kimi Delta Attention layer computes between its input
    projection and its output projection, from ``proj`` (B, S, 4 H d +
    2 H) = ``u [W_q | W_k | W_v | W_f | W_beta | W_g]``::

        q, k, v = silu(conv4(u W_{q,k,v}))     causal, depthwise, no bias
        q, k   /= |q|, |k|                     a head
        g       = lower_bound * sigmoid(exp(a_log_h) (u W_f + dt_bias))
        o       = delta_rule_chunked(q, k, v, g, sigmoid(u W_beta))
        out     = RMSNorm_head(o) * sigmoid(u W_g)_h

    ``conv_weight`` (3 H d, 1, K), ``a_log`` (H,), ``dt_bias`` and
    ``norm_gamma`` (H d,).  Returns (B, S, H d) in ``proj``'s dtype.

    Every step above is a head's own, so the heads go through in
    ``gcd(heads, _HEAD_GROUPS)`` groups one after another (``lax.map``),
    each recomputed in the backward from its slice of ``proj``
    (``jax.checkpoint``): a layer's float32 temporaries — a dozen copies
    of the (B, S, H d) activations, the chunks' matrices and states — are
    a group's at a time, forward and backward.  Scopes ``kda.heads`` (the
    grouping), ``kda.inputs`` and ``kda.output`` beside the rule's
    ``delta.*``."""
    b, s = proj.shape[:2]
    width = heads * head_dim
    groups = math.gcd(int(heads), _HEAD_GROUPS)
    per = heads // groups

    def grouped(t, lead=()):             # (..., H x) -> (G, ..., H/G x)
        t = t.reshape(lead + (groups, -1) + t.shape[len(lead) + 1:])
        return jnp.moveaxis(t, len(lead), 0)

    with jax.named_scope("kda.heads"):
        qkv = jnp.concatenate([grouped(
            proj[..., i * width:(i + 1) * width], (b, s))
            for i in range(3)], axis=-1)
        taps = jnp.concatenate([grouped(
            conv_weight[i * width:(i + 1) * width]) for i in range(3)],
            axis=1)
        parts = (qkv, grouped(proj[..., 3 * width:4 * width], (b, s)),
                 grouped(proj[..., 4 * width:4 * width + heads], (b, s)),
                 grouped(proj[..., 4 * width + heads:], (b, s)), taps,
                 grouped(a_log), grouped(dt_bias), grouped(norm_gamma))
    body = jax.checkpoint(lambda xs: _kda_mix(
        *xs, heads=per, head_dim=int(head_dim),
        lower_bound=float(lower_bound), chunk=int(chunk), eps=float(eps)))
    out = lax.map(body, parts)                     # (G, B, S, H/G d)
    with jax.named_scope("kda.heads"):
        return jnp.moveaxis(out, 0, 2).reshape(b, s, width).astype(
            proj.dtype)
