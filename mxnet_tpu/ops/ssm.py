"""The state-space scan of a Mamba-2 mixer (SSD, Dao & Gu arXiv:2405.21060),
computed in chunks: on a TPU, at shapes that are whole lane blocks, by the
Pallas kernels of ``ops/pallas_ssd.py`` (``pallas_ssd.ssd_dispatch``
decides from the platform and the shapes); everywhere else by ``_chunked``
here, plain ``jax.numpy`` / ``lax``, which is also the kernels' reference.

The recurrence, one head (state ``h`` (P, N), ``A`` a negative scalar)::

    h_t = exp(dt_t A) h_(t-1) + dt_t x_t (x) B_t        h_(-1) = 0
    y_t = h_t C_t + D x_t

is linear in ``h``, so a chunk of Q steps is three matrix products and the
chunks meet only through their states (``a_t = dt_t A``)::

    in-chunk      Y = (L o C B^T)(dt x)      L_ts = exp(sum_{s<r<=t} a_r), s <= t
    chunk states  S_c = sum_s exp(sum_{s<r<=end} a_r) dt_s x_s (x) B_s
    state passing h_c = exp(sum_chunk a) h_(c-1) + S_c
    output        y_t += exp(sum_{start<=r<=t} a_r) h_(c-1) C_t

Decays, cumulative sums and the states are float32 whatever the inputs
(a bfloat16 state or cumulative decay drifts over thousands of steps);
the products take their operands in the inputs' dtype and accumulate in
float32 — on both paths.  ``_chunked``'s four phases are
``jax.named_scope``s (``ssd.in_chunk``, ``ssd.chunk_states``,
``ssd.state_passing``, ``ssd.output``), so every device operation's
``op_name`` says which phase it belongs to; the kernels are named
``ssd_fwd``, ``ssd_states``, ``ssd_bwd``.

Differentiation of ``_chunked`` is autodiff through the chunked form under
``jax.checkpoint``: the backward recomputes the chunked forward from the
operator's INPUTS, because what autodiff would keep otherwise — the (Q, Q)
decay and score matrices of every head and chunk, in float32 — is 0.8 GB a
layer at 8,192 tokens and 64 heads against 0.1 GB of inputs.  The kernel
path keeps the same residuals (a ``custom_vjp`` that makes the chunk
states again).  Which path a traced shape took: the counters
``ssm.scan.kernel`` / ``ssm.scan.chunked`` and ``path`` on the ``ssm.scan``
event.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import context as _context
from .registry import register

__all__ = ["ssd_chunk_scan"]


def _chunked(x, dt, a_log, b, c, d_skip, dt_bias, chunk):
    """``ssd_chunk_scan`` on a sequence that is a whole number of chunks:
    x (Bt, S, G, R, P) (R heads a group), dt (Bt, S, G, R), b and c
    (Bt, S, G, N), the per-head vectors (G, R)."""
    bt, s, g, r, p = x.shape
    n = b.shape[-1]
    nc = s // chunk
    f32 = jnp.float32
    delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    a = delta * -jnp.exp(a_log.astype(f32))                # (Bt, S, G, R)
    xd = (x.astype(f32) * delta[..., None]).astype(x.dtype)     # dt x

    def chunks(t):
        return t.reshape((bt, nc, chunk) + t.shape[2:])

    a_c, x_c, xd_c, b_c, c_c = (chunks(t) for t in (a, x, xd, b, c))
    cum = jnp.cumsum(a_c, axis=2)                      # sum_{r<=t} a_r
    total = cum[:, :, -1]                              # (Bt, nc, G, R)

    with jax.named_scope("ssd.in_chunk"):
        scores = jnp.einsum("bztgn,bzsgn->bzgts", c_c, b_c,
                            preferred_element_type=f32)
        # exp(cum_t - cum_s) for s <= t: the exponent is <= 0 there
        by_head = jnp.moveaxis(cum, 2, 4)              # (Bt, nc, G, R, Q)
        seen = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) \
            >= lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        decay = jnp.exp(jnp.where(
            seen, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
        mix = (decay * scores[:, :, :, None]).astype(x.dtype)
        y = jnp.einsum("bzgrts,bzsgrp->bztgrp", mix, xd_c,
                       preferred_element_type=f32)

    with jax.named_scope("ssd.chunk_states"):
        to_end = jnp.exp(total[:, :, None] - cum)      # (Bt, nc, Q, G, R)
        states = jnp.einsum(
            "bzsgrp,bzsgn->bzgrpn",
            (xd_c.astype(f32) * to_end[..., None]).astype(x.dtype), b_c,
            preferred_element_type=f32)

    with jax.named_scope("ssd.state_passing"):
        def carry(h, xs):
            s_c, keep = xs
            return keep[..., None, None] * h + s_c, h  # emits h_(c-1)

        _, before = lax.scan(
            carry, jnp.zeros((bt, g, r, p, n), f32),
            (jnp.moveaxis(states, 1, 0), jnp.moveaxis(jnp.exp(total), 1, 0)))
        before = jnp.moveaxis(before, 0, 1)            # (Bt, nc, G, R, P, N)

    with jax.named_scope("ssd.output"):
        carried = jnp.einsum("bzgrpn,bztgn->bztgrp", before.astype(x.dtype),
                             c_c, preferred_element_type=f32)
        y = y + carried * jnp.exp(cum)[..., None]
        y = y + x_c.astype(f32) * d_skip.astype(f32)[..., None]
    return y.reshape(bt, s, g, r, p).astype(x.dtype)


@register("_contrib_ssd_chunk_scan", aliases=("ssd_chunk_scan",))
def ssd_chunk_scan(data, dt, a_log, b, c, d_skip, dt_bias, chunk: int = 128):
    """The Mamba-2 state-space scan with its skip, in chunks of ``chunk``
    steps (the module's docstring has the recurrence and the chunked form).

    ``data`` (Bt, S, H, P) the heads' inputs, ``dt`` (Bt, S, H) the raw
    time steps (``softplus(dt + dt_bias)`` is taken here, in float32, no
    clamp), ``a_log`` (H,) with ``A = -exp(a_log)``, ``b`` and ``c``
    (Bt, S, G, N) shared by the H / G heads of a group (head i reads group
    ``i // (H / G)``), ``d_skip`` and ``dt_bias`` (H,).  Returns y
    (Bt, S, H, P) in ``data``'s dtype; the state starts at zero.  A
    sequence that is no multiple of ``chunk`` is padded to one inside —
    steps of ``dt = 0`` and ``x = 0`` leave the state as it is — and the
    padding cut off again; one shorter than a chunk is one chunk of its
    own length.  On a TPU a shape ``pallas_ssd.ssd_dispatch`` accepts runs
    as Pallas kernels, forward and backward; the result, the dtype
    contract and the gradients are the same."""
    from .. import telemetry
    from . import pallas_ssd

    bt, s, h, p = data.shape
    g, n = b.shape[-2:]
    if h % g:
        raise ValueError("%d heads do not divide over %d groups" % (h, g))
    chunk = min(int(chunk), s)
    pad = -s % chunk
    path = pallas_ssd.ssd_dispatch(s + pad, chunk, h, p, g, n, data.dtype,
                                   on_tpu=_context.on_tpu(data))
    # trace time: once a traced shape, as attention.kernel.*
    telemetry.inc("ssm.scan.%s" % path)
    telemetry.event("ssm.scan", path, path=path, seq_len=int(s),
                    chunk=chunk, heads=int(h), state=int(n), groups=int(g),
                    head_dim=int(p), padded=bool(pad))
    x = data
    if pad:
        # softplus(-inf) = 0: a padded step neither decays nor writes
        x, b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (x, b, c))
        dt = jnp.pad(dt.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)),
                     constant_values=-jnp.inf)
    if path == "kernel":
        y = pallas_ssd.ssd_scan_kernels(x, dt, a_log, b, c, d_skip, dt_bias,
                                        chunk)
        return y[:, :s]

    def grouped(t):                      # (..., H) -> (..., G, R)
        return t.reshape(t.shape[:-1] + (g, h // g))

    y = jax.checkpoint(functools.partial(_chunked, chunk=chunk))(
        x.reshape(bt, s + pad, g, h // g, p), grouped(dt), grouped(a_log),
        b, c, grouped(d_skip), grouped(dt_bias))
    return y[:, :s].reshape(bt, s, h, p)
