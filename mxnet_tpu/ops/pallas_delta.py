"""The gated delta rule (``ops.delta_rule``) as Pallas kernels for TPU,
forward AND backward.

``ops/delta_rule.py`` has the recurrence and its chunked (WY) form.  As
plain XLA that form moves every chunk quantity through HBM — the inputs
transposed into (B, H, chunks, C, d) float32, the decayed C x C matrices,
the solved ``[U~ | W]``, the states of a ``lax.scan`` — and spends a
quarter of its time in XLA's expansion of the triangular solve.  Here a
grid step holds a PAIR of heads' chunk in VMEM (grid ``(batch, head pair,
chunk)``, the chunk axis sequential; one head where their count is odd)
and each head's state is a float32 VMEM scratch carried across the chunk
axis:

* the kernels read q, k, v and g where the delta layer leaves them, the
  pair's two 128-lane blocks of the (B, S, H*128) arrays, and beta from
  (B, S, H); they write o and the gradients the same way;
* a grid step makes the chunk's cumulative decays ``G`` (a product with a
  0/1 lower triangle, the float32 decays taken as three bfloat16 terms:
  exact), the two C x C matrices a sub-chunk of 16 keys at a time by the
  module's own exp(80) rule (keys scaled by ``exp(E_b - G_i) <= 1``,
  queries from the sub-chunk's first row on by ``exp(G_t - E_b)``), the
  unit lower-triangular ``T = I + Diag(beta) A_kk`` inverted by forward
  substitution on the vector unit in float32 — the pair's two (C, C)
  systems side by side in one (C, 2C) array, so that the one lane
  broadcast a step (the cross-lane unit's work, which bounds the step)
  serves both —, ``[U~ | W] = T^-1 Diag(beta) [V | Gamma o K]``, the
  output and the next state;
* the state is kept TRANSPOSED, (d_v, d_k): its decay ``Diag(Gamma_C)`` is
  then a scaling of its lanes and every product with it contracts lanes;
* the backward is a ``jax.custom_vjp`` whose residuals are the operator's
  inputs and the chunk-start states, which the forward kernel writes as
  its second output (d_k * d_v * 4 bytes a head and chunk); ``delta_bwd``
  walks the chunks in reverse with the state's gradient in VMEM, makes
  the chunk's intra quantities again from its inputs and its start state,
  and writes dq, dk, dv, dg and, a head's lanes at a time, the terms whose
  sum over the 128 lanes is d(beta) (summed by XLA outside).

Precision.  The chunked form's ``einsum``s compile on the v5e with no
``operand_precision`` (the compiled step's text): one bfloat16 pass of
the MXU, float32 accumulation.  Its triangular solve is XLA's
``InvertDiagBlocksLowerTriangular`` (substitution in float32) and a
product at ``operand_precision={highest,highest}``, as are the solve's
derivatives in JAX.  The kernels do the same: every product of the
chunked form takes bfloat16 operands and accumulates in float32; the
inverse is float32 substitution and its products (``T^-1 R``, ``T^-T dX``,
``dR X^T``) run at ``Precision.HIGHEST``; cumulative sums are exact.  The
state, decays and every accumulation are float32.  ``exact=True`` runs
every product at ``HIGHEST`` (the tests hold that to the chunked form in
float32).

``delta_dispatch`` decides, from what the code observes (platform, shapes,
the working set), whether a call takes the kernels; every other shape
keeps ``ops.delta_rule._chunked``.  Each ``pallas_call`` runs under a
``jax.named_scope`` (``delta.fwd``, ``delta.bwd``) beside the chunked
form's ``delta.*`` scopes, so a trace gives both paths' time to the same
kind.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import context as _context
from .pallas_attention import _LANES, _VMEM_CLAMP, per_batch_shard

__all__ = ["delta_dispatch", "delta_rule_kernels", "pallas_delta_fwd",
           "pallas_delta_bwd"]

_F32 = jnp.float32
_BF16 = jnp.bfloat16
_SUBCHUNK = 16          # as ops.delta_rule's: keys a block of the C x C
#                         matrices, exp(80) at the gate's bound -5

_HEADS_PER_BLOCK = 2    # heads a grid step: a pair shares each lane
#                         broadcast of the inverse's substitution

_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b.T: both contract their lanes
_TN = ((0,), (0,))      # a.T @ b: both contract their sublanes


def _vmem_bytes(chunk, head_dim, heads=_HEADS_PER_BLOCK):
    """The backward kernel's working set (the larger of the two), for
    ``heads`` heads a grid step: a head's double-buffered blocks — q, k, v,
    g, dO in, dq, dk, dv, dg, d(beta) out, (chunk, d) each, the start state
    (d, d) —, its carried gradient and its float32 temporaries: two
    (chunk, d) factors a sub-chunk, a dozen and a half (chunk, d) arrays,
    a dozen (chunk, chunk) blocks."""
    wide = chunk * head_dim * 4
    square = head_dim * head_dim * 4
    blocks = 2 * (10 * wide + square)
    temps = 2 * (chunk // _SUBCHUNK) * wide + 18 * wide \
        + 12 * chunk * chunk * 4 + 4 * square
    return heads * (blocks + square + temps)


def delta_dispatch(seq_len, chunk, heads, head_dim, dtype="float32",
                   on_tpu=None, v_dim=None):
    """``"kernel"`` or ``"chunked"`` for a rule of ``seq_len`` steps (after
    padding to chunks of ``chunk``) over ``heads`` heads of ``head_dim``:
    the kernels take a call on a TPU where a head (of q and k, and of v:
    ``v_dim``, ``head_dim`` where None) is one whole 128-lane block, the
    chunk a multiple of the 16-key sub-chunk, and the working set fits the
    kernels' VMEM budget (``pallas_attention._VMEM_CLAMP``); everything
    else is ``ops.delta_rule._chunked``."""
    del dtype                       # the kernels compute in float32
    if on_tpu is None:
        on_tpu = _context.on_tpu()
    v_dim = head_dim if v_dim is None else v_dim
    fits = on_tpu and head_dim == _LANES and v_dim == _LANES \
        and chunk % _SUBCHUNK == 0 and seq_len % chunk == 0 \
        and _vmem_bytes(chunk, head_dim, _block_heads(heads)) <= _VMEM_CLAMP
    return "kernel" if fits else "chunked"


# ---------------------------------------------------------------------------
# what the two kernels share
# ---------------------------------------------------------------------------

def _dot(a, b, dims, exact):
    """A product as the chunked form's compiles on the v5e — bfloat16
    operands, float32 accumulation — or at ``HIGHEST`` where ``exact``."""
    if exact:
        return lax.dot_general(a, b, (dims, ((), ())),
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=_F32)
    return lax.dot_general(a.astype(_BF16), b.astype(_BF16),
                           (dims, ((), ())), preferred_element_type=_F32)


def _sums(ones, x, dims):
    """A 0/1 matrix ``ones`` against float32 ``x`` on the MXU, exactly:
    ``x`` goes as three bfloat16 terms (their sum IS the number), each
    times a one, added in float32 — the cumulative sums of a chunk."""
    ones = ones.astype(_BF16)
    out = None
    for _ in range(3):
        term = x.astype(_BF16)
        part = lax.dot_general(ones, term, (dims, ((), ())),
                               preferred_element_type=_F32)
        out = part if out is None else out + part
        x = x - term.astype(_F32)
    return out


def _unit_lower_inverses(ns):
    """``(I + n)^-1`` of each strictly lower-triangular (C, C) float32 ``n``
    of ``ns``: forward substitution a column at a time, float32 on the
    vector unit — row t of the inverse less ``n[t, j]`` times its row j,
    for every row below j (the rows above the sublane tile that holds row
    j + 1 are left alone).  Where two fit one lane tile (C <= 64), two
    heads go side by side in the lanes of one (C, 2C) array: one lane
    gather a step broadcasts both heads' column j, the costly part of a
    step."""
    c = ns[0].shape[0]
    together = 2 if 2 * c <= _LANES else 1
    out = []
    for first in range(0, len(ns), together):
        pair = ns[first:first + together]
        n = jnp.concatenate(pair, axis=1)
        row = lax.broadcasted_iota(jnp.int32, n.shape, 0)
        lane = lax.broadcasted_iota(jnp.int32, n.shape, 1)
        inv = jnp.where(row == lane % c, 1.0, 0.0).astype(_F32)
        for j in range(c - 1):
            lo = (j + 1) // 8 * 8
            col = jnp.take_along_axis(n, lane - lane % c + j, axis=1,
                                      mode="promise_in_bounds")[lo:]
            update = inv[lo:] - col * inv[j:j + 1, :]
            inv = jnp.concatenate([inv[:lo], update], axis=0) if lo \
                else update
        out += [inv[:, h * c:(h + 1) * c] for h in range(len(pair))]
    return out


def _beta_column(beta, head):
    """Head ``head``'s column of a (C, H) block of beta: (C, 1)."""
    lane = lax.broadcasted_iota(jnp.int32, beta.shape, 1)
    return jnp.sum(jnp.where(lane == head, beta.astype(_F32), 0.0), axis=1,
                   keepdims=True)


def _intra(heads, chunk, scale, exact):
    """Each head's chunk quantities, from ``heads``, a list of (q, k, v,
    g, beta) — (chunk, d) float32 and beta (chunk, 1): the scaled queries,
    ``G``, ``Gamma``, each sub-chunk's factors as (chunk, d) arrays — its
    keys' ``exp(E_b - G_i)`` (zero off the sub-chunk) and the queries'
    ``exp(G_t - E_b)`` from its first row on (zero above) —, the two
    C x C matrices built from them a sub-chunk of keys at a time, the
    inverse and ``U~``, ``W``."""
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    seen, below = row >= col, row > col
    terms = []
    for q, k, _, g, _ in heads:
        qs = q * scale
        cum = _sums(seen.astype(_F32), g, _NN)          # G_t, <= 0
        after, key_f, both = [], [], None
        for b in range(chunk // _SUBCHUNK):
            lo, hi = b * _SUBCHUNK, (b + 1) * _SUBCHUNK
            end = cum[hi - 1:hi, :]                     # E_b
            # zero rows above the sub-chunk and, for its keys, below it
            above = [jnp.zeros((lo, q.shape[1]), _F32)] if lo else []
            under = [jnp.zeros((chunk - hi, q.shape[1]), _F32)] \
                if hi < chunk else []
            key_f.append(jnp.concatenate(
                above + [jnp.exp(end - cum[lo:hi])] + under, axis=0))
            after.append(jnp.concatenate(
                above + [jnp.exp(cum[lo:] - end)], axis=0))
            part = _dot(jnp.concatenate([qs * after[-1], k * after[-1]],
                                        axis=0), k * key_f[-1], _NT, exact)
            both = part if both is None else both + part  # (2C, C)
        terms.append(dict(qs=qs, cum=cum, gam=jnp.exp(cum), after=after,
                          key_f=key_f, seen=seen, below=below,
                          a_qk=jnp.where(seen, both[:chunk], 0.0),  # i <= t
                          a_kk=jnp.where(below, both[chunk:], 0.0)))  # i < t
    invs = _unit_lower_inverses([h[4] * t["a_kk"]
                                 for h, t in zip(heads, terms)])
    for (_, k, v, _, beta), t, inv in zip(heads, terms, invs):
        t.update(inv=inv, u_free=_dot(inv, beta * v, _NN, True),    # U~
                 w=_dot(inv, beta * t["gam"] * k, _NN, True))       # W
    return terms


def _chunk_params(pltpu, heads, head_dim):
    """What the two calls share: the chunk axis sequential, a (d, d)
    float32 VMEM scratch a head of the block carried across it."""
    return dict(
        scratch_shapes=[pltpu.VMEM((heads, head_dim, head_dim), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))


def _lanes(t):
    """(B, S, H, d) as the (B, S, H*d) array it is in memory."""
    return t.reshape(t.shape[:2] + (-1,))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(beta_ref, q_ref, k_ref, v_ref, g_ref, o_ref, st_ref, s_ref,
                *, chunk, scale, exact):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    heads, d = s_ref.shape[:2]
    ats = [slice(j * d, (j + 1) * d) for j in range(heads)]
    inputs = [(q_ref[:, at].astype(_F32), k_ref[:, at].astype(_F32),
               v_ref[:, at].astype(_F32), g_ref[:, at].astype(_F32),
               _beta_column(beta_ref[...], pl.program_id(1) * heads + j))
              for j, at in enumerate(ats)]
    terms = _intra(inputs, chunk, scale, exact)
    for j, (at, (_, k, _, _, _), t) in enumerate(zip(ats, inputs, terms)):
        st = s_ref[j]                                   # S_0^T (d_v, d_k)
        st_ref[j] = st
        u = t["u_free"] - _dot(t["w"], st, _NT, exact)  # U~ - W S_0
        o = _dot(t["gam"] * t["qs"], st, _NT, exact) \
            + _dot(t["a_qk"], u, _NN, exact)
        o_ref[:, at] = o.astype(o_ref.dtype)
        last = t["cum"][chunk - 1:chunk, :]             # G_C
        s_ref[j] = st * jnp.exp(last) + _dot(
            u, k * jnp.exp(last - t["cum"]), _TN, exact)


def pallas_delta_fwd(q, k, v, g, beta, chunk, scale, heads=1, exact=False,
                     interpret=False):
    """(o, states): o (B, S, H, d) in v's dtype of q, k, v, g (B, S, H, d)
    and beta (B, S, H); S a whole number of chunks; and the state BEFORE
    every chunk, transposed, (B, H, S / chunk, d_v, d_k) float32 — what the
    backward kernel reads.  A grid step's blocks: ``heads`` heads'
    (chunk, heads * d) lanes of the (B, S, H*d) arrays, the chunk's
    (chunk, H) rows of beta."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bt, s, h, d = q.shape
    grid = (bt, h // heads, s // chunk)
    lanes = pl.BlockSpec((None, chunk, heads * d),
                         lambda bi, hi, ci: (bi, ci, hi))
    starts = pl.BlockSpec((None, heads, None, d, d),
                          lambda bi, hi, ci: (bi, hi, ci, 0, 0))
    outputs = jax.ShapeDtypeStruct((bt, s, h * d), v.dtype)
    start_states = jax.ShapeDtypeStruct((bt, h, s // chunk, d, d), _F32)
    with jax.named_scope("delta.fwd"):
        o, states = pl.pallas_call(
            functools.partial(_fwd_kernel, chunk=chunk, scale=scale,
                              exact=exact),
            grid=grid,
            in_specs=[pl.BlockSpec((None, chunk, h),
                                   lambda bi, hi, ci: (bi, ci, 0)),
                      lanes, lanes, lanes, lanes],
            out_specs=[lanes, starts],
            out_shape=[outputs, start_states],
            interpret=interpret,
            name="delta_fwd",
            **_chunk_params(pltpu, heads, d),
        )(beta, _lanes(q), _lanes(k), _lanes(v), _lanes(g))
    return o.reshape(v.shape), states


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(beta_ref, q_ref, k_ref, v_ref, g_ref, st_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref, *, chunk,
                scale, exact):
    """A block's chunk of the backward, the chunks walked in reverse.  A
    head's, with ``S_0`` the state before the chunk, ``dS_1`` the carried
    gradient of the one after it, ``U = U~ - W S_0``, ``K_e = K o exp(G_C -
    G)``::

        O   = (Gamma o Q) S_0 + A_qk U
        S_1 = Diag(Gamma_C) S_0 + K_e^T U

    so ``dU = A_qk^T dO + K_e dS_1``, ``dA_qk = dO U^T`` (i <= t), ``dS_0 =
    (Gamma o Q)^T dO + Diag(Gamma_C) dS_1 - W^T dU``; through the solve
    ``dR = T^-T [dU | -dU S_0^T]``, ``dN = -dR [U~ | W]^T`` (i < t), ``N =
    Diag(beta) A_kk``; each C x C matrix back through its sub-chunks
    (query side ``after_b o (dA keys_b)``, key side ``f_b o (dA^T (X o
    after_b))``, and ``dG`` their two sides with opposite signs); ``dg``
    the reverse cumulative sum of ``dG``."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    heads, d = ds_ref.shape[:2]
    ats = [slice(j * d, (j + 1) * d) for j in range(heads)]
    inputs = [(q_ref[:, at].astype(_F32), k_ref[:, at].astype(_F32),
               v_ref[:, at].astype(_F32), g_ref[:, at].astype(_F32),
               _beta_column(beta_ref[...], pl.program_id(1) * heads + j))
              for j, at in enumerate(ats)]
    terms = _intra(inputs, chunk, scale, exact)
    for j, (at, (_, k, v, _, beta), t) in enumerate(zip(ats, inputs,
                                                         terms)):
        dq, dk, dv, dg, db, ds_ref[j] = _bwd_head(
            t, k, v, beta, do_ref[:, at].astype(_F32), st_ref[j], ds_ref[j],
            chunk=chunk, scale=scale, exact=exact)
        for ref, value in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv),
                           (dg_ref, dg), (db_ref, db)):
            ref[:, at] = value.astype(ref.dtype)


def _bwd_head(t, k, v, beta, do, st, ds1, *, chunk, scale, exact):
    """One head's chunk: (dq, dk, dv, dg, d(beta) over the lanes, dS_0^T)
    from its terms ``t`` (``_intra``), k, v and dO (chunk, d) float32, beta
    (chunk, 1), ``st`` = S_0^T and ``ds1`` = dS_1^T."""
    gam, cum, qs = t["gam"], t["cum"], t["qs"]
    u_free, w = t["u_free"], t["w"]
    u = u_free - _dot(w, st, _NT, exact)
    last = cum[chunk - 1:chunk, :]
    to_end = jnp.exp(last - cum)
    ke = k * to_end
    # through the output and the state update
    du = _dot(t["a_qk"], do, _TN, exact) + _dot(ke, ds1, _NT, exact)
    da_qk = jnp.where(t["seen"], _dot(do, u, _NT, exact), 0.0)
    dgq = _dot(do, st, _NN, exact)                      # d(Gamma o Q)
    ds0 = _dot(do, gam * qs, _TN, exact) + ds1 * jnp.exp(last) \
        - _dot(du, w, _TN, exact)
    dke = _dot(u, ds1, _NN, exact)
    dk = to_end * dke
    dcum = -ke * dke
    d_last = jnp.sum(ke * dke, axis=0, keepdims=True) \
        + jnp.exp(last) * jnp.sum(ds1 * st, axis=0, keepdims=True)
    dgam = qs * dgq
    dqs = gam * dgq
    # through the solve
    inv_t = t["inv"].T
    dr_v = _dot(inv_t, du, _NN, True)
    dr_k = _dot(inv_t, -_dot(du, st, _NN, exact), _NN, True)
    dn = jnp.where(t["below"], -_dot(dr_v, u_free, _NT, True)
                   - _dot(dr_k, w, _NT, True), 0.0)
    dk += beta * gam * dr_k
    dgam += beta * k * dr_k
    lane = lax.broadcasted_iota(jnp.int32, k.shape, 1)
    db = dr_v * v + dr_k * gam * k + jnp.where(
        lane == 0, jnp.sum(dn * t["a_kk"], axis=1, keepdims=True), 0.0)
    # through the two C x C matrices, a sub-chunk of keys at a time
    da = jnp.concatenate([da_qk, beta * dn], axis=0)    # (2C, C)
    da_t = da.T                                         # (C, 2C)
    dq_a = dk_q = dk_k = 0.0
    for after, key_f in zip(t["after"], t["key_f"]):
        by_query = _dot(da, k * key_f, _NN, exact)      # (2C, d)
        dq_a += after * by_query[:chunk]
        dk_q += after * by_query[chunk:]
        dk_k += key_f * _dot(da_t, jnp.concatenate(
            [qs * after, k * after], axis=0), _NN, exact)
    dqs += dq_a
    dk += dk_q + dk_k
    dcum += qs * dq_a + k * (dk_q - dk_k) + gam * dgam
    row = lax.broadcasted_iota(jnp.int32, k.shape, 0)
    dcum += jnp.where(row == chunk - 1, d_last, 0.0)
    return scale * dqs, dk, beta * dr_v, \
        _sums(t["seen"].astype(_F32), dcum, _TN), db, ds0  # dg: reverse cumsum


def pallas_delta_bwd(q, k, v, g, beta, states, do, chunk, scale, heads=1,
                     exact=False, interpret=False):
    """(dq, dk, dv, dg, dbeta): the gradients of ``pallas_delta_fwd``'s
    inputs from the cotangent ``do`` of o, the chunk-start ``states`` its
    second output; dbeta (B, S, H) float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bt, s, h, d = q.shape
    grid = (bt, h // heads, s // chunk)
    last = grid[2] - 1                 # the chunks from the last to the first
    lanes = pl.BlockSpec((None, chunk, heads * d),
                         lambda bi, hi, ci: (bi, last - ci, hi))
    with jax.named_scope("delta.bwd"):
        dq, dk, dv, dg, db = pl.pallas_call(
            functools.partial(_bwd_kernel, chunk=chunk, scale=scale,
                              exact=exact),
            grid=grid,
            in_specs=[pl.BlockSpec((None, chunk, h),
                                   lambda bi, hi, ci: (bi, last - ci, 0)),
                      lanes, lanes, lanes, lanes,
                      pl.BlockSpec((None, heads, None, d, d),
                                   lambda bi, hi, ci: (bi, hi, last - ci,
                                                       0, 0)),
                      lanes],
            out_specs=[lanes] * 5,
            out_shape=[jax.ShapeDtypeStruct((bt, s, h * d), t.dtype)
                       for t in (q, k, v, g)]
            + [jax.ShapeDtypeStruct((bt, s, h * d), _F32)],
            interpret=interpret,
            name="delta_bwd",
            **_chunk_params(pltpu, heads, d),
        )(beta, _lanes(q), _lanes(k), _lanes(v), _lanes(g), states,
          _lanes(do))
    # d(beta): the sum of a head's 128 lanes
    db = db.reshape(bt, s, h, d).sum(-1)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), \
        dg.reshape(g.shape), db


# ---------------------------------------------------------------------------
# the operator's kernel path, with its custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def delta_rule_kernels(q, k, v, g, beta, chunk, scale, exact=False,
                       interpret=False):
    """The rule through the kernels: q, k, v, g (B, S, H, 128), beta
    (B, S, H), S a whole number of chunks; o (B, S, H, 128) in v's dtype.
    Differentiable in all five; the residuals are the five and the
    chunk-start states."""
    return _rule_fwd(q, k, v, g, beta, chunk, scale, exact, interpret)[0]


def _block_heads(heads):
    """Heads a grid step: a pair, one where their count is odd."""
    return _HEADS_PER_BLOCK if heads % _HEADS_PER_BLOCK == 0 else 1


def _rule_fwd(q, k, v, g, beta, chunk, scale, exact, interpret):
    heads = _block_heads(q.shape[2])
    o, states = per_batch_shard(
        lambda *a: pallas_delta_fwd(*a, chunk, scale, heads, exact,
                                    interpret),
        (q, k, v, g, beta))
    return o, (q, k, v, g, beta, states)


def _rule_bwd(chunk, scale, exact, interpret, res, do):
    q, k, v, g, beta, states = res
    heads = _block_heads(q.shape[2])
    dq, dk, dv, dg, db = per_batch_shard(
        lambda *a: pallas_delta_bwd(*a, chunk, scale, heads, exact,
                                    interpret),
        (q, k, v, g, beta, states, do))
    return dq, dk, dv, dg, db.astype(beta.dtype)


delta_rule_kernels.defvjp(_rule_fwd, _rule_bwd)
