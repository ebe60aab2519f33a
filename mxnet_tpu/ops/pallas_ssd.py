"""The Mamba-2 state-space scan (``ops.ssm.ssd_chunk_scan``) as Pallas
kernels for TPU, forward AND backward.

``ops/ssm.py`` has the recurrence and its chunked form.  As plain XLA the
chunked form sends every intermediate through HBM — the (Q, Q) decay and
score blocks of every head and chunk in float32, the chunk states, 5-D
arrays with a 64-wide head minor that fill half of a 128-lane tile — and
passes the state through a ``lax.scan``.  Here a grid step holds ONE
group's chunk in VMEM (grid ``(batch, group, chunk)``, the chunk axis
sequential) and the state of the group's R heads is a float32 VMEM scratch
carried across the chunk axis:

* the kernels read x, B and C where the convolution left them, as lane
  blocks of the (B, S, H*P) and (B, S, G*N) arrays (a group's x is R*P
  lanes, its B and C are N lanes), and write y and the gradients the same
  way; no (B, S, G, R, P) array exists on their side;
* what is per head and per step — the in-chunk cumulative sum of
  ``a = dt A`` and ``delta = softplus(dt + dt_bias)`` — is made by one
  small XLA computation on the (B, S, H) arrays (``_scan_rows``) and
  handed in as lane rows (B, G, rows, S); a kernel turns the rows of its
  chunk into columns with one 128 x 128 transpose, so a decay block
  ``exp(cum_t - cum_s)`` is a column against a row;
* the state is kept TRANSPOSED, (N, R*P): a head's update is
  ``(B^T o w) x`` and its carried output ``C h^T``, plain products with
  the per-step weights as rows; heads narrower than 128 lanes share a
  lane block, each product taken with the other heads' lanes zero
  (``pallas_attention._head_lanes``: on a 128-deep MXU a 64-wide product
  costs the same passes);
* the backward is a ``jax.custom_vjp`` whose residuals are the operator's
  INPUTS: ``ssd_states`` makes the chunk-boundary states again (a
  transient of N*H*P*4 bytes a chunk), ``ssd_bwd`` walks the chunks in
  reverse with the state's gradient in VMEM, sums dB and dC over a
  group's heads, and returns the gradients of the rows — per head and
  step, their sums over P and over the (Q, Q) blocks taken inside — which
  ``_scan_rows``'s own transpose turns into d(dt), d(a_log), d(dt_bias).

Precision is ``_chunked``'s: decays, cumulative sums and states float32;
products take their operands in the inputs' dtype and accumulate in
float32 (float32 inputs multiply at ``Precision.HIGHEST``).

``ssd_dispatch`` decides, from what the code observes (platform, shapes,
the working set), whether a call takes the kernels; every other shape
keeps ``ops.ssm._chunked``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import context as _context
from .pallas_attention import (_LANES, _NEG_INF, _VMEM_CLAMP, _head_lanes,
                               per_batch_shard)

__all__ = ["ssd_dispatch", "ssd_scan_kernels", "pallas_ssd_fwd",
           "pallas_ssd_states", "pallas_ssd_bwd"]

_F32 = jnp.float32


def _round_up(n, to):
    return n + (-n) % to


def _lane_block(head_dim):
    """(lanes of a block, heads in it): a head of 128 lanes or more is its
    own block, narrower heads share a 128-lane block."""
    if head_dim >= _LANES:
        return head_dim, 1
    return _LANES, _LANES // head_dim


def _vmem_bytes(chunk, group_lanes, state, itemsize):
    """The backward kernel's working set (the largest of the three): the
    double-buffered blocks of x, dy, dx (chunk, R*P), B, C, dB, dC
    (chunk, N), the boundary state (N, R*P) float32, the carried gradient,
    and its float32 temporaries — half a dozen (chunk, R*P) and (N, R*P)
    arrays and as many (chunk, chunk) blocks."""
    wide = chunk * group_lanes
    st = state * group_lanes * 4
    blocks = 2 * (3 * wide * itemsize + 4 * chunk * state * itemsize + st)
    temps = 6 * wide * 4 + 4 * st + 8 * chunk * chunk * 4 \
        + 4 * chunk * state * 4
    return blocks + st + temps


def ssd_dispatch(seq_len, chunk, heads, head_dim, groups, state,
                 dtype="bfloat16", on_tpu=None):
    """``"kernel"`` or ``"chunked"`` for a scan of ``seq_len`` steps (after
    padding to chunks of ``chunk``): the kernels take a call on a TPU whose
    group is whole lane blocks (R*P a multiple of 128, a head a divisor or
    a multiple of 128 lanes), whose state N and chunk are multiples of 128,
    and whose working set fits the kernels' VMEM budget
    (``pallas_attention._VMEM_CLAMP``, the limit ``_blocks_fit`` holds the
    attention blocks to); everything else is ``ops.ssm._chunked``."""
    if on_tpu is None:
        on_tpu = _context.on_tpu()
    per_group = heads // groups
    group_lanes = per_group * head_dim
    fits = on_tpu and heads % groups == 0 \
        and group_lanes % _LANES == 0 \
        and (_LANES % head_dim == 0 or head_dim % _LANES == 0) \
        and state % _LANES == 0 and chunk % _LANES == 0 \
        and seq_len % chunk == 0 \
        and 2 * _round_up(per_group, 8) <= _LANES \
        and _vmem_bytes(chunk, group_lanes, state,
                        jnp.dtype(dtype).itemsize) <= _VMEM_CLAMP
    return "kernel" if fits else "chunked"


# ---------------------------------------------------------------------------
# the per-head, per-step rows (plain XLA, on (B, S, H) arrays)
# ---------------------------------------------------------------------------

def _scan_rows(dt, a_log, dt_bias, groups, chunk):
    """``softplus(dt + dt_bias)`` and the in-chunk cumulative sum of
    ``a = delta * -exp(a_log)`` as the kernels read them: (B, G, 2 Rp, S)
    float32, row r a group's head r's cumulative sum along the lanes, row
    Rp + r its delta (Rp: R rounded up to whole sublane tiles, the rows
    between are zero).  The kernels' gradient of this array goes back
    through this function's own transpose."""
    bt, s, h = dt.shape
    per_group = h // groups
    delta = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))
    a = delta * -jnp.exp(a_log.astype(_F32))
    cum = jnp.cumsum(a.reshape(bt, s // chunk, chunk, h), axis=2)

    def rows(t):
        t = jnp.moveaxis(t.reshape(bt, s, groups, per_group), 1, 3)
        return jnp.pad(t, ((0, 0), (0, 0),
                           (0, -per_group % 8), (0, 0)))
    return jnp.concatenate([rows(cum.reshape(bt, s, h)), rows(delta)],
                           axis=2)


# ---------------------------------------------------------------------------
# what the three kernels share
# ---------------------------------------------------------------------------

def _columns(rows):
    """(n, Q) float32 rows -> (Q, 128): row j along the sublanes of lane
    j, through one aligned transpose."""
    n, q = rows.shape
    return jnp.concatenate([rows, jnp.zeros((_LANES - n, q), _F32)],
                           axis=0).T


def _dot(a, b, dims, precision):
    return lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                           preferred_element_type=_F32)


_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b.T: both contract their lanes
_TN = ((0,), (0,))      # a.T @ b: both contract their sublanes


def _lane_sums(values, select):
    """``values`` (rows, lanes) float32 against a one-hot ``select``
    (lanes, 128): the sums of each row's lanes into the selected columns,
    on the MXU and exact — the float32 numbers go as three bfloat16 terms
    (their sum IS the number), each times a one, added in float32."""
    select = select.astype(jnp.bfloat16)
    out = None
    for _ in range(3):
        term = values.astype(jnp.bfloat16)
        part = _dot(term, select, _NN, None)
        out = part if out is None else out + part
        values = values - term.astype(_F32)
    return out


def _per_head(columns, first, heads, lane, width):
    """A (rows, lanes) array that holds, in the lanes of each of the
    block's ``heads`` heads, that head's column of ``columns``
    (``first + k`` for head k) — or its (1, 1) entry, for a one-row
    ``columns``."""
    out = columns[:, first + heads - 1:first + heads]
    for k in range(heads - 2, -1, -1):
        out = jnp.where(lane < (k + 1) * width,
                        columns[:, first + k:first + k + 1], out)
    # along the lanes here: Mosaic broadcasts one way at a time
    return jnp.broadcast_to(out, (out.shape[0], lane.shape[1]))


def _chunk_terms(rows_ref, per_group, chunk):
    """The rows of a chunk and what every kernel makes of them: the
    cumulative sums and deltas as rows (Rp, Q) and as columns (Q, 128),
    and ``exp(total - cum)`` as rows."""
    rp = _round_up(per_group, 8)
    rows = rows_ref[...]
    cum, delta = rows[:rp], rows[rp:]
    return cum, delta, _columns(rows), \
        jnp.exp(cum[:, chunk - 1:chunk] - cum), rp


def _decay(cols, cum, r, seen):
    """exp(cum_t - cum_s) for s <= t, else 0: head r's (Q, Q) block."""
    return jnp.exp(jnp.where(seen, cols[:, r:r + 1] - cum[r:r + 1, :],
                             _NEG_INF))


def _seen(chunk):
    return lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) \
        >= lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)


def _state_update(h_ref, h, x, b, to_end, delta, cols, geometry, precision):
    """``h_ref`` <- h_c^T from ``h`` = h_(c-1)^T (N, R*P): a lane block at
    a time, the decayed state plus each head's ``(B^T o w) x``."""
    per_group, head_dim, chunk = geometry
    width, in_block = _lane_block(head_dim)
    b_t = b.astype(_F32).T                               # (N, Q)
    e_total = jnp.exp(cols[chunk - 1:chunk, :])          # lane r: head r's
    lane = lax.broadcasted_iota(jnp.int32, (1, width), 1)
    for j in range(per_group * head_dim // width):
        at = slice(j * width, (j + 1) * width)
        new = jnp.zeros((b_t.shape[0], width), _F32)
        for k in range(in_block):
            r = j * in_block + k
            w = to_end[r:r + 1, :] * delta[r:r + 1, :]
            new += _dot((b_t * w).astype(x.dtype),
                        _head_lanes(x[:, at], k, in_block), _NN, precision)
        keep = _per_head(e_total, j * in_block, in_block, lane, head_dim)
        h_ref[:, at] = keep * h[:, at] + new


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, b_ref, c_ref, rows_ref, d_ref, y_ref, h_ref, *,
                geometry, precision):
    per_group, head_dim, chunk = geometry
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    x, b, c = x_ref[...], b_ref[...], c_ref[...]
    cum, delta, cols, to_end, _ = _chunk_terms(rows_ref, per_group, chunk)
    e_cols = jnp.exp(cols)
    scores = _dot(c, b, _NT, precision)                  # C B^T, [t, s]
    seen = _seen(chunk)
    h = h_ref[...]
    carried = _dot(c, h.astype(x.dtype), _NN, precision)     # C h^T
    width, in_block = _lane_block(head_dim)
    lane = lax.broadcasted_iota(jnp.int32, (chunk, width), 1)
    for j in range(per_group * head_dim // width):
        at = slice(j * width, (j + 1) * width)
        xb = x[:, at]
        y = jnp.zeros((chunk, width), _F32)
        for k in range(in_block):
            r = j * in_block + k
            mix = scores * _decay(cols, cum, r, seen) * delta[r:r + 1, :]
            y += _dot(mix.astype(x.dtype), _head_lanes(xb, k, in_block),
                      _NN, precision)
        y += carried[:, at] * _per_head(e_cols, j * in_block, in_block,
                                        lane, head_dim)
        y += xb.astype(_F32) * d_ref[:, at]
        y_ref[:, at] = y.astype(y_ref.dtype)
    _state_update(h_ref, h, x, b, to_end, delta, cols, geometry, precision)


def _states_kernel(x_ref, b_ref, rows_ref, st_ref, h_ref, *, geometry,
                   precision):
    per_group, head_dim, chunk = geometry
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    h = h_ref[...]
    st_ref[...] = h                                   # h_(c-1)^T
    _, delta, cols, to_end, _ = _chunk_terms(rows_ref, per_group, chunk)
    _state_update(h_ref, h, x_ref[...], b_ref[...], to_end, delta, cols,
                  geometry, precision)


def _precision(dtype):
    return lax.Precision.HIGHEST if jnp.dtype(dtype) == _F32 else None


def _lanes(t):
    """(B, S, H, P) or (B, S, G, N) as the (B, S, lanes) array it is in
    memory."""
    return t.reshape(t.shape[:2] + (-1,))


def _scan_shapes(x, b, chunk):
    """From x (B, S, H, P) and b (B, S, G, N): the grid (batch, group,
    chunk), a group's lanes R*P, N, and the kernels' static ``geometry``
    (heads a group, head width, chunk)."""
    bt, s, h, p = x.shape
    groups, n = b.shape[2:]
    per_group = h // groups
    return (bt, groups, s // chunk), per_group * p, n, (per_group, p, chunk)


def _scan_params(pltpu, n, group_lanes):
    """What the three calls share: the chunk axis sequential, with one
    (N, R*P) float32 VMEM scratch carried across it."""
    return dict(
        scratch_shapes=[pltpu.VMEM((n, group_lanes), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))


def pallas_ssd_fwd(x, b, c, rows, d_wide, chunk, interpret=False):
    """y (B, S, H, P) of x (B, S, H, P), b and c (B, S, G, N), the rows of
    ``_scan_rows`` and the skip (1, H*P) float32 (a head's D over its
    lanes); S a whole number of chunks.  A grid step's blocks: a group's
    (Q, R*P) lanes of the (B, S, H*P) array, its (Q, N) lanes of the
    (B, S, G*N) arrays, its rows, its lanes of the skip."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    grid, group_lanes, n, geometry = _scan_shapes(x, b, chunk)
    xf = _lanes(x)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, geometry=geometry,
                          precision=_precision(x.dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, group_lanes),
                         lambda bi, gi, ci: (bi, ci, gi)),
            pl.BlockSpec((None, chunk, n), lambda bi, gi, ci: (bi, ci, gi)),
            pl.BlockSpec((None, chunk, n), lambda bi, gi, ci: (bi, ci, gi)),
            pl.BlockSpec((None, None, rows.shape[2], chunk),
                         lambda bi, gi, ci: (bi, gi, 0, ci)),
            pl.BlockSpec((1, group_lanes), lambda bi, gi, ci: (0, gi)),
        ],
        out_specs=pl.BlockSpec((None, chunk, group_lanes),
                               lambda bi, gi, ci: (bi, ci, gi)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=interpret,
        name="ssd_fwd",
        **_scan_params(pltpu, n, group_lanes),
    )(xf, _lanes(b), _lanes(c), rows, d_wide).reshape(x.shape)


def pallas_ssd_states(x, b, rows, chunk, interpret=False):
    """The state BEFORE every chunk, transposed: (B, G, S / chunk, N, R*P)
    float32 — what the backward kernel reads, made again from the inputs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    grid, group_lanes, n, geometry = _scan_shapes(x, b, chunk)
    return pl.pallas_call(
        functools.partial(_states_kernel, geometry=geometry,
                          precision=_precision(x.dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, group_lanes),
                         lambda bi, gi, ci: (bi, ci, gi)),
            pl.BlockSpec((None, chunk, n), lambda bi, gi, ci: (bi, ci, gi)),
            pl.BlockSpec((None, None, rows.shape[2], chunk),
                         lambda bi, gi, ci: (bi, gi, 0, ci)),
        ],
        out_specs=pl.BlockSpec((None, None, None, n, group_lanes),
                               lambda bi, gi, ci: (bi, gi, ci, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(grid + (n, group_lanes), _F32),
        interpret=interpret,
        name="ssd_states",
        **_scan_params(pltpu, n, group_lanes),
    )(_lanes(x), _lanes(b), rows)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(x_ref, b_ref, c_ref, rows_ref, d_ref, st_ref, dy_ref,
                dx_ref, db_ref, dc_ref, drows_ref, dd_ref, dh_ref, *,
                geometry, precision):
    """One group's chunk of the backward, the chunks walked in reverse.
    With h0 the state before the chunk, dh1 the carried gradient of the
    state after it, G = C B^T, L the decay block, M = G o L o delta_s::

        y  = M x + e_t (C h0^T) + D x            e_t = exp(cum_t)
        h1 = exp(total) h0 + sum_s w_s x_s (x) B_s    w = exp(total - cum) delta

    so, with A = dy x^T (per head, [t, s])::

        dx  = M^T dy + w_s (B dh1^T) + D dy
        dG  = sum_heads A o L o delta_s;  dC += dG B,  dB += dG^T C
        dC += e_t (dy h0),  dB += w_s (x dh1)
        dh0 = exp(total) dh1 + (C^T o e) dy
        d cum_t  = rowsum(A o M) + <dy_t, e_t C h0^T>
                 = <dy_t, M x + e_t C h0^T>               (the t side)
        d cum_s  = -colsum(A o M) - w_s z_s,   z_s = <x_s, B_s dh1^T>
        d delta_s = colsum(A o G o L) + exp(total - cum_s) z_s
        d total  = exp(total) <dh1, h0> + sum_s w_s z_s   (into cum's last)

    Sums over the columns of a (Q, Q) block are sums over sublanes: rows.
    Sums over a head's lanes meet a selector on the MXU (``_lane_sums``),
    which drops them into that head's lane of one (Q, 128) accumulator;
    its transpose is rows too."""
    per_group, head_dim, chunk = geometry
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    x, b, c, dy = x_ref[...], b_ref[...], c_ref[...], dy_ref[...]
    dtype = x.dtype
    cum, delta, cols, to_end, rp = _chunk_terms(rows_ref, per_group, chunk)
    e_cols = jnp.exp(cols)
    e_rows = jnp.exp(cum)
    # w as columns: lane r from the total's lane r and delta's lane rp + r
    to_end_cols = jnp.exp(cols[chunk - 1:chunk, :] - cols)
    scores = _dot(c, b, _NT, precision)
    seen = _seen(chunk)
    c_t = c.astype(_F32).T                              # (N, Q)
    h0 = st_ref[...]
    dh1 = dh_ref[...]
    carried = _dot(c, h0.astype(dtype), _NN, precision)      # C h0^T
    b_dh = _dot(b, dh1.astype(dtype), _NN, precision)        # B dh1^T
    d_scores = jnp.zeros((chunk, chunk), _F32)
    d_b = jnp.zeros(b.shape, _F32)
    d_c = jnp.zeros(c.shape, _F32)
    sums = jnp.zeros((chunk, _LANES), _F32)      # lane r: t side, rp + r: z
    col_sums = jnp.zeros((rp, chunk), _F32)      # colsum(A o G o L), a row
    col_mixed = jnp.zeros((rp, chunk), _F32)     # colsum(A o M)
    state_dot = jnp.zeros((rp, chunk), _F32)     # exp(total) <dh1, h0>
    head_row = lax.broadcasted_iota(jnp.int32, (rp, chunk), 0)
    width, in_block = _lane_block(head_dim)
    lane_row = lax.broadcasted_iota(jnp.int32, (1, width), 1)
    lane = lax.broadcasted_iota(jnp.int32, (chunk, width), 1)
    # a block's lane l is the lane of ``sums`` of the head it belongs to
    head_of = lax.broadcasted_iota(jnp.int32, (width, _LANES), 0) // head_dim
    to_lane = lax.broadcasted_iota(jnp.int32, (width, _LANES), 1)
    for j in range(per_group * head_dim // width):
        at = slice(j * width, (j + 1) * width)
        xb, dyb, h0b, dh1b = x[:, at], dy[:, at], h0[:, at], dh1[:, at]
        dxb = jnp.zeros((chunk, width), _F32)
        y_in = jnp.zeros((chunk, width), _F32)
        dh0b = jnp.zeros(h0b.shape, _F32)
        keep = _per_head(e_cols[chunk - 1:chunk, :], j * in_block, in_block,
                         lane_row, head_dim)             # exp(total), (1, W)
        overlap = jnp.sum(dh1b * h0b, axis=0, keepdims=True) * keep
        for k in range(in_block):
            r = j * in_block + k
            xm = _head_lanes(xb, k, in_block)
            dym = _head_lanes(dyb, k, in_block)
            dl = delta[r:r + 1, :]
            decay = _decay(cols, cum, r, seen)
            gl = scores * decay
            a = _dot(dym, xb, _NT, precision)            # dy x^T, [t, s]
            mix = (gl * dl).astype(dtype)
            y_in += _dot(mix, xm, _NN, precision)
            dxb += _dot(mix, dym, _TN, precision)
            d_scores += a * (decay * dl)
            col_sums = jnp.where(head_row == r,
                                 jnp.sum(a * gl, axis=0, keepdims=True),
                                 col_sums)
            # the t side below is <dy, mix x> with mix ROUNDED: the s side
            # it cancels against takes the same numbers
            col_mixed = jnp.where(
                head_row == r,
                jnp.sum(a * mix.astype(_F32), axis=0, keepdims=True),
                col_mixed)
            d_c += e_cols[:, r:r + 1] * _dot(dym, h0b.astype(dtype), _NT,
                                             precision)
            dh0b += _dot((c_t * e_rows[r:r + 1, :]).astype(dtype), dym,
                         _NN, precision)
            w_col = to_end_cols[:, r:r + 1] * cols[:, rp + r:rp + r + 1]
            d_b += w_col * _dot(xm, dh1b.astype(dtype), _NT, precision)
            state_dot = jnp.where(
                head_row == r, jnp.broadcast_to(
                    jnp.sum(_head_lanes(overlap, k, in_block), axis=1,
                            keepdims=True), (1, chunk)),
                state_dot)
        e_wide = _per_head(e_cols, j * in_block, in_block, lane, head_dim)
        w_wide = _per_head(to_end_cols, j * in_block, in_block, lane,
                           head_dim) \
            * _per_head(cols, rp + j * in_block, in_block, lane, head_dim)
        dyf, xf = dyb.astype(_F32), xb.astype(_F32)
        first = head_of + j * in_block
        sums += _lane_sums(dyf * (y_in + carried[:, at] * e_wide),
                           to_lane == first)
        sums += _lane_sums(xf * b_dh[:, at], to_lane == first + rp)
        dxb += w_wide * b_dh[:, at] + dyf * d_ref[:, at]
        dx_ref[:, at] = dxb.astype(dx_ref.dtype)
        dh_ref[:, at] = keep * dh1b + dh0b
        dd_ref[:, at] += jnp.sum(dyf * xf, axis=0, keepdims=True)
    d_c += _dot(d_scores.astype(dtype), b, _NN, precision)
    d_b += _dot(d_scores.astype(dtype), c, _TN, precision)
    db_ref[...] = d_b.astype(db_ref.dtype)
    dc_ref[...] = d_c.astype(dc_ref.dtype)
    sums_t = sums.T                                     # (128, Q)
    t_side, z = sums_t[:rp], sums_t[rp:2 * rp]
    wz = to_end * delta * z
    d_total = state_dot + jnp.sum(wz, axis=1, keepdims=True)
    last = lax.broadcasted_iota(jnp.int32, (rp, chunk), 1) == chunk - 1
    drows_ref[:rp, :] = t_side - col_mixed - wz \
        + jnp.where(last, d_total, 0.0)
    drows_ref[rp:, :] = col_sums + to_end * z


def pallas_ssd_bwd(x, b, c, rows, d_wide, states, dy, chunk,
                   interpret=False):
    """(dx, db, dc, drows, dd): the gradients of ``pallas_ssd_fwd``'s x, b,
    c and rows (float32, ``rows``'s shape), and of the skip summed over the
    steps, (B, G, 1, R*P) float32 (the sum over a head's lanes and the
    batch is left to the caller)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    grid, group_lanes, n, geometry = _scan_shapes(x, b, chunk)
    last = grid[2] - 1                  # the chunks from the last to the first
    xf, bf, cf = _lanes(x), _lanes(b), _lanes(c)
    dx, db, dc, drows, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, geometry=geometry,
                          precision=_precision(x.dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, group_lanes),
                         lambda bi, gi, ci: (bi, last - ci, gi)),
            pl.BlockSpec((None, chunk, n),
                         lambda bi, gi, ci: (bi, last - ci, gi)),
            pl.BlockSpec((None, chunk, n),
                         lambda bi, gi, ci: (bi, last - ci, gi)),
            pl.BlockSpec((None, None, rows.shape[2], chunk),
                         lambda bi, gi, ci: (bi, gi, 0, last - ci)),
            pl.BlockSpec((1, group_lanes), lambda bi, gi, ci: (0, gi)),
            pl.BlockSpec((None, None, None, n, group_lanes),
                         lambda bi, gi, ci: (bi, gi, last - ci, 0, 0)),
            pl.BlockSpec((None, chunk, group_lanes),
                         lambda bi, gi, ci: (bi, last - ci, gi)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, group_lanes),
                         lambda bi, gi, ci: (bi, last - ci, gi)),
            pl.BlockSpec((None, chunk, n),
                         lambda bi, gi, ci: (bi, last - ci, gi)),
            pl.BlockSpec((None, chunk, n),
                         lambda bi, gi, ci: (bi, last - ci, gi)),
            pl.BlockSpec((None, None, rows.shape[2], chunk),
                         lambda bi, gi, ci: (bi, gi, 0, last - ci)),
            pl.BlockSpec((None, None, 1, group_lanes),
                         lambda bi, gi, ci: (bi, gi, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(xf.shape, x.dtype),
                   jax.ShapeDtypeStruct(bf.shape, b.dtype),
                   jax.ShapeDtypeStruct(cf.shape, c.dtype),
                   jax.ShapeDtypeStruct(rows.shape, _F32),
                   jax.ShapeDtypeStruct(grid[:2] + (1, group_lanes), _F32)],
        interpret=interpret,
        name="ssd_bwd",
        **_scan_params(pltpu, n, group_lanes),
    )(xf, bf, cf, rows, d_wide, states, _lanes(dy))
    return dx.reshape(x.shape), db.reshape(b.shape), dc.reshape(c.shape), \
        drows, dd


# ---------------------------------------------------------------------------
# the operator's kernel path, with its custom VJP
# ---------------------------------------------------------------------------

def _skip_lanes(d_skip, head_dim):
    return jnp.repeat(d_skip.astype(_F32), head_dim)[None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def ssd_scan_kernels(x, dt, a_log, b, c, d_skip, dt_bias, chunk,
                     interpret=False):
    """``ssd_chunk_scan`` through the kernels: x (B, S, H, P), dt
    (B, S, H), b and c (B, S, G, N), the per-head vectors (H,); S a whole
    number of chunks.  Differentiable in all seven; the residuals are the
    seven themselves."""
    return _scan_fwd(x, dt, a_log, b, c, d_skip, dt_bias, chunk,
                     interpret)[0]


def _scan_fwd(x, dt, a_log, b, c, d_skip, dt_bias, chunk, interpret):
    rows = _scan_rows(dt, a_log, dt_bias, b.shape[2], chunk)
    y = per_batch_shard(
        lambda x, b, c, rows, d: pallas_ssd_fwd(x, b, c, rows, d, chunk,
                                                interpret),
        (x, b, c, rows, _skip_lanes(d_skip, x.shape[-1])), replicated=(4,))
    return y, (x, dt, a_log, b, c, d_skip, dt_bias)


def _scan_bwd(chunk, interpret, res, dy):
    x, dt, a_log, b, c, d_skip, dt_bias = res
    groups = b.shape[2]
    rows, rows_vjp = jax.vjp(
        lambda dt, a_log, dt_bias: _scan_rows(dt, a_log, dt_bias, groups,
                                              chunk), dt, a_log, dt_bias)

    def kernels(x, b, c, rows, d, dy):
        states = pallas_ssd_states(x, b, rows, chunk, interpret)
        return pallas_ssd_bwd(x, b, c, rows, d, states, dy, chunk,
                              interpret)

    dx, db, dc, drows, dd = per_batch_shard(
        kernels, (x, b, c, rows, _skip_lanes(d_skip, x.shape[-1]), dy),
        replicated=(4,))
    d_dt, d_a_log, d_dt_bias = rows_vjp(drows)
    d_skip_grad = dd.reshape(dd.shape[0], -1, x.shape[-1]).sum((0, 2))
    return dx, d_dt, d_a_log, db, dc, d_skip_grad.astype(d_skip.dtype), \
        d_dt_bias


ssd_scan_kernels.defvjp(_scan_fwd, _scan_bwd)
