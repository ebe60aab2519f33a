"""The experts' block products (``ops.moe.grouped_matmul`` on blocks of
slots) as Pallas kernels for TPU, forward AND backward.

``ops/moe.py::_on_blocks`` lays the held experts' routes out in B blocks of
S slots, each block one expert's (``owner`` (B,), non-decreasing).  As plain
XLA a block's product needs its owner's weight as an operand of a batched
product: ``jnp.take(weights, owner, axis=0)``, a (B, K, M) COPY of the
weights a product (loops of ``dynamic-slice``s), made in the forward, made
again in the recomputing backward, and un-made by a ``scatter-add`` of the
same size into the G held rows.  Here ``owner`` is a prefetched scalar and a
block's product reads ``weights[owner[b]]`` where it lies, through the
weight's ``index_map``:

* ``moe_blocks_fwd``: ``blocks (B, S, K) x weights (G, K, M) -> (B, S, M)``.
  A grid step holds a tile of a block's slots with the WHOLE contraction
  and a tile of the weight's columns: no accumulator, one rounding to the
  output's dtype.  A weight tile whose index does not move between grid
  steps (consecutive blocks of one owner, the weight one tile) is not
  fetched again;
* ``moe_blocks_dx``: ``dy (B, S, M) x weights[owner]^T -> (B, S, K)``: the
  same kernel contracting the weight's LAST axis;
* ``moe_blocks_dw``: ``d_weights[g] = sum over the blocks b with
  owner[b] = g of blocks[b]^T dy[b]``.  An owner's blocks are consecutive
  grid steps on one output tile: a float32 VMEM scratch accumulates them and
  is rounded to the weights' dtype ONCE, when the owner changes.  An expert
  that owns no block is never visited: the output starts as zeros (aliased
  in, no second buffer), so its gradient is exactly zero.

Precision is the batched product's: operands in the inputs' dtype, float32
accumulation (float32 inputs multiply at ``Precision.HIGHEST``).  A width
that is no whole number of lane tiles (1856) is taken whole where it is
contracted, and in lane tiles with a padded edge where it is not.

``moe_product_dispatch`` decides, from what the code observes (platform,
dtype, shapes, the working set), whether a call takes the kernels; every
other shape keeps ``take`` + ``einsum``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import context as _context
from .pallas_attention import _LANES, _VMEM_CLAMP, _int_zero_cotangent

__all__ = ["moe_product_dispatch", "block_products", "pallas_block_product",
           "pallas_block_weight_grad", "einsum_block_products"]

_F32 = jnp.float32


def einsum_block_products(blocks, weights, owner):
    """The plain form: each block's owner's weight gathered, one dense
    batched product.  What the kernels are tested against, and the path of
    every call they do not take."""
    return jnp.einsum("gsk,gkm->gsm", blocks, jnp.take(weights, owner, axis=0),
                      preferred_element_type=blocks.dtype)


# ---------------------------------------------------------------------------
# tiles
# ---------------------------------------------------------------------------

def _tiles(n, ragged=False):
    """Tile sizes of a dimension of ``n``: the whole of it, then whole lane
    tiles, the largest first — those that do not divide ``n`` only where
    ``ragged`` (the edge tile is padded: fine for a dimension that no
    product contracts)."""
    return [n] + [t for t in range((n - 1) // _LANES * _LANES, 0, -_LANES)
                  if ragged or n % t == 0]


def _cdiv(a, b):
    return -(-a // b)


def _product_vmem(tiles, contract, itemsize):
    """A product's working set: the double-buffered tiles of the slots'
    rows (ts, C), the weight (C, tn) and the output (ts, tn), and the
    float32 product before it is rounded."""
    ts, tn = tiles
    return 2 * itemsize * (ts * contract + contract * tn + ts * tn) \
        + 4 * ts * tn


def _grad_vmem(tiles, itemsize):
    """The weight gradient's working set: the double-buffered tiles of the
    rows (ts, tk), the cotangent (ts, tm) and the output (tk, tm), the
    float32 accumulator and the float32 product added to it."""
    ts, tk, tm = tiles
    return 2 * itemsize * (ts * (tk + tm) + tk * tm) + 8 * tk * tm


@functools.lru_cache(maxsize=None)
def _product_tiles(slots, contract, width, itemsize):
    """The tiles ``(ts, tn)`` — of a block's slots, of the output's width —
    of ``(slots, contract) x (contract, width)``: the whole contraction
    always; of the rest what fits ``_VMEM_CLAMP`` with the least padded
    work, an output tile wider than one lane tile (128 columns a step run
    at 131–152 TFLOP/s where 256 and more run at 147–165: my chip runs,
    PR 36), then the least HBM traffic (the rows are read once; the weight
    once a block unless both are tiled), then the widest tile of the
    weight — whole, it stays in VMEM over an owner's blocks — and the most
    slots.  None where nothing fits."""
    best = None
    for ts in _tiles(slots):
        for tn in _tiles(width, ragged=True):
            tiles = ts, tn
            if _product_vmem(tiles, contract, itemsize) > _VMEM_CLAMP:
                continue
            row_tiles, col_tiles = slots // ts, _cdiv(width, tn)
            again = row_tiles if col_tiles > 1 else 1
            cost = (col_tiles * tn, tn == _LANES < width,
                    contract * width * again, -tn, -ts)
            if best is None or cost < best[0]:
                best = cost, tiles
    return best and best[1]


@functools.lru_cache(maxsize=None)
def _grad_tiles(slots, k, m, itemsize):
    """The tiles ``(ts, tk, tm)`` — of a block's slots (the contraction),
    of the gradient's rows and columns — of ``(slots, k)^T x (slots, m)``:
    what fits
    ``_VMEM_CLAMP`` with the least padded work, then the fewest grid steps
    (each adds its product to the accumulator: a pass over (tk, tm) float32
    that the MXU waits for), then the least HBM traffic.  None where
    nothing fits."""
    best = None
    for ts in _tiles(slots):
        for tk in _tiles(k, ragged=True):
            for tm in _tiles(m, ragged=True):
                tiles = ts, tk, tm
                if _grad_vmem(tiles, itemsize) > _VMEM_CLAMP:
                    continue
                k_tiles, m_tiles = _cdiv(k, tk), _cdiv(m, tm)
                cost = (k_tiles * tk * m_tiles * tm,
                        k_tiles * m_tiles * (slots // ts),
                        k * m_tiles + m * k_tiles)
                if best is None or cost < best[0]:
                    best = cost, tiles
    return best and best[1]


def moe_product_dispatch(width, k, m, dtype="bfloat16", on_tpu=None,
                         shards=1):
    """``"kernel"`` or ``"einsum"`` for blocks of ``width`` slots against
    (k, m) weights: the kernels take a call on a TPU, in bfloat16,
    where the slots are whole lane tiles, k and m whole sublane tiles, and
    all three products — forward, ``d_rows``, ``d_weights`` — have tiles
    whose working set fits the kernels' VMEM budget
    (``pallas_attention._VMEM_CLAMP``); not inside a program whose batch
    GSPMD shards over ``shards`` > 1 devices (a Mosaic kernel is not
    partitioned, and the blocks are the whole batch's).  Everything else is
    ``einsum_block_products``."""
    if on_tpu is None:
        on_tpu = _context.on_tpu()
    dtype = jnp.dtype(dtype)
    rows, cols = (m, k) if _stored_transposed(k, m) else (k, m)
    fits = on_tpu and shards == 1 and dtype == jnp.bfloat16 \
        and width % _LANES == 0 and k % 16 == 0 and m % 16 == 0 \
        and _product_tiles(width, k, m, dtype.itemsize) is not None \
        and _product_tiles(width, m, k, dtype.itemsize) is not None \
        and _grad_tiles(width, rows, cols, dtype.itemsize) is not None
    return "kernel" if fits else "einsum"


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _precision(x):
    return lax.Precision.HIGHEST if x.dtype == _F32 else None


def _product_kernel(owner_ref, x_ref, w_ref, o_ref, *, dims, precision):
    del owner_ref                       # read by the weight's index map
    o_ref[...] = lax.dot_general(
        x_ref[...], w_ref[...], (dims, ((), ())), precision=precision,
        preferred_element_type=_F32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("transposed", "backward",
                                             "tiles", "interpret"))
def pallas_block_product(x, weights, owner, transposed=False, backward=False,
                         tiles=None, interpret=False):
    """``x[b] @ weights[owner[b]]`` — x (B, S, K), weights (G, K, M) — or,
    ``transposed``, ``x[b] @ weights[owner[b]].T`` with x (B, S, M): the
    result (B, S, M) or (B, S, K) in x's dtype.  ``backward`` says whose
    name the kernel carries in a trace (``moe_blocks_dx``: x is a
    cotangent).  One jitted function a shape: a step's equal products share
    one lowered kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    blocks, slots, contract = x.shape
    width = weights.shape[1 if transposed else 2]
    ts, tn = tiles or _product_tiles(slots, contract, width,
                                     x.dtype.itemsize)
    if transposed:
        w_spec = pl.BlockSpec((None, tn, contract),
                              lambda b, s, n, owner: (owner[b], n, 0))
    else:
        w_spec = pl.BlockSpec((None, contract, tn),
                              lambda b, s, n, owner: (owner[b], 0, n))
    common = dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(blocks, slots // ts, _cdiv(width, tn)),
            in_specs=[pl.BlockSpec((None, ts, contract),
                                   lambda b, s, n, owner: (b, s, 0)),
                      w_spec],
            out_specs=pl.BlockSpec((None, ts, tn),
                                   lambda b, s, n, owner: (b, s, n))),
        out_shape=jax.ShapeDtypeStruct((blocks, slots, width), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)
    kernel = functools.partial(
        _product_kernel, precision=_precision(x),
        dims=((1,), (1,)) if transposed else ((1,), (0,)))
    if backward:
        return pl.pallas_call(kernel, name="moe_blocks_dx", **common)(
            owner, x, weights)
    return pl.pallas_call(kernel, name="moe_blocks_fwd", **common)(
        owner, x, weights)


def _weight_grad_kernel(owner_ref, x_ref, dy_ref, start_ref, o_ref, acc_ref,
                        *, per_block, precision):
    from jax.experimental import pallas as pl
    del start_ref                       # the output's own buffer, zeros
    j = pl.program_id(2)
    b = j // per_block
    last_block = pl.num_programs(2) // per_block - 1
    mine = owner_ref[b]
    opens = (j % per_block == 0) & (
        (b == 0) | (owner_ref[jnp.maximum(b - 1, 0)] != mine))
    closes = (j % per_block == per_block - 1) & (
        (b == last_block) | (owner_ref[jnp.minimum(b + 1, last_block)]
                             != mine))
    part = lax.dot_general(x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
                           precision=precision, preferred_element_type=_F32)

    @pl.when(opens)
    def _():
        acc_ref[...] = part

    @pl.when(jnp.logical_not(opens))
    def _():
        acc_ref[...] += part

    @pl.when(closes)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("held", "dtype", "tiles",
                                             "interpret"))
def pallas_block_weight_grad(x, dy, owner, held, dtype, tiles=None,
                             interpret=False):
    """``sum over b with owner[b] = g of x[b].T @ dy[b]`` for each of the
    ``held`` experts g: (held, K, M) in ``dtype``, summed in float32 and
    rounded once; exactly zero for an expert that owns no block.  ``owner``
    (B,) is non-decreasing."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    blocks, slots, k = x.shape
    m = dy.shape[2]
    ts, tk, tm = tiles or _grad_tiles(slots, k, m, x.dtype.itemsize)
    per_block = slots // ts
    return pl.pallas_call(
        functools.partial(_weight_grad_kernel, per_block=per_block,
                          precision=_precision(x)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(_cdiv(k, tk), _cdiv(m, tm), blocks * per_block),
            in_specs=[
                pl.BlockSpec((ts, tk), lambda kt, mt, j, owner: (j, kt)),
                pl.BlockSpec((ts, tm), lambda kt, mt, j, owner: (j, mt)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (None, tk, tm), lambda kt, mt, j, owner: (
                    owner[j // per_block], kt, mt)),
            scratch_shapes=[pltpu.VMEM((tk, tm), _F32)]),
        out_shape=jax.ShapeDtypeStruct((held, k, m), dtype),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="moe_blocks_dw",
    )(owner, x.reshape(-1, k), dy.reshape(-1, m),
      jnp.zeros((held, k, m), dtype))


# ---------------------------------------------------------------------------
# the product with its custom VJP
# ---------------------------------------------------------------------------

def _stored_transposed(k, m):
    """Whether the chip keeps a (G, k, m) weight with k minor: its layout
    for an array whose last dimension is no whole number of lane tiles and
    whose second-last is.  The kernels then read the (G, m, k) view, which
    is the array as it lies, and no copy of it."""
    return m % _LANES != 0 and k % _LANES == 0


def block_products(blocks, weights, owner, interpret=False):
    """``blocks[b] @ weights[owner[b]]`` through the kernels: blocks
    (B, S, K), weights (G, K, M), owner (B,) int32 non-decreasing.
    Differentiable in the first two; the residuals are the operands."""
    if _stored_transposed(*weights.shape[1:]):
        return _products(blocks, jnp.swapaxes(weights, 1, 2), owner, True,
                         interpret)
    return _products(blocks, weights, owner, False, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _products(x, w, owner, transposed, interpret):
    return pallas_block_product(x, w, owner, transposed=transposed,
                                interpret=interpret)


def _products_fwd(x, w, owner, transposed, interpret):
    return _products(x, w, owner, transposed, interpret), (x, w, owner)


def _products_bwd(transposed, interpret, res, dy):
    x, w, owner = res
    rows, cols = (dy, x) if transposed else (x, dy)
    return (pallas_block_product(dy, w, owner, transposed=not transposed,
                                 backward=True, interpret=interpret),
            pallas_block_weight_grad(rows, cols, owner, w.shape[0], w.dtype,
                                     interpret=interpret),
            _int_zero_cotangent(owner))


_products.defvjp(_products_fwd, _products_bwd)
