"""The sparse-expert core: dropless top-k routing, the ROUTES (k a token)
grouped by expert, one grouped matrix product per weight over the experts
HELD here, the k parts of a token summed with their gates.

Both callers go through it: ``gluon.contrib.nn.SparseExperts`` (a block on
one chip, told which slice of the experts it holds) and
``parallel.moe.moe_ffn_apply`` (the same grouping round an ``ep``
all-to-all).  Nothing has a capacity: a route to a held expert is always
computed, however uneven the routing.  Top-1 is the case of one route a
token, through the same grouping, gathers and products.

The grouped product on sorted rows is ``lax.ragged_dot``: the v5e compiler
lowers it to its own Mosaic kernel (``ragged-dot-none`` in the step
program: tiles of rows, each against its group's weight, work in
proportion to the rows that have a group), forward and both gradients.
With k > 1 routes a token and a known expert count the held experts run on
BLOCKS of slots instead (``_on_blocks``: constant work, whatever the
routing), each block against its owner's weight: on a TPU a Pallas product
that reads ``weights[owner[b]]`` where it lies and sums an owner's blocks
into its row of the weights' gradient (``ops/pallas_moe.py``: no gathered
copy of the weights, no ``scatter-add``), elsewhere the owners' weights
gathered and one dense batched product.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import context as _context
from .nn import activation as _activation
from .registry import register

__all__ = ["top1_route", "topk_route", "group_by_expert", "spread_rows",
           "collect_rows", "dispatch", "combine", "flat_routes", "plan_blocks",
           "blocks_fit", "grouped_matmul", "sparse_ffn", "gated_experts",
           "mlp_experts"]


def top1_route(probs, scores=None):
    """Top-1 of ``scores`` ((N, E); ``probs`` itself where none are given),
    gated by ``probs``: returns (expert (N,) int32, gate (N,)).  The
    scores take no gradient (an argmax has none)."""
    pick = probs if scores is None else scores
    expert = jnp.argmax(pick, axis=-1).astype(jnp.int32)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    return expert, gate


def topk_route(probs, k, scores=None, normalize=False):
    """The ``k`` largest of ``scores`` ((N, E); ``probs`` itself where none
    are given; ties go to the lower index), gated by ``probs``: returns
    (expert (N, k) int32, gate (N, k)).  ``normalize`` divides a token's
    gates by their sum over ALL k chosen (+ 1e-20), held here or not.  The
    scores take no gradient (a choice has none)."""
    pick = probs if scores is None else scores
    expert = lax.top_k(pick, k)[1].astype(jnp.int32)
    gate = jnp.take_along_axis(probs, expert, axis=1)
    if normalize:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    return expert, gate


def group_by_expert(expert, first, held):
    """Group N routes by the ``held`` experts ``first..first+held-1``.

    Returns ``order`` (N,): route indices, those of the first held expert
    first, routes to experts not held last; ``place`` (N,): its inverse
    (where route i went); ``sizes`` (held,): routes per held expert, so the
    first ``sizes.sum()`` rows of ``x[order]`` are the held experts'."""
    local = expert - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    place = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    return order, place, sizes


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_rows(x, index, back, fold):
    """``x[index]`` with ``back`` the way home: a gather forward and a
    gather backward (autodiff's scatter-add would serialise on the chip).
    The cotangent of ``x`` is the output's at ``back``, summed over
    ``fold`` consecutive equal parts (the k routes of a token)."""
    return jnp.take(x, index, axis=0)


def _gather_fwd(x, index, back, fold):
    return jnp.take(x, index, axis=0), (index, back)


def _gather_bwd(fold, res, g):
    index, back = res
    grad = jnp.take(g, back, axis=0)
    if fold > 1:
        grad = jnp.sum(grad.reshape((fold, -1) + grad.shape[1:]), axis=0,
                       dtype=jnp.float32).astype(g.dtype)
    return grad, _no_grad(index), _no_grad(back)


_gather_rows.defvjp(_gather_fwd, _gather_bwd)


def _no_grad(index):
    """The cotangent of an array of integers."""
    import numpy as onp
    return onp.zeros(index.shape, jax.dtypes.float0)


def spread_rows(x, order, place):
    """``x[order]``: the routes sorted by expert.  ``place`` is ``order``'s
    inverse.  Where ``order`` permutes k routes a row of ``x`` (k N entries
    over N rows, route r of token r % N), the rows come out once a route
    and the backward sums a token's k."""
    n = x.shape[0]
    fold = order.shape[0] // n
    return _gather_rows(x, order % n if fold > 1 else order, place, fold)


def collect_rows(out, order, place):
    """``spread_rows`` undone: the sorted rows back in route order,
    ``out[place]``."""
    return _gather_rows(out, place, order, 1)


def grouped_matmul(rows, weights, groups):
    """``rows[i] @ weights[g(i)]`` for weights (G, K, M), one of two ways.

    Rows (N, K) sorted by group, ``groups`` the ``sizes`` (G,) of rows per
    group: one ``lax.ragged_dot``.  Rows past ``sizes.sum()`` have no group
    and come out zero.  The chip's kernel leaves them UNWRITTEN — whatever
    the buffer held, NaN included — in the product and in the gradient it
    hands back for ``rows`` alike, so they are masked on the way in (which
    masks that gradient) and on the way out.

    Rows laid out in blocks of slots, (B, S, K), ``groups`` the ``owner``
    (B,) of each block, non-decreasing: every slot of block b against
    ``weights[owner[b]]``, the caller reads the ones it filled.  On a TPU,
    at the shapes ``pallas_moe.moe_product_dispatch`` takes, a Pallas
    product that reads the owner's weight where it lies and sums an owner's
    blocks into its row of the weights' gradient (``ops/pallas_moe.py``);
    elsewhere the owners' weights gathered and one dense batched product
    (``einsum_block_products``).  The same numbers, the same work, whatever
    the routing."""
    if rows.ndim == 3:      # blocks of slots: static in a compiled step
        return _block_products(rows, weights, groups)
    grouped = lax.broadcasted_iota(jnp.int32, (rows.shape[0], 1), 0) \
        < jnp.sum(groups)
    out = lax.ragged_dot(jnp.where(grouped, rows, jnp.zeros((), rows.dtype)),
                         weights, groups, preferred_element_type=rows.dtype)
    return jnp.where(grouped, out, jnp.zeros((), out.dtype))


def _block_products(blocks, weights, owner):
    """``grouped_matmul`` on blocks, by the path
    ``pallas_moe.moe_product_dispatch`` gives the shape."""
    from .. import telemetry
    from ..parallel.mesh import batch_shards
    from . import pallas_moe

    (count, width, k), (held, _, m) = blocks.shape, weights.shape
    path = pallas_moe.moe_product_dispatch(
        width, k, m, blocks.dtype,
        on_tpu=_context.on_tpu(blocks, weights), shards=batch_shards())
    # trace time: once a traced shape, as ssm.scan.* and attention.kernel.*
    telemetry.inc("moe.product.%s" % path)
    telemetry.event("moe.product", path, path=path, blocks=int(count),
                    width=int(width), k=int(k), m=int(m), held=int(held))
    if path == "kernel":
        return pallas_moe.block_products(blocks, weights, owner)
    return pallas_moe.einsum_block_products(blocks, weights, owner)


_ROWS_OVER_EVEN = 4        # slots in all, over an even router's held routes


def _on_rows(network, x, gates, weights, local, grouped):
    """The experts on ALL the routes sorted by expert (one ragged product
    a weight), back in route order and gated: (k N, D)."""
    order, place, sizes = grouped
    out = network(spread_rows(x, order, place), sizes, *weights)
    return collect_rows(out, order, place) * gates.astype(out.dtype)[:, None]


def _sum_routes(y, n):
    """A token's k gated parts, (k N, D) with route r of token r % N,
    summed in float32: (N, D)."""
    return jnp.sum(y.reshape(-1, n, y.shape[-1]), axis=0,
                   dtype=jnp.float32).astype(y.dtype)


def _rows_at_slots(x, slot_route):
    return jnp.take(x, jnp.maximum(slot_route, 0) % x.shape[0], axis=0,
                    mode="clip")


def _sum_at_tokens(out, weight, route_slot):
    """k gathers of (N, D) and one sum that reads them (on the chip one
    gather of (k, N, D) is converted to float32 on its own, 528 MB a
    Nemotron layer, before the sum reads it)."""
    return sum(w[:, None] * jnp.take(out, slot, axis=0,
                                     mode="clip").astype(jnp.float32)
               for w, slot in zip(weight.astype(jnp.float32),
                                  jnp.maximum(route_slot, 0))
               ).astype(out.dtype)


@jax.custom_vjp
def dispatch(x, slot_route, route_slot):
    """Token order to slot order: row s of the result is the row of ``x``
    (N, D) of the token whose route fills slot s — ``slot_route`` (S,) is
    that route's number, route r being a route of token r % N, or -1.  A
    slot that no route fills holds some token's row, is computed and is
    never read.  ``route_slot`` (k, N), the slot of each route or -1, is
    the way home: the cotangent of ``x`` is ``combine`` of the slots'
    cotangent with the weight one on every route that has a slot."""
    return _rows_at_slots(x, slot_route)


def _dispatch_fwd(x, slot_route, route_slot):
    return _rows_at_slots(x, slot_route), (slot_route, route_slot)


def _dispatch_bwd(res, g):
    slot_route, route_slot = res
    return (_sum_at_tokens(g, route_slot >= 0, route_slot),
            _no_grad(slot_route), _no_grad(route_slot))


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(out, weight, slot_route, route_slot):
    """Slot order back to token order, weighted and summed:
    ``y[t] = sum_j weight[j, t] * out[route_slot[j, t]]`` over the k
    routes of token t, in float32: (N, D).  ``weight`` (k, N) is zero
    where the route has no slot (``route_slot < 0``): the mask is k N
    scalars, and all that is route-sized are k gathers of (N, D) rows that
    one sum reads.
    ``dispatch``'s transpose: the cotangent of ``out`` is ``dispatch`` of
    the result's, each slot's row times the weight of the route that fills
    it, and the weights' cotangent the slots' row-wise dot of ``out`` with
    it, k N scalars read back by ``route_slot`` — slot-sized, both."""
    return _sum_at_tokens(out, weight, route_slot)


def _combine_fwd(out, weight, slot_route, route_slot):
    return _sum_at_tokens(out, weight, route_slot), (out, weight, slot_route,
                                                     route_slot)


def _combine_bwd(res, g):
    out, weight, slot_route, route_slot = res
    g_slots = _rows_at_slots(g, slot_route)
    weight_slot = jnp.where(slot_route >= 0, jnp.take(
        weight.reshape(-1), jnp.maximum(slot_route, 0), mode="clip"), 0)
    dots = jnp.sum(out.astype(jnp.float32) * g_slots.astype(jnp.float32),
                   axis=-1)
    d_weight = jnp.where(route_slot >= 0, jnp.take(
        dots, jnp.maximum(route_slot, 0), mode="clip"), 0)
    return (g_slots * weight_slot.astype(g.dtype)[:, None],
            d_weight.astype(weight.dtype), _no_grad(slot_route),
            _no_grad(route_slot))


combine.defvjp(_combine_fwd, _combine_bwd)


def _slots(blocks, local, grouped):
    """Who takes which slot of ``blocks = (count, width)``: an expert takes
    as many blocks as its routes need, in the order of the held experts.
    ``local`` (k N,) is each route's expert counted from the first held.
    Returns ``owner`` (count,) each block's expert, ``slot_route``
    (count x width,) the route that fills each slot or -1, and
    ``route_slot`` (k N,) its inverse: the slot of each route, -1 for a
    route to an expert not held.  Right where the experts' needs add up to
    no more than ``count`` blocks."""
    order, place, sizes = grouped
    count, width = blocks
    held, total = sizes.shape[0], local.shape[0]
    start = jnp.cumsum(sizes) - sizes             # an expert's first route
    need = (sizes + width - 1) // width           # blocks an expert
    first = jnp.cumsum(need) - need               # an expert's first block
    block = jnp.arange(count, dtype=jnp.int32)
    owner = jnp.minimum(jnp.searchsorted(
        jnp.cumsum(need), block, side="right").astype(jnp.int32), held - 1)
    offset = (block - jnp.take(first, owner))[:, None] * width \
        + jnp.arange(width, dtype=jnp.int32)[None, :]
    slot_route = jnp.where(
        offset < jnp.take(sizes, owner)[:, None], jnp.take(order, jnp.clip(
            jnp.take(start, owner)[:, None] + offset, 0, total - 1)), -1)
    mine = (local >= 0) & (local < held)
    local = jnp.clip(local, 0, held - 1)
    within = place - jnp.take(start, local)
    route_slot = jnp.where(
        mine, (jnp.take(first, local) + within // width) * width
        + within % width, -1)
    return owner, slot_route.reshape(-1), route_slot


def _on_blocks(network, blocks, x, gates, weights, local, grouped):
    """The same on ``blocks = (count, width)`` blocks of slots,
    (count, width, D): a block computes against its owner's weights —
    ``network`` gets the blocks, their ``owner`` and the weights as they
    are held — and the experts are one product a weight over every slot,
    whose time does not follow the routing.  The rows move through
    ``dispatch`` and ``combine``, the gates and the sum over a token's
    routes inside the latter: (N, D), and nothing here holds a (k N, D)
    array."""
    n = x.shape[0]
    owner, slot_route, route_slot = _slots(blocks, local, grouped)
    route_slot = route_slot.reshape(-1, n)
    rows = dispatch(x, slot_route, route_slot)
    out = network(rows.reshape(blocks + rows.shape[1:]), owner, *weights)
    return combine(out.reshape(rows.shape[0], -1),
                   jnp.where(route_slot >= 0, gates.reshape(-1, n), 0),
                   slot_route, route_slot)


def flat_routes(k, normalize):
    """Whether ``sparse_experts`` hands ``sparse_ffn`` one flat route a
    token, (N,) — sorted rows, never blocks — and not (N, k) routes."""
    return k == 1 and not normalize


def plan_blocks(total, held, num_experts):
    """The blocks of slots ``total`` routes over ``held`` of
    ``num_experts`` experts run on: ``(count, width)``, two blocks a held
    expert, each half of ``_ROWS_OVER_EVEN`` times an even router's routes
    to it (in 128s); None where that is no fewer rows than all the
    routes."""
    width = -(-_ROWS_OVER_EVEN * total // num_experts // 256) * 128
    return (2 * held, width) if 2 * held * width < total else None


def blocks_fit(blocks, sizes):
    """Whether experts with ``sizes`` (held,) routes each fit the
    ``blocks``, a block holding one expert's routes: on the device for the
    step (``sparse_ffn``), on the host for who counts which side ran
    (``publish_routing_counts``)."""
    count, width = blocks
    return ((sizes + width - 1) // width).sum() <= count


def _either(network, blocks, sizes, run):
    """``run`` on ``_on_blocks`` where the held experts' routes fit the
    ``blocks`` — tested on the device — and on ``_on_rows`` with the k
    parts summed otherwise (always, with no blocks): (N, D) either way."""
    def full(x, *rest):
        return _sum_routes(_on_rows(network, x, *rest), x.shape[0])

    if blocks is None:
        return run(full)
    return lax.cond(blocks_fit(blocks, sizes),
                    lambda: run(functools.partial(_on_blocks, network,
                                                  blocks)),
                    lambda: run(full))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _budgeted(network, blocks, x, gates, weights, local, grouped):
    """The experts on blocks or on sorted rows (``_either``): the same
    result either way.  The backward recomputes the side that ran from
    these inputs; no residual crosses the ``lax.cond`` (autodiff through
    a ``cond`` would hand back both sides' residuals, weights included,
    the side not taken as zeros)."""
    return _either(network, blocks, grouped[2], lambda side: side(
        x, gates, weights, local, grouped))


def _budgeted_fwd(network, blocks, *args):
    return _budgeted(network, blocks, *args), args


def _budgeted_bwd(network, blocks, args, g):
    x, gates, weights, local, grouped = args

    def back(side):
        return jax.vjp(lambda x, gates, weights: side(
            x, gates, weights, local, grouped), x, gates, weights)[1](g)

    d_x, d_gates, d_weights = _either(network, blocks, grouped[2], back)
    # the ``cond`` hands the weights' gradients out in the weights' dtype:
    # left to itself the compiler moves the optimizer's float32 cast into
    # both branches, (held, K, M) float32 a weight held to the step's end
    d_weights = lax.optimization_barrier(d_weights)
    return d_x, d_gates, d_weights, _no_grad(local), tuple(
        _no_grad(index) for index in grouped)


_budgeted.defvjp(_budgeted_fwd, _budgeted_bwd)


def sparse_ffn(x, expert, gate, ffn, first, held, num_experts=None):
    """The held experts' part of an expert layer.

    ``x`` (N, D) tokens, ``expert`` / ``gate`` (N,) from ``top1_route`` or
    (N, k) from ``topk_route``, ``ffn`` the experts' network with its
    weights (``gated_experts`` / ``mlp_experts``: ``ffn.network(rows,
    groups, *ffn.weights)`` on rows sorted by expert with their ``sizes``
    or on blocks of slots with their ``owner``, built from
    ``grouped_matmul``).  The k N routes go through ONE grouping: a
    token's row is gathered once a route, and its k results are summed
    with their gates.  Returns ``(y, sizes)``: ``y`` (N, D) is the sum of
    ``gate * Expert_e(x)`` over the token's routes to held experts, zero
    where none is held — the partial result an expert-parallel layer sums
    over its shares — and ``sizes`` (held,) the rows each held expert
    computed.

    With more than one route a token, what autodiff would keep for the
    backward — the gathered rows, the experts' activations and outputs,
    each k N rows — is k times the layer's own activations (0.7 GB a layer
    at 8,192 tokens x 6), most of it rows of experts held elsewhere; the
    routes' part is recomputed in the backward from ``x`` instead.  At one
    route a token it is kept.

    Dropless needs room for all k N routes, but an even router sends the
    held experts k N held / num_experts of them: where the layer says how
    many experts there are, the experts run on FOUR times that many slots,
    in 2 held blocks that an expert takes by need (a lumpy router gives
    one expert several times its share, the held experts together much
    less), as one product a weight over all the slots, a block against its
    owner's weight — constant work, whatever the routing — while the
    experts' needs fit the blocks; a step that needs more runs the ragged
    products on all k N sorted rows (one ``lax.cond`` on the device, exact
    on both sides)."""
    # one route a token (N,) or k of them (N, k): static in a compiled step
    # graftlint: disable-next=retrace-shape-branch -- rank dispatch
    if expert.ndim == 1:
        grouped = group_by_expert(expert, first, held)
        return _on_rows(ffn.network, x, gate, ffn.weights, None,
                        grouped), grouped[2]
    routes = expert.T.reshape(-1)
    grouped = group_by_expert(routes, first, held)
    blocks = None if num_experts is None else plan_blocks(
        routes.shape[0], held, num_experts)
    return _budgeted(ffn.network, blocks, x, gate.T.reshape(-1), ffn.weights,
                     routes - first, grouped), grouped[2]


class _Experts(NamedTuple):
    network: Callable          # (rows, groups, *weights) -> rows
    weights: tuple


def gated_experts(w_gate, w_up, w_down, activation=jax.nn.silu):
    """Gated experts: ``(activation(x Wg) * x Wu) Wd`` with Wg, Wu
    (held, D, F) and Wd (held, F, D)."""
    def network(rows, groups, w_gate, w_up, w_down):
        gate = grouped_matmul(rows, w_gate, groups)
        up = grouped_matmul(rows, w_up, groups)
        return grouped_matmul(activation(gate) * up, w_down, groups)
    return _Experts(network, (w_gate, w_up, w_down))


def mlp_experts(w_up, w_down, activation):
    """Experts without a gate matrix: ``activation(x Wu) Wd`` with Wu
    (held, D, F) and Wd (held, F, D)."""
    def network(rows, groups, w_up, w_down):
        return grouped_matmul(activation(grouped_matmul(rows, w_up, groups)),
                              w_down, groups)
    return _Experts(network, (w_up, w_down))


@register("_contrib_sparse_experts", num_outputs=4,
          aliases=("sparse_experts",))
def sparse_experts(data, probs, w_gate, w_up, w_down, bias, first: int = 0,
                   k: int = 1, normalize: bool = False, scale: float = 1.0,
                   activation: str = "silu"):
    """Dropless top-``k`` experts, the slice ``first..first+held-1`` of
    them held here (held = ``w_up.shape[0]``): ``data`` (..., S, D),
    ``probs`` (..., S, E) the router's scores of ALL E experts (a softmax,
    or sigmoids), ``bias`` (E,) its balancing bias.  A token goes to the
    ``k`` largest of ``probs + bias`` and is gated by ``probs`` of each —
    the bias moves the choice, never the gate — divided by their sum over
    the k chosen where ``normalize``, times ``scale``.  With ``w_gate``
    the experts are gated, ``Wd(act(Wg x) * Wu x)``; with ``w_gate=None``
    they are ``Wd act(Wu x)``; ``activation`` is any ``act_type`` of the
    ``Activation`` operator (``silu``, ``relu2``, ...).  Returns the held experts' part of the layer's output, the
    ROUTES to each of the E experts (fp32 counts: k a token), the rows
    each held expert computed, and each token's experts, (..., S) int32
    at ``k = 1`` and (..., S, k) otherwise."""
    lead, d = data.shape[:-1], data.shape[-1]
    n_experts = probs.shape[-1]
    probs = probs.reshape(-1, n_experts)
    pick = lax.stop_gradient(probs.astype(jnp.float32)) \
        + bias.astype(jnp.float32)
    if flat_routes(k, normalize):
        expert, gate = top1_route(probs, pick)
    else:
        expert, gate = topk_route(probs, k, pick, normalize)
    if scale != 1.0:
        gate = gate * scale
    act = functools.partial(_activation, act_type=activation)
    ffn = mlp_experts(w_up, w_down, act) if w_gate is None else \
        gated_experts(w_gate, w_up, w_down, act)
    y, sizes = sparse_ffn(data.reshape(-1, d), expert, gate, ffn, first,
                          w_up.shape[0], n_experts)
    load = jnp.sum(expert.reshape(-1)[:, None]
                   == jnp.arange(n_experts)[None, :],
                   axis=0, dtype=jnp.float32)
    return (y.reshape(lead + (d,)), lax.stop_gradient(load),
            sizes.astype(jnp.float32), expert.reshape(lead + expert.shape[1:]))
