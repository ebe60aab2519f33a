"""The sparse-expert core: dropless top-1 routing, the tokens grouped by
expert, one grouped matrix product per weight over the experts HELD here.

Both callers go through it: ``gluon.contrib.nn.SparseExperts`` (a block on
one chip, told which slice of the experts it holds) and
``parallel.moe.moe_ffn_apply`` (the same grouping round an ``ep``
all-to-all).  Nothing has a capacity: a token routed to a held expert is
always computed, however uneven the routing.

The grouped product is ``lax.ragged_dot``: the v5e compiler lowers it to
its own Mosaic kernel (``ragged-dot-none`` in the step program: tiles of
rows, each against its group's weight, work in proportion to the rows that
have a group), forward and both gradients.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register

__all__ = ["top1_route", "group_by_expert", "permute_rows", "grouped_matmul",
           "sparse_ffn"]


def top1_route(probs, scores=None):
    """Top-1 of ``scores`` ((N, E); ``probs`` itself where none are given),
    gated by ``probs``: returns (expert (N,) int32, gate (N,)).  The
    scores take no gradient (an argmax has none)."""
    pick = probs if scores is None else scores
    expert = jnp.argmax(pick, axis=-1).astype(jnp.int32)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    return expert, gate


def group_by_expert(expert, first, held):
    """Group N routed tokens by the ``held`` experts ``first..first+held-1``.

    Returns ``order`` (N,): token indices, those of the first held expert
    first, tokens of experts not held last; ``place`` (N,): its inverse
    (where token i went); ``sizes`` (held,): tokens per held expert, so the
    first ``sizes.sum()`` rows of ``x[order]`` are the held experts'."""
    local = expert - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    place = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    return order, place, sizes


@jax.custom_vjp
def permute_rows(x, order, place):
    """``x[order]`` where ``place`` is the inverse permutation: a gather
    forward and a gather backward (autodiff's scatter-add would serialise
    on the chip)."""
    return jnp.take(x, order, axis=0)


def _permute_fwd(x, order, place):
    return jnp.take(x, order, axis=0), (order, place)


def _permute_bwd(res, g):
    import numpy as onp
    order, place = res
    zero = onp.zeros(order.shape, jax.dtypes.float0)
    return jnp.take(g, place, axis=0), zero, zero


permute_rows.defvjp(_permute_fwd, _permute_bwd)


def grouped_matmul(rows, weights, sizes):
    """``rows[i] @ weights[g(i)]``: rows (N, K) sorted by group, weights
    (G, K, M), ``sizes`` (G,) rows per group.  Rows past ``sizes.sum()``
    have no group and come out zero.  The chip's kernel leaves them
    UNWRITTEN — whatever the buffer held, NaN included — in the product
    and in the gradient it hands back for ``rows`` alike, so they are
    masked on the way in (which masks that gradient) and on the way
    out."""
    grouped = lax.broadcasted_iota(jnp.int32, (rows.shape[0], 1), 0) \
        < jnp.sum(sizes)
    out = lax.ragged_dot(jnp.where(grouped, rows, jnp.zeros((), rows.dtype)),
                         weights, sizes, preferred_element_type=rows.dtype)
    return jnp.where(grouped, out, jnp.zeros((), out.dtype))


def sparse_ffn(x, expert, gate, ffn, first, held):
    """The held experts' part of a top-1 expert layer.

    ``x`` (N, D) tokens, ``expert`` / ``gate`` from ``top1_route``,
    ``ffn(rows, sizes)`` the experts' network on rows sorted by expert
    (built from ``grouped_matmul``).  Returns ``(y, sizes)``: ``y`` (N, D)
    is ``gate * Expert_e(x)`` for tokens whose expert is held and zero for
    the others — the partial result an expert-parallel layer sums over its
    shares — and ``sizes`` (held,) the rows each held expert computed."""
    order, place, sizes = group_by_expert(expert, first, held)
    out = ffn(permute_rows(x, order, place), sizes)
    y = permute_rows(out, place, order)
    return y * gate.astype(y.dtype)[:, None], sizes


def gated_experts(w_gate, w_up, w_down):
    """``ffn(rows, sizes)`` of gated SiLU experts: ``(silu(x Wg) * x Wu)
    Wd`` with Wg, Wu (held, D, F) and Wd (held, F, D)."""
    def ffn(rows, sizes):
        gate = grouped_matmul(rows, w_gate, sizes)
        up = grouped_matmul(rows, w_up, sizes)
        return grouped_matmul(jax.nn.silu(gate) * up, w_down, sizes)
    return ffn


@register("_contrib_sparse_experts", num_outputs=4,
          aliases=("sparse_experts",))
def sparse_experts(data, probs, w_gate, w_up, w_down, bias, first: int = 0):
    """Top-1 gated-SiLU experts, the slice ``first..first+held-1`` of them
    held here (held = ``w_gate.shape[0]``): ``data`` (..., S, D), ``probs``
    (..., S, E) the router's softmax over ALL E experts, ``bias`` (E,) its
    balancing bias.  A token goes to ``argmax(probs + bias)`` and is gated
    by ``probs`` of that expert.  Returns the held experts' part of the
    layer's output, the tokens routed to each of the E experts (fp32
    counts), the rows each held expert computed, and each token's expert
    (..., S) int32."""
    lead, d = data.shape[:-1], data.shape[-1]
    n_experts = probs.shape[-1]
    probs = probs.reshape(-1, n_experts)
    expert, gate = top1_route(
        probs, lax.stop_gradient(probs.astype(jnp.float32))
        + bias.astype(jnp.float32))
    y, sizes = sparse_ffn(data.reshape(-1, d), expert, gate,
                          gated_experts(w_gate, w_up, w_down), first,
                          w_gate.shape[0])
    load = jnp.sum(expert[:, None] == jnp.arange(n_experts)[None, :],
                   axis=0, dtype=jnp.float32)
    return (y.reshape(lead + (d,)), lax.stop_gradient(load),
            sizes.astype(jnp.float32), expert.reshape(lead))
