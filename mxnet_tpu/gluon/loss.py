"""Gluon losses.

Reference: ``python/mxnet/gluon/loss.py`` (882 LoC) — L1/L2, SigmoidBCE,
SoftmaxCE, KLDiv, CTC, Huber, Hinge/SquaredHinge, Logistic, Triplet,
PoissonNLL, Cosine.  Each loss is a HybridBlock whose math is ONE pure jnp
function dispatched through ``invoke_fn`` — a single tape node eagerly, and
fully fused into the train step under hybridize/jit (the reference's fused
``softmax_output`` op is subsumed by XLA fusing log_softmax+gather+mean).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import numeric_types
from ..ndarray.ndarray import invoke_fn
from .block import HybridBlock

__all__ = ["Loss", "L2Loss", "L1Loss", "SigmoidBinaryCrossEntropyLoss",
           "SigmoidBCELoss", "SoftmaxCrossEntropyLoss", "SoftmaxCELoss",
           "TiedSoftmaxCrossEntropyLoss", "BlockDiffusionLoss",
           "KLDivLoss", "CTCLoss", "HuberLoss", "HingeLoss",
           "SquaredHingeLoss", "LogisticLoss", "TripletLoss",
           "PoissonNLLLoss", "CosineEmbeddingLoss"]


def _w(loss, weight, sw):
    """(reference loss.py:37 _apply_weighting) global scale + per-sample
    weight."""
    if sw is not None:
        loss = loss * sw
    if weight is not None:
        assert isinstance(weight, numeric_types), "weight must be a number"
        loss = loss * weight
    return loss


def _mean_keep_batch(loss, batch_axis):
    axes = tuple(i for i in range(loss.ndim) if i != batch_axis)
    return jnp.mean(loss, axis=axes) if axes else loss


def _log_softmax(x, axis=-1):
    x_max = jax.lax.stop_gradient(jnp.max(x, axis=axis, keepdims=True))
    shifted = x - x_max
    return shifted - jnp.log(jnp.sum(jnp.exp(shifted), axis=axis, keepdims=True))


class Loss(HybridBlock):
    """Base loss (reference loss.py:59)."""

    def __init__(self, weight, batch_axis, **kwargs):
        super().__init__(**kwargs)
        self._weight = weight
        self._batch_axis = batch_axis

    def __repr__(self):
        s = "{name}(batch_axis={_batch_axis}, w={_weight})"
        return s.format(name=self.__class__.__name__, **self.__dict__)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def _dispatch(self, pure_fn, arrays, name):
        """Run the loss math as one op; None entries are compiled out."""
        present = [a is not None for a in arrays]
        ins = [a for a in arrays if a is not None]

        def fn(*vals):
            it = iter(vals)
            full = [next(it) if ok else None for ok in present]
            return pure_fn(*full)

        return invoke_fn(fn, ins, name=name)


class L2Loss(Loss):
    """0.5 * w * (pred - label)^2 (reference loss.py:126)."""

    def __init__(self, weight=1., batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        def fn(p, l, sw):
            loss = jnp.square(jnp.reshape(l, p.shape) - p)
            loss = _w(loss, self._weight / 2 if self._weight else None, sw)
            return _mean_keep_batch(loss, self._batch_axis)
        return self._dispatch(fn, [pred, label, sample_weight], "l2_loss")


class L1Loss(Loss):
    """w * |pred - label| (reference loss.py:166)."""

    def __init__(self, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        def fn(p, l, sw):
            loss = jnp.abs(jnp.reshape(l, p.shape) - p)
            loss = _w(loss, self._weight, sw)
            return _mean_keep_batch(loss, self._batch_axis)
        return self._dispatch(fn, [pred, label, sample_weight], "l1_loss")


class SigmoidBinaryCrossEntropyLoss(Loss):
    """BCE, optionally from logits, with pos_weight (reference loss.py:205)."""

    def __init__(self, from_sigmoid=False, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_sigmoid = from_sigmoid

    def hybrid_forward(self, F, pred, label, sample_weight=None, pos_weight=None):
        def fn(p, l, sw, pw):
            l = jnp.reshape(l, p.shape)
            if not self._from_sigmoid:
                if pw is None:
                    # stable: max(x,0) - x*z + log(1+exp(-|x|))
                    loss = jnp.maximum(p, 0) - p * l + jnp.log1p(jnp.exp(-jnp.abs(p)))
                else:
                    log_weight = 1 + (pw - 1) * l
                    loss = p - p * l + log_weight * (
                        jnp.log1p(jnp.exp(-jnp.abs(p))) + jnp.maximum(-p, 0))
            else:
                eps = 1e-12
                if pw is None:
                    loss = -(jnp.log(p + eps) * l + jnp.log(1. - p + eps) * (1. - l))
                else:
                    loss = -(jnp.log(p + eps) * l * pw
                             + jnp.log(1. - p + eps) * (1. - l))
            loss = _w(loss, self._weight, sw)
            return _mean_keep_batch(loss, self._batch_axis)
        return self._dispatch(fn, [pred, label, sample_weight, pos_weight],
                              "sigmoid_bce")


SigmoidBCELoss = SigmoidBinaryCrossEntropyLoss


class SoftmaxCrossEntropyLoss(Loss):
    """Softmax + CE in one fused op (reference loss.py:286; the
    ``softmax_output`` analogue, fused by XLA)."""

    def __init__(self, axis=-1, sparse_label=True, from_logits=False,
                 weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._axis = axis
        self._sparse_label = sparse_label
        self._from_logits = from_logits

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        def fn(p, l, sw):
            logp = p if self._from_logits else _log_softmax(p, self._axis)
            if self._sparse_label:
                lab = l.astype(jnp.int32)
                loss = -jnp.take_along_axis(
                    logp, jnp.expand_dims(lab, self._axis), axis=self._axis)
                loss = jnp.squeeze(loss, axis=self._axis)
            else:
                loss = -jnp.sum(logp * jnp.reshape(l, logp.shape), axis=self._axis)
            loss = _w(loss, self._weight, sw)
            return _mean_keep_batch(loss, self._batch_axis)
        return self._dispatch(fn, [pred, label, sample_weight], "softmax_ce")


SoftmaxCELoss = SoftmaxCrossEntropyLoss


class TiedSoftmaxCrossEntropyLoss(Loss):
    """Softmax cross-entropy of a head TIED to the embedding, without the
    logits: ``pred`` is the pair ``(hidden (B, S, D), weight (V, D))`` a
    language model returns in training, ``label`` (B, S) the target ids.
    The head's gradients are made in its forward pass, a block of tokens
    against all V rows at a time (``ops.nn.tied_softmax_cross_entropy``:
    three products a step, no logits formed twice), so a 131k-row head
    over 16k tokens never holds more logits than ``block_rows`` rows of
    the vocabulary against all tokens would.  A label outside 0..V-1
    (``ignore_label``, -1) marks a position that predicts nothing; the
    loss of a row is the mean over its other positions.  Every
    per-position weight — that mask, ``sample_weight``, the loss's
    ``weight``, one over the row's count — goes INTO the op as its
    ``scale`` and a row is only summed here, so the cotangent the op sees
    under a batch mean or a scaled loss is one number; weighting the
    per-position losses of the op OUTSIDE it stays exact and costs the
    backward a second pass over the logits."""

    def __init__(self, block_rows=8192, ignore_label=-1, weight=None,
                 batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._block_rows = block_rows
        self._ignore = ignore_label

    @staticmethod
    def _per_row(counted):
        """What a row's weighted sum is divided by: its counted
        positions."""
        return jnp.maximum(jnp.sum(counted, axis=-1, keepdims=True), 1.0)

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        from ..ops.nn import tied_softmax_cross_entropy

        hidden, table = pred

        def fn(h, w, l, sw):
            lab = l.astype(jnp.int32)
            counted = (lab != self._ignore).astype(jnp.float32)
            scale = _w(counted, self._weight, sw) / self._per_row(counted)
            return jnp.sum(tied_softmax_cross_entropy(
                h, w, lab, scale=scale, block_rows=self._block_rows),
                axis=-1)
        return self._dispatch(fn, [hidden, table, label, sample_weight],
                              "tied_softmax_ce")


class BlockDiffusionLoss(TiedSoftmaxCrossEntropyLoss):
    """The block-diffusion objective (BD3-LM, arXiv:2503.09573) on the
    noised half of a row, through the blocked cross-entropy: ``pred`` is
    ``(hidden (B, L, D), head weight (V, D))``, position i of the noised
    half predicting token i; ``label`` (B, L) the clean ids at the masked
    positions and ``ignore_label`` elsewhere; ``sample_weight`` (B, L) a
    position's ``1 / t_b``.  The loss of a row is ``(1 / L) sum_i w_i *
    -log p(x0_i | row)`` over its masked positions: one over ALL L
    positions, not over the masked ones — with the weights the sum is an
    unbiased bound on the row's likelihood whatever the noise drew.  The
    weights go into the operator's ``scale`` with the rest.

    A step builder that carries ONE label array
    (``parallel.DataParallelStep``) gives ``label`` as (B, 2, L) float32,
    the ids stacked on the weights, and no ``sample_weight``
    (``model_zoo.block_diffusion_row`` makes it)."""

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        # graftlint: disable-next=retrace-shape-branch -- rank dispatch: the
        # stacked label of a step builder that carries one array
        if sample_weight is None and len(label.shape) == 3:
            label, sample_weight = label[:, 0], label[:, 1]
        return super().hybrid_forward(F, pred, label, sample_weight)

    @staticmethod
    def _per_row(counted):
        return jnp.float32(counted.shape[-1])


class KLDivLoss(Loss):
    """KL divergence (reference loss.py:358)."""

    def __init__(self, from_logits=True, axis=-1, weight=None, batch_axis=0,
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_logits = from_logits
        self._axis = axis

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        def fn(p, l, sw):
            logp = p if self._from_logits else _log_softmax(p, self._axis)
            loss = l * (jnp.log(l + 1e-12) - logp)
            loss = _w(loss, self._weight, sw)
            return _mean_keep_batch(loss, self._batch_axis)
        return self._dispatch(fn, [pred, label, sample_weight], "kldiv")


class CTCLoss(Loss):
    """Connectionist temporal classification (reference loss.py:417, kernel
    ``src/operator/nn/ctc_loss.cc`` / warp-ctc).

    TPU-native: log-space forward algorithm over ``lax.scan`` —
    differentiable with jax.grad; blank = alphabet index 0 as in the
    reference.
    """

    def __init__(self, layout="NTC", label_layout="NT", weight=None, **kwargs):
        assert layout in ["NTC", "TNC"], "Only 'NTC' and 'TNC' layouts are supported"
        assert label_layout in ["NT", "TN"], "Only 'NT' and 'TN' label layouts are supported"
        self._layout = layout
        self._label_layout = label_layout
        batch_axis = label_layout.find("N")
        super().__init__(weight, batch_axis, **kwargs)

    def hybrid_forward(self, F, pred, label, pred_lengths=None,
                       label_lengths=None, sample_weight=None):
        def fn(p, lab, plen, llen, sw):
            if self._layout == "NTC":
                p = jnp.transpose(p, (1, 0, 2))  # -> TNC
            if self._label_layout == "TN":
                lab = jnp.transpose(lab)  # -> NT
            T, N, C = p.shape
            L = lab.shape[1]
            log_probs = _log_softmax(p, -1)
            labels = lab.astype(jnp.int32)
            plen_i = jnp.full((N,), T, jnp.int32) if plen is None \
                else plen.astype(jnp.int32)
            if llen is None:
                # 0/-1 padding marks end of each label sequence (reference)
                llen_i = jnp.sum((labels > 0).astype(jnp.int32), axis=1)
            else:
                llen_i = llen.astype(jnp.int32)
            labels = jnp.maximum(labels, 0)

            blank = 0
            S = 2 * L + 1
            ext = jnp.full((N, S), blank, jnp.int32)
            ext = ext.at[:, 1::2].set(labels)

            neg_inf = -1e30
            alpha0 = jnp.full((N, S), neg_inf)
            alpha0 = alpha0.at[:, 0].set(log_probs[0][:, blank])
            alpha0 = alpha0.at[:, 1].set(
                jnp.take_along_axis(log_probs[0], ext[:, 1:2], 1)[:, 0])

            same_as_prev2 = jnp.concatenate(
                [jnp.ones((N, 2), bool), ext[:, 2:] == ext[:, :-2]], axis=1)

            def scan_fn(alpha, inputs):
                t, lp_t = inputs
                shift1 = jnp.concatenate(
                    [jnp.full((N, 1), neg_inf), alpha[:, :-1]], axis=1)
                shift2 = jnp.concatenate(
                    [jnp.full((N, 2), neg_inf), alpha[:, :-2]], axis=1)
                shift2 = jnp.where(same_as_prev2, neg_inf, shift2)
                merged = jnp.logaddexp(jnp.logaddexp(alpha, shift1), shift2)
                emit = jnp.take_along_axis(lp_t, ext, axis=1)
                new_alpha = merged + emit
                active = (t < plen_i)[:, None]
                return jnp.where(active, new_alpha, alpha), None

            ts = jnp.arange(1, T)
            alpha_T, _ = jax.lax.scan(scan_fn, alpha0, (ts, log_probs[1:]))

            end1 = 2 * llen_i
            end2 = jnp.maximum(2 * llen_i - 1, 0)
            a1 = jnp.take_along_axis(alpha_T, end1[:, None], 1)[:, 0]
            a2 = jnp.take_along_axis(alpha_T, end2[:, None], 1)[:, 0]
            ll = jnp.logaddexp(a1, a2)
            return _w(-ll, self._weight, sw)
        return self._dispatch(
            fn, [pred, label, pred_lengths, label_lengths, sample_weight],
            "ctc_loss")


class HuberLoss(Loss):
    """Smooth L1 (reference loss.py:484)."""

    def __init__(self, rho=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._rho = rho

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        def fn(p, l, sw):
            loss = jnp.abs(jnp.reshape(l, p.shape) - p)
            loss = jnp.where(loss > self._rho,
                             loss - 0.5 * self._rho,
                             (0.5 / self._rho) * jnp.square(loss))
            loss = _w(loss, self._weight, sw)
            return _mean_keep_batch(loss, self._batch_axis)
        return self._dispatch(fn, [pred, label, sample_weight], "huber")


class HingeLoss(Loss):
    """max(0, margin - pred*label) (reference loss.py:529)."""

    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        def fn(p, l, sw):
            loss = jnp.maximum(self._margin - p * jnp.reshape(l, p.shape), 0)
            loss = _w(loss, self._weight, sw)
            return _mean_keep_batch(loss, self._batch_axis)
        return self._dispatch(fn, [pred, label, sample_weight], "hinge")


class SquaredHingeLoss(Loss):
    """max(0, margin - pred*label)^2 (reference loss.py:572)."""

    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        def fn(p, l, sw):
            loss = jnp.square(
                jnp.maximum(self._margin - p * jnp.reshape(l, p.shape), 0))
            loss = _w(loss, self._weight, sw)
            return _mean_keep_batch(loss, self._batch_axis)
        return self._dispatch(fn, [pred, label, sample_weight], "sq_hinge")


class LogisticLoss(Loss):
    """log(1 + exp(-pred*label)) (reference loss.py:615)."""

    def __init__(self, weight=None, batch_axis=0, label_format="signed", **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._label_format = label_format
        if self._label_format not in ["signed", "binary"]:
            raise ValueError("label_format can only be signed or binary, received %s."
                             % label_format)

    def hybrid_forward(self, F, pred, label, sample_weight=None):
        def fn(p, l, sw):
            l = jnp.reshape(l, p.shape)
            if self._label_format == "signed":
                l = (l + 1.0) / 2.0
            loss = jnp.maximum(p, 0) - p * l + jnp.log1p(jnp.exp(-jnp.abs(p)))
            loss = _w(loss, self._weight, sw)
            return _mean_keep_batch(loss, self._batch_axis)
        return self._dispatch(fn, [pred, label, sample_weight], "logistic")


class TripletLoss(Loss):
    """max(0, |a-p|^2 - |a-n|^2 + margin) (reference loss.py:665)."""

    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, pred, positive, negative):
        def fn(a, pos, neg):
            pos = jnp.reshape(pos, a.shape)
            neg = jnp.reshape(neg, a.shape)
            axes = tuple(range(1, a.ndim))
            loss = jnp.sum(jnp.square(a - pos) - jnp.square(a - neg), axis=axes)
            loss = jnp.maximum(loss + self._margin, 0)
            return _w(loss, self._weight, None)
        return self._dispatch(fn, [pred, positive, negative], "triplet")


class PoissonNLLLoss(Loss):
    """Poisson NLL (reference loss.py:707)."""

    def __init__(self, weight=None, from_logits=True, batch_axis=0,
                 compute_full=False, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_logits = from_logits
        self._compute_full = compute_full

    def hybrid_forward(self, F, pred, target, sample_weight=None, epsilon=1e-08):
        def fn(p, t, sw):
            t = jnp.reshape(t, p.shape)
            if self._from_logits:
                loss = jnp.exp(p) - t * p
            else:
                loss = p - t * jnp.log(p + epsilon)
            if self._compute_full:
                stirling = t * jnp.log(t) - t + 0.5 * jnp.log(2 * t * jnp.pi)
                stirling = jnp.where(t > 1, stirling, jnp.zeros_like(stirling))
                loss = loss + stirling
            loss = _w(loss, self._weight, sw)
            return jnp.mean(loss)
        return self._dispatch(fn, [pred, target, sample_weight], "poisson_nll")


class CosineEmbeddingLoss(Loss):
    """Cosine-distance loss between paired vectors (reference loss.py:766)."""

    def __init__(self, weight=None, batch_axis=0, margin=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def hybrid_forward(self, F, input1, input2, label, sample_weight=None):
        def fn(x1, x2, l, sw):
            x1 = jnp.reshape(x1, (x1.shape[0], -1))
            x2 = jnp.reshape(x2, (x2.shape[0], -1))
            l = jnp.reshape(l, (-1,))
            cos = jnp.sum(x1 * x2, axis=1) / jnp.maximum(
                jnp.linalg.norm(x1, axis=1) * jnp.linalg.norm(x2, axis=1), 1e-12)
            loss = jnp.where(l == 1, 1.0 - cos,
                             jnp.maximum(cos - self._margin, 0))
            return _w(loss, self._weight, sw)
        return self._dispatch(fn, [input1, input2, label, sample_weight],
                              "cosine_embedding")
