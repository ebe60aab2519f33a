"""SDAR mixture-of-experts decoders (JetLM SDAR-30B-A3B, ``model_type``
``sdar_moe``; arXiv:2510.06303), trained by block diffusion (BD3-LM,
arXiv:2503.09573): a pre-norm decoder whose every layer is
``x += Attn(RMSNorm(x)); x += MoE(RMSNorm(x))`` — grouped-query attention
with an RMS norm on every head's q and k and rotary embedding at the
token's own position id; a linear softmax router over ALL ``num_experts``,
a token to the ``experts_per_token`` largest with their probabilities
renormalised, gated-SiLU experts, no shared expert — a final RMSNorm and a
head that is NOT tied to the embedding.  No bias anywhere.

**Block diffusion.**  A row of L clean tokens ``x0`` is cut into blocks of
``block_length``; block b draws a noise level ``t_b`` and each of its
tokens becomes ``mask_id`` with probability ``t_b``: ``xt``.  The model
reads ONE row of 2L tokens ``[x0 ; xt]`` with position ids ``[0..L-1,
0..L-1]`` under a mask that is data: every token sees the clean blocks
before its own; a clean token its own clean block besides; a noised token
its own noised block, both ways; no clean token sees a noised one.
``block_diffusion_row`` makes the row, the ids, the mask's integers (the
form ``ops.pallas_attention.flash_attention`` takes: a tile of the 2L x 2L
square that holds no live pair is never visited), the labels and their
weights ``1 / t_b``; the hidden states are cut to the noised half before
the head, and position i of it predicts token i, not i + 1.

**One chip's share of an expert-parallel deployment**: ``experts_held=
(first, end)`` and a ``vocab_size`` that is the slice held, as
``model_zoo.zaya1`` and ``model_zoo.nemotron_h`` have them.

Training: ``net(row, position_ids, q_mask, kv_mask)`` returns ``(hidden of
the noised half (B, L, D), head weight)`` for
``gluon.loss.BlockDiffusionLoss``; with ``positions`` (B, P) last, the
logits at those places of the noised half::

    net = gluon.model_zoo.sdar(num_layers=6, vocab_size=18992,
                               experts_held=(0, 16))
    row = gluon.model_zoo.block_diffusion_row(tokens, 4, 18991, rs)
    step = parallel.DataParallelStep(
        net, gluon.loss.BlockDiffusionLoss(),
        mx.optimizer.Adam(1e-4, multi_precision=True))
    loss = step((row.tokens, row.position_ids, row.q_mask, row.kv_mask),
                row.label)
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as onp

from ..block import HybridBlock
from ..nn import Dense, Embedding, RMSNorm
from ..contrib.nn.moe import LinearRouter, SparseExperts
from ..contrib.nn.transformer import GroupedQueryAttention, MaskTileCount

__all__ = ["SDARLayer", "SDARModel", "sdar", "BlockDiffusionRow",
           "block_diffusion_mask", "block_diffusion_row"]

_NEVER = 2 ** 31 - 1       # the rank of a key that no reach sees


class BlockDiffusionRow(NamedTuple):
    """``block_diffusion_row``'s arrays (numpy; B rows of L clean tokens)."""
    tokens: onp.ndarray          # (B, 2L) int32: [x0 ; xt]
    position_ids: onp.ndarray    # (B, 2L) int32: [0..L-1, 0..L-1]
    q_mask: onp.ndarray          # (B, 2L, 2) int32: a query's [reach, own]
    kv_mask: onp.ndarray         # (B, 2L, 2) int32: a key's [rank, own]
    label: onp.ndarray           # (B, 2, L) float32: x0 where xt is masked
    #                              (else -1), stacked on the weights 1 / t_b
    noise: onp.ndarray           # (B, L / block_length) float32: t_b


def block_diffusion_mask(length, block_length, batch=1):
    """The mask of a ``[clean ; noised]`` row of 2 x ``length`` tokens in
    blocks of ``block_length``, as ``flash_attention``'s ``q_mask`` and
    ``kv_mask`` (batch, 2 length, 2).  With b a token's block: a clean key
    has rank b, a noised key never ranks; a clean query reaches b, a
    noised one b - 1; the noised tokens' ``own`` is b, the clean ones have
    none.  So ``rank <= reach`` is "the clean blocks up to mine" (before
    mine, for a noised query) and ``own == own`` "my own noised block"."""
    block = onp.arange(length) // block_length
    none = onp.full(length, -1)
    q_mask = onp.stack([onp.concatenate([block, block - 1]),
                        onp.concatenate([none, block])], axis=-1)
    kv_mask = onp.stack([onp.concatenate([block, onp.full(length, _NEVER)]),
                         onp.concatenate([none, block])], axis=-1)
    return tuple(onp.broadcast_to(m.astype("int32"), (batch,) + m.shape)
                 for m in (q_mask, kv_mask))


def block_diffusion_row(tokens, block_length, mask_id, rs, t_min=1e-3):
    """Noise ``tokens`` (B, L) for one block-diffusion training step, from
    the ``numpy.random.RandomState`` ``rs``: a ``t_b`` uniform in
    (``t_min``, 1) a block (the linear schedule: a token is masked with
    probability ``t_b`` and its loss weighs ``1 / t_b``), each token of
    the block replaced by ``mask_id`` with that probability.  Returns a
    ``BlockDiffusionRow``."""
    tokens = onp.asarray(tokens)
    batch, length = tokens.shape
    if length % block_length:
        raise ValueError("%d tokens are no whole blocks of %d"
                         % (length, block_length))
    noise = rs.uniform(t_min, 1.0, (batch, length // block_length))
    t = onp.repeat(noise, block_length, axis=1)
    masked = rs.uniform(size=tokens.shape) < t
    row = onp.concatenate([tokens, onp.where(masked, mask_id, tokens)], 1)
    q_mask, kv_mask = block_diffusion_mask(length, block_length, batch)
    label = onp.stack([onp.where(masked, tokens, -1),
                       onp.where(masked, 1.0 / t, 0.0)], axis=1)
    return BlockDiffusionRow(
        row.astype("int32"),
        onp.tile(onp.arange(length, dtype="int32"), (batch, 2)),
        q_mask, kv_mask, label.astype("float32"), noise.astype("float32"))


class SDARLayer(HybridBlock):
    """``x += Attn(RMSNorm(x)); x += MoE(RMSNorm(x))`` (the module's
    docstring)."""

    def __init__(self, units, attention, experts, epsilon, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attn_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                     prefix="attn_norm_")
            self.attention = GroupedQueryAttention(
                units, qk_norm=True, epsilon=epsilon, prefix="attn_",
                **attention)
            self.ffn_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                    prefix="ffn_norm_")
            self.router = LinearRouter(units, experts["num_experts"],
                                       scoring="softmax", prefix="router_")
            self.experts = SparseExperts(units, normalize_gates=True,
                                         prefix="experts_", **experts)

    def hybrid_forward(self, F, x, position_ids=None, q_mask=None,
                       kv_mask=None):
        x = x + self.attention(self.attn_norm(x), position_ids, q_mask,
                               kv_mask)
        h = self.ffn_norm(x)
        return x + self.experts(h, self.router(h))


class SDARModel(HybridBlock):
    """Embedding -> ``num_layers`` ``SDARLayer``s -> the noised half ->
    RMSNorm -> untied head.  See the module's docstring for the call
    forms; the defaults are the published SDAR-30B-A3B-Chat."""

    def __init__(self, vocab_size=151936, units=2048, num_layers=48,
                 num_heads=32, num_kv_heads=4, head_dim=128, rope_theta=1e6,
                 num_experts=128, experts_per_token=8, expert_hidden=768,
                 experts_held=None, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        attention = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                         head_dim=head_dim, rope_theta=rope_theta)
        experts = dict(hidden_size=expert_hidden, num_experts=num_experts,
                       experts_held=experts_held,
                       experts_per_token=experts_per_token)
        self.layers = []
        with self.name_scope():
            self.embed = Embedding(vocab_size, units, prefix="embed_")
            for i in range(num_layers):
                layer = SDARLayer(units, attention, experts, epsilon,
                                  prefix="layer%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            self.final_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                      prefix="final_norm_")
            self.head = Dense(vocab_size, flatten=False, use_bias=False,
                              in_units=units, prefix="head_")
            self.mask_tiles = MaskTileCount(head_dim, calls=num_layers,
                                            prefix="mask_")

    def hybrid_forward(self, F, token_ids, position_ids, q_mask, kv_mask,
                       positions=None):
        self.mask_tiles(q_mask, kv_mask)
        x = self.embed(token_ids)
        for layer in self.layers:
            x = layer(x, position_ids, q_mask, kv_mask)
        # the noised half, BEFORE the norm and the head: the clean half
        # predicts nothing
        half = x.shape[1] // 2
        hidden = self.final_norm(F.slice_axis(x, axis=1, begin=half,
                                              end=2 * half))
        if positions is None:
            return hidden, self.head.weight.data()
        return self.head(F.gather_positions(hidden, positions))


def sdar(**kwargs):
    """SDAR-30B-A3B-Chat as published (48 layers, hidden 2048, 32 query on
    4 key-value heads of 128 with q/k norm and rotary at theta 1e6, 128
    gated-SiLU experts of width 768 with 8 a token, untied vocabulary
    151,936); keyword arguments override."""
    return SDARModel(**kwargs)
