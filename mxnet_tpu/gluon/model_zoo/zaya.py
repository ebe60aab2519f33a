"""ZAYA1 (Zyphra; arXiv:2511.17127, CCA arXiv:2510.04476): a pre-norm
decoder whose every layer is ``x += CCA(RMSNorm(x)); x += MoE(RMSNorm(x))``
— compressed convolutional attention and dropless top-1 sparse experts
behind a small MLP router with depth averaging — with the output head tied
to the embedding.

``zaya1()`` builds the published ZAYA1-8B; every size is an argument.

**One chip's share of an expert-parallel deployment.**  ``experts_held=
(first, end)`` tells every layer which of its ``num_experts`` experts this
chip holds: the router still scores all of them, the layer computes the
held experts for the tokens routed to them and adds ONLY that part to the
residual — what the absent experts would have added is left out, and that
partial result is what goes on to the next layer (across chips,
``parallel.moe`` sums the shares through an ``ep`` exchange).
``experts_held=None`` holds them all.  A vocabulary slice is a smaller
vocabulary: build with ``vocab_size=`` the rows held here (the first
131,136 of 262,272, say), draw the token ids from the slice, and logits
and loss are over the slice.

**Routing.**  A token goes to ``argmax(p + b)``: the router's softmax plus
the balancing bias ``b`` of ``SparseExperts`` (no gradient, zeros at the
start).  ``bias_update_rate=u`` keeps ``b`` by the auxiliary-loss-free rule,
once a training step: ``b_e += u * sign(mean load - load_e)``.  A router at
its random initialisation sends most of a layer's tokens to one or two
experts; the rule spreads them over its first hundred or so steps.

Training: ``net(tokens)`` returns ``(hidden, embedding)`` for
``gluon.loss.TiedSoftmaxCrossEntropyLoss``, which never forms the
(tokens x vocabulary) logits: it takes a block of tokens against the whole
vocabulary at a time and makes the head's gradients while the block's
logits are there, three products a step and no logits formed twice;
``net(tokens, positions)`` returns the logits at ``positions`` (B, P) of
each row::

    net = gluon.model_zoo.zaya1(num_layers=4, vocab_size=131136,
                                experts_held=(0, 8), bias_update_rate=1e-4)
    step = parallel.DataParallelStep(
        net, gluon.loss.TiedSoftmaxCrossEntropyLoss(),
        mx.optimizer.Adam(1e-4, multi_precision=True))
    loss = step(tokens, next_tokens)       # last column of labels: -1
"""
from __future__ import annotations

from ..block import HybridBlock
from ..nn import Embedding, RMSNorm
from ..contrib.nn.moe import DepthRouter, SparseExperts
from ..contrib.nn.transformer import CompressedConvAttention

__all__ = ["ZAYA1Layer", "ZAYA1Model", "zaya1"]


class ZAYA1Layer(HybridBlock):
    """``x += CCA(RMSNorm(x)); x += MoE(RMSNorm(x))``.  Takes and returns
    ``(x, r)``: ``r`` is the router's hidden state, which the next layer's
    router averages in (``None`` into the first layer)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 expert_hidden, num_experts, router_hidden, experts_held,
                 conv_kernels, rotary_dim, rope_theta, epsilon,
                 first_layer=False, bias_update_rate=0.0, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attn_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                     prefix="attn_norm_")
            self.attention = CompressedConvAttention(
                units, num_heads, num_kv_heads, head_dim,
                conv_kernels=conv_kernels, rotary_dim=rotary_dim,
                rope_theta=rope_theta, prefix="cca_")
            self.moe_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                    prefix="moe_norm_")
            self.router = DepthRouter(units, router_hidden, num_experts,
                                      carry=not first_layer,
                                      epsilon=epsilon, prefix="router_")
            self.experts = SparseExperts(units, expert_hidden, num_experts,
                                         experts_held=experts_held,
                                         bias_update_rate=bias_update_rate,
                                         prefix="experts_")

    def hybrid_forward(self, F, x, r_prev=None):
        x = x + self.attention(self.attn_norm(x))
        h = self.moe_norm(x)
        probs, r = self.router(h, r_prev)
        return x + self.experts(h, probs), r


class ZAYA1Model(HybridBlock):
    """Tied embedding -> ``num_layers`` ``ZAYA1Layer`` -> RMSNorm.  See the
    module's docstring for the two call forms."""

    def __init__(self, vocab_size=262272, units=2048, num_layers=40,
                 num_heads=8, num_kv_heads=2, head_dim=128,
                 expert_hidden=2048, num_experts=16, router_hidden=256,
                 experts_held=None, conv_kernels=(2, 2),
                 partial_rotary_factor=0.5, rope_theta=5e6, epsilon=1e-5,
                 bias_update_rate=0.0, **kwargs):
        super().__init__(**kwargs)
        self.layers = []
        with self.name_scope():
            self.embed = Embedding(vocab_size, units, prefix="embed_")
            for i in range(num_layers):
                layer = ZAYA1Layer(
                    units, num_heads, num_kv_heads, head_dim, expert_hidden,
                    num_experts, router_hidden, experts_held, conv_kernels,
                    int(head_dim * partial_rotary_factor), rope_theta,
                    epsilon, first_layer=i == 0,
                    bias_update_rate=bias_update_rate,
                    prefix="layer%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            self.final_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                      prefix="final_norm_")

    def hybrid_forward(self, F, token_ids, positions=None):
        x, r = self.embed(token_ids), None
        for layer in self.layers:
            x, r = layer(x, r)
        hidden = self.final_norm(x)
        table = self.embed.weight.data()
        if positions is None:
            return hidden, table
        picked = F.gather_positions(hidden, positions)
        return F.FullyConnected(picked, table, no_bias=True, flatten=False,
                                num_hidden=table.shape[0])


def zaya1(**kwargs):
    """ZAYA1-8B as published (40 layers, hidden 2048, 8 query on 2
    key-value heads of 128, 16 experts of width 2048 with 1 a token, router
    hidden 256, tied vocabulary 262,272); keyword arguments override."""
    return ZAYA1Model(**kwargs)
