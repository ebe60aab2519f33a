"""Laguna mixture-of-experts decoders (poolside Laguna-S-2.1, ``model_type``
``laguna``): a pre-norm decoder, ``x += Attn_l(RMSNorm(x)); x +=
FFN_l(RMSNorm(x))``, whose layers differ by three lists read a layer at a
time —

* ``layer_types[l]``: ``full_attention`` (causal, every key before it) or
  ``sliding_attention`` (the ``window`` keys that end with the query's
  own), each with ITS rotary embedding: the full layers a YaRN-scaled one
  on part of a head's dimensions, the window layers the default rule on
  all of them;
* ``heads_per_layer[l]``: the query heads of layer l, all on the same
  ``num_kv_heads`` key-value heads (48 on the full layers and 72 on the
  window layers of the published model: groups of 6 and 9);
* ``mlp_layer_types[l]``: ``dense`` (a gated feed-forward block of
  ``dense_hidden``) or ``sparse`` (a linear softmax router over ALL
  ``num_experts``, a token to the ``experts_per_token`` largest with their
  probabilities renormalised and scaled by ``routed_scale``, gated-SiLU
  experts, beside one shared gated expert that every token passes) —

every head's output gated by ``sigmoid(RMSNorm(x) W_g)`` before the output
projection, a final RMSNorm and a head that is NOT tied to the embedding.
No bias anywhere.

The window layers run ``flash_attention``'s masked kernels on three
integers a query made from the shapes (the tiles outside the band are
never visited), the full layers the plain causal ones; the model keeps one
``MaskTileCount`` over its window layers.

**One chip's share of an expert-parallel deployment**: ``experts_held=
(first, end)`` and a ``vocab_size`` that is the slice held, as
``model_zoo.nemotron_h`` and ``model_zoo.sdar`` have them; the shared
expert, the dense layer, attention and the router are what every chip
computes alike.

Training: ``net(tokens)`` returns ``(hidden, head weight)`` for
``gluon.loss.TiedSoftmaxCrossEntropyLoss`` (which takes any (V, D) head);
``net(tokens, positions)`` the logits at ``positions`` (B, P)::

    net = gluon.model_zoo.laguna(num_layers=5, vocab_size=12544,
                                 experts_held=(0, 8))
    step = parallel.DataParallelStep(
        net, gluon.loss.TiedSoftmaxCrossEntropyLoss(),
        mx.optimizer.Adam(1e-4, multi_precision=True))
    loss = step(tokens, next_tokens)       # last column of labels: -1
"""
from __future__ import annotations

from ... import ndarray as nd
from ...ops.pallas_attention import window_mask
from ..block import HybridBlock
from ..nn import Dense, Embedding, RMSNorm
from ..contrib.nn.moe import LinearRouter, SparseExperts
from ..contrib.nn.transformer import (GatedFFN, GroupedQueryAttention,
                                      MaskTileCount)

__all__ = ["LagunaLayer", "LagunaModel", "laguna"]

FULL, SLIDING = "full_attention", "sliding_attention"
# the published rotary embeddings (config.json: rope_parameters), as
# ``rotary_embedding``'s keyword arguments
FULL_ROPE = dict(rotary_dim=64, theta=500000.0, rope_type="yarn",
                 factor=128.0, original_length=8192, beta_fast=32.0,
                 beta_slow=1.0, attention_factor=1.4852030263919618)
SLIDING_ROPE = dict(theta=10000.0)


class LagunaLayer(HybridBlock):
    """``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))`` with the attention
    ``attention`` describes (``GroupedQueryAttention``'s arguments) and
    the feed-forward block of ``kind``: ``"dense"`` (``dense_hidden``
    wide) or ``"sparse"`` (``experts``: ``SparseExperts``' arguments and
    ``shared_hidden``)."""

    def __init__(self, units, attention, kind, dense_hidden, experts,
                 epsilon, **kwargs):
        super().__init__(**kwargs)
        if kind not in ("dense", "sparse"):
            raise ValueError("feed-forward kind %r is neither dense nor "
                             "sparse" % (kind,))
        self.kind = kind
        with self.name_scope():
            self.attn_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                     prefix="attn_norm_")
            self.attention = GroupedQueryAttention(
                units, head_gate=True, epsilon=epsilon, prefix="attn_",
                **attention)
            self.ffn_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                    prefix="ffn_norm_")
            if kind == "dense":
                self.ffn = GatedFFN(units, dense_hidden, in_units=units,
                                    prefix="ffn_")
            else:
                experts = dict(experts)
                shared_hidden = experts.pop("shared_hidden")
                self.router = LinearRouter(units, experts["num_experts"],
                                           scoring="softmax",
                                           prefix="router_")
                self.experts = SparseExperts(units, normalize_gates=True,
                                             prefix="experts_", **experts)
                self.shared = GatedFFN(units, shared_hidden, in_units=units,
                                       prefix="shared_")

    def hybrid_forward(self, F, x):
        x = x + self.attention(self.attn_norm(x))
        h = self.ffn_norm(x)
        if self.kind == "dense":
            return x + self.ffn(h)
        return x + self.experts(h, self.router(h)) + self.shared(h)


class LagunaModel(HybridBlock):
    """Embedding -> one ``LagunaLayer`` an entry of the three lists ->
    RMSNorm -> untied head.  See the module's docstring for the two call
    forms; the defaults are the published Laguna-S-2.1 (a full layer
    every fourth, from layer 0; layer 0 dense)."""

    def __init__(self, vocab_size=100352, units=3072, num_layers=48,
                 layer_types=None, heads_per_layer=None,
                 mlp_layer_types=None, num_kv_heads=8, head_dim=128,
                 window=512, full_rope=FULL_ROPE, sliding_rope=SLIDING_ROPE,
                 dense_hidden=12288, num_experts=256, experts_per_token=10,
                 expert_hidden=1024, shared_hidden=1024, routed_scale=2.5,
                 experts_held=None, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        if layer_types is None:
            layer_types = [SLIDING if i % 4 else FULL
                           for i in range(num_layers)]
        if heads_per_layer is None:
            heads_per_layer = [48 if kind == FULL else 72
                               for kind in layer_types]
        if mlp_layer_types is None:
            mlp_layer_types = ["sparse" if i else "dense"
                               for i in range(num_layers)]
        lists = (layer_types, heads_per_layer, mlp_layer_types)
        if any(len(entries) != num_layers for entries in lists):
            raise ValueError("the three lists give %r layers, num_layers %d"
                             % (tuple(len(e) for e in lists), num_layers))
        if any(kind not in (FULL, SLIDING) for kind in layer_types):
            raise ValueError("layer_types holds other than %r and %r: %r"
                             % (FULL, SLIDING, sorted(set(layer_types))))
        experts = dict(hidden_size=expert_hidden, num_experts=num_experts,
                       experts_held=experts_held,
                       experts_per_token=experts_per_token,
                       gate_scale=routed_scale, shared_hidden=shared_hidden)
        self._window = window
        self.layers = []
        with self.name_scope():
            self.embed = Embedding(vocab_size, units, prefix="embed_")
            for i, (kind, heads, ffn) in enumerate(zip(*lists)):
                attention = dict(
                    num_heads=heads, num_kv_heads=num_kv_heads,
                    head_dim=head_dim,
                    rope=full_rope if kind == FULL else sliding_rope,
                    window=None if kind == FULL else window)
                layer = LagunaLayer(units, attention, ffn, dense_hidden,
                                    experts, epsilon, prefix="layer%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            self.final_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                      prefix="final_norm_")
            self.head = Dense(vocab_size, flatten=False, use_bias=False,
                              in_units=units, prefix="head_")
            self.mask_tiles = MaskTileCount(
                head_dim, calls=layer_types.count(SLIDING), window=window,
                prefix="mask_")

    def hybrid_forward(self, F, token_ids, positions=None):
        batch, seq = token_ids.shape
        if self._window < seq:
            # what the window layers' kernels skip by, counted once for all
            # of them: the band is a function of the shapes
            self.mask_tiles(*(nd.array(m, dtype="int32") for m in
                              window_mask(batch, seq, seq, self._window)))
        x = self.embed(token_ids)
        for layer in self.layers:
            x = layer(x)
        hidden = self.final_norm(x)
        if positions is None:
            return hidden, self.head.weight.data()
        return self.head(F.gather_positions(hidden, positions))


def laguna(**kwargs):
    """Laguna-S-2.1 as published (48 layers, hidden 3072; 48 query heads
    on the full layers and 72 on the window layers of 512, all on 8
    key-value heads of 128, a gate a head; layer 0 a gated dense block of
    12,288, the others 256 gated-SiLU experts of width 1024 with 10 a
    token, scaled 2.5, beside a shared expert of 1024; untied vocabulary
    100,352); keyword arguments override."""
    return LagunaModel(**kwargs)
