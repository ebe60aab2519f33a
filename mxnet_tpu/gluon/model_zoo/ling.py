"""Ling hybrid linear / latent-attention mixture-of-experts decoders
(inclusionAI Ling-3.0-flash, ``model_type`` ``bailing_hybrid``): a pre-norm
decoder, ``x += Mixer_l(RMSNorm(x)); x += FFN_l(RMSNorm(x))``, whose layer
l is —

* a mixer by ``layer_group_size``: multi-head latent attention
  (``LatentAttention``: no query compression, a 512-wide key-value latent,
  heads of 128 + 64 rotary dimensions under an interleaved rotary, q/k
  norm, values of 128) where ``(l + 1) % layer_group_size == 0``, else Kimi
  Delta Attention (``KimiDeltaAttention``: the gated delta rule with a
  decay a channel, bounded below, short convolutions, a norm a head);
  every head's output gated by ``sigmoid(RMSNorm(x) W_g)`` before the
  output projection;
* a feed-forward block by ``first_k_dense_replace``: a gated dense block
  of ``dense_hidden`` for l below it, else ``num_experts`` gated-SiLU
  experts behind a sigmoid router with a correction bias, a token to the
  ``experts_per_token`` largest ``s + b`` among the ``topk_groups`` best of
  ``expert_groups`` groups, gated by ``s`` renormalised over the chosen
  and scaled by ``routed_scale``, beside one shared gated expert —

then a final RMSNorm and a head that is NOT tied to the embedding.  No
bias anywhere.

``layers`` names the published layers kept (default all ``num_layers``):
each block is named by its published index, so ``layers=(0, 2, 3, 4, 5,
6, 7)`` is layer 0 (delta attention, dense), then one whole period of
the pattern — four delta layers, the latent layer 5, two delta layers —
each sparse.

**One chip's share of an expert-parallel deployment**: ``experts_held=
(first, end)`` and a ``vocab_size`` that is the slice held, as
``model_zoo.laguna`` has them.

Training: ``net(tokens)`` returns ``(hidden, head weight)`` for
``gluon.loss.TiedSoftmaxCrossEntropyLoss``; ``net(tokens, positions)`` the
logits at ``positions`` (B, P)::

    net = gluon.model_zoo.ling(layers=(0, 2, 3, 4, 5, 6, 7),
                               vocab_size=19648, experts_held=(0, 8))
    step = parallel.DataParallelStep(
        net, gluon.loss.TiedSoftmaxCrossEntropyLoss(),
        mx.optimizer.Adam(1e-4, multi_precision=True))
    loss = step(tokens, next_tokens)       # last column of labels: -1
"""
from __future__ import annotations

from ..block import HybridBlock
from ..nn import Dense, Embedding, RMSNorm
from ..contrib.nn.moe import LinearRouter, SparseExperts
from ..contrib.nn.ssm import KimiDeltaAttention
from ..contrib.nn.transformer import GatedFFN, LatentAttention

__all__ = ["LingLayer", "LingModel", "ling", "layer_kinds"]

# the published latent layers' rotary embedding (config.json: rope_theta,
# rotary_dim, rope_interleave), as ``rotary_embedding``'s keyword arguments
MLA_ROPE = dict(theta=6e6, interleave=True)


def layer_kinds(index, layer_group_size=6, first_k_dense_replace=2):
    """(mixer, feed-forward) of published layer ``index``: ``"mla"`` where
    ``(index + 1) % layer_group_size == 0`` else ``"kda"``; ``"dense"``
    below ``first_k_dense_replace`` else ``"sparse"``."""
    mixer = "mla" if (index + 1) % layer_group_size == 0 else "kda"
    return mixer, "dense" if index < first_k_dense_replace else "sparse"


class LingLayer(HybridBlock):
    """``x += Mixer(RMSNorm(x)); x += FFN(RMSNorm(x))``: the mixer ``mixer``
    (``"kda"``: ``KimiDeltaAttention``'s arguments ``kda``; ``"mla"``:
    ``LatentAttention``'s ``mla``), the feed-forward block of ``ffn``
    (``"dense"``, ``dense_hidden`` wide, or ``"sparse"``: ``SparseExperts``'
    arguments ``experts`` and ``shared_hidden``).  The pre-norm is named
    after the mixer (``kda_norm`` / ``mla_norm``)."""

    def __init__(self, units, mixer, ffn, kda, mla, dense_hidden, experts,
                 epsilon, **kwargs):
        super().__init__(**kwargs)
        if mixer not in ("kda", "mla") or ffn not in ("dense", "sparse"):
            raise ValueError("layer kinds %r / %r are not kda or mla / "
                             "dense or sparse" % (mixer, ffn))
        self.kind = ffn
        with self.name_scope():
            self.mixer_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                      prefix="%s_norm_" % mixer)
            self.mixer = KimiDeltaAttention(
                units, epsilon=epsilon, prefix="kda_", **kda) \
                if mixer == "kda" else LatentAttention(
                    units, epsilon=epsilon, prefix="mla_", **mla)
            self.ffn_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                    prefix="ffn_norm_")
            if ffn == "dense":
                self.ffn = GatedFFN(units, dense_hidden, in_units=units,
                                    prefix="ffn_")
            else:
                experts = dict(experts)
                shared_hidden = experts.pop("shared_hidden")
                self.router = LinearRouter(units, experts["num_experts"],
                                           scoring="sigmoid",
                                           prefix="router_")
                self.experts = SparseExperts(units, normalize_gates=True,
                                             prefix="experts_", **experts)
                self.shared = GatedFFN(units, shared_hidden, in_units=units,
                                       prefix="shared_")

    def hybrid_forward(self, F, x):
        x = x + self.mixer(self.mixer_norm(x))
        h = self.ffn_norm(x)
        if self.kind == "dense":
            return x + self.ffn(h)
        return x + self.experts(h, self.router(h)) + self.shared(h)


class LingModel(HybridBlock):
    """Embedding -> one ``LingLayer`` a kept layer -> RMSNorm -> untied
    head.  See the module's docstring for the layers and the two call
    forms; the defaults are the published Ling-3.0-flash (42 layers, hidden
    2560, 32 heads of 128, a latent layer every sixth, layers 0-1 dense)."""

    def __init__(self, vocab_size=157184, units=2560, num_layers=42,
                 layers=None, layer_group_size=6, first_k_dense_replace=2,
                 num_heads=32, head_dim=128, conv_kernel=4, lower_bound=-5.0,
                 chunk_size=64, kv_rank=512, nope_dim=128, rope_dim=64,
                 v_dim=128, mla_rope=MLA_ROPE, dense_hidden=6144,
                 num_experts=512, experts_per_token=8, expert_groups=8,
                 topk_groups=4, expert_hidden=768, shared_hidden=768,
                 routed_scale=2.5, experts_held=None, bias_update_rate=1e-3,
                 epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        layers = list(range(num_layers) if layers is None else layers)
        if any(not 0 <= i < num_layers for i in layers) \
                or layers != sorted(set(layers)):
            raise ValueError("layers %r are not ascending indices of %d"
                             % (layers, num_layers))
        kda = dict(num_heads=num_heads, head_dim=head_dim,
                   conv_kernel=conv_kernel, lower_bound=lower_bound,
                   chunk_size=chunk_size)
        mla = dict(num_heads=num_heads, kv_rank=kv_rank, nope_dim=nope_dim,
                   rope_dim=rope_dim, v_dim=v_dim, rope=mla_rope)
        experts = dict(hidden_size=expert_hidden, num_experts=num_experts,
                       experts_held=experts_held,
                       experts_per_token=experts_per_token,
                       expert_groups=expert_groups, topk_groups=topk_groups,
                       gate_scale=routed_scale,
                       bias_update_rate=bias_update_rate,
                       shared_hidden=shared_hidden)
        self.layers = []
        with self.name_scope():
            self.embed = Embedding(vocab_size, units, prefix="embed_")
            for i in layers:
                mixer, ffn = layer_kinds(i, layer_group_size,
                                         first_k_dense_replace)
                layer = LingLayer(units, mixer, ffn, kda, mla, dense_hidden,
                                  experts, epsilon, prefix="layer%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            self.final_norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                      prefix="final_norm_")
            self.head = Dense(vocab_size, flatten=False, use_bias=False,
                              in_units=units, prefix="head_")

    def hybrid_forward(self, F, token_ids, positions=None):
        x = self.embed(token_ids)
        for layer in self.layers:
            x = layer(x)
        hidden = self.final_norm(x)
        if positions is None:
            return hidden, self.head.weight.data()
        return self.head(F.gather_positions(hidden, positions))


def ling(**kwargs):
    """Ling-3.0-flash as published (42 layers, hidden 2560; Kimi Delta
    Attention with a latent-attention layer every sixth, 32 heads of 128;
    layers 0-1 a gated dense block of 6,144, the others 512 gated-SiLU
    experts of 768 with 8 a token from 4 of 8 groups, scaled 2.5, beside a
    shared expert of 768; untied vocabulary 157,184); keyword arguments
    override."""
    return LingModel(**kwargs)
