"""Nemotron-H style hybrid decoders (NVIDIA Nemotron-3-Nano-30B-A3B,
``model_type`` ``nemotron_h``): a pre-norm residual stack with ONE mixer a
layer, ``x += Mixer_l(RMSNorm(x))``, the mixer read from a pattern string —
``M`` a Mamba-2 mixer (``gluon.contrib.nn.Mamba2Mixer``), ``E`` sparse
experts beside a shared expert, ``*`` causal grouped-query attention
without a position embedding (position comes from the Mamba layers) — a
final RMSNorm, and a head that is NOT tied to the embedding.

An ``E`` layer: a linear router scores ALL ``num_experts`` with a sigmoid,
a token goes to the ``experts_per_token`` largest of ``score + bias`` (the
balancing bias of ``SparseExperts``: no gradient, kept by the
auxiliary-loss-free rule at ``bias_update_rate``), the gates are the
chosen scores divided by their sum and scaled by ``routed_scale``, the
experts are ``Wdown relu(Wup x)^2``; a shared expert of the same form,
``shared_hidden`` wide, is added for every token.

**One chip's share of an expert-parallel deployment.**  ``experts_held=
(first, end)`` tells every ``E`` layer which experts this chip holds: the
router still scores all of them, the layer adds the held experts' part and
the shared expert (which every chip computes alike), and what the absent
experts would have added is left out.  A vocabulary slice is a smaller
vocabulary: build with ``vocab_size=`` the rows held.

Training: ``net(tokens)`` returns ``(hidden, head weight)`` for
``gluon.loss.TiedSoftmaxCrossEntropyLoss`` (which takes any (V, D) head: a
block of tokens against all rows at a time, never the whole logits);
``net(tokens, positions)`` returns the logits at ``positions`` (B, P)::

    net = gluon.model_zoo.nemotron_h(pattern="MEMEM*EME", vocab_size=16384,
                                     experts_held=(0, 8),
                                     bias_update_rate=1e-3)
    step = parallel.DataParallelStep(
        net, gluon.loss.TiedSoftmaxCrossEntropyLoss(),
        mx.optimizer.Adam(1e-4, multi_precision=True))
    loss = step(tokens, next_tokens)       # last column of labels: -1
"""
from __future__ import annotations

from ..block import HybridBlock
from ..nn import Dense, Embedding, RMSNorm
from ..contrib.nn.moe import LinearRouter, SparseExperts
from ..contrib.nn.ssm import Mamba2Mixer
from ..contrib.nn.transformer import GroupedQueryAttention, PositionwiseFFN

__all__ = ["NemotronHLayer", "NemotronHModel", "nemotron_h"]

PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


class NemotronHLayer(HybridBlock):
    """``x + Mixer(RMSNorm(x))`` with the mixer of ``kind``: ``"M"``,
    ``"E"`` or ``"*"`` (the module's docstring)."""

    def __init__(self, kind, units, epsilon, mamba, attention, experts,
                 **kwargs):
        super().__init__(**kwargs)
        if kind not in ("M", "E", "*"):
            raise ValueError("layer kind %r is none of M, E, *" % (kind,))
        self.kind = kind
        with self.name_scope():
            self.norm = RMSNorm(epsilon=epsilon, in_channels=units,
                                prefix="norm_")
            if kind == "M":
                self.mixer = Mamba2Mixer(units, epsilon=epsilon,
                                         prefix="mamba_", **mamba)
            elif kind == "*":
                self.mixer = GroupedQueryAttention(units, prefix="attn_",
                                                   **attention)
            else:
                experts = dict(experts)
                shared_hidden = experts.pop("shared_hidden")
                self.router = LinearRouter(units, experts["num_experts"],
                                           scoring="sigmoid",
                                           prefix="router_")
                self.experts = SparseExperts(
                    units, gated=False, activation="relu2",
                    normalize_gates=True, prefix="experts_", **experts)
                self.shared = PositionwiseFFN(
                    units, shared_hidden, activation="relu2",
                    use_bias=False, in_units=units, prefix="shared_")

    def hybrid_forward(self, F, x):
        h = self.norm(x)
        if self.kind == "E":
            return x + self.experts(h, self.router(h)) + self.shared(h)
        return x + self.mixer(h)


class NemotronHModel(HybridBlock):
    """Embedding -> one ``NemotronHLayer`` a character of ``pattern`` ->
    RMSNorm -> untied head.  See the module's docstring for the two call
    forms; the defaults are the published Nemotron-3-Nano-30B-A3B."""

    def __init__(self, pattern=PUBLISHED_PATTERN, vocab_size=131072,
                 units=2688, mamba_heads=64, mamba_head_dim=64,
                 state_size=128, num_groups=8, conv_kernel=4, chunk_size=128,
                 dt_range=(1e-3, 1e-1), dt_floor=1e-4, num_heads=32,
                 num_kv_heads=2, head_dim=128, num_experts=128,
                 experts_per_token=6, expert_hidden=1856, shared_hidden=3712,
                 routed_scale=2.5, experts_held=None, bias_update_rate=0.0,
                 epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        mamba = dict(num_heads=mamba_heads, head_dim=mamba_head_dim,
                     state_size=state_size, num_groups=num_groups,
                     conv_kernel=conv_kernel, chunk_size=chunk_size,
                     dt_range=dt_range, dt_floor=dt_floor)
        attention = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                         head_dim=head_dim)
        experts = dict(hidden_size=expert_hidden, num_experts=num_experts,
                       experts_held=experts_held,
                       experts_per_token=experts_per_token,
                       gate_scale=routed_scale,
                       bias_update_rate=bias_update_rate,
                       shared_hidden=shared_hidden)
        self.layers = []
        with self.name_scope():
            self.embed = Embedding(vocab_size, units, prefix="embed_")
            for i, kind in enumerate(pattern):
                layer = NemotronHLayer(kind, units, epsilon, mamba,
                                       attention, experts,
                                       prefix="layer%d_" % i)
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


def nemotron_h(**kwargs):
    """Nemotron-3-Nano-30B-A3B as published (52 layers of the pattern
    ``MEMEM*E...``, hidden 2688; Mamba-2 mixers of 64 heads of 64, state
    128, 8 groups, kernel 4, chunks of 128; 32 query on 2 key-value heads
    of 128; 128 relu-squared experts of width 1856 with 6 a token, scaled
    2.5, beside a shared expert of 3712; untied vocabulary 131,072);
    keyword arguments override."""
    return NemotronHModel(**kwargs)
