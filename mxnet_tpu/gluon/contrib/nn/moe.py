"""Sparse experts as Gluon blocks: a router over ALL the experts of a layer
and a ``SparseExperts`` block that is told which of them it holds.

One chip of an expert-parallel deployment holds a slice of each layer's
experts.  ``SparseExperts(..., num_experts=16, experts_held=(0, 8))`` routes
every token over all 16 (``experts_per_token`` routes a token), computes
experts 0..7 for the routes that go to them — all of them, whatever the
imbalance: nothing has a capacity — and returns ONLY that part of the
layer's output; routes to experts held elsewhere add zero.  That partial result is what the caller adds to the
residual and hands to the next layer; the shares of all the chips add up to
the whole layer (``tests/test_zaya.py``).  ``experts_held=None`` holds them
all.  Across chips, ``parallel.moe`` puts an ``ep`` all-to-all round the
same core (``ops/moe.py``).

Routing is observable without a callback in the step: the block keeps the
last step's counts as non-trainable state (as BatchNorm keeps its running
statistics), and ``publish_routing_counts`` reads them into ``telemetry``.
"""
from __future__ import annotations

import weakref

import jax
import numpy as onp

from .... import autograd, telemetry
from ....ops import moe as _core
from ...block import HybridBlock
from ...nn import Dense, RMSNorm

__all__ = ["DepthRouter", "LinearRouter", "SparseExperts",
           "publish_routing_counts"]

_LIVE = weakref.WeakSet()      # the SparseExperts blocks of this process


class DepthRouter(HybridBlock):
    """The router of a ZAYA1 layer: ``r = x Wd (+ gamma * r_prev)`` in a
    ``hidden``-wide space (``carry``: the previous layer's ``r`` comes in,
    scaled by a learned scalar — depth averaging), then
    ``softmax(W3 gelu(W2 gelu(W1 RMSNorm(r))))`` over ALL ``num_experts``.
    Returns ``(probs, r)``; ``r`` goes to the next layer's router."""

    def __init__(self, units, hidden, num_experts, carry=True, epsilon=1e-5,
                 **kwargs):
        super().__init__(**kwargs)
        self._carry = carry
        with self.name_scope():
            def dense(width, in_units, prefix, activation=None):
                return Dense(width, flatten=False, use_bias=False,
                             in_units=in_units, activation=activation,
                             prefix=prefix)
            self.down = dense(hidden, units, "down_")
            self.norm = RMSNorm(epsilon=epsilon, in_channels=hidden,
                                prefix="norm_")
            self.fc1 = dense(hidden, hidden, "fc1_", "gelu")
            self.fc2 = dense(hidden, hidden, "fc2_", "gelu")
            self.fc3 = dense(num_experts, hidden, "fc3_")
            if carry:
                self.depth_gamma = self.params.get(
                    "depth_gamma", shape=(1,), init="ones")

    def hybrid_forward(self, F, x, r_prev=None, depth_gamma=None):
        r = self.down(x)
        if self._carry and r_prev is not None:
            r = r + F.broadcast_mul(r_prev, depth_gamma.reshape((1, 1, 1)))
        logits = self.fc3(self.fc2(self.fc1(self.norm(r))))
        return F.softmax(logits.astype("float32"), axis=-1), r


class LinearRouter(HybridBlock):
    """A router that is one linear map: ``scoring(x W)`` over ALL
    ``num_experts``, in float32 (no bias).  ``scoring`` is ``"sigmoid"``
    (each expert's score on its own: DeepSeek-V3, arXiv:2412.19437
    section 2.1.2) or ``"softmax"``."""

    def __init__(self, units, num_experts, scoring="sigmoid", **kwargs):
        super().__init__(**kwargs)
        if scoring not in ("sigmoid", "softmax"):
            raise ValueError("scoring=%r is neither sigmoid nor softmax"
                             % (scoring,))
        self._scoring = scoring
        with self.name_scope():
            self.weight = self.params.get("weight",
                                          shape=(num_experts, units))

    def hybrid_forward(self, F, x, weight):
        logits = F.FullyConnected(
            x.astype("float32"), weight.astype("float32"), no_bias=True,
            flatten=False, num_hidden=weight.shape[0])
        return F.sigmoid(logits) if self._scoring == "sigmoid" \
            else F.softmax(logits, axis=-1)


class SparseExperts(HybridBlock):
    """Dropless top-k experts, a slice of them held here.

    ``forward(x, probs)``: ``x`` (B, S, units), ``probs`` (B, S,
    num_experts) the router's scores (a softmax, or sigmoids).  Token t
    goes to the ``experts_per_token`` largest of ``probs + balance_bias``
    and gets, from each of them that is held, ``gate_e * Expert_e(x)``;
    from the others zero (see the module's docstring).  ``gate_e`` is
    ``probs[e]`` — the bias moves the choice, never the gate — divided by
    the sum over ALL the chosen where ``normalize_gates``, times
    ``gate_scale``.  The expert network is ``Wdown(act(Wgate x) * Wup x)``
    where ``gated`` and ``Wdown act(Wup x)`` otherwise, ``activation`` one
    of ``silu``, ``gelu``, ``relu2``.  ``expert_groups``: the experts are
    that many groups in order and a token chooses only from its
    ``topk_groups`` best groups, a group's score the sum of its two
    largest ``probs + balance_bias`` (``ops.moe.topk_route``).  The
    defaults are one gated-SiLU expert a token.

    ``balance_bias`` takes no gradient (``grad_req="null"``) and starts at
    zero.  It is kept by the auxiliary-loss-free balancing rule (Wang et
    al., arXiv:2408.15664; DeepSeek-V3, arXiv:2412.19437): once a TRAINING
    step, after the step's tokens are routed, ``b_e += bias_update_rate *
    sign(mean load - load_e)`` — an expert that got fewer tokens than an
    even share is raised, one that got more is lowered, and the next step
    routes by the new bias.  ``bias_update_rate=0`` (the default) leaves
    the bias where the caller set it.  The rate is in the units of
    ``probs``: the published 1e-3 goes with scores that spread over tenths.

    State, not trained, rewritten by every training step: ``expert_load``
    (num_experts,) the ROUTES to each expert of the layer
    (``experts_per_token`` a token), and ``rows_computed`` (held,) the rows
    each held expert's products covered.  ``last_expert`` is each token's
    experts at the last EAGER call — (B, S) with one a token, (B, S, k)
    otherwise — for whoever wants to look at the routing itself (a
    compiled step keeps none: its shape follows the batch)."""

    def __init__(self, units, hidden_size, num_experts, experts_held=None,
                 bias_update_rate=0.0, experts_per_token=1, gated=True,
                 activation="silu", normalize_gates=False, gate_scale=1.0,
                 expert_groups=None, topk_groups=None, **kwargs):
        super().__init__(**kwargs)
        self._bias_update_rate = float(bias_update_rate)
        self.experts_per_token = int(experts_per_token)
        self._network = dict(k=self.experts_per_token,
                             normalize=bool(normalize_gates),
                             scale=float(gate_scale), activation=activation)
        if expert_groups is not None:
            self._network.update(groups=int(expert_groups),
                                 topk_groups=int(topk_groups))
        first, end = experts_held or (0, num_experts)
        if not 0 <= first < end <= num_experts:
            raise ValueError("experts_held=%r is no slice of %d experts"
                             % (experts_held, num_experts))
        self.num_experts, self.experts_held = num_experts, (first, end)
        self.last_expert = None
        held = end - first
        with self.name_scope():
            if gated:
                self.gate_weight = self.params.get(
                    "gate_weight", shape=(held, units, hidden_size))
            self.up_weight = self.params.get(
                "up_weight", shape=(held, units, hidden_size))
            self.down_weight = self.params.get(
                "down_weight", shape=(held, hidden_size, units))
            self.balance_bias = self.params.get(
                "balance_bias", shape=(num_experts,), init="zeros",
                grad_req="null", differentiable=False)
            self.expert_load = self.params.get(
                "expert_load", shape=(num_experts,), init="zeros",
                grad_req="null", differentiable=False)
            self.rows_computed = self.params.get(
                "rows_computed", shape=(held,), init="zeros",
                grad_req="null", differentiable=False)
        _LIVE.add(self)

    def cast(self, dtype):
        """The counts stay float32: bfloat16 counts no further than 256."""
        super().cast(dtype)
        for p in (self.expert_load, self.rows_computed, self.balance_bias):
            p.cast("float32")

    def ran_on_blocks(self, load, rows):
        """Which side of ``ops.moe.sparse_ffn`` a step with ``load``
        (num_experts,) routes and ``rows`` (held,) of them computed here
        ran: True the dense products over blocks of slots, False the
        ragged products, None where the layer has no blocks (one flat route
        a token, or a held share too large for them)."""
        if _core.flat_routes(self._network["k"], self._network["normalize"]):
            return None
        blocks = _core.plan_blocks(int(load.sum()), len(rows),
                                   self.num_experts)
        return None if blocks is None else bool(_core.blocks_fit(
            blocks, rows.astype("int64")))

    def hybrid_forward(self, F, x, probs, up_weight, down_weight,
                       balance_bias, expert_load, rows_computed,
                       gate_weight=None):
        y, load, rows, expert = F.sparse_experts(
            x, probs, gate_weight, up_weight, down_weight, balance_bias,
            first=self.experts_held[0], **self._network)
        if not isinstance(expert._data, jax.core.Tracer):
            self.last_expert = expert
        if autograd.is_training():
            with autograd.pause():
                self.expert_load.set_data(load)
                self.rows_computed.set_data(rows)
                self.balance_bias.set_data(
                    balance_bias + self._bias_update_rate
                    * F.sign(F.mean(load) - load))
        return y


def publish_routing_counts():
    """Read the routing counts every live ``SparseExperts`` block kept at
    its last training step into ``telemetry`` and return them.

    The counts are of ROUTES, ``experts_per_token`` a token.  Gauges
    (summed over the blocks): ``moe.tokens_routed`` (routes to any
    expert), ``moe.tokens_local`` (routes to held experts),
    ``moe.dropped`` (routes to held experts that no product covered:
    0 — the layer has no capacity to overflow), ``moe.routes_per_token``
    (the largest ``experts_per_token`` among the blocks),
    ``moe.layers_with_blocks`` (the blocks whose experts have blocks of
    slots to run on: ``ops.moe.plan_blocks`` of the step's routes) and
    ``moe.layers_on_blocks`` (those of them whose held routes FIT the
    blocks at the last step — ``ops.moe.blocks_fit`` of ``rows``, the rule
    the step tests on the device — and so ran the dense products; the
    others ran the ragged side of ``sparse_ffn``'s ``lax.cond``).
    ``moe.expert_load`` is one event a block with its vector.  Returns ``{block name: {"load":
    [...], "held": (first, end), "rows": [...], "on_blocks": True, False
    or None with no blocks}}``, empty before the first
    training step.  One device read a block, after the window: nothing is
    called back from inside the step."""
    out = {}
    for block in sorted(_LIVE, key=lambda b: b.name):
        if block.expert_load._data is None:
            continue
        load = onp.asarray(block.expert_load.data().asnumpy(), "float64")
        rows = onp.asarray(block.rows_computed.data().asnumpy(), "float64")
        if load.sum() == 0:
            continue
        out[block.name] = {"load": load.tolist(), "rows": rows.tolist(),
                           "held": block.experts_held,
                           "routes_per_token": block.experts_per_token,
                           "on_blocks": block.ran_on_blocks(load, rows)}
        telemetry.event("moe.expert_load", block.name, load=load.tolist(),
                        held=list(block.experts_held),
                        routes_per_token=block.experts_per_token)
    held = [sum(v["load"][v["held"][0]:v["held"][1]]) for v in out.values()]
    telemetry.gauge("moe.tokens_routed",
                    sum(sum(v["load"]) for v in out.values()))
    telemetry.gauge("moe.tokens_local", sum(held))
    telemetry.gauge("moe.routes_per_token", max(
        (v["routes_per_token"] for v in out.values()), default=0))
    telemetry.gauge("moe.dropped",
                    sum(held) - sum(sum(v["rows"]) for v in out.values()))
    sides = [v["on_blocks"] for v in out.values()
             if v["on_blocks"] is not None]
    telemetry.gauge("moe.layers_with_blocks", len(sides))
    telemetry.gauge("moe.layers_on_blocks", sum(sides))
    return out
