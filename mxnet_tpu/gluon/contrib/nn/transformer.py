"""Transformer building blocks wired to the fused flash-attention kernel.

Capability target: the attention stack BASELINE.json config 5 (BERT-base
pretraining) needs — the reference's building blocks are the
``_contrib_interleaved_matmul_selfatt_*`` /``_contrib_div_sqrt_dim`` ops
(``src/operator/contrib/transformer.cc``) composed by GluonNLP; here the
hot path is ONE op, ``_contrib_flash_attention`` (Pallas TPU kernel with
fwd+bwd, ``ops/pallas_attention.py``), and the interleaved ops are also
provided for ported code (``ops/contrib_ops.py``).

Layers are batch-major (batch, seq, units), Gluon convention.
Attention-probability dropout is applied to the attention *output* when
the flash path is active (the fused kernel never materializes the
probability matrix — the approximation every flash implementation makes).
Padding masks (``valid_length``) run inside the flash kernel's online
softmax; only an arbitrary additive ``mask`` forces the dense path.
"""
from __future__ import annotations

import weakref

import numpy as onp

from .... import autograd, initializer, telemetry
from ....ops.pallas_attention import bshd_layout_fits
from ...block import HybridBlock
from ...nn import Dense, Dropout, LayerNorm

__all__ = ["MultiHeadAttention", "GroupedQueryAttention", "PositionwiseFFN",
           "GatedFFN", "TransformerEncoderCell", "TransformerEncoder",
           "CompressedConvAttention", "MaskTileCount", "publish_mask_tiles"]

_TILE_COUNTS = weakref.WeakSet()   # the MaskTileCount blocks of this process


class MultiHeadAttention(HybridBlock):
    """Self-attention with a fused qkv projection.

    softmax(q·kᵀ/√d [+ mask])·v over ``num_heads`` heads.  The score/
    softmax/value contraction runs in the Pallas flash kernel on TPU
    (jnp blockwise elsewhere); with an additive mask it falls back to the
    explicit dense composition (equivalent to the reference's
    interleaved_matmul_selfatt_qk → softmax → valatt pipeline).
    """

    def __init__(self, units, num_heads, dropout=0.0, causal=False,
                 use_bias=True, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise ValueError("units %d not divisible by num_heads %d"
                             % (units, num_heads))
        self._units = units
        self._heads = num_heads
        self._causal = causal
        with self.name_scope():
            self.qkv = Dense(3 * units, flatten=False, use_bias=use_bias,
                             prefix="qkv_")
            self.proj = Dense(units, flatten=False, use_bias=use_bias,
                              prefix="out_")
            self.drop = Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x, mask=None, valid_length=None):
        b, l = x.shape[0], x.shape[1]
        d = self._units // self._heads
        # (B, L, 3E) -> three (B, L, H, D) views: no copy
        q, k, v = (part.reshape(b, l, self._heads, d) for part in
                   F.split(self.qkv(x), num_outputs=3, axis=-1))
        if mask is None and bshd_layout_fits(self._heads, d):
            # padding masks (per-row valid length) run INSIDE the flash
            # kernel — masked inside the softmax — so padded batches (the
            # normal BERT case) keep the fused path.  Heads this wide are
            # read where the projection left them, two 64-wide heads a
            # 128-lane block, and the output goes straight into ``proj``:
            # the step holds no head transpose (PERF.md §6, PR 27).
            out = F.flash_attention_bshd(q, k, v, kv_lens=valid_length,
                                         causal=self._causal)
        else:
            # (B, H, L, D): heads the lane blocks do not fit, and the
            # dense composition an additive mask needs
            q, k, v = (part.transpose(axes=(0, 2, 1, 3))
                       for part in (q, k, v))
            if mask is None:
                out = F.flash_attention(q, k, v, kv_lens=valid_length,
                                        causal=self._causal)
            else:
                scores = F.batch_dot(q.reshape(-1, l, d),
                                     k.reshape(-1, l, d),
                                     transpose_b=True) / (d ** 0.5)
                scores = scores.reshape(b, self._heads, l, l) + mask
                if valid_length is not None:
                    # both given: fold the padding mask into the additive
                    # mask (keys at/after the row's valid length score -inf)
                    col = F.arange(0, l).reshape(1, 1, 1, l)
                    vl = valid_length.astype("float32").reshape(-1, 1, 1, 1)
                    scores = scores + \
                        F.broadcast_greater_equal(col, vl) * -1e30
                probs = F.softmax(scores, axis=-1)
                out = F.batch_dot(probs.reshape(-1, l, l),
                                  v.reshape(-1, l, d))
                out = out.reshape(b, self._heads, l, d)
            out = out.transpose(axes=(0, 2, 1, 3))
        out = self.proj(out.reshape(b, l, self._units))
        if self.drop is not None:
            out = self.drop(out)
        return out


class GroupedQueryAttention(HybridBlock):
    """Self-attention with fewer key-value heads than query heads:
    ``softmax(q k^T / sqrt(head_dim) + mask) v`` over ``num_heads`` query
    heads of ``head_dim``, every ``num_heads / num_kv_heads`` of them
    reading one key-value head, no bias.  One fused projection to
    [q | k | v]; attention runs in the flash kernels, the query heads
    sharing their key-value head through the kernels' index maps (no
    repeated K/V).

    ``qk_norm``: q and k get an RMS norm over ``head_dim`` before anything
    else, each with one learned gain of ``head_dim`` that its heads share.
    ``rope_theta``: q and k are rotated (rotate-half, all of ``head_dim``)
    at the token's place in the row, or at ``position_ids`` (B, S) where
    the call gives them; None: no position embedding.  ``rope``: the
    rotation by ``rotary_embedding``'s keyword arguments instead
    (``rotary_dim``, ``theta``, ``rope_type="yarn"`` with ``factor``,
    ``original_length``, ``beta_fast``, ``beta_slow``,
    ``attention_factor``): a partial or a scaled one.
    ``head_gate``: every head's output is multiplied by ``sigmoid(x W_g)``
    before the output projection, ``W_g`` (units, num_heads) — the
    head-wise output gate of Qiu et al., arXiv:2505.06708.
    ``window``: a query sees the ``window`` keys that end with its own
    (``flash_attention``'s ``window``: the mask's three integers made from
    the shapes, the tiles under the band never visited).

    ``forward(x)`` is causal, inside the window where there is one.
    ``forward(x, position_ids, q_mask, kv_mask)`` takes the mask as data,
    (B, S, 2) integers each (``q_mask`` (B, S, 3) with a floor) — a
    query's [reach, own(, floor)], a key's [rank, own],
    ``flash_attention`` has the rule — and is causal only if they say
    so."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 qk_norm=False, rope_theta=None, epsilon=1e-6, rope=None,
                 head_gate=False, window=None, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError("%d query heads do not divide over %d "
                             "key-value heads" % (num_heads, num_kv_heads))
        if rope is not None and rope_theta is not None:
            raise ValueError("rope_theta is rope={'theta': ...}: give one")
        self._heads = (num_heads, num_kv_heads, head_dim)
        self._rope = dict(rope) if rope is not None else \
            None if rope_theta is None else {"theta": rope_theta}
        self._epsilon, self._window = epsilon, window
        with self.name_scope():
            self.qkv = Dense((num_heads + 2 * num_kv_heads) * head_dim,
                             flatten=False, use_bias=False, in_units=units,
                             prefix="qkv_")
            self.proj = Dense(units, flatten=False, use_bias=False,
                              in_units=num_heads * head_dim, prefix="out_")
            self.gate = Dense(num_heads, flatten=False, use_bias=False,
                              in_units=units, prefix="gate_") \
                if head_gate else None
            if qk_norm:
                self.q_norm_gamma = self.params.get(
                    "q_norm_gamma", shape=(head_dim,), init="ones")
                self.k_norm_gamma = self.params.get(
                    "k_norm_gamma", shape=(head_dim,), init="ones")

    def hybrid_forward(self, F, x, position_ids=None, q_mask=None,
                       kv_mask=None, q_norm_gamma=None, k_norm_gamma=None):
        heads, kv_heads, d = self._heads
        b, s = x.shape[0], x.shape[1]
        qkv = self.qkv(x)

        def part(begin, end, gamma=None, rotate=False):
            # (B, S, h d) -> (B, h, S, d)
            out = F.slice_axis(qkv, axis=-1, begin=begin * d,
                               end=end * d).reshape(b, s, end - begin, d)
            if gamma is not None:
                out = F.RMSNorm(out, gamma, eps=self._epsilon)
            out = out.transpose(axes=(0, 2, 1, 3))
            if rotate and self._rope is not None:
                out = F.rotary_embedding(out, position_ids, **self._rope)
            return out

        out = F.flash_attention(
            part(0, heads, q_norm_gamma, True),
            part(heads, heads + kv_heads, k_norm_gamma, True),
            part(heads + kv_heads, heads + 2 * kv_heads),
            causal=q_mask is None, q_mask=q_mask, kv_mask=kv_mask,
            window=self._window if q_mask is None else None)
        out = out.transpose(axes=(0, 2, 1, 3))               # (B, S, h, d)
        if self.gate is not None:
            gate = F.sigmoid(self.gate(x).astype("float32")).astype(x.dtype)
            out = F.broadcast_mul(out, gate.reshape(b, s, heads, 1))
        return self.proj(out.reshape(b, s, -1))


class MaskTileCount(HybridBlock):
    """What a mask given as data leaves of the attention kernels' tiles,
    observable without a callback in the step: ``forward(q_mask,
    kv_mask)`` counts, from the per-tile summary the kernels skip by
    (``ops.pallas_attention.mask_tiles``), the tiles a head row of one
    ``flash_attention`` call visits and has — forward, ``dq`` and
    ``dk/dv`` together — times ``calls`` (the layers a model runs under
    the one mask), and a TRAINING step keeps the pair as non-trainable
    state, as ``SparseExperts`` keeps its loads; ``publish_mask_tiles``
    reads it.  Zeros where the kernels stream no K blocks (short rows,
    no TPU).  ``window``: the masks handed in are
    ``ops.pallas_attention.window_mask``'s of that window, whose calls
    plan their blocks by the band."""

    def __init__(self, head_dim, calls=1, dtype="bfloat16", window=None,
                 **kwargs):
        super().__init__(**kwargs)
        self._plan = dict(head_dim=int(head_dim), dtype=dtype, window=window)
        self._calls = float(calls)
        with self.name_scope():
            self.tiles = self.params.get(
                "tiles", shape=(2,), init="zeros", grad_req="null",
                differentiable=False)
        _TILE_COUNTS.add(self)

    def cast(self, dtype):
        """The counts stay float32; the kernels' blocks follow ``dtype``."""
        super().cast(dtype)
        self.tiles.cast("float32")
        self._plan["dtype"] = str(dtype)

    def hybrid_forward(self, F, q_mask, kv_mask, tiles):
        visited, total = F.attention_mask_tiles(q_mask, kv_mask,
                                                **self._plan)
        counts = F.stack(visited, total) * self._calls
        if autograd.is_training():
            with autograd.pause():
                self.tiles.set_data(counts)
        return counts


def publish_mask_tiles():
    """Read what every live ``MaskTileCount`` kept at its last training
    step into ``telemetry`` — gauges ``attention.mask.tiles_visited`` and
    ``attention.mask.tiles_total``, summed over the blocks — and return
    the pair.  One device read a block, after the window: nothing is
    called back from inside the step."""
    visited = total = 0.0
    for block in _TILE_COUNTS:
        if block.tiles._data is not None:
            pair = onp.asarray(block.tiles.data().asnumpy(), "float64")
            visited, total = visited + pair[0], total + pair[1]
    telemetry.gauge("attention.mask.tiles_visited", visited)
    telemetry.gauge("attention.mask.tiles_total", total)
    return visited, total


class PositionwiseFFN(HybridBlock):
    """The dense feed-forward block: Dense→activation→Dense (+dropout).
    ``activation`` is any of ``Activation``'s (``gelu``, ``relu2`` — the
    squared ReLU — ...); ``use_bias=False`` leaves both biases out;
    ``in_units`` (the input's width: ``units``) fixes the shapes at
    construction, 0 defers them to the first call as ``Dense`` does."""

    def __init__(self, units, hidden_size, activation="gelu", dropout=0.0,
                 use_bias=True, in_units=0, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.expand = Dense(hidden_size, flatten=False,
                                activation=activation, use_bias=use_bias,
                                in_units=in_units, prefix="fc1_")
            self.contract = Dense(units, flatten=False, use_bias=use_bias,
                                  in_units=hidden_size if in_units else 0,
                                  prefix="fc2_")
            self.drop = Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x):
        out = self.contract(self.expand(x))
        if self.drop is not None:
            out = self.drop(out)
        return out


class GatedFFN(HybridBlock):
    """The gated dense feed-forward block: ``W_down(act(W_gate x) *
    W_up x)``, no bias — the network ``SparseExperts`` runs an expert
    (``gated``), dense: a leading dense layer's, a shared expert's.
    ``activation`` is any of ``Activation``'s; ``in_units`` as
    ``PositionwiseFFN``'s."""

    def __init__(self, units, hidden_size, activation="silu", in_units=0,
                 **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            def dense(width, in_units, prefix, activation=None):
                return Dense(width, flatten=False, use_bias=False,
                             in_units=in_units, activation=activation,
                             prefix=prefix)
            self.gate = dense(hidden_size, in_units, "gate_", activation)
            self.up = dense(hidden_size, in_units, "up_")
            self.down = dense(units, hidden_size if in_units else 0,
                              "down_")

    def hybrid_forward(self, F, x):
        return self.down(self.gate(x) * self.up(x))


class TransformerEncoderCell(HybridBlock):
    """Post-LN (BERT-style) encoder layer:
    x → x+MHA(x) → LN → +FFN → LN."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 causal=False, layer_norm_eps=1e-12, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attention = MultiHeadAttention(units, num_heads,
                                                dropout=dropout,
                                                causal=causal,
                                                prefix="attn_")
            self.attn_norm = LayerNorm(epsilon=layer_norm_eps,
                                       prefix="attn_ln_")
            self.ffn = PositionwiseFFN(units, hidden_size, dropout=dropout,
                                       prefix="ffn_")
            self.ffn_norm = LayerNorm(epsilon=layer_norm_eps,
                                      prefix="ffn_ln_")

    def hybrid_forward(self, F, x, mask=None, valid_length=None):
        x = self.attn_norm(x + self.attention(x, mask, valid_length))
        return self.ffn_norm(x + self.ffn(x))


class TransformerEncoder(HybridBlock):
    """A stack of ``num_layers`` encoder cells."""

    def __init__(self, num_layers, units, hidden_size, num_heads,
                 dropout=0.0, causal=False, **kwargs):
        super().__init__(**kwargs)
        self.cells = []
        with self.name_scope():
            for i in range(num_layers):
                cell = TransformerEncoderCell(
                    units, hidden_size, num_heads, dropout=dropout,
                    causal=causal, prefix="layer%d_" % i)
                self.register_child(cell)
                self.cells.append(cell)

    def hybrid_forward(self, F, x, mask=None, valid_length=None):
        for cell in self.cells:
            x = cell(x, mask, valid_length)
        return x


class CompressedConvAttention(HybridBlock):
    """Compressed convolutional attention (CCA, arXiv:2510.04476): causal
    grouped-query attention wholly in a compressed latent.

    ``x`` (B, S, units) is projected down to ``num_heads`` query heads and
    ``num_kv_heads`` key-value heads of ``head_dim`` (no bias anywhere);
    q and k are mixed along the sequence by two causal convolutions
    (depthwise of kernel ``conv_kernels[0]``, then grouped with one group
    a head of kernel ``conv_kernels[1]``), get the q-k mean added, are
    L2-normalised (k with a learned temperature a key-value head) and
    rotated on ``rotary_dim`` of their dimensions; the values are half from
    this step and half from the step before.  The equations are
    ``ops.nn.cca_qkv``'s.  Attention runs in the flash kernels, the query
    heads sharing their key-value head through the kernels' index maps,
    and the output goes up from the latent to ``units``."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 conv_kernels=(2, 2), rotary_dim=0, rope_theta=10000.0,
                 **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads or num_kv_heads % 2:
            raise ValueError(
                "%d query heads must divide over an even number of "
                "key-value heads, got %d" % (num_heads, num_kv_heads))
        self._shape = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                           rotary_dim=rotary_dim, theta=float(rope_theta))
        q_width, kv_width = num_heads * head_dim, num_kv_heads * head_dim
        channels = q_width + kv_width
        with self.name_scope():
            def down(width, prefix):
                return Dense(width, flatten=False, use_bias=False,
                             in_units=units, prefix=prefix)
            self.q_proj = down(q_width, "q_")
            self.k_proj = down(kv_width, "k_")
            self.v_now = down(kv_width // 2, "v_now_")
            self.v_prev = down(kv_width // 2, "v_prev_")
            self.out_proj = Dense(units, flatten=False, use_bias=False,
                                  in_units=q_width, prefix="out_")
            # taps drawn so that a convolution's output is as large as its
            # input whatever the model's own initializer gives matrices
            self.conv0_weight = self.params.get(
                "conv0_weight", shape=(channels, 1, conv_kernels[0]),
                init=initializer.Normal(0.5))
            self.conv1_weight = self.params.get(
                "conv1_weight", shape=(channels, head_dim, conv_kernels[1]),
                init=initializer.Normal(
                    (head_dim * conv_kernels[1]) ** -0.5))
            self.k_scale = self.params.get(
                "k_scale", shape=(num_kv_heads,), init="ones")

    def hybrid_forward(self, F, x, conv0_weight, conv1_weight, k_scale):
        q, k, v = F.cca_qkv(self.q_proj(x), self.k_proj(x), self.v_now(x),
                            self.v_prev(x), conv0_weight, conv1_weight,
                            k_scale, **self._shape)
        out = F.flash_attention(q, k, v, causal=True)      # (B, H, S, d)
        b, s = x.shape[0], x.shape[1]
        return self.out_proj(out.transpose(axes=(0, 2, 1, 3)).reshape(
            b, s, -1))
