"""Laguna through ``gluon.model_zoo.laguna`` against the plain float32
reference in ``benchmark/configs/laguna-s-2.1-ep32share/model.py``, at toy
sizes on the CPU, seeded weights: YaRN's frequencies against the closed
form, the scaled partial rotary embedding, the head gate, the gated dense
block, a window layer against a dense band at groups of 6 and 9, the whole
model with both layer types and both head counts (logits, loss, every
parameter's gradient), the step through ``DataParallelStep``, ``compare``'s
three controls, the tracing, and THE SHARE TEST: four shares' held-expert
parts, with the shared expert and the residual counted once, add up to the
uncut layer."""
import importlib.util
import json
import math
import os

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, context, gluon, parallel, telemetry
from mxnet_tpu.gluon.contrib import nn as cnn
from mxnet_tpu.gluon.model_zoo.laguna import FULL, SLIDING, LagunaLayer
from mxnet_tpu.ops import nn as nn_ops
from mxnet_tpu.ops import pallas_attention as PA

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "..", "benchmark", "configs",
                      "laguna-s-2.1-ep32share")


def _load_model():
    spec = importlib.util.spec_from_file_location(
        "laguna_bench_model", os.path.join(CONFIG, "model.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


M = _load_model()


def _toy_sizes(**over):
    with open(os.path.join(CONFIG, "config.json")) as f:
        config = json.load(f)
    sizes = {k: v for k, v in config.items() if k != "rehearsal"}
    for key, value in config["rehearsal"].items():
        sizes[key] = dict(sizes[key], **value) \
            if isinstance(value, dict) and key != "rope_parameters" \
            else value
    sizes.update(over)
    return sizes


def _toy(dtype="float32", seed=3, **over):
    """(sizes, net, tokens, labels) of the rehearsal-sized model, two
    rows: layers full, sliding x 3, full with 6, 9, 9, 9, 6 query heads on
    one key-value head, a window of 8 in a row of 64."""
    sizes = _toy_sizes(**over)
    mx.random.seed(seed)
    onp.random.seed(seed)
    rs = onp.random.RandomState(seed)
    net = M._net(sizes)
    # wider than the cell's 0.02 so that at toy widths no path is faint
    net.initialize(mx.init.Normal(0.2))
    if dtype != "float32":
        net.cast(dtype)
    return (sizes, net) + M.draw_tokens(sizes, rs, 2)


def _ids(a):
    return mx.nd.array(onp.asarray(a).astype("int32"), dtype="int32")


def _rel(got, want):
    return float(onp.abs(got - want).max() / max(onp.abs(want).max(), 1e-30))


# ---------------------------------------------------------------------------
# rotary: the rule, the part, the factor
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("theta,factor,r,length", [
    (500000.0, 128.0, 64, 8192),      # the published full layers
    (10000.0, 4.0, 128, 4096),
    (1000000.0, 32.0, 32, 2048)])
def test_yarn_frequencies_are_the_closed_form(theta, factor, r, length):
    """``yarn_ramp`` and the blended frequencies against the formula
    written out: corr(n) = r ln(L / (2 pi n)) / (2 ln theta), low =
    floor(corr(32)), high = ceil(corr(1)), a linear ramp between."""
    low = max(math.floor(r * math.log(length / (2 * math.pi * 32))
                         / (2 * math.log(theta))), 0)
    high = min(math.ceil(r * math.log(length / (2 * math.pi * 1))
                         / (2 * math.log(theta))), r - 1)
    assert 0 <= low < high
    i = onp.arange(r // 2)
    ramp = onp.clip((i - low) / (high - low), 0, 1)
    onp.testing.assert_allclose(
        nn_ops.yarn_ramp(r, theta, length), ramp, rtol=1e-6, atol=1e-7)
    assert ramp[0] == 0 and ramp[-1] == 1         # both ends are met
    inv = theta ** (-2.0 * i / r)
    want = inv * (1 - ramp) + inv / factor * ramp
    # the reference's own closed form, and the op's angles at position 1:
    # x = (1, 0) pairs turn into (cos f, sin f)
    given = {"partial_rotary_factor": 1, "rope_theta": theta,
             "rope_type": "yarn", "factor": factor,
             "original_max_position_embeddings": length, "beta_fast": 32,
             "beta_slow": 1, "attention_factor": 1.25}
    freq, scale, width = M.rotary_frequencies(given, r)
    onp.testing.assert_allclose(freq, want, rtol=1e-12)
    assert (scale, width) == (1.25, r)
    x = jnp.concatenate([jnp.ones((1, 1, 2, r // 2)),
                         jnp.zeros((1, 1, 2, r // 2))], -1)
    out = onp.asarray(nn_ops.rotary_embedding(
        x, theta=theta, rope_type="yarn", factor=factor,
        original_length=length, attention_factor=1.25))[0, 0, 1]
    onp.testing.assert_allclose(out[:r // 2], 1.25 * onp.cos(want),
                                rtol=2e-5, atol=2e-6)
    onp.testing.assert_allclose(out[r // 2:], 1.25 * onp.sin(want),
                                rtol=2e-5, atol=2e-6)


def test_partial_scaled_rotary_leaves_the_rest_and_the_default_alone():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 10, 16))
    out = nn_ops.rotary_embedding(x, rotary_dim=8, theta=5e5,
                                  rope_type="yarn", factor=128.0,
                                  original_length=16, attention_factor=1.5)
    onp.testing.assert_array_equal(onp.asarray(out[..., 8:]),
                                   onp.asarray(x[..., 8:]))
    # position 0 turns nothing: the factor alone is left
    onp.testing.assert_allclose(onp.asarray(out[:, :, 0, :8]),
                                1.5 * onp.asarray(x[:, :, 0, :8]), rtol=1e-6)
    # the default rule with its defaults spelled out is the old call
    onp.testing.assert_array_equal(
        onp.asarray(nn_ops.rotary_embedding(x, theta=1e4)),
        onp.asarray(nn_ops.rotary_embedding(
            x, None, 0, 1e4, "default", 1.0, 0, 32.0, 1.0, 1.0)))
    with pytest.raises(ValueError, match="neither default nor yarn"):
        nn_ops.rotary_embedding(x, rope_type="linear")


def test_rotary_census_counts_the_rule_once_a_traced_shape():
    x = jnp.ones((1, 2, 8, 16))
    before = dict(telemetry.snapshot()["counters"])

    @jax.jit
    def both(x):
        return nn_ops.rotary_embedding(x, theta=1e4) + nn_ops.rotary_embedding(
            x, rotary_dim=8, theta=5e5, rope_type="yarn", factor=128.0,
            original_length=16)
    both(x)
    both(x)                       # the second call traces nothing
    after = telemetry.snapshot()["counters"]
    assert after["rotary.rule.default"] \
        == before.get("rotary.rule.default", 0) + 1
    assert after["rotary.rule.yarn"] == before.get("rotary.rule.yarn", 0) + 1
    event = [e for e in telemetry.snapshot()["events"]
             if e.get("kind") == "rotary"][-1]
    assert (event["rule"], event["rotary_dim"], event["factor"]) \
        == ("yarn", 8, 128.0)


# ---------------------------------------------------------------------------
# the blocks
# ---------------------------------------------------------------------------

def test_gated_ffn_is_down_of_silu_gate_times_up():
    rs = onp.random.RandomState(0)
    block = cnn.GatedFFN(6, 10, in_units=6)
    block.initialize(mx.init.Normal(0.5))
    x = rs.randn(2, 5, 6).astype("float32")
    w = {name[len(block.prefix):]: p.data().asnumpy()
         for name, p in block.collect_params().items()}
    assert sorted(w) == ["down_weight", "gate_weight", "up_weight"]
    gate = x @ w["gate_weight"].T
    want = (gate / (1 + onp.exp(-gate)) * (x @ w["up_weight"].T)) \
        @ w["down_weight"].T
    onp.testing.assert_allclose(block(mx.nd.array(x)).asnumpy(), want,
                                rtol=1e-5, atol=1e-6)


def _dense_attention(x, w, heads, kv_heads, d, window=None, gate=None):
    """Plain grouped-query attention of one row with no rotary, by
    repetition, under a dense causal (band) mask, gated a head."""
    s = x.shape[0]
    qkv = x @ w["qkv_weight"].T
    q = qkv[:, :heads * d].reshape(s, heads, d).transpose(1, 0, 2)
    k, v = (onp.repeat(qkv[:, (heads + j * kv_heads) * d:
                           (heads + (j + 1) * kv_heads) * d].reshape(
        s, kv_heads, d).transpose(1, 0, 2), heads // kv_heads, 0)
        for j in (0, 1))
    i, j = onp.arange(s)[:, None], onp.arange(s)[None, :]
    seen = (j <= i) if window is None else (j <= i) & (j > i - window)
    score = onp.where(seen, q @ k.transpose(0, 2, 1) / d ** 0.5, -1e30)
    p = onp.exp(score - score.max(-1, keepdims=True))
    out = (p / p.sum(-1, keepdims=True)) @ v                  # (h, s, d)
    out = out.transpose(1, 0, 2)
    if gate is not None:
        out = out * (1 / (1 + onp.exp(-(x @ gate.T))))[:, :, None]
    return out.reshape(s, heads * d) @ w["out_weight"].T


@pytest.mark.parametrize("heads,kv_heads,window", [
    (6, 1, None), (9, 1, 5), (12, 2, 7), (18, 2, 40)])
def test_attention_block_gates_a_head_and_keeps_its_window(heads, kv_heads,
                                                           window):
    """Groups of 6 and 9 query heads a key-value head, the head gate, a
    window narrower than the row and one that covers it."""
    d, units, s = 8, 12, 24
    block = cnn.GroupedQueryAttention(units, heads, kv_heads, d,
                                      head_gate=True, window=window)
    block.initialize(mx.init.Normal(0.4))
    w = {name[len(block.prefix):]: p.data().asnumpy()
         for name, p in block.collect_params().items()}
    assert w["gate_weight"].shape == (heads, units)
    x = onp.random.RandomState(1).randn(2, s, units).astype("float32")
    got = block(mx.nd.array(x)).asnumpy()
    for b in range(2):
        want = _dense_attention(x[b], w, heads, kv_heads, d, window,
                                w["gate_weight"])
        onp.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-5)
    # without the gate the same weights give another answer
    plain = _dense_attention(x[0], w, heads, kv_heads, d, window)
    assert onp.abs(plain - got[0]).max() > 1e-3
    with pytest.raises(ValueError, match="give one"):
        cnn.GroupedQueryAttention(units, heads, kv_heads, d, rope_theta=1e4,
                                  rope={"theta": 1e4})


def test_model_reads_its_layers_from_the_three_lists():
    sizes, net, _, _ = _toy()
    kinds = [(layer.attention._window, layer.attention._heads[0],
              layer.attention._rope.get("rope_type", "default"), layer.kind)
             for layer in net.layers]
    assert kinds == [(None, 6, "yarn", "dense"), (8, 9, "default", "sparse"),
                     (8, 9, "default", "sparse"), (8, 9, "default", "sparse"),
                     (None, 6, "yarn", "sparse")]
    assert net.layers[0].attention._rope["rotary_dim"] == 8
    assert net.mask_tiles._calls == 3
    with pytest.raises(ValueError, match="three lists"):
        gluon.model_zoo.laguna(num_layers=2, layer_types=[FULL])
    with pytest.raises(ValueError, match="layer_types holds"):
        gluon.model_zoo.laguna(num_layers=1, layer_types=["linear"],
                               heads_per_layer=[8],
                               mlp_layer_types=["dense"])
    with pytest.raises(ValueError, match="neither dense nor sparse"):
        gluon.model_zoo.laguna(num_layers=1, layer_types=[SLIDING],
                               heads_per_layer=[8], mlp_layer_types=["moe"])
    # the published pattern: a full layer every fourth, layer 0 dense
    published = gluon.model_zoo.laguna(
        num_layers=8, vocab_size=32, units=16, num_kv_heads=1, head_dim=8,
        dense_hidden=8, num_experts=4, experts_per_token=2, expert_hidden=8,
        shared_hidden=8,
        full_rope=dict(rotary_dim=4, theta=5e5, rope_type="yarn",
                       factor=128.0, original_length=16))
    assert [(l.attention._window is None, l.attention._heads[0], l.kind)
            for l in published.layers] == [
        (True, 48, "dense")] + [(False, 72, "sparse")] * 3 + [
        (True, 48, "sparse")] + [(False, 72, "sparse")] * 3


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

def test_dense_mask_is_the_band():
    seen = onp.asarray(M.dense_mask(jnp.arange(6), 6, 3))
    assert ["".join(".X"[int(v)] for v in row) for row in seen] == [
        "X.....", "XX....", "XXX...", ".XXX..", "..XXX.", "...XXX"]
    assert onp.asarray(M.dense_mask(jnp.arange(6), 6)).sum() == 21
    sizes = _toy_sizes()
    assert M.live_pairs(sizes, SLIDING) == 8 * 9 // 2 + 56 * 8
    assert M.live_pairs(sizes, FULL) == 64 * 65 // 2


def test_logits_match_reference_float32():
    sizes, net, tokens, _ = _toy()
    positions = onp.stack([onp.arange(0, sizes["seq_len"], 5)] * 2)
    got = net(_ids(tokens), _ids(positions)).asnumpy()
    want = M.reference_forward(M.host_params(net), tokens, positions, sizes)
    assert got.shape == want.shape == (2, positions.shape[1],
                                       sizes["vocab_size"])
    assert onp.abs(got - want).max() <= 1e-5 * onp.abs(want).max()
    hidden, table = net(_ids(tokens))
    assert hidden.shape == (2, sizes["seq_len"], sizes["hidden_size"])
    assert table.shape == (sizes["vocab_size"], sizes["hidden_size"])


def test_loss_and_every_gradient_match_reference_float32():
    sizes, net, tokens, labels = _toy()
    loss_fn = gluon.loss.TiedSoftmaxCrossEntropyLoss(
        block_rows=sizes["train"]["loss_block_rows"])
    with autograd.record():
        loss = loss_fn(net(_ids(tokens)), _ids(labels)).mean()
    loss.backward()
    want_loss, want = M.reference_loss_and_grads(
        M.host_params(net), tokens, labels, sizes)
    assert abs(float(loss.asnumpy()) - want_loss) <= 1e-5 * want_loss
    trained = {name[len(net.prefix):]: p
               for name, p in net.collect_params().items()
               if p.grad_req != "null"}
    assert set(trained) == set(want)
    # both layer types, both head counts, the gate, the dense block, the
    # shared expert: every one of them carries a gradient that agrees
    for name in ("layer0_attn_gate_weight", "layer1_attn_gate_weight",
                 "layer0_ffn_gate_weight", "layer2_shared_up_weight",
                 "layer4_attn_qkv_weight", "layer3_router_weight"):
        assert onp.abs(want[name]).max() > 0, name
    for name, p in trained.items():
        assert _rel(p.grad().asnumpy(), want[name]) < 5e-5, name


def test_compare_passes_and_each_control_fails():
    """``compare`` hands on matching logits; the reference in float8, the
    reference with the window's lower bound dropped and the reference
    with the default rotary rule on the full layers each fail the logits'
    limit (or the routing's: NaN).  Half the positions lie past the
    window's reach."""
    from benchmark import correct

    sizes, net, tokens, _ = _toy()
    positions = M.draw_positions(sizes, onp.random.RandomState(0), 2)
    past = sizes["train"]["check_positions_past"]
    assert ((positions >= past).sum(1) == positions.shape[1] // 2).all()
    logits = net(_ids(tokens), _ids(positions)).asnumpy()
    chosen = onp.stack([net.layers[i].experts.last_expert.asnumpy()
                        for i in (1, 2, 3, 4)])
    assert chosen.shape == (4, 2, sizes["seq_len"], 3)
    args = (logits, chosen, M.host_params(net), tokens, positions, sizes)
    assert correct.logits_agree(*M.compare(*args))["ok"]
    for control in ("float8", "drop_window", "default_rotary"):
        assert not correct.logits_agree(
            *M.compare(*args, **{control: True}))["ok"], control


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_trains_through_data_parallel_step(dtype):
    """``HybridBlock`` -> ``DataParallelStep`` -> ``Adam(multi_precision)``:
    the step lowers ONCE, the loss falls, the routing counts ride as
    state (3 routes a token here), and the program carries the blocks'
    names."""
    sizes, net, tokens, labels = _toy(dtype=dtype)
    step = parallel.DataParallelStep(
        net, gluon.loss.TiedSoftmaxCrossEntropyLoss(
            block_rows=sizes["train"]["loss_block_rows"]),
        mx.optimizer.Adam(learning_rate=3e-3,
                          multi_precision=dtype != "float32"))
    data, label = _ids(tokens), _ids(labels)
    losses = [float(step(data, label).asnumpy().astype("float32").mean())
              for _ in range(8)]
    assert len(step._cache) == 1
    assert all(onp.isfinite(losses)) and losses[-1] < losses[0] - 0.05
    routes = tokens.size * sizes["num_experts_per_tok"]
    counts = cnn.publish_routing_counts()
    mine = [v for name, v in counts.items() if name.startswith(net.prefix)]
    assert len(mine) == 4
    for record in mine:
        assert sum(record["load"]) == routes
        assert record["routes_per_token"] == 3
    assert not net.layers[1].experts.balance_bias.data().asnumpy().any()
    if dtype == "float32":
        text = step.lower(data, label).as_text(debug_info=True)
        for block in ("layer0_attn_qkv", "layer0_attn_gate", "layer0_ffn_up",
                      "layer1_attn", "layer1_router", "layer1_experts",
                      "layer1_shared_down", "final_norm"):
            assert "/%s%s/" % (net.prefix, block) in text, block


# ---------------------------------------------------------------------------
# tracing: the window in the census, the tiles at trace time
# ---------------------------------------------------------------------------

def test_window_call_is_planned_and_counted_at_trace_time(monkeypatch):
    """A window call is a masked call whose mask comes from the shapes:
    under ``jit`` too the event carries ``window``, ``tiles`` and
    ``tiles_visited``; the model's one ``MaskTileCount`` counts its three
    window layers' tiles, forward and both backward kernels."""
    q_mask, kv_mask = (jnp.asarray(m)
                       for m in PA.window_mask(1, 4096, 4096, 512))
    assert q_mask.shape == (1, 4096, 3) and kv_mask.shape == (1, 4096, 2)
    assert q_mask[0, 1000].tolist() == [1000, -1, 489]
    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    q = jax.ShapeDtypeStruct((1, 9, 4096, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 1, 4096, 128), jnp.bfloat16)
    before = dict(telemetry.snapshot()["counters"])
    jax.eval_shape(lambda q, k, v: PA.flash_attention(
        q, k, v, True, None, None, None, None, None, None, 512), q, k, k)
    after = telemetry.snapshot()["counters"]
    for name in ("attention.kernel.window", "attention.kernel.masked",
                 "attention.kernel.streaming"):
        assert after[name] == before.get(name, 0) + 1, name
    event = [e for e in telemetry.snapshot()["events"]
             if e.get("kind") == "attention_dispatch"][-1]
    block_q, block_k = event["block_q"], event["block_k"]
    n_q, n_k = 4096 // block_q, 4096 // block_k
    band = sum(1 for i in range(n_q) for j in range(n_k)
               if j * block_k <= i * block_q + block_q - 1
               and j * block_k + block_k - 1 >= i * block_q - 511)
    assert (event["window"], event["masked"], event["tiles"],
            event["tiles_visited"]) == (512, True, n_q * n_k, band)
    assert band < 0.6 * n_q * n_k
    # a window that covers the row is plain causal attention
    jax.eval_shape(lambda q, k, v: PA.flash_attention(
        q, k, v, True, None, None, None, None, None, None, 4096), q, k, k)
    last = [e for e in telemetry.snapshot()["events"]
            if e.get("kind") == "attention_dispatch"][-1]
    assert "window" not in last and "masked" not in last
    assert telemetry.snapshot()["counters"]["attention.kernel.window"] \
        == after["attention.kernel.window"]
    with pytest.raises(ValueError, match="goes with causal=True"):
        jax.eval_shape(lambda q, k, v: PA.flash_attention(
            q, k, v, False, None, None, None, None, None, None, 512),
            q, k, k)
    # the blocks follow the band: 512 x 512 under a window of 512, where
    # 15 of 64 tiles are live in each of the three kernels
    assert (block_q, block_k, band) == (512, 512, 15)
    visited, total = (float(x) for x in PA.mask_tiles(q_mask, kv_mask, 128,
                                                      window=512))
    assert (visited, total) == (3 * 15, 3 * 64)
    counter = cnn.MaskTileCount(128, calls=3, window=512)
    counter.initialize()
    with autograd.train_mode():
        counter(mx.nd.array(q_mask, dtype="int32"),
                mx.nd.array(kv_mask, dtype="int32"))
    assert counter.tiles.data().asnumpy().tolist() == [3 * visited,
                                                       3 * total]


# ---------------------------------------------------------------------------
# THE SHARE TEST
# ---------------------------------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer():
    """4 shares of 8 of 32 experts, 5 routes a token, a sliding layer of 9
    query heads on 1 key-value head.  Attention, the residual and the
    SHARED expert — which every share computes alike — counted ONCE, plus
    the shares' held-expert parts, equal the uncut reference layer; one
    share alone does not."""
    experts, units, hidden, k, d, heads = 32, 16, 12, 5, 8, 9
    rs = onp.random.RandomState(0)
    full = {"attn_norm_gamma": rs.rand(units) + 0.5,
            "ffn_norm_gamma": rs.rand(units) + 0.5,
            "attn_qkv_weight": rs.randn((heads + 2) * d, units) * 0.3,
            "attn_gate_weight": rs.randn(heads, units) * 0.5,
            "attn_out_weight": rs.randn(units, heads * d) * 0.3,
            "router_weight": rs.randn(experts, units) * 0.5,
            "experts_gate_weight": rs.randn(experts, units, hidden) * 0.3,
            "experts_up_weight": rs.randn(experts, units, hidden) * 0.3,
            "experts_down_weight": rs.randn(experts, hidden, units) * 0.3,
            "shared_gate_weight": rs.randn(hidden, units) * 0.3,
            "shared_up_weight": rs.randn(hidden, units) * 0.3,
            "shared_down_weight": rs.randn(units, hidden) * 0.3}
    full = {name: value.astype("float32") for name, value in full.items()}
    length, window = 20, 6
    x = rs.randn(1, length, units).astype("float32")
    attention = dict(num_heads=heads, num_kv_heads=1, head_dim=d,
                     rope={"theta": 1e4}, window=window)
    outs, parts = [], []
    for first in range(0, experts, 8):
        layer = LagunaLayer(units, attention, "sparse", 0, dict(
            hidden_size=hidden, num_experts=experts,
            experts_held=(first, first + 8), experts_per_token=k,
            gate_scale=2.5, shared_hidden=hidden), 1e-6)
        layer.initialize()
        for name, p in layer.collect_params().items():
            short = name[len(layer.prefix):]
            if short in full:
                held = short.startswith("experts_")
                p.set_data(mx.nd.array(
                    full[short][first:first + 8] if held else full[short]))
        xs = mx.nd.array(x)
        after = xs + layer.attention(layer.attn_norm(xs))
        h = layer.ffn_norm(after)
        parts.append(layer.experts(h, layer.router(h)).asnumpy())
        outs.append(layer(xs).asnumpy())
        alike = (after + layer.shared(h)).asnumpy()
    sizes = {"rms_norm_eps": 1e-6, "head_dim": d, "sliding_window": window,
             "layer_types": [SLIDING], "mlp_layer_types": ["sparse"],
             "num_attention_heads_per_layer": [heads],
             "num_key_value_heads": 1,
             "rope_parameters": {SLIDING: {
                 "rope_type": "default", "rope_theta": 1e4,
                 "partial_rotary_factor": 1}},
             "num_experts_per_tok": k, "moe_routed_scaling_factor": 2.5,
             "deployment": {"experts_held": [0, experts]}}
    with jax.default_matmul_precision("highest"):
        want = onp.asarray(M._layer_fn(sizes, 0)(
            jnp.asarray(x[0]),
            {name: jnp.asarray(v) for name, v in full.items()})[0])[None]
    onp.testing.assert_allclose(sum(parts) + alike, want, rtol=2e-5,
                                atol=2e-6)
    onp.testing.assert_allclose(outs[2], parts[2] + alike, rtol=1e-5,
                                atol=1e-6)
    assert onp.abs(outs[2] - want).max() > 1e-2
