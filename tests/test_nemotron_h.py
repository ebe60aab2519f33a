"""Nemotron-H through ``gluon.model_zoo.nemotron_h`` against the plain
float32 reference in ``benchmark/configs/nemotron3-nano-30b-ep16share/
model.py``, at toy sizes on the CPU, seeded weights: the chunked state-space
scan against the step-by-step recurrence (value and every gradient), each
kind of layer against the reference's layer, the whole model (logits, loss,
every parameter's gradient), the step through ``DataParallelStep``, top-k
routing, and THE SHARE TEST: the shares' held-expert parts plus the shared
expert and the residual counted once add up to the uncut layer."""
import importlib.util
import json
import os
import re

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, parallel, telemetry
from mxnet_tpu.gluon.contrib import nn as cnn
from mxnet_tpu.gluon.model_zoo.nemotron_h import NemotronHLayer
from mxnet_tpu.ops import moe as moe_ops
from mxnet_tpu.ops import nn as nn_ops
from mxnet_tpu.ops.ssm import ssd_chunk_scan

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "..", "benchmark", "configs",
                      "nemotron3-nano-30b-ep16share")


def _load_model():
    spec = importlib.util.spec_from_file_location(
        "nemotron_bench_model", os.path.join(CONFIG, "model.py"))
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
            if isinstance(value, dict) else value
    sizes.update(over)
    return sizes


def _toy(dtype="float32", seed=3, bias_update_rate=0.0, **over):
    """(sizes, net, tokens, labels) of the rehearsal-sized model (pattern
    ``ME*E``: all three kinds)."""
    sizes = _toy_sizes(**over)
    sizes["train"] = dict(sizes["train"], bias_update_rate=bias_update_rate)
    mx.random.seed(seed)
    onp.random.seed(seed)
    rs = onp.random.RandomState(seed)
    net = M._net(sizes)
    # wider than the cell's 0.02 so that at toy widths no path is faint
    net.initialize(mx.init.Normal(0.2))
    if dtype != "float32":
        net.cast(dtype)
    tokens, labels = M.draw_tokens(sizes, rs, 2)
    return sizes, net, tokens, labels


def _ids(a):
    return mx.nd.array(onp.asarray(a).astype("int32"), dtype="int32")


def _rel(got, want):
    got, want = onp.asarray(got, "float64"), onp.asarray(want, "float64")
    return onp.linalg.norm(got - want) / max(onp.linalg.norm(want), 1e-30)


# ---------------------------------------------------------------------------
# the scan operator
# ---------------------------------------------------------------------------

def _recurrence(x, dt, a_log, b, c, d_skip, dt_bias, state_dtype=None):
    """h_t = exp(dt_t A) h_(t-1) + dt_t x_t (x) B_t; y_t = h_t C_t + D x_t,
    one step at a time.  ``state_dtype``: the state is rounded to it after
    every step (the control of the operator's float32 state)."""
    heads, groups = x.shape[2], b.shape[2]
    delta = jax.nn.softplus(dt + dt_bias)
    a = -jnp.exp(a_log)
    bh, ch = (jnp.repeat(t, heads // groups, axis=2) for t in (b, c))

    def step(h, t):
        x_t, d_t, b_t, c_t = t
        h = jnp.exp(d_t * a)[..., None, None] * h \
            + (d_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        if state_dtype is not None:
            h = h.astype(state_dtype).astype(jnp.float32)
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t) \
            + d_skip[:, None] * x_t

    _, y = jax.lax.scan(
        step, jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:]),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, delta, bh, ch)))
    return jnp.moveaxis(y, 0, 1)


def _scan_inputs(seq, seed=0, batch=2, heads=4, head_dim=3, groups=2,
                 state=5):
    rs = onp.random.RandomState(seed)
    shapes = [(batch, seq, heads, head_dim), (batch, seq, heads), (heads,),
              (batch, seq, groups, state), (batch, seq, groups, state),
              (heads,), (heads,)]
    return [jnp.asarray(rs.randn(*s) * (0.5 if i == 2 else 1.0),
                        jnp.float32) for i, s in enumerate(shapes)]


@pytest.mark.parametrize("seq", [32, 29, 5],
                         ids=["chunks", "padded", "shorter_than_a_chunk"])
def test_ssd_chunk_scan_matches_the_recurrence(seq):
    """Value and the gradient of EVERY input (x, dt, A_log, B, C, D,
    dt_bias) at a sequence that is a multiple of the chunk (8), one that is
    padded inside the operator, and one shorter than a chunk."""
    args = _scan_inputs(seq)
    weight = jnp.asarray(onp.random.RandomState(1).randn(
        *args[0].shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = ssd_chunk_scan(*args, chunk=8)
        want = _recurrence(*args)
        assert got.shape == want.shape
        assert float(jnp.abs(got - want).max()) \
            <= 1e-5 * float(jnp.abs(want).max())
        every = tuple(range(len(args)))
        g_got = jax.grad(lambda *a: jnp.sum(
            ssd_chunk_scan(*a, chunk=8) * weight), argnums=every)(*args)
        g_want = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * weight),
                          argnums=every)(*args)
    for i, (a, b) in enumerate(zip(g_got, g_want)):
        assert _rel(a, b) < 1e-5, i


def test_a_bfloat16_scan_state_fails_where_float32_holds():
    """S = 8192 on the slowest head the configuration initialises (dt =
    time_step_min = 1e-3, A = -1: a memory of a thousand steps): the
    chunked operator stays within 1e-4 of the float32 recurrence, the
    recurrence with its state rounded to bfloat16 after every step is
    further off than the 3% the cell's logits are held to."""
    rs = onp.random.RandomState(2)
    seq, p, n = 8192, 8, 16
    x = jnp.asarray(rs.randn(1, seq, 1, p), jnp.float32)
    b, c = (jnp.asarray(rs.randn(1, seq, 1, n), jnp.float32)
            for _ in range(2))
    dt0 = onp.array([1e-3])
    dt_bias = jnp.asarray(dt0 + onp.log(-onp.expm1(-dt0)), jnp.float32)
    args = [x, jnp.zeros((1, seq, 1)), jnp.zeros((1,)), b, c,
            jnp.zeros((1,)), dt_bias]
    with jax.default_matmul_precision("highest"):
        want = _recurrence(*args)
        scale = float(jnp.abs(want).max())
        chunked = ssd_chunk_scan(*args, chunk=128)
        assert float(jnp.abs(chunked - want).max()) <= 1e-4 * scale
        rounded = _recurrence(*args, state_dtype=jnp.bfloat16)
    assert float(jnp.abs(rounded - want).max()) > 0.03 * scale


def test_scan_census_and_scopes():
    """At trace time: the counter ``ssm.scan.chunked``, one ``ssm.scan``
    event with the traced shape, and the four phases as named scopes in
    the lowered program."""
    args = _scan_inputs(29)
    before = telemetry.counter("ssm.scan.chunked")
    text = jax.jit(lambda *a: ssd_chunk_scan(*a, chunk=8)).lower(
        *args).as_text(debug_info=True)
    assert telemetry.counter("ssm.scan.chunked") == before + 1
    event = [e for e in telemetry.snapshot(events=256)["events"]
             if e["kind"] == "ssm.scan"][-1]
    assert {k: event[k] for k in ("seq_len", "chunk", "heads", "state",
                                  "groups", "padded")} == {
        "seq_len": 29, "chunk": 8, "heads": 4, "state": 5, "groups": 2,
        "padded": True}
    for scope in ("ssd.in_chunk", "ssd.chunk_states", "ssd.state_passing",
                  "ssd.output"):
        assert "%s/" % scope in text, scope


# ---------------------------------------------------------------------------
# small operators and blocks
# ---------------------------------------------------------------------------

def test_causal_conv1d_takes_a_bias_at_kernel_4():
    rs = onp.random.RandomState(0)
    x = rs.randn(2, 9, 6).astype("float32")
    w = rs.randn(6, 1, 4).astype("float32")
    bias = rs.randn(6).astype("float32")
    got = onp.asarray(nn_ops.causal_conv1d(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias), groups=6))
    padded = onp.concatenate([onp.zeros((2, 3, 6), "float32"), x], axis=1)
    want = bias + sum(padded[:, j:j + 9] * w[:, 0, j] for j in range(4))
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # x_t alone reaches y_t through the last tap
    assert onp.allclose(got[:, 0], bias + x[:, 0] * w[:, 0, 3], atol=1e-6)


def test_gated_group_rms_norm_against_plain_numpy():
    """``RMSNorm(groups=4)(y, z)``: the gate BEFORE the norm, each group
    of 3 channels with its own mean, one gain a channel."""
    rs = onp.random.RandomState(1)
    y = rs.randn(2, 5, 12).astype("float32")
    z = rs.randn(2, 5, 12).astype("float32")
    gamma = rs.rand(12).astype("float32") + 0.5
    block = gluon.nn.RMSNorm(epsilon=1e-5, in_channels=12, groups=4)
    block.initialize()
    block.gamma.set_data(mx.nd.array(gamma))
    got = block(mx.nd.array(y), mx.nd.array(z)).asnumpy()
    gated = (y * z / (1 + onp.exp(-z))).reshape(2, 5, 4, 3)
    want = (gated / onp.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(2, 5, 12) * gamma
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # without a gate and with one group it is the plain norm
    plain = gluon.nn.RMSNorm(in_channels=12)
    plain.initialize()
    onp.testing.assert_allclose(
        plain(mx.nd.array(y)).asnumpy(),
        y / onp.sqrt((y ** 2).mean(-1, keepdims=True) + 1e-5), rtol=1e-5,
        atol=1e-6)


def test_relu2_feed_forward_block_has_no_bias():
    rs = onp.random.RandomState(2)
    block = cnn.PositionwiseFFN(6, 10, activation="relu2", use_bias=False,
                                in_units=6)
    block.initialize(mx.init.Normal(0.5))
    assert sorted(name[len(block.prefix):]
                  for name in block.collect_params()) == [
        "fc1_weight", "fc2_weight"]
    x = rs.randn(3, 4, 6).astype("float32")
    w1 = block.expand.weight.data().asnumpy()
    w2 = block.contract.weight.data().asnumpy()
    onp.testing.assert_allclose(
        block(mx.nd.array(x)).asnumpy(),
        onp.maximum(x @ w1.T, 0) ** 2 @ w2.T, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the layers and the whole model against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("index,kind", [(0, "M"), (1, "E"), (2, "*")],
                         ids=["mamba2_mixer", "experts", "attention"])
def test_each_layer_kind_matches_the_reference_layer(index, kind):
    sizes, net, _, _ = _toy()
    layer = net.layers[index]
    assert layer.kind == kind
    x = onp.random.RandomState(5).randn(2, sizes["seq_len"],
                                        sizes["hidden_size"]).astype(
        "float32")
    got = layer(mx.nd.array(x)).asnumpy()
    params = M._layer_params(M.host_params(net), index)
    fn = M._layer_fn(sizes, kind)
    with jax.default_matmul_precision("highest"):
        want = onp.stack([onp.asarray(fn(jnp.asarray(row), {
            k: jnp.asarray(v) for k, v in params.items()})[0]) for row in x])
    assert onp.abs(got - want).max() <= 2e-5 * onp.abs(want).max()
    # the mixer adds something: the layer is not its residual
    assert onp.abs(got - x).max() > 1e-2


def test_logits_match_reference_float32():
    sizes, net, tokens, _ = _toy()
    positions = onp.stack([onp.arange(0, sizes["seq_len"], 7)] * 2)
    got = net(_ids(tokens), _ids(positions)).asnumpy()
    want = M.reference_forward(M.host_params(net), tokens, positions, sizes)
    assert got.shape == want.shape == (2, positions.shape[1],
                                       sizes["vocab_size"])
    assert onp.abs(got - want).max() <= 1e-5 * onp.abs(want).max()


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
    for name, p in trained.items():
        assert _rel(p.grad().asnumpy(), want[name]) < 2e-5, name


def test_compare_holds_the_routing_to_its_limits():
    """``compare`` hands on the logits when the chosen sets agree, and NaN
    — no verdict — when 1% of a layer's tokens have another set although
    the reference's 6th and 7th scores are clearly apart."""
    sizes, net, tokens, _ = _toy(seq_len=512)
    positions = onp.stack([onp.arange(0, sizes["seq_len"], 16)] * 2)
    logits = net(_ids(tokens), _ids(positions)).asnumpy()
    chosen = onp.stack([layer.experts.last_expert.asnumpy()
                        for layer in net.layers if layer.kind == "E"])
    params = M.host_params(net)
    got, want = M.compare(logits, chosen, params, tokens, positions, sizes)
    assert onp.abs(got - want).max() <= 1e-5 * onp.abs(want).max()
    _, scores = M.reference_hidden(params, tokens, sizes, follow=chosen)
    k = sizes["num_experts_per_tok"]
    ordered = -onp.sort(-scores[1], axis=-1)
    gap = (ordered[..., k - 1] - ordered[..., k]).ravel()
    clearest = onp.argsort(-gap)[:gap.size // 100]
    wrong = chosen.copy()
    flat = wrong[1].reshape(-1, k)
    others = onp.argsort(-scores[1].reshape(-1, scores.shape[-1]),
                         axis=-1)[:, k]
    flat[clearest, 0] = others[clearest]       # the 7th in place of one
    got, _ = M.compare(logits, wrong, params, tokens, positions, sizes)
    assert onp.isnan(got).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_trains_through_data_parallel_step(dtype):
    """``HybridBlock`` -> ``DataParallelStep`` -> ``Adam(multi_precision)``:
    the step lowers ONCE, the loss falls, the routing counts ride as state
    (6 routes a token at the cell's sizes, 3 here), nothing is dropped, and
    the program carries the blocks' names and the scan's scopes."""
    sizes, net, tokens, labels = _toy(dtype=dtype, bias_update_rate=1e-3)
    step = parallel.DataParallelStep(
        net, gluon.loss.TiedSoftmaxCrossEntropyLoss(
            block_rows=sizes["train"]["loss_block_rows"]),
        mx.optimizer.Adam(learning_rate=3e-3,
                          multi_precision=dtype != "float32"))
    losses = [float(step(_ids(tokens), _ids(labels)).asnumpy().astype(
        "float32").mean()) for _ in range(8)]
    assert len(step._cache) == 1
    assert all(onp.isfinite(losses)) and losses[-1] < losses[0] - 0.05
    routes = tokens.size * sizes["num_experts_per_tok"]
    counts = cnn.publish_routing_counts()
    mine = [v for name, v in counts.items() if name.startswith(net.prefix)]
    assert len(mine) == 2
    for record in mine:
        assert sum(record["load"]) == routes
        assert record["rows"] == record["load"][:len(record["rows"])]
        assert record["routes_per_token"] == 3
    gauges = telemetry.snapshot()["gauges"]
    assert gauges["moe.dropped"] == 0
    assert gauges["moe.routes_per_token"] >= 3
    # the balancing bias moved, by one rate a step
    bias = net.layers[1].experts.balance_bias.data().asnumpy()
    assert 0 < onp.abs(bias).max() <= 8 * 1e-3 + 1e-6
    if dtype == "float32":
        text = step.lower(_ids(tokens), _ids(labels)).as_text(
            debug_info=True)
        for block in ("layer0_mamba", "layer0_mamba_in", "layer0_mamba_norm",
                      "layer1_router", "layer1_experts", "layer1_shared_fc1",
                      "layer2_attn_qkv", "final_norm"):
            assert "/%s%s/" % (net.prefix, block) in text, block
        for scope in ("ssd.in_chunk", "ssd.chunk_states",
                      "ssd.state_passing", "ssd.output"):
            assert re.search(r"jvp\(forward\)[^\"]*%s/" % re.escape(scope),
                             text), scope


def test_model_flops_counts_the_share():
    with open(os.path.join(CONFIG, "config.json")) as f:
        sizes = json.load(f)
    flops = M.model_flops(sizes)
    assert 17.4e12 < flops < 17.9e12               # ISSUE 30: 17.6 TFLOP
    seq, hidden = sizes["seq_len"], sizes["hidden_size"]
    head = 6 * seq * hidden * sizes["vocab_size"]
    assert 0.11 < head / flops < 0.13
    # every expert held: 16 times the routed experts' products
    whole = M.model_flops(dict(sizes, n_routed_experts=128))
    routed = 6 * seq * 4 * 6 * 2 * hidden * sizes["moe_intermediate_size"]
    assert whole - flops == pytest.approx(routed * 15 / 16, rel=1e-9)


# ---------------------------------------------------------------------------
# top-k routing and THE SHARE TEST
# ---------------------------------------------------------------------------

def _expert_layers(shares, seed=0, experts=8, units=8, hidden=12,
                   shared_hidden=16, k=3, scale=2.5):
    """One ``E`` layer a share of ``experts`` experts, their weights the
    slices of one seeded full set; returns (layers, full weights)."""
    rs = onp.random.RandomState(seed)
    full = {"router_weight": rs.randn(experts, units) * 0.5,
            "experts_up_weight": rs.randn(experts, units, hidden) * 0.3,
            "experts_down_weight": rs.randn(experts, hidden, units) * 0.3,
            "shared_fc1_weight": rs.randn(shared_hidden, units) * 0.3,
            "shared_fc2_weight": rs.randn(units, shared_hidden) * 0.3,
            "norm_gamma": rs.rand(units) + 0.5,
            "experts_balance_bias": rs.randn(experts) * 0.05}
    full = {name: value.astype("float32") for name, value in full.items()}
    layers = []
    for first, end in shares:
        layer = NemotronHLayer(
            "E", units, 1e-5, {}, {}, dict(
                hidden_size=hidden, num_experts=experts,
                experts_held=(first, end), experts_per_token=k,
                gate_scale=scale, shared_hidden=shared_hidden))
        layer.initialize()
        for name, p in layer.collect_params().items():
            short = name[len(layer.prefix):]
            if short in full:
                value = full[short]
                p.set_data(mx.nd.array(
                    value[first:end] if short.startswith("experts_")
                    and short != "experts_balance_bias" else value))
        layers.append(layer)
    return layers, full


def _toy_layer_sizes(experts=8, units=8, hidden=12, shared_hidden=16, k=3):
    return dict(_toy_sizes(), hidden_size=units,
                moe_intermediate_size=hidden,
                moe_shared_expert_intermediate_size=shared_hidden,
                num_experts_per_tok=k,
                published={"n_routed_experts": experts},
                deployment={"experts_held": [0, experts]})


def test_expert_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST: 4 shares of 2 of 8 experts, 3 routes a token.  The
    shares' held-expert parts, plus the shared expert and the residual —
    which every share computes alike — counted ONCE, equal the uncut
    reference layer; a route is some share's, never two's."""
    shares = [(0, 2), (2, 4), (4, 6), (6, 8)]
    layers, full = _expert_layers(shares)
    x = onp.random.RandomState(4).randn(2, 40, 8).astype("float32")
    xs = mx.nd.array(x)
    parts = []
    for layer in layers:
        h = layer.norm(xs)
        parts.append(layer.experts(h, layer.router(h)).asnumpy())
    alike = layers[0].shared(layers[0].norm(xs)).asnumpy() + x
    for layer in layers[1:]:                       # computed alike
        onp.testing.assert_allclose(
            layer.shared(layer.norm(xs)).asnumpy() + x, alike, rtol=1e-6)
    fn = M._layer_fn(_toy_layer_sizes(), "E")
    with jax.default_matmul_precision("highest"):
        want = onp.stack([onp.asarray(fn(
            jnp.asarray(row), {k: jnp.asarray(v)
                               for k, v in full.items()})[0]) for row in x])
    onp.testing.assert_allclose(sum(parts) + alike, want, rtol=2e-5,
                                atol=2e-6)
    # one share alone is its own layer's output, and not the whole
    onp.testing.assert_allclose(layers[1](xs).asnumpy(), parts[1] + alike,
                                rtol=1e-5, atol=1e-6)
    assert onp.abs(parts[1] + alike - want).max() > 1e-2
    # the loads are of ROUTES, the same on every share: 3 a token
    for layer in layers:
        with autograd.train_mode():
            layer(xs)
        load = layer.experts.expert_load.data().asnumpy()
        assert load.sum() == 3 * 80
        first, end = layer.experts.experts_held
        onp.testing.assert_array_equal(
            layer.experts.rows_computed.data().asnumpy(), load[first:end])


def _route_case(case):
    rs = onp.random.RandomState(7)
    scores = rs.rand(12, 8).astype("float32") * 0.8 + 0.1
    bias = onp.zeros(8, "float32")
    if case == "ties":
        scores[:, :] = 0.5                  # every expert ties: 0, 1, 2
    elif case == "bias":
        bias[7] = 10.0                      # expert 7 is always chosen
    return scores, bias


@pytest.mark.parametrize("case", ["plain", "ties", "bias"])
def test_topk_routing(case):
    """The 3 largest of ``s + b``; ties go to the lower index; the bias
    moves the choice and not the gate; the gates are the chosen scores
    over their sum, times 2.5; the load counts 3 routes a token; nothing
    is dropped."""
    scores, bias = _route_case(case)
    expert, gate = moe_ops.topk_route(
        jnp.asarray(scores), 3, jnp.asarray(scores + bias), normalize=True)
    expert, gate = onp.asarray(expert), onp.asarray(gate) * 2.5
    want = onp.argsort(-(scores + bias), axis=-1, kind="stable")[:, :3]
    onp.testing.assert_array_equal(expert, want)
    onp.testing.assert_allclose(gate.sum(-1), 2.5, rtol=1e-6)
    picked = onp.take_along_axis(scores, want, axis=-1)
    onp.testing.assert_allclose(
        gate, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    if case == "ties":
        assert (expert == [0, 1, 2]).all()
    if case == "bias":
        assert (expert[:, 0] == 7).all()
        assert gate[:, 0].max() < 2.5 * 0.9 / (0.9 + 0.2)   # s_7, not s_7 + b
    # through the block: all 8 held, the plain loop over the experts
    block = cnn.SparseExperts(6, 10, 8, experts_per_token=3, gated=False,
                              activation="relu2", normalize_gates=True,
                              gate_scale=2.5)
    block.initialize(mx.init.Normal(0.3))
    block.balance_bias.set_data(mx.nd.array(bias))
    x = onp.random.RandomState(8).randn(1, 12, 6).astype("float32")
    with autograd.train_mode():
        got = block(mx.nd.array(x), mx.nd.array(scores[None])).asnumpy()
    up = block.up_weight.data().asnumpy()
    down = block.down_weight.data().asnumpy()
    dense = onp.zeros_like(x[0])
    for e in range(8):
        y = onp.maximum(x[0] @ up[e], 0) ** 2 @ down[e]
        dense += onp.where(expert == e, gate, 0).sum(-1)[:, None] * y
    onp.testing.assert_allclose(got[0], dense, rtol=1e-5, atol=1e-6)
    load = block.expert_load.data().asnumpy()
    onp.testing.assert_array_equal(load, onp.bincount(expert.ravel(),
                                                     minlength=8))
    assert load.sum() == 3 * 12
    cnn.publish_routing_counts()
    assert telemetry.snapshot()["gauges"]["moe.dropped"] == 0
    assert block.last_expert.shape == (1, 12, 3)


def test_one_route_a_token_is_the_k1_case_of_the_same_code():
    """``topk_route`` at k = 1 picks what ``top1_route`` picks, and the
    flat-route core gives the same layer output either way."""
    rs = onp.random.RandomState(9)
    probs = jnp.asarray(jax.nn.softmax(jnp.asarray(
        rs.randn(20, 6).astype("float32")), axis=-1))
    e1, g1 = moe_ops.top1_route(probs)
    ek, gk = moe_ops.topk_route(probs, 1)
    onp.testing.assert_array_equal(onp.asarray(e1), onp.asarray(ek)[:, 0])
    onp.testing.assert_allclose(onp.asarray(g1), onp.asarray(gk)[:, 0])
    x = jnp.asarray(rs.randn(20, 4).astype("float32"))
    ffn = moe_ops.mlp_experts(
        jnp.asarray(rs.randn(3, 4, 5).astype("float32")),
        jnp.asarray(rs.randn(3, 5, 4).astype("float32")), jax.nn.gelu)
    y1, s1 = moe_ops.sparse_ffn(x, e1, g1, ffn, 2, 3)
    yk, sk = moe_ops.sparse_ffn(x, ek, gk, ffn, 2, 3)
    onp.testing.assert_allclose(onp.asarray(y1), onp.asarray(yk), rtol=1e-6)
    onp.testing.assert_array_equal(onp.asarray(s1), onp.asarray(sk))


@pytest.mark.parametrize("case", ["fits_the_budget", "falls_back"])
def test_expert_blocks_compute_every_held_route(case):
    """One of 16 experts held, 2 routes a token, 600 tokens: the expert is
    one dense product over 2 blocks of 256 slots (four times an even
    router's 75 routes, rounded up) while its routes fit them, and the
    ragged product over all 1,200 sorted routes when a bias sends every
    token to it — the same result as the plain loop either way, value and
    gradient, nothing dropped."""
    rs = onp.random.RandomState(11)
    scores = (rs.rand(1, 600, 16) * 0.8 + 0.1).astype("float32")
    bias = onp.zeros(16, "float32")
    if case == "falls_back":
        bias[3] = 10.0
    block = cnn.SparseExperts(6, 10, 16, experts_held=(3, 4),
                              experts_per_token=2, gated=False,
                              activation="relu2", normalize_gates=True)
    block.initialize(mx.init.Normal(0.3))
    block.balance_bias.set_data(mx.nd.array(bias))
    x = mx.nd.array(rs.randn(1, 600, 6).astype("float32"))
    x.attach_grad()
    with autograd.record():
        got = block(x, mx.nd.array(scores))
        loss = (got * got).sum()
    loss.backward()
    held = int(block.rows_computed.data().asnumpy()[0])
    assert (held > 512) == (case == "falls_back")
    assert held == block.expert_load.data().asnumpy()[3]
    up = jnp.asarray(block.up_weight.data().asnumpy()[0])
    down = jnp.asarray(block.down_weight.data().asnumpy()[0])
    chosen = onp.argsort(-(scores[0] + bias), axis=-1, kind="stable")[:, :2]
    picked = onp.take_along_axis(scores[0], chosen, axis=-1)
    gate = jnp.asarray(onp.where(chosen == 3, picked / picked.sum(
        -1, keepdims=True), 0).sum(-1))

    def dense(x):
        return gate[:, None] * (jnp.maximum(x @ up, 0) ** 2 @ down)

    want = dense(jnp.asarray(x.asnumpy()[0]))
    onp.testing.assert_allclose(got.asnumpy()[0], onp.asarray(want),
                                rtol=1e-5, atol=1e-6)
    want_grad = jax.grad(lambda x: jnp.sum(dense(x) ** 2))(
        jnp.asarray(x.asnumpy()[0]))
    onp.testing.assert_allclose(x.grad.asnumpy()[0], onp.asarray(want_grad),
                                rtol=1e-4, atol=1e-5)


def _hand_made_slots(k, tokens=9, slots=16, seed=0):
    """A slot plan made by hand, no router: token 0 has all its k routes
    in slots, token 1 none, the others each route with probability a half;
    the routes with a slot sit in distinct random slots of ``slots``, the
    other slots are empty.  Route r is route r // tokens of token
    r % tokens.  Returns (slot_route (slots,), route_slot (k, tokens))."""
    rs = onp.random.RandomState(seed)
    has = rs.rand(k, tokens) < 0.5
    has[:, 0], has[:, 1] = True, False
    routes = onp.flatnonzero(has.reshape(-1))
    assert len(routes) <= slots
    where = rs.permutation(slots)[:len(routes)]
    slot_route = onp.full(slots, -1, "int32")
    slot_route[where] = routes
    route_slot = onp.full(k * tokens, -1, "int32")
    route_slot[routes] = where
    return slot_route, route_slot.reshape(k, tokens)


@pytest.mark.parametrize("k", [1, 6, 8], ids=lambda k: "k%d" % k)
def test_dispatch_and_combine_against_plain_loops(k):
    """``ops.moe.dispatch`` (token order to slot order) and ``combine``
    (back, weighted, a token's k routes summed) on a plan made by hand,
    against plain ``numpy`` loops: both results and every cotangent — each
    one's backward is the other.  A token with all k routes in slots, one
    with none, empty slots."""
    tokens, slots, width = 9, 8 * 9, 5
    slot_route, route_slot = _hand_made_slots(k, tokens, slots, seed=k)
    rs = onp.random.RandomState(100 + k)
    x = rs.randn(tokens, width).astype("float32")
    out = rs.randn(slots, width).astype("float32")
    weight = onp.where(route_slot >= 0, rs.rand(k, tokens), 0) \
        .astype("float32")
    g_rows = rs.randn(slots, width).astype("float32")
    g_y = rs.randn(tokens, width).astype("float32")
    maps = (jnp.asarray(slot_route), jnp.asarray(route_slot))

    rows, back = jax.vjp(lambda x: moe_ops.dispatch(x, *maps),
                         jnp.asarray(x))
    y, home = jax.vjp(lambda out, weight: moe_ops.combine(out, weight, *maps),
                      jnp.asarray(out), jnp.asarray(weight))
    dx, = back(jnp.asarray(g_rows))
    d_out, d_weight = home(jnp.asarray(g_y))

    want_y, want_dx = onp.zeros_like(x), onp.zeros_like(x)
    want_d_out, want_d_weight = onp.zeros_like(out), onp.zeros_like(weight)
    for s, route in enumerate(slot_route):
        if route >= 0:
            onp.testing.assert_array_equal(onp.asarray(rows)[s],
                                           x[route % tokens])
    for j in range(k):
        for t in range(tokens):
            s = route_slot[j, t]
            if s < 0:
                continue
            assert slot_route[s] == j * tokens + t
            want_y[t] += weight[j, t] * out[s]
            want_dx[t] += g_rows[s]
            want_d_out[s] = weight[j, t] * g_y[t]
            want_d_weight[j, t] = out[s] @ g_y[t]
    assert (route_slot[:, 0] >= 0).all() and (route_slot[:, 1] < 0).all()
    assert not want_y[1].any() and (slot_route < 0).any()
    for got, want in ((y, want_y), (dx, want_dx), (d_out, want_d_out),
                      (d_weight, want_d_weight)):
        onp.testing.assert_allclose(onp.asarray(got), want, rtol=1e-5,
                                    atol=1e-6)


def _route_order_form(network, blocks, x, gates, weights, local, grouped):
    """The blocks side as the parent of PR 34 had it, in plain indexing
    for autodiff: the slots' outputs gathered to ROUTE order, masked,
    gated there, the k parts summed in float32."""
    owner, slot_route, route_slot = moe_ops._slots(blocks, local, grouped)
    n = x.shape[0]
    rows = x[jnp.maximum(slot_route, 0) % n]
    out = network(rows.reshape(blocks + (-1,)), owner,
                  *weights).reshape(rows.shape)
    routes = jnp.where((route_slot >= 0)[:, None],
                       out[jnp.maximum(route_slot, 0)], 0) \
        * gates.astype(out.dtype)[:, None]
    return jnp.sum(routes.reshape(-1, n, routes.shape[-1]), axis=0,
                   dtype=jnp.float32).astype(out.dtype)


# (k, tokens): 12 of 64 experts held, 24 blocks of 128 slots
_BLOCK_CASES = {"k1": (1, 4096), "k6": (6, 640), "k8": (8, 512)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(_BLOCK_CASES))
def test_blocks_side_matches_the_route_order_form(case, dtype):
    """The experts on blocks of slots through ``dispatch`` and
    ``combine`` against autodiff of the route-order form the parent had,
    on the same slots: the layer's output and the cotangents of ``x``, the
    gates and each weight, at 1, 6 and 8 routes a token; one held expert
    with no route at all, blocks left empty, token 0 with all its routes
    held and token 1 with none.  In float32 to rounding; with bfloat16
    inputs no further (rms) from the float32 result than the parent's
    form is."""
    k, n = _BLOCK_CASES[case]
    experts, first, held, units, hidden = 64, 8, 12, 16, 24
    rs = onp.random.RandomState(40 + k)
    # no token chooses expert 10 (held); token 0 only held ones, token 1 none
    allowed = onp.array([e for e in range(experts) if e != 10])
    expert = onp.stack([rs.choice(allowed, k, replace=False)
                        for _ in range(n)]).astype("int32")
    expert[0] = onp.array([e for e in range(first, first + held)
                           if e != 10])[:k]
    expert[1] = onp.arange(first + held, first + held + k)
    gate = rs.rand(n, k).astype("float32") + 0.1
    weights = (rs.randn(held, units, hidden).astype("float32") * 0.3,
               rs.randn(held, hidden, units).astype("float32") * 0.3)
    x = rs.randn(n, units).astype("float32")
    g = rs.randn(n, units).astype("float32")

    routes = jnp.asarray(expert.T.reshape(-1))
    grouped = moe_ops.group_by_expert(routes, first, held)
    blocks = moe_ops.plan_blocks(k * n, held, experts)
    assert blocks == (24, 128)
    sizes = onp.asarray(grouped[2])
    assert sizes[2] == 0 and sizes.sum() > 0
    assert bool(moe_ops.blocks_fit(blocks, grouped[2]))
    assert -(-sizes // 128).sum() < 24                  # blocks left empty
    network = moe_ops.mlp_experts(None, None, jax.nn.gelu).network

    def both(cast):
        args = [jnp.asarray(a, cast) for a in (x, *weights)]
        gates = jnp.asarray(gate.T.reshape(-1))

        def run(form):
            y, back = jax.vjp(
                lambda x, gates, up, down: form(
                    network, blocks, x, gates, (up, down), routes - first,
                    grouped), args[0], gates, *args[1:])
            return [onp.asarray(v, "float32")
                    for v in (y,) + back(jnp.asarray(g, cast))]
        return run(moe_ops._budgeted), run(_route_order_form)

    (got, want), names = both("float32"), ("y", "dx", "dgates", "dup",
                                           "ddown")
    assert not want[0][1].any() and want[0][0].any()
    if dtype == "float32":
        for name, a, b in zip(names, got, want):
            onp.testing.assert_allclose(a, b, rtol=2e-5, err_msg=name,
                                        atol=2e-6 * onp.abs(b).max())
        return
    new, parents = both("bfloat16")
    for name, a, b, exact in zip(names, new, parents, want):
        mine, parent = (onp.sqrt(onp.mean((v - exact) ** 2)) for v in (a, b))
        print(name, "rms error", mine, "the parent's form", parent)
        assert mine <= 1.1 * parent, name      # two roundings of one size


@pytest.mark.parametrize("case", ["fits_the_budget", "falls_back", "flat"])
def test_publish_routing_counts_says_which_side_ran(case):
    """The layer of ``test_expert_blocks_compute_every_held_route``: with
    its routes inside the 2 blocks of 256 slots the block's record says
    ``on_blocks`` True, with every token sent to the held expert False —
    the step's own rule, ``ops.moe.blocks_fit``, on the rows the step
    counted — and with one flat route a token None (no blocks to be on).
    The gauges count the layers with blocks and those on them, and the
    benchmark's reader gives their share."""
    rs = onp.random.RandomState(11)
    scores = (rs.rand(1, 600, 16) * 0.8 + 0.1).astype("float32")
    bias = onp.zeros(16, "float32")
    if case == "falls_back":
        bias[3] = 10.0
    flat = case == "flat"
    block = cnn.SparseExperts(6, 10, 16, experts_held=(3, 4),
                              experts_per_token=1 if flat else 2,
                              gated=False, activation="relu2",
                              normalize_gates=not flat)
    block.initialize(mx.init.Normal(0.3))
    block.balance_bias.set_data(mx.nd.array(bias))
    with autograd.train_mode():
        block(mx.nd.array(rs.randn(1, 600, 6).astype("float32")),
              mx.nd.array(scores))
    records = cnn.publish_routing_counts()
    want = {"fits_the_budget": True, "falls_back": False, "flat": None}
    assert records[block.name]["on_blocks"] is want[case]
    sides = [r["on_blocks"] for r in records.values()
             if r["on_blocks"] is not None]
    gauges = telemetry.snapshot()["gauges"]
    assert gauges["moe.layers_with_blocks"] == len(sides)
    assert gauges["moe.layers_on_blocks"] == sum(sides)
    spec = importlib.util.spec_from_file_location("reader", os.path.join(
        HERE, "..", "benchmark", "layer_metrics",
        "moe_blocks_side_share.train.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert reader.read({}) == (100.0 * sum(sides) / len(sides)
                               if sides else None)


def test_a_lumpy_expert_takes_the_blocks_it_needs():
    """2 of 32 experts held, 2 routes a token, 1,024 tokens: 4 blocks of
    128 slots, an even share 64 routes an expert.  One held expert gets
    some 350 routes — five times its share, three blocks' worth — its
    neighbour under a hundred: the lumpy expert takes the blocks it needs,
    the layer stays on the dense product (no fallback), and the result is
    the plain loop's."""
    rs = onp.random.RandomState(12)
    scores = (rs.rand(1, 1024, 32) * 0.5).astype("float32")
    scores[0, :290, 4] = 0.9            # 290 tokens choose expert 4 first
    scores[0, 290:310, 5] = 0.9         # 20 choose expert 5
    block = cnn.SparseExperts(6, 10, 32, experts_held=(4, 6),
                              experts_per_token=2, gated=False,
                              activation="relu2", normalize_gates=True)
    block.initialize(mx.init.Normal(0.3))
    x = rs.randn(1, 1024, 6).astype("float32")
    with autograd.train_mode():
        got = block(mx.nd.array(x), mx.nd.array(scores)).asnumpy()[0]
    rows = block.rows_computed.data().asnumpy()
    assert rows[0] > 2 * 128 and onp.ceil(rows / 128).sum() <= 4, rows
    chosen = onp.argsort(-scores[0], axis=-1, kind="stable")[:, :2]
    picked = onp.take_along_axis(scores[0], chosen, axis=-1)
    gates = picked / picked.sum(-1, keepdims=True)
    want = onp.zeros_like(x[0])
    for i, e in enumerate((4, 5)):
        y = onp.maximum(x[0] @ block.up_weight.data().asnumpy()[i], 0) ** 2 \
            @ block.down_weight.data().asnumpy()[i]
        want += onp.where(chosen == e, gates, 0).sum(-1)[:, None] * y
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
