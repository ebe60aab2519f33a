"""ZAYA1 through ``gluon.model_zoo.zaya1`` against the plain float32
reference in ``benchmark/configs/zaya1-8b-ep2share/model.py``, at toy sizes
on the CPU, seeded weights: the whole model (logits, loss, every
parameter's gradient), its parts (CCA's causality and value shift, the
router's depth averaging, dropless routing under imbalance, grouped-query
attention, the blocked tied cross-entropy), and the two SHARE tests: the
expert layer's shares add up to the uncut layer, the vocabulary halves'
logits concatenate to the whole head's."""
import importlib.util
import json
import os
import re

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, telemetry
from mxnet_tpu.gluon.contrib import nn as cnn
from mxnet_tpu.ops import moe as moe_ops
from mxnet_tpu.ops import nn as nn_ops
from mxnet_tpu.ops import pallas_attention as PA

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "..", "benchmark", "configs",
                      "zaya1-8b-ep2share")


def _load_model():
    spec = importlib.util.spec_from_file_location(
        "zaya_bench_model", os.path.join(CONFIG, "model.py"))
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
    """(sizes, net, tokens, labels) of the rehearsal-sized model; the
    balancing bias moves in its training steps only where
    ``bias_update_rate`` says so (the reference has no such rule: it is
    given the bias)."""
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
# the whole model against the reference
# ---------------------------------------------------------------------------

def test_logits_match_reference_float32():
    sizes, net, tokens, _ = _toy()
    positions = onp.stack([onp.arange(0, sizes["seq_len"], 3)] * 2)
    got = net(_ids(tokens), _ids(positions)).asnumpy()
    want = M.reference_forward(M.host_params(net), tokens, positions, sizes)
    assert got.shape == want.shape == (2, positions.shape[1],
                                       sizes["vocab_size"])
    assert onp.abs(got - want).max() <= 1e-5 * onp.abs(want).max()


def test_loss_and_every_gradient_match_reference_float32():
    sizes, net, tokens, labels = _toy()
    params = M.host_params(net)
    loss_fn = gluon.loss.TiedSoftmaxCrossEntropyLoss(
        block_rows=sizes["train"]["loss_block_rows"])
    with autograd.record():
        loss = loss_fn(net(_ids(tokens)), _ids(labels)).mean()
    loss.backward()
    want_loss, want = M.reference_loss_and_grads(params, tokens, labels,
                                                 sizes)
    assert abs(float(loss.asnumpy()) - want_loss) <= 1e-5 * want_loss
    trained = {name[len(net.prefix):]: p
               for name, p in net.collect_params().items()
               if p.grad_req != "null"}
    assert set(trained) == set(want)
    for name, p in trained.items():
        assert _rel(p.grad().asnumpy(), want[name]) <= 1e-5, name
    assert float(onp.abs(want["layer1_router_depth_gamma"]).max()) > 0


def test_bfloat16_stays_in_its_band():
    """bfloat16 weights and activations against the float32 reference on
    the SAME (bf16-rounded) weights, compared as the benchmark's
    ``check()`` compares them: the routing choices agree for nearly every
    token, and with the reference following the system's choices the
    logits land within 3% of the largest logit (the benchmark's
    tolerance; 8 bits of mantissa round by 0.4% at every activation, 3
    bits — fp8 — would miss it by far)."""
    sizes, net, tokens, _ = _toy(dtype="bfloat16")
    params = M.host_params(net)
    positions = onp.stack([onp.arange(0, sizes["seq_len"], 4)] * 2)
    got = net(_ids(tokens), _ids(positions)).asnumpy().astype("float32")
    chosen = onp.stack([layer.experts.last_expert.asnumpy()
                        for layer in net.layers])
    got, want = M.compare(got, chosen, params, tokens, positions, sizes)
    assert onp.isfinite(got).all()          # the routing passed its limits
    assert onp.abs(got - want).max() <= 0.03 * onp.abs(want).max()
    # following its own choices the reference is reference_forward
    _, probs = M.reference_hidden(params, tokens, sizes)
    if (probs.argmax(-1) == chosen).all():
        onp.testing.assert_allclose(
            M.reference_forward(params, tokens, positions, sizes), want)


def test_trains_through_data_parallel_step_with_fp32_master():
    from mxnet_tpu import parallel

    sizes, net, tokens, labels = _toy(dtype="bfloat16",
                                      bias_update_rate=1e-3)
    step = parallel.DataParallelStep(
        net, gluon.loss.TiedSoftmaxCrossEntropyLoss(block_rows=32),
        mx.optimizer.Adam(learning_rate=1e-3, multi_precision=True))
    losses = [float(step(_ids(tokens), _ids(labels)).asnumpy()
                    .astype("float32")) for _ in range(6)]
    assert all(onp.isfinite(losses)) and losses[-1] < losses[0]
    # no eager gradient buffer was allocated for the step's sake
    assert all(p._grad is None for p in net.collect_params().values())
    # the bias moved one rate a step, against the sign of the load's error
    bias = net.layers[0].experts.balance_bias.data().asnumpy()
    assert 0 < onp.abs(bias).max() <= 6 * 1e-3 + 1e-6
    counts = cnn.publish_routing_counts()
    mine = {k: v for k, v in counts.items() if k.startswith(net.prefix)}
    assert len(mine) == sizes["num_hidden_layers"]
    for record in mine.values():
        first, end = record["held"]
        assert sum(record["load"]) == tokens.size
        assert record["rows"] == record["load"][first:end]
    assert telemetry.snapshot()["gauges"]["moe.dropped"] == 0
    # the rule ran in the steps: the bias is state the step carried
    assert all(onp.abs(layer.experts.balance_bias.data().asnumpy()).max() > 0
               for layer in net.layers)


# ---------------------------------------------------------------------------
# CCA
# ---------------------------------------------------------------------------

def _cca(seed=0):
    mx.random.seed(seed)
    onp.random.seed(seed)
    block = cnn.CompressedConvAttention(32, 4, 2, 8, rotary_dim=4,
                                        rope_theta=100.0)
    block.initialize(mx.init.Normal(0.3))
    return block


def test_cca_is_causal():
    """The output at t is unchanged by inputs after t — through both
    convolutions, the value shift and the attention."""
    block = _cca()
    x = onp.random.RandomState(1).randn(2, 24, 32).astype("float32")
    y = block(mx.nd.array(x)).asnumpy()
    x2 = x.copy()
    x2[:, 13:] += 1.0
    y2 = block(mx.nd.array(x2)).asnumpy()
    onp.testing.assert_allclose(y2[:, :13], y[:, :13], rtol=1e-5, atol=1e-6)
    assert onp.abs(y2[:, 13:] - y[:, 13:]).max() > 1e-3


def test_cca_value_shift_and_conv_taps():
    """Key-value head 0's values come from h_t, head 1's from h_(t-1)
    (zero at t = 0); the depthwise convolution's tap K-1 meets the current
    step."""
    rs = onp.random.RandomState(2)
    q, k = rs.randn(1, 6, 32).astype("float32"), \
        rs.randn(1, 6, 16).astype("float32")
    v_now, v_prev = rs.randn(1, 6, 8).astype("float32"), \
        rs.randn(1, 6, 8).astype("float32")
    w0 = rs.randn(48, 1, 2).astype("float32")
    w1 = rs.randn(48, 8, 2).astype("float32")
    _, _, v = nn_ops.cca_qkv(q, k, v_now, v_prev, w0, w1, onp.ones(2, "f"),
                             num_heads=4, num_kv_heads=2)
    onp.testing.assert_allclose(v[0, 0], v_now[0])
    onp.testing.assert_allclose(v[0, 1, 1:], v_prev[0, :-1])
    assert not onp.asarray(v[0, 1, 0]).any()
    z = rs.randn(1, 6, 48).astype("float32")
    got = onp.asarray(nn_ops.causal_conv1d(z, w0, groups=48))
    want = z * w0[:, 0, 1]
    want[:, 1:] += z[:, :-1] * w0[:, 0, 0]
    onp.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_rms_norm_and_l2_normalize_against_plain_numpy():
    rs = onp.random.RandomState(3)
    x = rs.randn(2, 3, 5, 8).astype("float32") * 3
    gamma, scale = rs.rand(8).astype("float32"), rs.rand(3).astype("float32")
    want = x / onp.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * gamma
    onp.testing.assert_allclose(mx.nd.RMSNorm(
        mx.nd.array(x), mx.nd.array(gamma), eps=1e-5).asnumpy(), want,
        rtol=1e-5, atol=1e-6)
    unit = onp.asarray(nn_ops.l2_normalize(x, scale))
    onp.testing.assert_allclose(
        onp.linalg.norm(unit, axis=-1),
        onp.broadcast_to(scale[None, :, None] * 8 ** 0.5, (2, 3, 5)),
        rtol=1e-5)
    onp.testing.assert_allclose(
        unit / onp.linalg.norm(unit, axis=-1, keepdims=True),
        x / onp.linalg.norm(x, axis=-1, keepdims=True), rtol=1e-5,
        atol=1e-6)
    # bfloat16 in, bfloat16 out, statistics in float32
    assert nn_ops.rms_norm(jnp.asarray(x, jnp.bfloat16),
                           jnp.asarray(gamma)).dtype == jnp.bfloat16


def test_rotary_on_part_of_a_head_keeps_norms_and_the_rest():
    x = onp.random.RandomState(0).randn(1, 2, 5, 8).astype("float32")
    y = onp.asarray(nn_ops.rotary_embedding(x, rotary_dim=4, theta=50.0))
    onp.testing.assert_allclose(y[..., 4:], x[..., 4:])
    onp.testing.assert_allclose(y[:, :, 0], x[:, :, 0], rtol=1e-6)
    onp.testing.assert_allclose(onp.linalg.norm(y[..., :4], axis=-1),
                                onp.linalg.norm(x[..., :4], axis=-1),
                                rtol=1e-5)
    assert onp.abs(y[:, :, 1:, :4] - x[:, :, 1:, :4]).max() > 1e-3


# ---------------------------------------------------------------------------
# router and experts
# ---------------------------------------------------------------------------

def test_router_depth_averaging_across_layers():
    """Layer l's router adds gamma_l times layer l-1's hidden state: with
    gamma 0 it routes as a first layer would, and the state it hands on is
    its own projection plus the scaled carry."""
    mx.random.seed(0)
    onp.random.seed(0)
    router = cnn.DepthRouter(16, 8, 4, carry=True)
    router.initialize(mx.init.Normal(0.5))
    rs = onp.random.RandomState(0)
    x = mx.nd.array(rs.randn(1, 5, 16).astype("float32"))
    r_prev = mx.nd.array(rs.randn(1, 5, 8).astype("float32"))
    p_none, r_none = router(x)
    router.depth_gamma.set_data(mx.nd.array([0.0]))
    p_zero, r_zero = router(x, r_prev)
    onp.testing.assert_allclose(p_zero.asnumpy(), p_none.asnumpy(),
                                rtol=1e-6)
    router.depth_gamma.set_data(mx.nd.array([0.5]))
    p_half, r_half = router(x, r_prev)
    onp.testing.assert_allclose(
        r_half.asnumpy(), r_none.asnumpy() + 0.5 * r_prev.asnumpy(),
        rtol=1e-5, atol=1e-6)
    assert onp.abs(p_half.asnumpy() - p_none.asnumpy()).max() > 1e-4
    onp.testing.assert_allclose(p_half.asnumpy().sum(-1), 1.0, rtol=1e-5)


def _experts(held, seed=0, experts=16, units=8, hidden=12):
    """A SparseExperts block holding ``held`` of ``experts``, its weights
    the slice of one seeded full set."""
    rs = onp.random.RandomState(seed)
    full = {name: rs.randn(experts, *shape).astype("float32") * 0.3
            for name, shape in (("gate_weight", (units, hidden)),
                                ("up_weight", (units, hidden)),
                                ("down_weight", (hidden, units)))}
    block = cnn.SparseExperts(units, hidden, experts, experts_held=held)
    block.initialize()
    first, end = block.experts_held
    for name, value in full.items():
        getattr(block, name).set_data(mx.nd.array(value[first:end]))
    return block, full


def _dense_experts(x, probs, full):
    """The plain loop over ALL the experts."""
    expert = probs.argmax(-1)
    out = onp.zeros_like(x)
    for e in range(probs.shape[-1]):
        h = x @ full["gate_weight"][e]
        y = (h / (1 + onp.exp(-h)) * (x @ full["up_weight"][e])) \
            @ full["down_weight"][e]
        out += onp.where((expert == e)[..., None],
                         probs[..., e:e + 1] * y, 0.0)
    return out


def test_dropless_routing_under_forced_imbalance():
    """8 tokens in 9 go to ONE held expert: every one of them is computed
    (result equal to the dense loop), nothing is dropped."""
    block, full = _experts((0, 8))
    rs = onp.random.RandomState(1)
    x = rs.randn(2, 36, 8).astype("float32")
    logits = rs.randn(2, 36, 16).astype("float32")
    logits[:, onp.arange(36) % 9 != 0, 3] += 20.0
    probs = onp.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    with autograd.train_mode():
        got = block(mx.nd.array(x), mx.nd.array(probs)).asnumpy()
    held_only = dict(full)
    want = _dense_experts(x, probs, held_only)
    want[probs.argmax(-1) >= 8] = 0.0
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    load = block.expert_load.data().asnumpy()
    assert load[3] == 64 and load.sum() == 72
    counts = cnn.publish_routing_counts()[block.name]
    assert counts["rows"] == counts["load"][:8]
    assert telemetry.snapshot()["gauges"]["moe.dropped"] == 0


def test_expert_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST: the layer holding experts 0..7 plus the layer
    holding 8..15 equals the plain loop over all 16 (nothing in this layer
    is computed alike by both shares, so nothing is counted twice)."""
    low, full = _experts((0, 8))
    high, _ = _experts((8, 16))
    whole, _ = _experts(None)
    rs = onp.random.RandomState(4)
    x = rs.randn(2, 40, 8).astype("float32")
    probs = onp.asarray(jax.nn.softmax(
        jnp.asarray(rs.randn(2, 40, 16).astype("float32") * 2), axis=-1))
    xs, ps = mx.nd.array(x), mx.nd.array(probs)
    parts = low(xs, ps).asnumpy() + high(xs, ps).asnumpy()
    want = _dense_experts(x, probs, full)
    onp.testing.assert_allclose(parts, want, rtol=1e-5, atol=1e-6)
    onp.testing.assert_allclose(whole(xs, ps).asnumpy(), want, rtol=1e-5,
                                atol=1e-6)
    # a token is some share's, never both's
    assert not ((onp.abs(low(xs, ps).asnumpy()).sum(-1) > 0)
                & (onp.abs(high(xs, ps).asnumpy()).sum(-1) > 0)).any()


def test_vocabulary_halves_concatenate_to_the_whole_head():
    """THE SHARE TEST of the head: the model built on the first half of
    the embedding's rows gives the first half of the whole model's logits,
    and the same hidden states against the second half's rows give the
    rest (the token ids are drawn from the first half, the slice a chip
    that holds it looks its inputs up in)."""
    sizes, whole, tokens, _ = _toy()
    vocab = sizes["vocab_size"]
    tokens = tokens % (vocab // 2)
    positions = _ids(onp.stack([onp.arange(0, sizes["seq_len"], 5)] * 2))
    want = whole(_ids(tokens), positions).asnumpy()
    table = whole.embed.weight.data().asnumpy()
    half = M._net(dict(sizes, vocab_size=vocab // 2))
    half.initialize()
    for (_, p), (_, q) in zip(sorted(half.collect_params().items()),
                              sorted(whole.collect_params().items())):
        p.set_data(q.data() if p.shape == q.shape
                   else mx.nd.array(table[:vocab // 2]))
    first = half(_ids(tokens), positions).asnumpy()
    hidden, _ = half(_ids(tokens))
    second = mx.nd.FullyConnected(
        mx.nd.gather_positions(hidden, positions),
        mx.nd.array(table[vocab // 2:]), no_bias=True, flatten=False,
        num_hidden=vocab // 2).asnumpy()
    assert first.shape[-1] == second.shape[-1] == vocab // 2
    onp.testing.assert_allclose(onp.concatenate([first, second], axis=-1),
                                want, rtol=1e-5, atol=1e-6)


def test_step_program_carries_the_blocks_names():
    """Every new block has a ``Block.name``: the step program's operations
    sit under it in both passes, so a device trace can be read by block."""
    from mxnet_tpu import parallel

    sizes, net, tokens, labels = _toy()
    step = parallel.DataParallelStep(
        net, gluon.loss.TiedSoftmaxCrossEntropyLoss(block_rows=32),
        mx.optimizer.Adam(learning_rate=1e-3))
    step(_ids(tokens), _ids(labels))
    text = step.lower(_ids(tokens), _ids(labels)).as_text(debug_info=True)
    for block in ("embed", "layer0_attn_norm", "layer0_cca", "layer0_cca_q",
                  "layer1_router", "layer1_router_fc2", "layer1_experts",
                  "final_norm"):
        name = net.prefix + block
        for phase in ("jvp(forward)", "transpose(jvp(forward))"):
            assert re.search(r'loc\("jit\(step_fn\)/%s/[^"]*\b%s/'
                             % (re.escape(phase), name), text), (phase, block)


# ---------------------------------------------------------------------------
# attention and the head's loss
# ---------------------------------------------------------------------------

def _dense_gqa(q, k, v, causal):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (q.shape[-1] ** 0.5)
    if causal:
        t = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("blocks", [(128, 128), (256, 256), (128, 256)],
                         ids=["stream_split_bwd", "one_block",
                              "one_k_block_q_streamed"])
@pytest.mark.parametrize("causal", [True, False])
def test_gqa_kernels_match_dense(blocks, causal):
    """8 query heads on 2 key-value heads through the kernels' index maps
    (interpret mode): forward, dq, and dk/dv summed over the group inside
    the kernel (split backward) or after it (one-K-block kernels)."""
    rs = onp.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 8, 256, 32).astype("float32"))
    k = jnp.asarray(rs.randn(1, 2, 256, 32).astype("float32"))
    v = jnp.asarray(rs.randn(1, 2, 256, 32).astype("float32"))
    do = jnp.asarray(rs.randn(1, 8, 256, 32).astype("float32"))
    kw = dict(causal=causal, block_q=blocks[0], block_k=blocks[1],
              interpret=True)
    out, lse = PA.pallas_flash_attention(q, k, v, return_lse=True, **kw)
    want, vjp = jax.vjp(lambda q, k, v: _dense_gqa(q, k, v, causal),
                        q, k, v)
    got = PA.pallas_flash_attention_bwd(q, k, v, out, lse, do, **kw)
    onp.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    for g, w in zip(got, vjp(do)):
        assert g.shape == w.shape
        onp.testing.assert_allclose(g, w, rtol=1e-4, atol=2e-5)


def test_gqa_through_flash_attention_op():
    rs = onp.random.RandomState(1)
    q = jnp.asarray(rs.randn(2, 4, 48, 16).astype("float32"))
    k = jnp.asarray(rs.randn(2, 2, 48, 16).astype("float32"))
    v = jnp.asarray(rs.randn(2, 2, 48, 16).astype("float32"))
    got = jax.grad(lambda *a: (PA.flash_attention(*a, True, None) ** 2)
                   .sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_dense_gqa(*a, True) ** 2).sum(),
                    (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        onp.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError):
        PA.flash_attention(q, k[:, :1].repeat(3, 1), v, True, None)


def _head_inputs(dtype="float32", seq=8):
    """(hidden, weight, labels with two ignored positions, the per-position
    scale a loss makes of them and a sample weight, a per-position
    cotangent) of a 3 x ``seq`` head over 96 rows."""
    rs = onp.random.RandomState(0)
    h = jnp.asarray(rs.randn(3, seq, 16).astype("float32")).astype(dtype)
    w = jnp.asarray(rs.randn(96, 16).astype("float32") * 0.5).astype(dtype)
    lab = rs.randint(0, 96, (3, seq))
    g = jnp.asarray(rs.rand(3, seq).astype("float32"))
    ignored = lab.copy()
    ignored[0, 2] = ignored[2, seq - 1] = -1
    counted = (ignored != -1).astype("float32")
    scale = counted * rs.rand(3, 1).astype("float32") \
        / counted.sum(-1, keepdims=True)
    return h, w, jnp.asarray(lab), jnp.asarray(ignored), \
        jnp.asarray(scale), g


def _plain_ce(h, w, lab):
    logp = jax.nn.log_softmax(
        h.astype(jnp.float32) @ w.astype(jnp.float32).T, axis=-1)
    return -jnp.take_along_axis(
        logp, jnp.clip(lab, 0, None)[..., None], axis=-1)[..., 0]


@pytest.mark.parametrize("block_rows", [8, 24, 96, 1000])
@pytest.mark.parametrize("scaled", [False, True],
                         ids=["no_scale", "scale_with_ignored_labels"])
@pytest.mark.parametrize("cotangent", ["uniform", "per_position"])
def test_blocked_tied_cross_entropy_matches_unblocked(cotangent, scaled,
                                                      block_rows):
    """Loss and both gradients against the plain ``log_softmax``: a
    cotangent of one number for every position multiplies the gradients
    the forward made, any other goes through the loop that forms the
    logits again — both exact."""
    h, w, lab, ignored, scale, g = _head_inputs(seq=7)
    if scaled:
        lab = ignored
    else:
        scale = None
    if cotangent == "uniform":
        g = jnp.full(g.shape, 0.3, jnp.float32)

    def plain(h, w):
        ce = _plain_ce(h, w, lab)
        return ce if scale is None else ce * scale

    def blocked(h, w):
        return nn_ops.tied_softmax_cross_entropy(
            h, w, lab, scale=scale, block_rows=block_rows)

    onp.testing.assert_allclose(blocked(h, w), plain(h, w), rtol=1e-5,
                                atol=1e-5)
    got = jax.grad(lambda h, w: (blocked(h, w) * g).sum(), (0, 1))(h, w)
    want = jax.grad(lambda h, w: (plain(h, w) * g).sum(), (0, 1))(h, w)
    for a, b in zip(got, want):
        onp.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert nn_ops.vocab_block_rows(131136, 8196) == 8196
    assert nn_ops.vocab_block_rows(96, block_rows) in (8, 24, 96)


@pytest.mark.parametrize("seq,block_rows,positions", [
    (8192, 8196, 512), (8, 24, 2), (8, 48, 4), (8, 96, 8), (7, 24, 1),
    (8, 1, 1)])
def test_token_blocks_hold_the_logits_a_block_of_rows_would(
        seq, block_rows, positions):
    """``block_rows`` is the budget of one block of logits whichever way
    the blocks lie: a block of tokens is the largest divisor of the
    sequence with B x positions x V <= N x block_rows (the cell: 512
    positions of 2 rows, 16 blocks), and the scale's gradient is the
    cross-entropy itself."""
    vocab = 131136 if seq == 8192 else 96
    assert nn_ops.token_block_positions(seq, vocab, block_rows) == positions
    if seq == 8192:
        return
    h, w, _, lab, scale, g = _head_inputs(seq=seq)
    got = jax.grad(lambda s: (nn_ops.tied_softmax_cross_entropy(
        h, w, lab, scale=s, block_rows=block_rows) * g).sum())(scale)
    counted = onp.asarray(lab) != -1     # an ignored label picks no logit
    onp.testing.assert_allclose(
        onp.asarray(got)[counted],
        onp.asarray(_plain_ce(h, w, lab) * g)[counted], rtol=1e-5, atol=1e-5)


def test_bfloat16_forward_gradients_equal_the_recomputed_ones():
    """bf16 hidden states and table, the SAME inputs down both paths: the
    gradients made in the forward (a uniform cotangent) against the
    recomputing loop's (the same cotangent, told apart from uniform by one
    position whose scale is 0) agree to bf16's rounding, and both with
    the float32 reference on the rounded inputs."""
    h, w, _, lab, scale, _ = _head_inputs("bfloat16")

    def loss(g):
        return lambda h, w: (nn_ops.tied_softmax_cross_entropy(
            h, w, lab, scale=scale, block_rows=24) * g).sum()

    uniform = jnp.full(lab.shape, 0.25, jnp.float32)
    # position (0, 2) is ignored (scale 0): its cotangent changes nothing
    odd = uniform.at[0, 2].set(7.0)
    before = telemetry.counter("tied_ce.path.recompute")
    fast = jax.jit(jax.grad(loss(uniform), (0, 1)))(h, w)
    slow = jax.jit(jax.grad(loss(odd), (0, 1)))(h, w)
    assert telemetry.counter("tied_ce.path.recompute") == before + 2
    want = jax.grad(lambda h, w: (_plain_ce(h, w, lab) * scale
                                  * uniform).sum(), (0, 1))(
        h.astype(jnp.float32), w.astype(jnp.float32))
    for a, b, c in zip(fast, slow, want):
        assert a.dtype == b.dtype == jnp.bfloat16
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        top = float(jnp.abs(c).max())
        # one bf16 rounding of each (2 ** -9 relative), dl's rounding
        # summed over the products
        assert float(jnp.abs(a - b).max()) <= 2 ** -7 * top
        assert float(jnp.abs(a - c).max()) <= 2 ** -6 * top
        assert float(jnp.abs(b - c).max()) <= 2 ** -6 * top


@pytest.mark.parametrize("mesh_shape", [None, (2,)], ids=["one_device",
                                                          "dp2"])
def test_data_parallel_step_takes_the_heads_gradients_from_its_forward(
        mesh_shape):
    """A ``DataParallelStep`` trace bumps ``tied_ce.path.forward_grads``
    once (and ``recompute`` once, for the fallback traced under the
    conditional beside it); a forward without differentiation bumps
    neither; the step's SGD update is the reference's gradient.  Over a
    ``dp`` mesh each shard sums its own dW: no (V, D) all-reduce inside
    the head's loop."""
    from mxnet_tpu import parallel

    sizes, net, tokens, labels = _toy()
    _, want = M.reference_loss_and_grads(M.host_params(net), tokens, labels,
                                         sizes)
    before = {name[len(net.prefix):]: p.data().asnumpy()
              for name, p in net.collect_params().items()}
    loss_fn = gluon.loss.TiedSoftmaxCrossEntropyLoss(
        block_rows=sizes["train"]["loss_block_rows"])
    counts = lambda: [telemetry.counter("tied_ce.path." + k)
                      for k in ("forward_grads", "recompute")]
    start = counts()
    loss_fn(net(_ids(tokens)), _ids(labels))
    assert counts() == start
    mesh = mesh_shape and parallel.device_mesh(
        mesh_shape, ("dp",), devices=jax.devices()[:mesh_shape[0]])
    step = parallel.DataParallelStep(
        net, loss_fn, mx.optimizer.SGD(learning_rate=1.0), mesh=mesh)
    data, label = _ids(tokens), _ids(labels)
    if mesh is not None:
        data, label = (parallel.shard_batch(x, mesh) for x in (data, label))
    step(data, label)
    assert counts() == [start[0] + 1, start[1] + 1]
    for name, grad in want.items():
        moved = before[name] - net.collect_params()[
            net.prefix + name].data().asnumpy()
        # a difference of float32 parameters: their own rounding over a
        # gradient a hundredth their size
        assert _rel(moved, grad) <= 1e-4, name
    if mesh is not None:
        text = step.lower(data, label).compile().as_text()
        vocab, units = before["embed_weight"].shape
        for body in re.findall(r"\n%?[\w.\-]*(?:body|region)[\w.\-]* \(.*?\n}",
                               text, re.S):
            assert not re.search(r"f32\[%d,%d\][^=\n]* all-reduce\("
                                 % (vocab, units), body)


def test_model_flops_counts_the_share():
    with open(os.path.join(CONFIG, "config.json")) as f:
        sizes = json.load(f)
    flops = M.model_flops(sizes)
    head = 6 * sizes["seq_len"] * sizes["hidden_size"] * sizes["vocab_size"]
    assert 0.70 < head / flops < 0.80       # the head is ~3/4 of the row
    whole = M.model_flops(dict(sizes, num_experts=16))
    assert whole - flops == 6 * sizes["seq_len"] * 4 * 3 * 2048 * 2048 // 2


def _lumpy_probs(seed, shape=(2, 512, 16)):
    """A router that sends half the tokens to expert 5."""
    rs = onp.random.RandomState(seed)
    logits = rs.randn(*shape).astype("float32") * 0.3
    logits[:, ::2, 5] += 1.0
    return onp.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1)), rs


def test_bias_rule_moves_each_bias_one_rate_against_its_load_error():
    """One training call: the tokens are routed by the bias as it stood,
    then ``b_e += rate * sign(mean load - load_e)``; an evaluation call and
    a rate of zero leave the bias alone."""
    probs, rs = _lumpy_probs(5)
    x = mx.nd.array(rs.randn(2, 512, 8).astype("float32"))
    block = cnn.SparseExperts(8, 12, 16, experts_held=(0, 8),
                              bias_update_rate=0.01)
    block.initialize(mx.init.Normal(0.3))
    start = (rs.randn(16) * 0.02).astype("float32")
    block.balance_bias.set_data(mx.nd.array(start))
    with autograd.train_mode():
        block(x, mx.nd.array(probs))
    load = block.expert_load.data().asnumpy()
    onp.testing.assert_array_equal(
        load, onp.bincount((probs + start).argmax(-1).ravel(), minlength=16))
    after = block.balance_bias.data().asnumpy()
    onp.testing.assert_allclose(after, start + 0.01 * onp.sign(64 - load),
                                rtol=0, atol=1e-7)
    block(x, mx.nd.array(probs))                     # evaluation
    onp.testing.assert_array_equal(block.balance_bias.data().asnumpy(),
                                   after)
    still = cnn.SparseExperts(8, 12, 16, experts_held=(0, 8))
    still.initialize(mx.init.Normal(0.3))
    with autograd.train_mode():
        still(x, mx.nd.array(probs))
    assert not still.balance_bias.data().asnumpy().any()


def test_bias_rule_spreads_a_lumpy_router_over_training_steps():
    """From a zero bias, a batch of its own every step: the most loaded
    expert comes down from half the tokens to near an even share (64
    tokens: a few of them are a tenth of it), on a batch the rule has never
    seen too."""
    block = cnn.SparseExperts(8, 12, 16, experts_held=(0, 8),
                              bias_update_rate=0.003)
    block.initialize(mx.init.Normal(0.3))
    worst = []
    for step in range(120):
        probs, rs = _lumpy_probs(100 + step)
        with autograd.train_mode():
            block(mx.nd.array(rs.randn(2, 512, 8).astype("float32")),
                  mx.nd.array(probs))
        worst.append(block.expert_load.data().asnumpy().max() / 64)
    assert worst[0] > 6 and max(worst[-10:]) < 1.7
    unseen, _ = _lumpy_probs(7)
    bias = block.balance_bias.data().asnumpy()
    load = onp.bincount((unseen + bias).argmax(-1).ravel(), minlength=16)
    assert load.max() / 64 < 1.7


def test_reference_routes_by_the_same_bias():
    """Every layer's bias moved by a few training calls: the system and the
    reference both route by ``argmax(p + b)`` and the logits still agree."""
    sizes, net, tokens, labels = _toy(bias_update_rate=0.02)
    for _ in range(3):
        with autograd.train_mode():
            net(_ids(tokens))
    biases = [layer.experts.balance_bias.data().asnumpy()
              for layer in net.layers]
    assert all(onp.abs(b).max() > 0 for b in biases)
    positions = onp.stack([onp.arange(0, sizes["seq_len"], 7)] * 2)
    got = net(_ids(tokens), _ids(positions)).asnumpy()
    chosen = onp.stack([layer.experts.last_expert.asnumpy()
                        for layer in net.layers])
    params = M.host_params(net)
    want = M.reference_forward(params, tokens, positions, sizes)
    assert onp.abs(got - want).max() <= 1e-5 * onp.abs(want).max()
    # and not by argmax(p): the bias changed some token's expert
    _, probs = M.reference_hidden(params, tokens, sizes)
    assert (probs.argmax(-1) != chosen).any()


def test_compare_fails_a_wrong_route_where_the_reference_is_clear():
    """``compare`` hands on the logits when the routes agree, and NaN — no
    verdict — when 1% of a layer's tokens go elsewhere although the
    reference's two best experts are clearly apart: under the agreement
    floor's 4%, over the clear tokens' 0.5%."""
    sizes, net, tokens, _ = _toy(seq_len=512)
    positions = onp.stack([onp.arange(0, sizes["seq_len"], 16)] * 2)
    logits = net(_ids(tokens), _ids(positions)).asnumpy()
    chosen = onp.stack([layer.experts.last_expert.asnumpy()
                        for layer in net.layers])
    params = M.host_params(net)
    got, want = M.compare(logits, chosen, params, tokens, positions, sizes)
    assert onp.abs(got - want).max() <= 1e-5 * onp.abs(want).max()
    _, probs = M.reference_hidden(params, tokens, sizes, follow=chosen)
    best = onp.sort(probs[1], axis=-1)
    gap = (best[..., -1] - best[..., -2]).ravel()
    wrong = chosen.copy()
    clearest = onp.argsort(-gap)[:gap.size // 100]
    flat = wrong[1].reshape(-1)
    flat[clearest] = (flat[clearest] + 1) % probs.shape[-1]
    got, _ = M.compare(logits, wrong, params, tokens, positions, sizes)
    assert onp.isnan(got).all()


def test_embedding_gradient_sums_repeated_ids_in_float32():
    """bfloat16 table, ids that repeat by the hundred: autodiff's
    scatter-add rounds every addition to 8 bits of mantissa; the summed
    lookup adds in float32 and casts once."""
    rs = onp.random.RandomState(0)
    ids = jnp.asarray(rs.zipf(1.3, 4096).clip(max=499))
    g = jnp.asarray((rs.randn(4096, 32) * 1e-3 + 2e-4).astype("float32"))
    exact = onp.zeros((500, 32))
    onp.add.at(exact, onp.asarray(ids), onp.asarray(g, "float64"))
    table = jnp.zeros((500, 32), jnp.bfloat16)

    def grad(lookup):
        return onp.asarray(jax.grad(lambda w: (lookup(w).astype(
            jnp.float32) * g).sum())(table), "float64")

    summed = grad(lambda w: nn_ops.embedding(ids, w))
    scattered = grad(lambda w: jnp.take(w, ids, axis=0))
    assert _rel(summed, exact) < 4e-3
    assert _rel(scattered, exact) > 3 * _rel(summed, exact)
    # and it is the same lookup; a float32 table keeps autodiff's gradient
    onp.testing.assert_array_equal(nn_ops.embedding(ids, table + 1),
                                   jnp.take(table + 1, ids, axis=0))
    wide = jnp.zeros((500, 32), jnp.float32)
    onp.testing.assert_array_equal(
        jax.grad(lambda w: (nn_ops.embedding(ids, w) * g).sum())(wide),
        jax.grad(lambda w: (jnp.take(w, ids, axis=0) * g).sum())(wide))


def test_embedding_gradient_sums_per_shard_under_a_sharded_batch():
    """Inside a program whose batch GSPMD shards over ``dp`` (the
    ``DataParallelStep`` layout) each shard sorts and sums its own ids and
    the tables are added across shards: the same gradient as on one device,
    and no sort over the gathered batch."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel.mesh import batch_sharded_over

    mesh = Mesh(onp.array(jax.devices()[:4]), ("dp",))
    rs = onp.random.RandomState(1)
    ids = jnp.asarray(rs.zipf(1.3, (8, 256)).clip(max=99))
    g = jnp.asarray((rs.randn(8, 256, 16) * 1e-2).astype("float32"),
                    jnp.bfloat16)
    table = jnp.zeros((100, 16), jnp.bfloat16)

    def grad(w, i, g):
        return jax.vjp(lambda w_: nn_ops.embedding(i, w_), w)[1](g)[0]

    def sharded(w, i, g):
        with batch_sharded_over(mesh):
            return grad(w, i, g)

    over = NamedSharding(mesh, P("dp"))
    program = jax.jit(sharded).lower(
        table, jax.device_put(ids, over), jax.device_put(g, over)).compile()
    got = program(table, jax.device_put(ids, over), jax.device_put(g, over))
    want = grad(table, ids, g)
    assert _rel(onp.asarray(got, "float64"),
                onp.asarray(want, "float64")) < 1e-2
    assert "all-gather" not in program.as_text()
