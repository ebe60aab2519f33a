"""SDAR through ``gluon.model_zoo.sdar`` against the plain float32 reference
in ``benchmark/configs/sdar-30b-a3b-ep8share/model.py``, at toy sizes on the
CPU, seeded weights: block diffusion's mask (the three clauses against a
hand-written table, and against the integers the kernels take), the noising
helper, rotary embedding at given position ids, the objective, the whole
model (logits, loss, every parameter's gradient), the step through
``DataParallelStep``, ``compare``'s two controls, and THE SHARE TEST: the
eight shares' held-expert parts, with the residual counted once, add up to
the uncut layer."""
import importlib.util
import json
import os

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, parallel, telemetry
from mxnet_tpu.gluon.contrib import nn as cnn
from mxnet_tpu.gluon.model_zoo import block_diffusion_mask, \
    block_diffusion_row
from mxnet_tpu.gluon.model_zoo.sdar import SDARLayer
from mxnet_tpu.ops import nn as nn_ops
from mxnet_tpu.ops import pallas_attention as PA

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "..", "benchmark", "configs",
                      "sdar-30b-a3b-ep8share")


def _load_model():
    spec = importlib.util.spec_from_file_location(
        "sdar_bench_model", os.path.join(CONFIG, "model.py"))
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


def _toy(dtype="float32", seed=3, **over):
    """(sizes, net, row) of the rehearsal-sized model, two rows."""
    sizes = _toy_sizes(**over)
    mx.random.seed(seed)
    onp.random.seed(seed)
    rs = onp.random.RandomState(seed)
    net = M._net(sizes)
    # wider than the cell's 0.02 so that at toy widths no path is faint
    net.initialize(mx.init.Normal(0.2))
    if dtype != "float32":
        net.cast(dtype)
    return sizes, net, M.draw_row(sizes, rs, 2)


def _ids(a):
    return mx.nd.array(onp.asarray(a).astype("int32"), dtype="int32")


def _inputs(row):
    return tuple(_ids(a) for a in (row.tokens, row.position_ids, row.q_mask,
                                   row.kv_mask))


def _rel(got, want):
    return float(onp.abs(got - want).max() / max(onp.abs(want).max(), 1e-30))


# ---------------------------------------------------------------------------
# the mask
# ---------------------------------------------------------------------------

# L = 8, B = 4: rows and columns are [c0 c1 | n0 n1] in blocks (c clean, n
# noised); an X is a whole 4 x 4 block of live pairs
_HAND = ["X...",     # clean block 0: its own block
         "XX..",     # clean block 1: the clean past and its own block
         "..X.",     # noised block 0: no past; its own noised block
         "X..X"]     # noised block 1: clean block 0; its own noised block


def test_dense_mask_is_the_hand_written_table():
    want = onp.kron(onp.array([[c == "X" for c in line] for line in _HAND]),
                    onp.ones((4, 4), bool))
    got = onp.asarray(M.dense_mask(jnp.arange(16), 8, 4))
    onp.testing.assert_array_equal(got, want)
    assert got.sum() == M.live_pairs({"seq_len": 8,
                                      "train": {"block_length": 4}}) == 96
    # the control drops the noised diagonal and nothing else
    dropped = onp.asarray(M.dense_mask(jnp.arange(16), 8, 4, True))
    assert (want & ~dropped).sum() == 32 and not (dropped & ~want).any()
    assert not dropped[8:, 8:].any()


@pytest.mark.parametrize("length,block", [(8, 4), (24, 4), (32, 8), (12, 1)])
def test_mask_integers_say_what_the_clauses_say(length, block):
    """``block_diffusion_mask``'s two integers a token, through the
    kernels' rule, are the dense mask of the three clauses."""
    q_mask, kv_mask = block_diffusion_mask(length, block, batch=2)
    assert q_mask.shape == kv_mask.shape == (2, 2 * length, 2)
    reach, q_own = q_mask[0, :, 0, None], q_mask[0, :, 1, None]
    rank, k_own = kv_mask[0, None, :, 0], kv_mask[0, None, :, 1]
    seen = (rank <= reach) | ((k_own == q_own) & (q_own >= 0))
    want = onp.asarray(M.dense_mask(jnp.arange(2 * length), length, block))
    onp.testing.assert_array_equal(seen, want)
    n = length // block
    assert seen.sum() == block * block * (n * (n + 1) // 2
                                          + n * (n - 1) // 2 + n)
    # no clean query sees a noised key
    assert not seen[:length, length:].any()


def test_live_pairs_at_the_cells_size_are_a_quarter():
    with open(os.path.join(CONFIG, "config.json")) as f:
        sizes = json.load(f)
    assert M.live_pairs(sizes) == 8396800 + 8380416 + 16384 == 16793600
    assert M.live_pairs(sizes) / 8192 ** 2 == pytest.approx(0.2503, abs=1e-4)
    assert M.attention_flops(sizes) == 18 * 128 * 16793600 * 32 * 6
    flops = M.model_flops(sizes)
    assert 12.8e12 < flops < 13.1e12
    # the attention products count the live pairs, forward and backward
    assert 6 * 2 * 128 * 16793600 * 32 * 6 / flops == pytest.approx(
        0.383, abs=0.01)
    # every expert held: eight times the routed experts' products
    whole = M.model_flops(dict(sizes, num_experts=128))
    routed = 6 * 8192 * 6 * 8 * 3 * 2048 * 768
    assert whole - flops == pytest.approx(routed * 7 / 8, rel=1e-9)


def test_noising_a_row():
    rs = onp.random.RandomState(5)
    tokens = rs.randint(0, 90, (3, 64))
    row = block_diffusion_row(tokens, 4, 95, rs, t_min=0.05)
    clean, noised = row.tokens[:, :64], row.tokens[:, 64:]
    onp.testing.assert_array_equal(clean, tokens)
    masked = noised == 95
    onp.testing.assert_array_equal(noised[~masked], tokens[~masked])
    onp.testing.assert_array_equal(row.position_ids,
                                   onp.tile(onp.arange(64), (3, 2)))
    assert row.noise.shape == (3, 16)
    assert (row.noise >= 0.05).all() and (row.noise < 1).all()
    t = onp.repeat(row.noise, 4, axis=1)
    ids, weight = row.label[:, 0], row.label[:, 1]
    onp.testing.assert_array_equal(ids[masked], tokens[masked])
    assert (ids[~masked] == -1).all() and (weight[~masked] == 0).all()
    onp.testing.assert_allclose(weight[masked], 1.0 / t[masked], rtol=1e-6)
    # a block's share of masked tokens follows its t
    assert abs(masked.mean() - row.noise.mean()) < 0.1
    with pytest.raises(ValueError):
        block_diffusion_row(tokens[:, :62], 4, 95, rs)


# ---------------------------------------------------------------------------
# rotary at position ids, the objective, the tile count
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rotary_dim", [0, 8])
def test_rotary_at_arange_is_the_old_result_bit_for_bit(rotary_dim):
    x = jnp.asarray(onp.random.RandomState(0).randn(2, 3, 40, 16), "float32")
    old = nn_ops.rotary_embedding(x, rotary_dim=rotary_dim, theta=50.0)
    pos = jnp.tile(jnp.arange(40), (2, 1))
    new = nn_ops.rotary_embedding(x, pos, rotary_dim=rotary_dim, theta=50.0)
    assert (onp.asarray(old) == onp.asarray(new)).all()


def test_rotary_at_repeated_ids_turns_both_halves_alike():
    x = onp.random.RandomState(1).randn(2, 3, 20, 16).astype("float32")
    both = onp.concatenate([x, x], axis=2)
    pos = jnp.tile(jnp.arange(20), (2, 2))
    out = onp.asarray(nn_ops.rotary_embedding(jnp.asarray(both), pos,
                                              theta=1e4))
    onp.testing.assert_array_equal(out[:, :, :20], out[:, :, 20:])
    onp.testing.assert_array_equal(
        out[:, :, :20], onp.asarray(nn_ops.rotary_embedding(
            jnp.asarray(x), theta=1e4)))


def test_block_diffusion_loss_is_the_weighted_sum_over_all_positions():
    rs = onp.random.RandomState(2)
    hidden, table = rs.randn(2, 12, 8).astype("float32"), \
        rs.randn(20, 8).astype("float32")
    ids = rs.randint(0, 20, (2, 12)).astype("float32")
    ids[:, ::3] = -1
    weight = onp.where(ids >= 0, rs.uniform(1, 5, (2, 12)), 0.0).astype(
        "float32")
    logp = jax.nn.log_softmax(jnp.asarray(hidden) @ jnp.asarray(table).T)
    picked = onp.take_along_axis(onp.asarray(logp), onp.maximum(
        ids, 0).astype(int)[..., None], axis=-1)[..., 0]
    want = (onp.where(ids >= 0, -picked * weight, 0.0)).sum(-1) / 12
    loss = gluon.loss.BlockDiffusionLoss(block_rows=8)
    pred = (mx.nd.array(hidden), mx.nd.array(table))
    apart = loss(pred, mx.nd.array(ids), mx.nd.array(weight)).asnumpy()
    stacked = loss(pred, mx.nd.array(onp.stack([ids, weight], 1))).asnumpy()
    onp.testing.assert_allclose(apart, want, rtol=1e-5)
    onp.testing.assert_array_equal(apart, stacked)
    # the parent class divides by the counted positions instead
    other = gluon.loss.TiedSoftmaxCrossEntropyLoss(block_rows=8)(
        pred, mx.nd.array(ids), mx.nd.array(weight)).asnumpy()
    onp.testing.assert_allclose(other * (ids >= 0).sum(-1), want * 12,
                                rtol=1e-5)


def test_mask_tile_count_follows_the_kernels_summary(monkeypatch):
    """On a TPU the count is the summary's at the planned blocks, forward
    once and the two backward kernels at theirs; here, where no kernel
    streams, zeros."""
    q_mask, kv_mask = (jnp.asarray(m)
                       for m in block_diffusion_mask(4096, 4, 1))
    assert [float(x) for x in PA.mask_tiles(q_mask, kv_mask, 128)] == [0, 0]
    from mxnet_tpu import context
    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    visited, total = (float(x) for x in PA.mask_tiles(q_mask, kv_mask, 128))
    # 1024 x 1024 tiles: 24 of 64 a head row (10 clean on clean, 10
    # noised on clean, 4 noised on noised), in each of the three kernels
    assert (visited, total) == (3 * 24, 3 * 64)
    counter = cnn.MaskTileCount(128, calls=6)
    counter.initialize()
    with autograd.train_mode():
        counter(mx.nd.array(q_mask, dtype="int32"),
                mx.nd.array(kv_mask, dtype="int32"))
    assert counter.tiles.data().asnumpy().tolist() == [6 * 72, 6 * 192]
    seen = cnn.publish_mask_tiles()
    gauges = telemetry.snapshot()["gauges"]
    assert gauges["attention.mask.tiles_visited"] == seen[0] >= 6 * 72
    assert gauges["attention.mask.tiles_total"] == seen[1] >= 6 * 192


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

def test_logits_match_reference_float32():
    sizes, net, row = _toy()
    positions = onp.stack([onp.arange(0, sizes["seq_len"], 7)] * 2)
    got = net(*_inputs(row), _ids(positions)).asnumpy()
    want = M.reference_forward(M.host_params(net), row, positions, sizes)
    assert got.shape == want.shape == (2, positions.shape[1],
                                       sizes["vocab_size"])
    assert onp.abs(got - want).max() <= 1e-5 * onp.abs(want).max()
    # the hidden states handed to the loss are the noised half's
    hidden, table = net(*_inputs(row))
    assert hidden.shape == (2, sizes["seq_len"], sizes["hidden_size"])
    assert table.shape == (sizes["vocab_size"], sizes["hidden_size"])


def test_loss_and_every_gradient_match_reference_float32():
    sizes, net, row = _toy()
    loss_fn = gluon.loss.BlockDiffusionLoss(
        block_rows=sizes["train"]["loss_block_rows"])
    with autograd.record():
        loss = loss_fn(net(*_inputs(row)), mx.nd.array(row.label)).mean()
    loss.backward()
    want_loss, want = M.reference_loss_and_grads(M.host_params(net), row,
                                                 sizes)
    assert abs(float(loss.asnumpy()) - want_loss) <= 1e-5 * want_loss
    trained = {name[len(net.prefix):]: p
               for name, p in net.collect_params().items()
               if p.grad_req != "null"}
    assert set(trained) == set(want)
    for name, p in trained.items():
        assert _rel(p.grad().asnumpy(), want[name]) < 5e-5, name


def test_compare_passes_and_each_control_fails():
    """``compare`` hands on matching logits; the reference in float8, and
    the reference with the noised tokens' own block dropped from the mask,
    each fail the logits' limit (or the routing's: NaN)."""
    from benchmark import correct

    sizes, net, row = _toy(seq_len=128)
    positions = onp.stack([onp.arange(0, sizes["seq_len"], 4)] * 2)
    logits = net(*_inputs(row), _ids(positions)).asnumpy()
    chosen = onp.stack([layer.experts.last_expert.asnumpy()
                        for layer in net.layers])
    assert chosen.shape == (2, 2, 2 * sizes["seq_len"], 3)
    params = M.host_params(net)
    args = (logits, chosen, params, row, positions, sizes)
    assert correct.logits_agree(*M.compare(*args))["ok"]
    assert not correct.logits_agree(*M.compare(*args, float8=True))["ok"]
    assert not correct.logits_agree(
        *M.compare(*args, drop_own_block=True))["ok"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_trains_through_data_parallel_step(dtype):
    """``HybridBlock`` -> ``DataParallelStep`` -> ``Adam(multi_precision)``
    with the stacked label: the step lowers ONCE, the loss falls, the
    routing counts ride as state (3 routes a token here, over the WHOLE
    2L row), and the program carries the blocks' names."""
    sizes, net, row = _toy(dtype=dtype)
    step = parallel.DataParallelStep(
        net, gluon.loss.BlockDiffusionLoss(
            block_rows=sizes["train"]["loss_block_rows"]),
        mx.optimizer.Adam(learning_rate=3e-3,
                          multi_precision=dtype != "float32"))
    label = mx.nd.array(row.label)
    losses = [float(step(_inputs(row), label).asnumpy().astype(
        "float32").mean()) for _ in range(8)]
    assert len(step._cache) == 1
    assert all(onp.isfinite(losses)) and losses[-1] < losses[0] - 0.05
    routes = row.tokens.size * sizes["num_experts_per_tok"]
    counts = cnn.publish_routing_counts()
    mine = [v for name, v in counts.items() if name.startswith(net.prefix)]
    assert len(mine) == 2
    for record in mine:
        assert sum(record["load"]) == routes
        assert record["routes_per_token"] == 3
    # no balancing rule runs: the bias stays where it started
    assert not net.layers[1].experts.balance_bias.data().asnumpy().any()
    if dtype == "float32":
        text = step.lower(_inputs(row), label).as_text(debug_info=True)
        for block in ("layer0_attn_qkv", "layer0_attn", "layer1_router",
                      "layer1_experts", "final_norm", "mask"):
            assert "/%s%s/" % (net.prefix, block) in text, block


# ---------------------------------------------------------------------------
# THE SHARE TEST
# ---------------------------------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer():
    """8 shares of 2 of 16 experts, 4 routes a token, on a block-diffusion
    row.  Attention and the residual — which every share computes alike —
    counted ONCE, plus the shares' held-expert parts, equal the uncut
    reference layer; one share alone does not."""
    experts, units, hidden, k = 16, 16, 12, 4
    heads = dict(num_heads=4, num_kv_heads=1, head_dim=8, rope_theta=1e4)
    rs = onp.random.RandomState(0)
    full = {"attn_norm_gamma": rs.rand(units) + 0.5,
            "ffn_norm_gamma": rs.rand(units) + 0.5,
            "attn_q_norm_gamma": rs.rand(8) + 0.5,
            "attn_k_norm_gamma": rs.rand(8) + 0.5,
            "attn_qkv_weight": rs.randn(6 * 8, units) * 0.3,
            "attn_out_weight": rs.randn(units, 4 * 8) * 0.3,
            "router_weight": rs.randn(experts, units) * 0.5,
            "experts_gate_weight": rs.randn(experts, units, hidden) * 0.3,
            "experts_up_weight": rs.randn(experts, units, hidden) * 0.3,
            "experts_down_weight": rs.randn(experts, hidden, units) * 0.3}
    full = {name: value.astype("float32") for name, value in full.items()}
    length = 20
    x = rs.randn(1, 2 * length, units).astype("float32")
    q_mask, kv_mask = block_diffusion_mask(length, 4)
    operands = (_ids(onp.tile(onp.arange(length), (1, 2))), _ids(q_mask),
                _ids(kv_mask))
    outs, parts = [], []
    for first in range(0, experts, 2):
        layer = SDARLayer(units, heads, dict(
            hidden_size=hidden, num_experts=experts,
            experts_held=(first, first + 2), experts_per_token=k), 1e-6)
        layer.initialize()
        for name, p in layer.collect_params().items():
            short = name[len(layer.prefix):]
            if short in full:
                held = short.startswith("experts_")
                p.set_data(mx.nd.array(
                    full[short][first:first + 2] if held else full[short]))
        xs = mx.nd.array(x)
        after = xs + layer.attention(layer.attn_norm(xs), *operands)
        h = layer.ffn_norm(after)
        parts.append(layer.experts(h, layer.router(h)).asnumpy())
        outs.append(layer(xs, *operands).asnumpy())
        alike = after.asnumpy()
    sizes = {"rms_norm_eps": 1e-6, "num_attention_heads": 4,
             "num_key_value_heads": 1, "head_dim": 8, "rope_theta": 1e4,
             "seq_len": length, "train": {"block_length": 4},
             "num_experts_per_tok": k,
             "deployment": {"experts_held": [0, experts]}}
    with jax.default_matmul_precision("highest"):
        want = onp.asarray(M._layer_fn(sizes)(
            jnp.asarray(x[0]), jnp.tile(jnp.arange(length, dtype="float32"),
                                        2),
            {name: jnp.asarray(v) for name, v in full.items()})[0])[None]
    onp.testing.assert_allclose(sum(parts) + alike, want, rtol=2e-5,
                                atol=2e-6)
    onp.testing.assert_allclose(outs[3], parts[3] + alike, rtol=1e-5,
                                atol=1e-6)
    assert onp.abs(outs[3] - want).max() > 1e-2
