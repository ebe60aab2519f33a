"""Ling through ``gluon.model_zoo.ling`` against the plain float32 reference
in ``benchmark/configs/ling-3.0-flash-ep64share/model.py``, at toy sizes on
the CPU, seeded weights: the chunked gated delta rule against its token
recurrence (forward and all five gradients, at the gate's lower bound on
every step, at lengths that are no multiple of the chunk, at beta near 0
and 1), group-limited routing against a direct rule, the interleaved
rotary against explicit pair rotation, the whole model with both mixers
(logits, loss, every parameter's gradient), the step through
``DataParallelStep``, ``compare``'s three controls, the counters, and THE
SHARE TEST: four shares' held-expert parts, with the shared expert and the
residual counted once, add up to the uncut layer under group routing."""
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
from mxnet_tpu.gluon.model_zoo.ling import LingLayer, layer_kinds
from mxnet_tpu.ops import delta_rule as DR
from mxnet_tpu.ops import moe as moe_ops
from mxnet_tpu.ops import nn as nn_ops

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "..", "benchmark", "configs",
                      "ling-3.0-flash-ep64share")


def _load_model():
    spec = importlib.util.spec_from_file_location(
        "ling_bench_model", os.path.join(CONFIG, "model.py"))
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
    """(sizes, net, tokens, labels) of the rehearsal's widths, two rows of
    64, one layer of each kind the cut has: layer 0 (delta, dense), layer 4
    (delta, sparse), layer 5 (latent, sparse); 4 of 16 experts held in 4
    groups, 3 routes a token from 2 groups; chunks of 16."""
    sizes = _toy_sizes(**over)
    sizes["num_hidden_layers"] = 3
    sizes["deployment"] = dict(sizes["deployment"], layers_kept=[0, 4, 5])
    mx.random.seed(seed)
    onp.random.seed(seed)
    rs = onp.random.RandomState(seed)
    net = M._net(sizes)
    # wider than the cell's 0.02 so that at toy widths no path is faint
    net.initialize(mx.init.Normal(0.2))
    for layer in net.layers:
        if layer.kind == "sparse":
            # a correction bias that moves choices, as a trained one does
            layer.experts.balance_bias.set_data(mx.nd.array(
                rs.randn(sizes["published"]["num_experts"]) * 0.05))
    if dtype != "float32":
        net.cast(dtype)
    return (sizes, net) + M.draw_tokens(sizes, rs, 2)


def _ids(a):
    return mx.nd.array(onp.asarray(a).astype("int32"), dtype="int32")


def _rel(got, want):
    return float(onp.abs(got - want).max() / max(onp.abs(want).max(), 1e-30))


# ---------------------------------------------------------------------------
# the chunked delta rule
# ---------------------------------------------------------------------------

def _rule_inputs(rs, length, heads=3, dk=16, dv=8, gate="bounded",
                 beta="random"):
    def unit(x):
        return x / onp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rs.randn(2, length, heads, dk))
    k = unit(rs.randn(2, length, heads, dk))
    v = rs.randn(2, length, heads, dv)
    if gate == "bound":
        g = onp.full((2, length, heads, dk), -5.0)
    else:
        g = -5.0 / (1.0 + onp.exp(-3.0 * rs.randn(2, length, heads, dk)))
    if beta == "extremes":
        b = onp.where(rs.rand(2, length, heads) < 0.5, 1e-4, 1.0 - 1e-4)
    else:
        b = 1.0 / (1.0 + onp.exp(-2.0 * rs.randn(2, length, heads)))
    return [jnp.asarray(t, jnp.float32) for t in (q, k, v, g, b)]


# (id, length, chunk, gate, beta)
_RULE_CASES = [
    ("random_gates", 128, 32, "bounded", "random"),
    ("lower_bound_every_step", 130, 64, "bound", "random"),
    ("no_multiple_of_the_chunk", 100, 32, "bounded", "random"),
    ("beta_near_0_and_1", 96, 32, "bounded", "extremes"),
    ("shorter_than_a_chunk", 21, 64, "bounded", "random"),
]


@pytest.mark.parametrize("case", [c[0] for c in _RULE_CASES])
def test_delta_rule_matches_the_token_recurrence(case):
    """Forward and the gradients of q, k, v, g and beta, against
    ``delta_rule_recurrent`` (a token at a time).  At g = -5 on every step
    a chunk of 64 spans a decay of exp(-320): formed as exp(G_t) exp(-G_i)
    it would overflow float32 (past exp(88.7)); the sub-chunks hold it."""
    _, length, chunk, gate, beta = next(c for c in _RULE_CASES
                                        if c[0] == case)
    args = _rule_inputs(onp.random.RandomState(7), length, gate=gate,
                        beta=beta)
    cot = jnp.asarray(onp.random.RandomState(8).randn(
        2, length, 3, 8), jnp.float32)
    def value_and_grads(f):
        def run(*a):
            out, back = jax.vjp(f, *a)
            return out, back(cot)
        return jax.jit(run)(*args)

    with jax.default_matmul_precision("highest"):
        (got, mine), (want, theirs) = (value_and_grads(f) for f in (
            lambda *a: DR.delta_rule_chunked(*a, chunk=chunk,
                                             lower_bound=-5.0),
            DR.delta_rule_recurrent))
    assert got.shape == want.shape == (2, length, 3, 8)
    assert _rel(onp.asarray(got), onp.asarray(want)) < 1e-5
    grads = (mine, theirs)
    if gate == "bound":
        with onp.errstate(over="ignore"):
            assert not onp.isfinite(onp.exp(onp.float32(5.0 * chunk)))
    # each gradient within 1e-4 of its own largest entry, or within 2e-5 of
    # dv's where that is more: at alpha = e^-5 on every step dg is a
    # hundredth of dv, and dg at the row's first step — zero, since the
    # state starts at zero — is a sum over the chunk of terms that cancel,
    # which float32 leaves at ~1e-5 of dv's scale
    floor = 0.2 * float(onp.abs(onp.asarray(grads[1][2])).max())
    for name, a, b in zip("q k v g beta".split(), *grads):
        a, b = onp.asarray(a), onp.asarray(b)
        assert onp.isfinite(a).all(), name
        assert onp.abs(a - b).max() < 1e-4 * max(onp.abs(b).max(), floor), \
            name


def test_delta_rule_keeps_the_dtype_and_refuses_a_bound_too_low():
    args = _rule_inputs(onp.random.RandomState(1), 40)
    out = DR.delta_rule_chunked(*args[:2], args[2].astype(jnp.bfloat16),
                                *args[3:], chunk=16)
    assert out.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="spans more than"):
        DR.delta_rule_chunked(*args, chunk=16, lower_bound=-6.0)
    with pytest.raises(ValueError, match="no multiple of the sub-chunk"):
        DR.delta_rule_chunked(*args, chunk=24)


def test_delta_rule_is_counted_once_a_traced_shape():
    args = [jax.ShapeDtypeStruct(t.shape, t.dtype)
            for t in _rule_inputs(onp.random.RandomState(1), 192)]
    before = telemetry.counter("linear_attention.scan.chunked")
    fn = jax.jit(lambda *a: DR.delta_rule_chunked(*a, lower_bound=-5.0))
    jax.eval_shape(fn, *args)
    assert telemetry.counter("linear_attention.scan.chunked") == before + 1
    event = [e for e in telemetry.snapshot()["events"]
             if e.get("kind") == "linear_attention.scan"][-1]
    assert {key: event[key] for key in ("seq_len", "chunk", "subchunk",
                                        "heads", "head_dim",
                                        "lower_bound")} \
        == {"seq_len": 192, "chunk": 64, "subchunk": 16, "heads": 3,
            "head_dim": 16, "lower_bound": -5.0}
    text = jax.jit(jax.grad(lambda *a: DR.delta_rule_chunked(*a).sum(),
                            argnums=(0, 1, 2, 3, 4))).lower(
        *args).as_text(debug_info=True)
    for scope in ("delta.intra", "delta.states", "delta.output"):
        assert scope in text, scope


@pytest.mark.parametrize("groups", [2, 4])
def test_kimi_delta_attention_gives_every_head_grouping_the_same(
        monkeypatch, groups):
    """The heads run in groups one after another: the output and every
    gradient are the one-group call's."""
    rs = onp.random.RandomState(5)
    heads, d, length = 4, 8, 40
    args = [jnp.asarray(a, jnp.float32) for a in (
        rs.randn(1, length, 4 * heads * d + 2 * heads),
        rs.randn(3 * heads * d, 1, 4) * 0.5, onp.log(rs.rand(heads) + 1),
        rs.randn(heads * d) * 0.1, rs.rand(heads * d) + 0.5)]

    def run(n):
        monkeypatch.setattr(DR, "_HEAD_GROUPS", n)

        def f(*a):
            return DR.kimi_delta_attention(*a, heads=heads, head_dim=d,
                                           chunk=16)
        cot = jnp.asarray(rs.randn(1, length, heads * d), jnp.float32)

        def both(*a):
            out, back = jax.vjp(f, *a)
            return out, back(cot)
        return jax.jit(both)(*args)

    rs = onp.random.RandomState(6)
    one = run(1)
    rs = onp.random.RandomState(6)
    many = run(groups)
    assert _rel(onp.asarray(many[0]), onp.asarray(one[0])) < 1e-6
    for a, b in zip(many[1], one[1]):
        assert _rel(onp.asarray(a), onp.asarray(b)) < 1e-5


# ---------------------------------------------------------------------------
# routing in groups of experts, the interleaved rotary
# ---------------------------------------------------------------------------

def _grouped_rule(scores, k, groups, kept):
    """The rule a token at a time in numpy: a group's score the sum of its
    two largest, the ``kept`` best groups (ties to the lower group), the
    ``k`` best experts in them (ties to the lower index)."""
    out = []
    for row in scores:
        by_group = row.reshape(groups, -1)
        score = onp.sort(by_group, axis=1)[:, -2:].sum(1)
        best = sorted(range(groups), key=lambda g: (-score[g], g))[:kept]
        allowed = [e for g in best for e in range(
            g * by_group.shape[1], (g + 1) * by_group.shape[1])]
        out.append(sorted(allowed, key=lambda e: (-row[e], e))[:k])
    return onp.asarray(out)


def test_grouped_topk_route_is_the_direct_rule_ties_included():
    rs = onp.random.RandomState(11)
    # scores on a coarse grid: ties inside and across groups
    probs = onp.round(rs.rand(200, 32) * 8) / 8
    before = telemetry.counter("moe.route.grouped")
    expert, gate = moe_ops.topk_route(jnp.asarray(probs), 5,
                                      normalize=True, groups=8,
                                      topk_groups=3)
    assert telemetry.counter("moe.route.grouped") == before + 1
    event = [e for e in telemetry.snapshot()["events"]
             if e.get("kind") == "moe.route"][-1]
    assert (event["experts"], event["groups"], event["topk_groups"],
            event["k"]) == (32, 8, 3, 5)
    want = _grouped_rule(probs, 5, 8, 3)
    assert (onp.asarray(expert) == want).all()
    picked = onp.take_along_axis(probs, want, 1)
    onp.testing.assert_allclose(
        onp.asarray(gate), picked / (picked.sum(1, keepdims=True) + 1e-20),
        rtol=1e-6)
    # the ungrouped call is the plain top-k and bumps nothing
    plain, _ = moe_ops.topk_route(jnp.asarray(probs), 5)
    assert (onp.asarray(plain) == onp.argsort(-probs, 1,
                                              kind="stable")[:, :5]).all()
    assert telemetry.counter("moe.route.grouped") == before + 1
    with pytest.raises(ValueError, match="give no"):
        moe_ops.topk_route(jnp.asarray(probs), 9, groups=8, topk_groups=2)


def test_sparse_experts_choose_in_groups_through_topk_route():
    """The experts' block takes its group-limited choice from
    ``topk_route``: the routes are the direct rule on ``probs + bias``;
    one flat route a token takes no groups."""
    rs = onp.random.RandomState(12)
    probs = onp.round(rs.rand(40, 16) * 8) / 8
    bias = onp.round(rs.randn(16) * 4) / 32
    data = jnp.asarray(rs.randn(40, 8), jnp.float32)
    weights = [jnp.asarray(rs.randn(16, *shape) * 0.3, jnp.float32)
               for shape in ((8, 6), (8, 6), (6, 8))]
    before = telemetry.counter("moe.route.grouped")
    expert = moe_ops.sparse_experts(
        data, jnp.asarray(probs, jnp.float32), *weights,
        jnp.asarray(bias, jnp.float32), k=3, normalize=True, groups=4,
        topk_groups=2)[3]
    assert telemetry.counter("moe.route.grouped") == before + 1
    assert (onp.asarray(expert) == _grouped_rule(
        (probs + bias).astype("float32"), 3, 4, 2)).all()
    with pytest.raises(ValueError, match="topk_route"):
        moe_ops.sparse_experts(data, jnp.asarray(probs, jnp.float32),
                               *weights, jnp.asarray(bias, jnp.float32),
                               groups=4, topk_groups=2)


def test_default_routing_and_rotary_trace_the_programs_they_did():
    """The paths every existing cell takes: ``topk_route`` without groups
    and rotate-half rotary are the programs they were (the jaxpr of the
    code as it stood)."""
    probs = jax.ShapeDtypeStruct((64, 16), jnp.float32)

    def before(p, k=4):
        expert = jax.lax.top_k(p, k)[1].astype(jnp.int32)
        gate = jnp.take_along_axis(p, expert, axis=1)
        return expert, gate / (jnp.sum(gate, axis=-1, keepdims=True)
                               + 1e-20)
    assert str(jax.make_jaxpr(lambda p: moe_ops.topk_route(
        p, 4, normalize=True))(probs)) == str(jax.make_jaxpr(before)(probs))
    x = jax.ShapeDtypeStruct((1, 2, 8, 16), jnp.float32)
    assert str(jax.make_jaxpr(lambda t: nn_ops.rotary_embedding(
        t, rotary_dim=8))(x)) == str(jax.make_jaxpr(
            lambda t: nn_ops.rotary_embedding(t, rotary_dim=8,
                                              interleave=False))(x))


def test_interleaved_rotary_turns_pairs_2i_and_2i_plus_1():
    rs = onp.random.RandomState(2)
    x = rs.randn(2, 3, 7, 20).astype("float32")
    r, theta = 12, 6e6
    got = onp.asarray(nn_ops.rotary_embedding(jnp.asarray(x), rotary_dim=r,
                                              theta=theta, interleave=True))
    want = x.copy()
    for t in range(7):
        for i in range(r // 2):
            angle = t * theta ** (-2.0 * i / r)
            c, s = onp.cos(angle), onp.sin(angle)
            a, b = x[..., t, 2 * i], x[..., t, 2 * i + 1]
            want[..., t, 2 * i] = a * c - b * s
            want[..., t, 2 * i + 1] = b * c + a * s
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert (got[..., r:] == x[..., r:]).all()


def test_layer_kinds_follow_the_group_size_and_the_dense_layers():
    kinds = [layer_kinds(i) for i in range(42)]
    assert sum(m == "mla" for m, _ in kinds) == 7
    assert [i for i, (m, _) in enumerate(kinds) if m == "mla"] \
        == [5, 11, 17, 23, 29, 35, 41]
    assert [f for _, f in kinds[:3]] == ["dense", "dense", "sparse"]
    sizes = _toy_sizes()
    assert [M._kinds(sizes, i) for i in M._kept(sizes)] == [
        ("kda", "dense")] + [("kda", "sparse")] * 3 + [("mla", "sparse")] \
        + [("kda", "sparse")] * 2


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

def test_logits_match_reference_float32():
    sizes, net, tokens, _ = _toy()
    positions = onp.stack([onp.arange(0, sizes["seq_len"], 5)] * 2)
    got = net(_ids(tokens), _ids(positions)).asnumpy()
    want = M.reference_forward(M.host_params(net), tokens, positions, sizes)
    assert got.shape == want.shape == (2, positions.shape[1],
                                       sizes["vocab_size"])
    assert onp.abs(got - want).max() <= 2e-5 * onp.abs(want).max()
    hidden, table = net(_ids(tokens))
    assert hidden.shape == (2, sizes["seq_len"], sizes["hidden_size"])
    assert table.shape == (sizes["vocab_size"], sizes["hidden_size"])


def test_loss_and_every_gradient_match_reference_float32():
    sizes, net, tokens, labels = _toy()
    # the parameters as the forward reads them: a training forward moves
    # the correction bias by the sign rule after it has routed
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
    # both mixers, the delta rule's gate and its float32 vectors, the
    # latent's norms, the dense block, the router, the shared expert: every
    # one of them carries a gradient that agrees
    for name in ("layer0_kda_proj_weight", "layer4_kda_conv_weight",
                 "layer0_kda_a_log", "layer4_kda_dt_bias",
                 "layer4_kda_out_norm_gamma", "layer5_mla_kv_b_weight",
                 "layer5_mla_q_norm_gamma", "layer5_mla_k_norm_gamma",
                 "layer5_mla_kv_norm_gamma", "layer5_mla_gate_weight",
                 "layer0_ffn_gate_weight", "layer4_shared_up_weight",
                 "layer5_router_weight", "layer4_experts_down_weight"):
        assert onp.abs(want[name]).max() > 0, name
    for name, p in trained.items():
        assert _rel(p.grad().asnumpy(), want[name]) < 1e-4, name


def test_compare_passes_and_each_control_fails():
    """``compare`` hands on matching logits; the reference in float8, the
    reference with the delta rule's erase term dropped and the reference
    with one decay a head each fail the logits' limit (or the routing's:
    NaN).  Half the positions lie past ``check_positions_past``."""
    from benchmark import correct

    sizes, net, tokens, _ = _toy()
    positions = M.draw_positions(sizes, onp.random.RandomState(0), 2)
    past = sizes["train"]["check_positions_past"]
    assert ((positions >= past).sum(1) == positions.shape[1] // 2).all()
    logits = net(_ids(tokens), _ids(positions)).asnumpy()
    chosen = onp.stack([layer.experts.last_expert.asnumpy()
                        for layer in net.layers if layer.kind == "sparse"])
    assert chosen.shape == (2, 2, sizes["seq_len"], 3)
    args = (logits, chosen, M.host_params(net), tokens, positions, sizes)
    assert correct.logits_agree(*M.compare(*args))["ok"]
    for control in ("float8", "no_erase", "head_decay"):
        assert not correct.logits_agree(
            *M.compare(*args, **{control: True}))["ok"], control


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_trains_through_data_parallel_step(dtype):
    """``HybridBlock`` -> ``DataParallelStep`` -> ``Adam(multi_precision)``:
    the step lowers ONCE, the loss falls, the routing counts ride as state
    (3 routes a token here), the correction bias moves by the sign rule,
    the trace bumps both new counters, and the program carries the blocks'
    and the rule's names."""
    sizes, net, tokens, labels = _toy(dtype=dtype)
    step = parallel.DataParallelStep(
        net, gluon.loss.TiedSoftmaxCrossEntropyLoss(
            block_rows=sizes["train"]["loss_block_rows"]),
        mx.optimizer.Adam(learning_rate=3e-3,
                          multi_precision=dtype != "float32"))
    data, label = _ids(tokens), _ids(labels)
    counted = [telemetry.counter(name) for name in
               ("linear_attention.scan.chunked", "moe.route.grouped")]
    bias = net.layers[1].experts.balance_bias.data().asnumpy().copy()
    losses = [float(step(data, label).asnumpy().astype("float32").mean())
              for _ in range(8)]
    assert len(step._cache) == 1
    assert all(onp.isfinite(losses)) and losses[-1] < losses[0] - 0.05
    assert telemetry.counter("linear_attention.scan.chunked") > counted[0]
    assert telemetry.counter("moe.route.grouped") > counted[1]
    routes = tokens.size * sizes["num_experts_per_tok"]
    counts = cnn.publish_routing_counts()
    mine = [v for name, v in counts.items() if name.startswith(net.prefix)]
    assert len(mine) == 2
    for record in mine:
        assert sum(record["load"]) == routes
        assert record["routes_per_token"] == 3
    # eight steps of the sign rule at u = 1e-3: whole thousandths, each
    # step +1, -1 or 0 (a load equal to the mean)
    moved = (net.layers[1].experts.balance_bias.data().asnumpy()
             - bias) / 1e-3
    assert onp.abs(moved - onp.round(moved)).max() < 1e-2
    assert onp.abs(moved).max() <= 8 + 1e-2 and onp.abs(moved).max() >= 1
    if dtype == "float32":
        text = step.lower(data, label).as_text(debug_info=True)
        for block in ("layer0_kda_proj", "layer0_kda_out", "layer0_ffn_up",
                      "layer5_mla_q", "layer5_mla_kv_norm", "layer4_router",
                      "layer4_experts", "layer4_shared_down", "final_norm"):
            assert "/%s%s/" % (net.prefix, block) in text, block
        for scope in ("kda.inputs", "kda.output", "delta.intra",
                      "delta.states", "delta.output"):
            assert "/%s/" % scope in text, scope


def test_blocks_put_the_scan_apart_from_its_layer():
    """``BLOCKS`` gives the rule's scopes to ``delta_scan`` though they lie
    inside the delta layer's block — the chunked form's and the kernels',
    forward and backward —, everything else of the layer to
    ``linear_attention``, the latent layer to ``attention``."""
    import re
    from benchmark import reduce_trace

    blocks = {kind: [re.compile(p) for p in found]
              for kind, found in M.BLOCKS.items()}
    cases = {
        "jit(step_fn)/jvp(forward)/lingmodel0/lingmodel0_layer2/"
        "lingmodel0_layer2_kda/checkpoint/kda.inputs/mul": "linear_attention",
        "jit(step_fn)/transpose(jvp(forward))/lingmodel0_layer2/"
        "lingmodel0_layer2_kda/delta.intra/dot_general": "delta_scan",
        # the backward names a scope inside autodiff's own
        "jit(step_fn)/transpose(jvp(forward))/lingmodel0_layer2/"
        "lingmodel0_layer2_kda/while/body/closed_call/checkpoint/"
        "transpose(jvp(delta.intra))/triangular_solve": "delta_scan",
        "jit(step_fn)/transpose(jvp(forward))/lingmodel0_layer2/"
        "lingmodel0_layer2_kda/while/body/closed_call/checkpoint/"
        "jvp(kda.inputs)/mul": "linear_attention",
        # the kernels on a TPU, as the lowered step names them: the
        # forward, the group's recomputation of it and the backward
        "jit(step_fn)/jvp(forward)/lingmodel0_layer2/lingmodel0_layer2_kda/"
        "while/body/closed_call/delta.fwd/delta_fwd/pallas_call":
            "delta_scan",
        "jit(step_fn)/transpose(jvp(forward))/lingmodel0_layer2/"
        "lingmodel0_layer2_kda/while/body/closed_call/checkpoint/"
        "rematted_computation/delta.fwd/delta_fwd/pallas_call": "delta_scan",
        "jit(step_fn)/transpose(jvp(forward))/lingmodel0_layer2/"
        "lingmodel0_layer2_kda/while/body/closed_call/checkpoint/"
        "delta.bwd/delta_bwd/pallas_call": "delta_scan",
        "jit(step_fn)/jvp(forward)/lingmodel0_layer2/lingmodel0_layer2_kda/"
        "kda.heads/concatenate": "linear_attention",
        "jit(step_fn)/jvp(forward)/lingmodel0_layer2/lingmodel0_layer2_kda/"
        "lingmodel0_layer2_kda_proj/dot_general": "linear_attention",
        "jit(step_fn)/jvp(forward)/lingmodel0_layer3/"
        "lingmodel0_layer3_kda_norm/mul": "linear_attention",
        "jit(step_fn)/jvp(forward)/lingmodel0_layer5/lingmodel0_layer5_mla/"
        "lingmodel0_layer5_mla_q/dot_general": "attention",
        "jit(step_fn)/jvp(forward)/lingmodel0_layer5/"
        "lingmodel0_layer5_mla_norm/mul": "attention",
        "jit(step_fn)/jvp(forward)/lingmodel0_layer0/lingmodel0_layer0_ffn/"
        "lingmodel0_layer0_ffn_up/dot_general": "ffn",
        "jit(step_fn)/jvp(forward)/lingmodel0_layer6/"
        "lingmodel0_layer6_ffn_norm/mul": "experts",
    }
    for name, kind in cases.items():
        assert reduce_trace.kind_of(name, blocks) == kind, name


# ---------------------------------------------------------------------------
# THE SHARE TEST
# ---------------------------------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer():
    """4 shares of 4 of 16 experts in 4 groups, 3 routes a token from the 2
    best groups, a correction bias, a delta-attention layer of 4 heads.
    Attention, the residual and the SHARED expert — which every share
    computes alike — counted ONCE, plus the shares' held-expert parts,
    equal the uncut reference layer under group routing; one share alone
    does not."""
    experts, units, hidden, k, heads, d = 16, 16, 12, 3, 4, 8
    width = heads * d
    rs = onp.random.RandomState(0)
    full = {"kda_norm_gamma": rs.rand(units) + 0.5,
            "ffn_norm_gamma": rs.rand(units) + 0.5,
            "kda_proj_weight": rs.randn(4 * width + 2 * heads, units) * 0.3,
            "kda_conv_weight": rs.randn(3 * width, 1, 4) * 0.5,
            "kda_a_log": onp.log(rs.rand(heads) * 4 + 1),
            "kda_dt_bias": rs.randn(width) * 0.1,
            "kda_out_norm_gamma": rs.rand(width) + 0.5,
            "kda_out_weight": rs.randn(units, width) * 0.3,
            "router_weight": rs.randn(experts, units) * 0.5,
            "experts_balance_bias": rs.randn(experts) * 0.05,
            "experts_gate_weight": rs.randn(experts, units, hidden) * 0.3,
            "experts_up_weight": rs.randn(experts, units, hidden) * 0.3,
            "experts_down_weight": rs.randn(experts, hidden, units) * 0.3,
            "shared_gate_weight": rs.randn(hidden, units) * 0.3,
            "shared_up_weight": rs.randn(hidden, units) * 0.3,
            "shared_down_weight": rs.randn(units, hidden) * 0.3}
    full = {name: value.astype("float32") for name, value in full.items()}
    x = rs.randn(1, 40, units).astype("float32")
    kda = dict(num_heads=heads, head_dim=d, chunk_size=16)
    outs, parts = [], []
    for first in range(0, experts, 4):
        layer = LingLayer(units, "kda", "sparse", kda, None, 0, dict(
            hidden_size=hidden, num_experts=experts,
            experts_held=(first, first + 4), experts_per_token=k,
            expert_groups=4, topk_groups=2, gate_scale=2.5,
            shared_hidden=hidden), 1e-6)
        layer.initialize()
        for name, p in layer.collect_params().items():
            short = name[len(layer.prefix):]
            if short in full:
                held = short.startswith("experts_") \
                    and short != "experts_balance_bias"
                p.set_data(mx.nd.array(
                    full[short][first:first + 4] if held else full[short]))
        xs = mx.nd.array(x)
        after = xs + layer.mixer(layer.mixer_norm(xs))
        h = layer.ffn_norm(after)
        parts.append(layer.experts(h, layer.router(h)).asnumpy())
        outs.append(layer(xs).asnumpy())
        alike = (after + layer.shared(h)).asnumpy()
    sizes = dict(_toy_sizes(), hidden_size=units, num_attention_heads=heads,
                 head_dim=d, num_experts_per_tok=k, n_group=4, topk_group=2,
                 deployment={"experts_held": [0, experts]})
    with jax.default_matmul_precision("highest"):
        want = onp.asarray(M._layer_fn(sizes, 2)(
            jnp.asarray(x[0]),
            {name: jnp.asarray(v) for name, v in full.items()})[0])[None]
    onp.testing.assert_allclose(sum(parts) + alike, want, rtol=2e-5,
                                atol=2e-6)
    onp.testing.assert_allclose(outs[2], parts[2] + alike, rtol=1e-5,
                                atol=1e-6)
    assert onp.abs(outs[2] - want).max() > 1e-2
