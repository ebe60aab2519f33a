"""BatchNorm→residual-add→ReLU tests (``_contrib_BatchNormAddRelu``).

The op is plain ``jax.numpy`` on the ND tensor, differentiated by JAX;
it, the gluon layer and the ResNet wiring are tested against a float32
reference and against the unfused composition (reference discipline:
``check_consistency`` between the cuDNN BatchNormAddRelu and the composed
ops).
"""
import numpy as onp
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops.nn import batch_norm, batch_norm_add_relu


def _rand(shape, seed, dtype="float32"):
    x = onp.random.RandomState(seed).uniform(-1, 1, shape).astype("float32")
    return jnp.asarray(x, jnp.dtype(dtype))


def _operands(axis, dtype, shape=(4, 6, 5, 7)):
    """data and residual in ``dtype``; gamma, beta and positive moving
    statistics in float32, as the layer keeps them."""
    C = shape[axis]
    return (_rand(shape, 30, dtype), _rand(shape, 31, dtype),
            _rand((C,), 32) + 1.5, _rand((C,), 33),
            0.3 * _rand((C,), 34), 0.5 * _rand((C,), 35) + 1.0)


def _reference(x, r, gamma, beta, mm, mv, axis, batch, eps):
    """The op's mathematics in float32 throughout, two-pass statistics."""
    x, r = x.astype(jnp.float32), r.astype(jnp.float32)
    shp = [1] * x.ndim
    shp[axis] = x.shape[axis]
    if batch:
        ax = tuple(i for i in range(x.ndim) if i != axis % x.ndim)
        mm, mv = jnp.mean(x, axis=ax), jnp.var(x, axis=ax)
    y = (x - mm.reshape(shp)) * jax.lax.rsqrt(mv.reshape(shp) + eps)
    return jnp.maximum(y * gamma.reshape(shp) + beta.reshape(shp) + r, 0.0)


@pytest.mark.parametrize("stats", ["training", "use_global_stats"])
@pytest.mark.parametrize("axis", [1, -1], ids=["nchw", "nhwc"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_op_forward_and_all_four_grads_match_float32_reference(dtype, axis,
                                                               stats):
    """Forward and the gradients of data, residual, gamma and beta
    against ``jax.grad`` of the float32 reference: batch statistics are
    differentiated through, moving statistics are constants."""
    x, r, gamma, beta, mm, mv = _operands(axis, dtype)
    batch = stats == "training"
    kw = dict(eps=1e-5, fix_gamma=False, axis=axis,
              use_global_stats=not batch)
    # a fixed cotangent: the gradients are linear in it, so bf16 rounding
    # of the output does not feed back into what is compared
    ct = _rand(x.shape, 36)

    def op_loss(x, r, gamma, beta):
        out = batch_norm_add_relu(x, r, gamma, beta, mm, mv, **kw)[0]
        return jnp.sum(out.astype(jnp.float32) * ct)

    def ref_loss(x, r, gamma, beta):
        return jnp.sum(_reference(x, r, gamma, beta, mm, mv, axis, batch,
                                  kw["eps"]) * ct)

    out, mean, var = batch_norm_add_relu(x, r, gamma, beta, mm, mv, **kw)
    ref = _reference(x, r, gamma, beta, mm, mv, axis, batch, kw["eps"])
    assert out.dtype == x.dtype and out.shape == x.shape
    assert mean.shape == var.shape == gamma.shape
    tol = 6e-2 if dtype == "bfloat16" else 1e-4
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) < tol
    got = jax.grad(op_loss, argnums=(0, 1, 2, 3))(x, r, gamma, beta)
    want = jax.grad(ref_loss, argnums=(0, 1, 2, 3))(x, r, gamma, beta)
    for name, g, w in zip(("data", "residual", "gamma", "beta"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        g32, w32 = g.astype(jnp.float32), w.astype(jnp.float32)
        # the ReLU mask flips where a pre-activation within bf16 rounding
        # of zero changes sign, so compare in the mean, not the maximum
        err = float(jnp.mean(jnp.abs(g32 - w32)))
        assert err < tol * (1e-3 + float(jnp.mean(jnp.abs(w32)))) \
            or err < 1e-5, (name, err)


@pytest.mark.parametrize("axis", [1, -1], ids=["nchw", "nhwc"])
def test_bf16_output_is_the_fp32_scale_shift_arithmetic_rounded_once(axis):
    """The tail holds fp32 from the folded scale/shift to the ReLU and
    casts ONCE (the arithmetic the Pallas kernel and its CPU path had) —
    not ``_bn_apply``'s bf16 scale/shift: equal to the explicit fp32
    formula to one bf16 rounding."""
    x, r, gamma, beta, mm, mv = _operands(axis, "bfloat16")
    out = batch_norm_add_relu(x, r, gamma, beta, mm, mv, eps=1e-5,
                              fix_gamma=False, axis=axis,
                              use_global_stats=True)[0]
    shp = [1] * x.ndim
    shp[axis] = x.shape[axis]
    scale = jax.lax.rsqrt(mv + 1e-5) * gamma
    shift = beta - mm * scale
    want = jnp.maximum(x.astype(jnp.float32) * scale.reshape(shp)
                       + shift.reshape(shp) + r.astype(jnp.float32), 0.0)
    assert out.dtype == jnp.bfloat16
    err = jnp.abs(out.astype(jnp.float32) - want)
    # half a bf16 ulp (2**-9 relative) of the fp32 value, with a hair of
    # room for a fused multiply-add
    assert bool(jnp.all(err <= jnp.abs(want) * 2.0 ** -8 + 1e-30))


@pytest.mark.parametrize("axis", [1, -1], ids=["nchw", "nhwc"])
def test_op_is_plain_jnp_on_the_nd_tensor(axis):
    """What the chip measured against: no kernel, no ``custom_vjp``, and no
    reshape of an activation tensor — only the per-channel VECTORS are
    reshaped for the broadcast — forward or backward."""
    x, r, gamma, beta, mm, mv = _operands(axis, "bfloat16")

    def loss(x, r, gamma, beta):
        out = batch_norm_add_relu(x, r, gamma, beta, mm, mv, axis=axis,
                                  fix_gamma=False)[0]
        return jnp.sum(out.astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(
        x, r, gamma, beta)
    prims = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert not [p for p in prims if "custom" in p or "pallas" in p], prims
    for e in jaxpr.jaxpr.eqns:
        if e.primitive.name in ("reshape", "transpose", "concatenate"):
            assert all(v.aval.size <= gamma.size for v in e.invars), e


def test_residual_of_another_shape_is_refused():
    x, r, gamma, beta, mm, mv = _operands(1, "float32")
    with pytest.raises(ValueError, match="residual shape"):
        batch_norm_add_relu(x, r[:, :, :1], gamma, beta, mm, mv)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bn_add_relu_op_matches_unfused_composition(dtype):
    """The registered op == BatchNorm → add → relu, fwd AND bwd."""
    x = _rand((4, 8, 6, 6), 40, dtype)
    res = _rand((4, 8, 6, 6), 41, dtype)
    gamma = _rand((8,), 42)
    beta = _rand((8,), 43)
    mm = jnp.zeros((8,), jnp.float32)
    mv = jnp.ones((8,), jnp.float32)
    kw = dict(eps=1e-5, fix_gamma=False, training=True)

    def fused(x, res, gamma, beta):
        return batch_norm_add_relu(x, res, gamma, beta, mm, mv, **kw)

    def composed(x, res, gamma, beta):
        out, mean, var = batch_norm(x, gamma, beta, mm, mv, **kw)
        return jnp.maximum(out + res, 0.0), mean, var

    o1, m1, v1 = fused(x, res, gamma, beta)
    o2, m2, v2 = composed(x, res, gamma, beta)
    # the op holds f32 through the whole tail while the composed path
    # casts scale/shift to data dtype first — rounding-level
    # disagreement, not an error
    tol = 2e-2 if dtype == "bfloat16" else 1e-4
    assert float(jnp.max(jnp.abs(o1.astype(jnp.float32)
                                 - o2.astype(jnp.float32)))) < tol
    assert float(jnp.max(jnp.abs(m1 - m2))) == 0.0
    assert float(jnp.max(jnp.abs(v1 - v2))) == 0.0

    g1 = jax.grad(lambda *a: jnp.sum(fused(*a)[0].astype(jnp.float32) ** 2),
                  argnums=(0, 1, 2, 3))(x, res, gamma, beta)
    g2 = jax.grad(lambda *a: jnp.sum(composed(*a)[0].astype(jnp.float32)
                                     ** 2),
                  argnums=(0, 1, 2, 3))(x, res, gamma, beta)
    # relative comparison: the squared-sum loss makes |grad| large, and
    # the two paths accumulate bf16-rounded terms in different orders —
    # gamma/beta grads sum ~B*H*W such terms, so allow a few percent
    gtol = 0.05 if dtype == "bfloat16" else 1e-4
    for a, b in zip(g1, g2):
        b32 = b.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b32)))
        assert err / (1.0 + float(jnp.max(jnp.abs(b32)))) < gtol


def test_batchnorm_add_relu_layer_matches_composed_layers():
    """The gluon layer == nn.BatchNorm + add + relu, including the
    moving-stats update and the backward through mx autograd."""
    mx.random.seed(0)
    bn = nn.BatchNorm(in_channels=4)
    fused = nn.BatchNormAddReLU(in_channels=4)
    bn.initialize()
    fused.initialize()
    rs = onp.random.RandomState(5)
    # identical (non-trivial) affine params on both layers
    g = rs.uniform(0.5, 1.5, (4,)).astype("float32")
    b = rs.uniform(-1, 1, (4,)).astype("float32")
    for layer in (bn, fused):
        layer.gamma.set_data(mx.nd.array(g))
        layer.beta.set_data(mx.nd.array(b))
    x = mx.nd.array(rs.uniform(-1, 1, (3, 4, 5, 5)).astype("float32"))
    r = mx.nd.array(rs.uniform(-1, 1, (3, 4, 5, 5)).astype("float32"))
    x1, r1 = x.copy(), r.copy()
    x.attach_grad()
    r.attach_grad()
    x1.attach_grad()
    r1.attach_grad()
    with autograd.record():
        y = fused(x, r)
    y.backward()
    with autograd.record():
        yref = mx.nd.relu(bn(x1) + r1)
    yref.backward()
    assert onp.abs(y.asnumpy() - yref.asnumpy()).max() < 1e-5
    assert onp.abs(x.grad.asnumpy() - x1.grad.asnumpy()).max() < 1e-5
    assert onp.abs(r.grad.asnumpy() - r1.grad.asnumpy()).max() < 1e-5
    # moving stats advanced identically
    assert onp.abs(fused.running_mean.data().asnumpy()
                   - bn.running_mean.data().asnumpy()).max() < 1e-6
    assert onp.abs(fused.running_var.data().asnumpy()
                   - bn.running_var.data().asnumpy()).max() < 1e-6


def test_resnet_v1_blocks_end_in_the_bn_add_relu_layer():
    """The bench path (resnet50_v1 and friends) ends every v1 residual
    body with the BN+add+relu layer, at the SAME structural
    position/name a plain BatchNorm had."""
    from mxnet_tpu.gluon.model_zoo.vision.resnet import (BasicBlockV1,
                                                         BottleneckV1)
    for cls in (BasicBlockV1, BottleneckV1):
        blk = cls(64, 1, downsample=True, in_channels=32)
        tail = list(blk.body)[-1]
        assert isinstance(tail, nn.BatchNormAddReLU)
    net = mx.gluon.model_zoo.vision.resnet50_v1(classes=10)
    tails = [list(unit.body)[-1]
             for stage in list(net.features)[4:8] for unit in stage]
    assert tails and all(isinstance(t, nn.BatchNormAddReLU)
                         for t in tails)


def test_residual_net_train_eval_consistency():
    """End-to-end: a stack of the actual ResNet v1 units trains (loss
    descends through autograd + Trainer) and the eval path (moving
    stats through the op's use_global branch) stays finite.  (The
    eager autograd/Trainer loop, not a donated DataParallelStep: a
    donated conv-net step jit trips a pre-existing jax-CPU persistent-
    cache deserialization bug unrelated to the tail — the donated
    on-chip resnet50 path is covered by the benchmark.)"""
    from mxnet_tpu.gluon.model_zoo.vision.resnet import BottleneckV1
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(BottleneckV1(16, 1, downsample=True, in_channels=3))
    net.add(BottleneckV1(16, 1, False, in_channels=16))
    net.add(nn.GlobalAvgPool2D(), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    rs = onp.random.RandomState(0)
    x = mx.nd.array(rs.uniform(size=(2, 3, 16, 16)).astype("float32"))
    y = mx.nd.array(rs.randint(0, 10, (2,)).astype("float32"))
    net(x)        # materialize deferred shapes
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.05})
    losses = []
    for _ in range(7):
        with autograd.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
        trainer.step(batch_size=2)
        losses.append(float(loss.asnumpy()))
    assert losses[-1] < losses[0]
    out = net(x)      # eval path (moving stats)
    assert onp.isfinite(out.asnumpy()).all()
