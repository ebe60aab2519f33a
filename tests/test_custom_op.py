"""mx.operator.CustomOp / CustomOpProp (reference python/mxnet/operator.py,
src/operator/custom/custom-inl.h:52; test strategy:
tests/python/unittest/test_operator.py test_custom_op) — the classic
numpy-softmax custom op trained under the imperative (autograd) path and
the Module path, plus jit/grad composition."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon


class NumpySoftmax(mx.operator.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        x = in_data[0].asnumpy()
        y = onp.exp(x - x.max(axis=1, keepdims=True))
        y /= y.sum(axis=1, keepdims=True)
        self.assign(out_data[0], req[0], y)

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        y = out_data[0].asnumpy()
        gy = out_grad[0].asnumpy()
        dot = (gy * y).sum(axis=1, keepdims=True)
        self.assign(in_grad[0], req[0], y * (gy - dot))


@mx.operator.register("numpy_softmax")
class NumpySoftmaxProp(mx.operator.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=True)

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def create_operator(self, ctx, in_shapes, in_dtypes):
        return NumpySoftmax()


def _np_softmax(x):
    y = onp.exp(x - x.max(axis=1, keepdims=True))
    return y / y.sum(axis=1, keepdims=True)


def test_custom_forward_matches_numpy():
    x = onp.random.RandomState(0).randn(4, 5).astype("float32")
    out = mx.nd.Custom(mx.nd.array(x), op_type="numpy_softmax")
    onp.testing.assert_allclose(out.asnumpy(), _np_softmax(x), rtol=1e-5)


def test_custom_grad_matches_builtin():
    rs = onp.random.RandomState(1)
    x = rs.randn(3, 4).astype("float32")
    a = mx.nd.array(x)
    a.attach_grad()
    with autograd.record():
        y = mx.nd.Custom(a, op_type="numpy_softmax")
        loss = (y * y).sum()
    loss.backward()
    got = a.grad.asnumpy()

    b = mx.nd.array(x)
    b.attach_grad()
    with autograd.record():
        y2 = mx.nd.softmax(b, axis=-1)
        loss2 = (y2 * y2).sum()
    loss2.backward()
    onp.testing.assert_allclose(got, b.grad.asnumpy(), rtol=1e-4, atol=1e-5)


def test_custom_under_jit_gluon():
    """Custom op inside a hybridized (jitted) Gluon block."""
    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.fc = gluon.nn.Dense(6)

        def hybrid_forward(self, F, x):
            return F.Custom(self.fc(x), op_type="numpy_softmax")

    net = Net()
    net.initialize()
    net.hybridize()
    x = mx.nd.array(onp.random.RandomState(2).randn(5, 3).astype("float32"))
    out = net(x)
    onp.testing.assert_allclose(out.asnumpy().sum(axis=1),
                                onp.ones(5), rtol=1e-5)


def test_custom_trains_under_module():
    """The reference's canonical usage: a Custom head in a Module graph."""
    from mxnet_tpu import sym
    data = sym.var("data")
    fc = sym.FullyConnected(data, num_hidden=2, name="fc")
    out = sym.Custom(fc, op_type="numpy_softmax")
    rs = onp.random.RandomState(3)
    x = rs.randn(32, 4).astype("float32")
    w = (x[:, 0] > 0).astype("float32")

    import mxnet_tpu.module as mod_mod
    m = mod_mod.Module(out, data_names=["data"], label_names=None)
    m.bind(data_shapes=[("data", (32, 4))])
    m.init_params(mx.init.Xavier())
    m.init_optimizer(optimizer="sgd",
                     optimizer_params={"learning_rate": 0.5})
    from mxnet_tpu.io import NDArrayIter
    losses = []
    for _ in range(40):
        m.forward(mx.io.DataBatch([mx.nd.array(x)], None))
        probs = m.get_outputs()[0]
        p = probs.asnumpy()
        losses.append(-onp.log(p[onp.arange(32), w.astype(int)] + 1e-9).mean())
        # grad of CE wrt softmax output probs
        g = onp.zeros_like(p)
        g[onp.arange(32), w.astype(int)] = -1.0 / (p[onp.arange(32),
                                                     w.astype(int)] + 1e-9)
        m.backward([mx.nd.array(g / 32)])
        m.update()
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


def test_custom_op_traced_under_jit_and_jit_of_grad():
    """A CustomOp is a host callback inside the compiled program: it
    runs under jit, and under jit(grad) (transform tracers layered on the
    staging tracer) the backward callback supplies the gradient."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx

    class Plus1(mx.operator.CustomOp):
        def forward(self, is_train, req, in_data, out_data, aux):
            self.assign(out_data[0], req[0],
                        in_data[0].asnumpy() + 1.0)

        def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
            self.assign(in_grad[0], req[0], 3.0 * out_grad[0].asnumpy())

    @mx.operator.register("plus1_jit")
    class Plus1Prop(mx.operator.CustomOpProp):
        def list_arguments(self):
            return ["data"]

        def infer_shape(self, in_shape):
            return in_shape, [in_shape[0]]

        def create_operator(self, ctx, shapes, dtypes):
            return Plus1()

    out = mx.nd.Custom(mx.nd.ones((2, 2)), op_type="plus1_jit")
    assert float(out.asnumpy().sum()) == 8.0
    out = jax.jit(lambda x: mx.nd.Custom(
        mx.nd.from_jax(x), op_type="plus1_jit")._data)(jnp.ones((2, 2)))
    onp.testing.assert_array_equal(onp.asarray(out), onp.full((2, 2), 2.0))
    g = jax.jit(jax.grad(lambda x: mx.nd.Custom(
        mx.nd.from_jax(x), op_type="plus1_jit")._data.sum()))(
            jnp.ones((2, 2)))
    onp.testing.assert_array_equal(onp.asarray(g), onp.full((2, 2), 3.0))


def test_custom_op_first_use_under_jit():
    """The first CustomOp use in a process may be under jit (a
    hybridized block whose first op is the custom op)."""
    import jax
    import jax.numpy as jnp

    out = jax.jit(lambda x: mx.nd.Custom(
        mx.nd.from_jax(x), op_type="numpy_softmax")._data)(
            jnp.ones((2, 3)))
    onp.testing.assert_allclose(onp.asarray(out),
                                onp.full((2, 3), 1.0 / 3), rtol=1e-6)
