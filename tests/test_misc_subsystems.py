"""Monitor / visualization / runtime features / engine knobs (reference
tests: test_monitor in test_operator.py, runtime feature tests)."""
import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import sym


def _mlp():
    data = sym.var("data")
    net = sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = sym.Activation(net, act_type="relu", name="relu1")
    net = sym.FullyConnected(net, num_hidden=3, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def test_monitor_collects_stats():
    net = _mlp()
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 6))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params(mx.init.Xavier())
    mon = mx.monitor.Monitor(interval=1, pattern=".*weight.*")
    mod.install_monitor(mon)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(onp.ones((4, 6), onp.float32))],
        label=[mx.nd.array(onp.zeros(4, onp.float32))])
    mon.tic()
    mod.forward(batch, is_train=True)
    mod.backward()
    res = mon.toc_print()
    names = {k for _, k, _ in res}
    assert "fc1_weight" in names and "fc2_weight" in names
    assert "fc1_weight_grad" in names
    assert all("bias" not in n for n in names)


def test_monitor_interval():
    mon = mx.monitor.Monitor(interval=2)
    mon.tic()
    assert mon.activated
    mon.toc()
    mon.tic()
    assert not mon.activated


def test_print_summary(capsys):
    net = _mlp()
    total = mx.viz.print_summary(net, shape={"data": (4, 6)})
    out = capsys.readouterr().out
    assert "fc1 (FullyConnected)" in out
    assert "softmax (SoftmaxOutput)" in out
    # fc1: 6*8+8=56, fc2: 8*3+3=27
    assert total == 83


def test_runtime_features():
    feats = mx.runtime.Features()
    assert feats.is_enabled("CPU")
    assert feats.is_enabled("BF16")
    assert not feats.is_enabled("CUDNN")
    assert any(f.name == "TPU" for f in mx.runtime.feature_list())
    try:
        feats.is_enabled("NOPE")
        raise AssertionError("should raise")
    except RuntimeError:
        pass


def test_engine_knobs():
    assert mx.engine.engine_type() == "ThreadedEnginePerDevice"
    with mx.engine.naive_engine():
        assert mx.engine.engine_type() == "NaiveEngine"
        # ops still work eagerly under disable_jit
        x = mx.nd.array(onp.ones(3, onp.float32))
        assert float((x + x).sum().asscalar()) == 6.0
    assert mx.engine.engine_type() == "ThreadedEnginePerDevice"
    prev = mx.engine.set_bulk_size(4)
    with mx.engine.bulk(32):
        pass
    mx.engine.set_bulk_size(prev)


def test_namespace_submodules_forward():
    """mx.nd.random / mx.nd.linalg / mx.sym.random / mx.sym.linalg mirror
    the upstream module layout (reference python/mxnet/ndarray/{random,
    linalg}.py and symbol twins)."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import symbol as sym

    mx.random.seed(0)
    assert mx.nd.random.normal(0, 1, (2, 3)).shape == (2, 3)
    assert mx.nd.random.randn(4, 2).shape == (4, 2)
    assert mx.random.uniform(0, 1, (3,)).shape == (3,)

    a = mx.nd.array(onp.eye(3, dtype="float32") * 4)
    onp.testing.assert_allclose(mx.nd.linalg.potrf(a).asnumpy(),
                                onp.eye(3) * 2, rtol=1e-5)
    x = sym.var("x")
    det = sym.linalg.det(x)
    got = det.eval_imperative({"x": a})
    assert abs(float(got.asnumpy()) - 64.0) < 1e-3
    assert sym.random.uniform(0, 1, shape=(2, 2)).eval_imperative(
        {}).shape == (2, 2)
