"""Static per-chip HBM estimator (tools.lint.hbm) vs the runtime
telemetry gauges.

Acceptance (ISSUE 7): the static estimate for the PR-5 ZeRO bench
config (123 -> 2048 -> 1024 -> 10 fp32 MLP, Adam, 8-way dp mesh) must
agree with the runtime ``parallel.optimizer_state_bytes_per_chip``
gauge within 10% for BOTH the replicated and the dp-sharded layout.
The estimator is fed hand-written shapes (not runtime metadata), so the
two numbers are computed independently.
"""
import numpy as onp
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, telemetry
from mxnet_tpu.gluon import nn

from tools.lint import hbm


@pytest.fixture
def mesh8():
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
    m = parallel.device_mesh((8,), ("dp",))
    old = parallel.get_mesh()
    parallel.set_mesh(m)
    yield m
    parallel.set_mesh(old)


def test_padded_size_matches_collectives():
    """The estimator's padding arithmetic IS the ZeRO layout's — drift
    here silently skews every estimate."""
    from mxnet_tpu.parallel import collectives as coll
    for n in (1, 2, 3, 7, 100, 1000, 2048 * 123 + 5):
        for a in (1, 2, 4, 8, 16):
            assert hbm.padded_size(n, a) == coll.padded_size(n, a), (n, a)


def test_leaf_arithmetic():
    assert hbm.dtype_itemsize("float32") == 4
    assert hbm.dtype_itemsize("bfloat16") == 2
    # (1000,) over 8 chips: padded to 1000->1000? no: 125*8=1000 exact;
    # (1001,) pads to 1008
    assert hbm.leaf_bytes_per_chip((1000,), "float32",
                                   hbm.DP_SHARDED, 8) == 1000 * 4 // 8
    assert hbm.leaf_bytes_per_chip((7, 11, 13), "float32",
                                   hbm.DP_SHARDED, 8) == \
        hbm.padded_size(7 * 11 * 13, 8) * 4 // 8
    assert hbm.leaf_bytes_per_chip((1000,), "float32",
                                   hbm.REPLICATED, 8) == 4000
    # multi-precision: a bf16 weight carries an fp32 master as an extra
    # leaf and its state leaves are fp32
    est = hbm.estimate_step_hbm([((10,), "bfloat16")], axis_size=4,
                                state_leaves=2, shard_optimizer=True,
                                multi_precision=True)
    assert est["opt_state_bytes"] == 3 * hbm.padded_size(10, 4) * 4 // 4


def _bench_net(hidden=2048):
    """The PR-5 zero_sharded_update bench leg: 123-feature
    input, Dense(hidden)->Dense(hidden//2)->Dense(10), fp32, Adam."""
    onp.random.seed(7)
    mx.random.seed(7)
    net = nn.HybridSequential()
    net.add(nn.Dense(hidden, activation="relu"),
            nn.Dense(hidden // 2, activation="relu"), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(onp.random.rand(256, 123).astype("float32"))
    y = mx.nd.array(onp.random.randint(0, 10, (256,)).astype("float32"))
    net(x)
    return net, x, y


def _bench_param_spec(hidden=2048):
    """The same architecture written down statically — Dense weight is
    (units, in_units), bias (units,)."""
    dims = [(hidden, 123), (hidden // 2, hidden), (10, hidden // 2)]
    spec = []
    for units, in_units in dims:
        spec.append(((units, in_units), "float32"))
        spec.append(((units,), "float32"))
    return spec


@pytest.mark.parametrize("shard", [False, True])
def test_static_estimate_matches_runtime_gauge(mesh8, shard):
    telemetry.reset()
    net, x, y = _bench_net()
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    step = parallel.DataParallelStep(
        net, lambda o, l: L(o, l), mx.optimizer.Adam(learning_rate=1e-3),
        mesh=mesh8, shard_optimizer=shard)
    gauge = telemetry.snapshot()["gauges"][
        "parallel.optimizer_state_bytes_per_chip"]
    assert gauge > 0
    est = hbm.estimate_step_hbm(_bench_param_spec(), axis_size=8,
                                state_leaves=2, shard_optimizer=shard)
    assert abs(est["opt_state_bytes"] - gauge) <= 0.10 * gauge, \
        (est["opt_state_bytes"], gauge)
    # the step's own journaling helper rides the same arithmetic
    m = step.hbm_estimate()
    assert m is not None
    assert m["opt_state_bytes_per_chip"] == est["opt_state_bytes"]
    assert m["n_shards"] == (8 if shard else 1)
    telemetry.reset()


def test_hbm_event_journaled_per_program(mesh8):
    """Every compiled signature journals ONE hbm/estimate event whose
    state bytes match the construction-time gauge; a cache hit journals
    nothing."""
    telemetry.reset()
    onp.random.seed(3)
    mx.random.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(7, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(onp.random.rand(16, 9).astype("float32"))
    y = mx.nd.array(onp.random.randint(0, 4, (16,)).astype("float32"))
    net(x)
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    step = parallel.DataParallelStep(
        net, lambda o, l: L(o, l),
        mx.optimizer.SGD(learning_rate=0.1, momentum=0.9),
        mesh=mesh8, shard_optimizer=True)
    step(x, y).asnumpy()

    def hbm_events():
        snap = telemetry.snapshot(events=4096)
        return snap, [e for e in snap["events"]
                      if e["kind"] == "hbm" and e["name"] == "estimate"]

    snap, evs = hbm_events()
    assert len(evs) == 1
    ev = evs[0]
    assert ev["mode"] == "call"
    assert ev["program"].startswith("DataParallelStep[")
    assert ev["opt_state_bytes_per_chip"] == \
        snap["gauges"]["parallel.optimizer_state_bytes_per_chip"]
    assert ev["activation_bytes_per_chip"] > 0
    assert ev["total_bytes_per_chip"] >= ev["params_bytes_per_chip"]
    step(x, y).asnumpy()          # same signature: cached, no new event
    _, evs = hbm_events()
    assert len(evs) == 1
    telemetry.reset()
