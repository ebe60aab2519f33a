"""Parallelism tests on the 8-device CPU mesh (the analogue of the
reference's `tools/launch.py --launcher local` multi-process fixtures,
SURVEY.md §4)."""
import functools

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon import loss as gloss


@pytest.fixture
def mesh8():
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
    m = parallel.device_mesh((8,), ("dp",))
    old = parallel.get_mesh()
    parallel.set_mesh(m)
    yield m
    parallel.set_mesh(old)


def test_shard_batch_and_replicate(mesh8):
    x = mx.nd.array(onp.arange(32, dtype="float32").reshape(16, 2))
    xs = parallel.shard_batch(x, mesh8)
    assert xs.shape == (16, 2)
    onp.testing.assert_allclose(xs.asnumpy(), x.asnumpy())
    w = parallel.replicate(mx.nd.ones((3, 3)), mesh8)
    onp.testing.assert_allclose(w.asnumpy(), onp.ones((3, 3)))


def test_data_parallel_step_descends(mesh8):
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(4))
    net.initialize()
    x = mx.nd.array(onp.random.randn(16, 8).astype("float32"))
    y = mx.nd.array(onp.random.randint(0, 4, 16).astype("float32"))
    net(x)  # complete deferred init
    L = gloss.SoftmaxCrossEntropyLoss()
    step = parallel.DataParallelStep(
        net, lambda o, l: L(o, l),
        mx.optimizer.SGD(learning_rate=0.5, momentum=0.9), mesh=mesh8)
    losses = [float(step(x, y).asscalar()) for _ in range(8)]
    assert losses[-1] < losses[0]


def test_data_parallel_matches_single_device(mesh8):
    """Sharded-step training must produce the same parameters as the
    eager single-device Trainer (check_consistency analogue for DP)."""
    onp.random.seed(0)
    x = onp.random.randn(16, 8).astype("float32")
    y = onp.random.randint(0, 4, 16).astype("float32")

    def build():
        onp.random.seed(42)
        mx.random.seed(42)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="tanh"), nn.Dense(4))
        net.initialize()
        net(mx.nd.array(x))
        return net

    L = gloss.SoftmaxCrossEntropyLoss()

    net_a = build()
    step = parallel.DataParallelStep(
        net_a, lambda o, l: L(o, l),
        mx.optimizer.SGD(learning_rate=0.1), mesh=mesh8)
    for _ in range(4):
        step(mx.nd.array(x), mx.nd.array(y))

    net_b = build()
    trainer = gluon.Trainer(net_b.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore=None)
    for _ in range(4):
        with mx.autograd.record():
            l = L(net_b(mx.nd.array(x)), mx.nd.array(y)).mean()
        l.backward()
        trainer.step(1)  # DataParallelStep takes the mean loss itself

    for (ka, pa), (kb, pb) in zip(net_a.collect_params().items(),
                                  net_b.collect_params().items()):
        onp.testing.assert_allclose(
            pa.data().asnumpy(), pb.data().asnumpy(), rtol=2e-4, atol=2e-5)


def test_scan_steps_matches_sequential_calls(mesh8):
    """k steps through scan_steps (one compiled lax.scan program) must
    follow the exact same trajectory as k per-call steps."""
    onp.random.seed(3)
    xs = onp.random.randn(4, 16, 8).astype("float32")
    ys = onp.random.randint(0, 4, (4, 16)).astype("float32")

    def build():
        onp.random.seed(7)
        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
        net.initialize()
        net(mx.nd.array(xs[0]))
        L = gloss.SoftmaxCrossEntropyLoss()
        return net, parallel.DataParallelStep(
            net, lambda o, l: L(o, l),
            mx.optimizer.SGD(learning_rate=0.2, momentum=0.9), mesh=mesh8)

    net_a, step_a = build()
    losses_scan = step_a.scan_steps(mx.nd.array(xs), mx.nd.array(ys))
    assert losses_scan.shape == (4,)

    net_b, step_b = build()
    losses_seq = [float(step_b(mx.nd.array(x), mx.nd.array(y)).asscalar())
                  for x, y in zip(xs, ys)]

    onp.testing.assert_allclose(losses_scan.asnumpy(), losses_seq,
                                rtol=1e-5, atol=1e-6)
    for (ka, pa), (kb, pb) in zip(net_a.collect_params().items(),
                                  net_b.collect_params().items()):
        onp.testing.assert_allclose(
            pa.data().asnumpy(), pb.data().asnumpy(), rtol=2e-5, atol=2e-6)


def test_scan_steps_first_call_adam_is_finite(mesh8):
    """A fresh step whose FIRST dispatch is scan_steps must seed the
    device step counter at 1: Adam's bias correction divides by
    1-beta**t, which is 0/0 at t=0 (regression: scan seeded t=0)."""
    x = onp.random.RandomState(2).randn(3, 8, 6).astype("float32")
    y = onp.random.RandomState(3).randint(0, 4, (3, 8)).astype("float32")
    L = gloss.SoftmaxCrossEntropyLoss()

    def build():
        onp.random.seed(21)
        mx.random.seed(21)
        n = nn.HybridSequential()
        n.add(nn.Dense(4))
        n.initialize()
        n(mx.nd.array(x[0]))
        return n, parallel.DataParallelStep(
            n, lambda o, l: L(o, l), mx.optimizer.Adam(learning_rate=1e-2),
            mesh=mesh8)

    # identical twin trained per-call: Adam's t sequence must match, so
    # the trajectories must match exactly
    net, step = build()
    net_b, step_b = build()

    losses = step.scan_steps(mx.nd.array(x), mx.nd.array(y))
    assert onp.isfinite(losses.asnumpy()).all()
    for _, p in net.collect_params().items():
        assert onp.isfinite(p.data().asnumpy()).all()

    l_seq = [float(step_b(mx.nd.array(xi), mx.nd.array(yi)).asscalar())
             for xi, yi in zip(x, y)]
    onp.testing.assert_allclose(losses.asnumpy(), l_seq, rtol=1e-5,
                                atol=1e-6)
    for (ka, pa), (kb, pb) in zip(net.collect_params().items(),
                                  net_b.collect_params().items()):
        onp.testing.assert_allclose(pa.data().asnumpy(),
                                    pb.data().asnumpy(),
                                    rtol=2e-5, atol=2e-6)


def test_scan_steps_then_call_interleave(mesh8):
    """scan_steps leaves the step counter/opt state usable by __call__."""
    net = nn.HybridSequential()
    net.add(nn.Dense(4))
    net.initialize()
    x = onp.random.RandomState(0).randn(2, 8, 6).astype("float32")
    y = onp.random.RandomState(1).randint(0, 4, (2, 8)).astype("float32")
    net(mx.nd.array(x[0]))
    L = gloss.SoftmaxCrossEntropyLoss()
    step = parallel.DataParallelStep(
        net, lambda o, l: L(o, l), mx.optimizer.SGD(learning_rate=0.1),
        mesh=mesh8)
    step.scan_steps(mx.nd.array(x), mx.nd.array(y))
    out = step(mx.nd.array(x[0]), mx.nd.array(y[0]))
    assert onp.isfinite(float(out.asscalar()))
    assert step._t == 3


def test_psum_in_shard_map(mesh8):
    from jax.sharding import PartitionSpec as P

    def f(x):
        return parallel.psum(x, "dp")

    fn = jax.shard_map(f, mesh=mesh8, in_specs=P("dp"), out_specs=P(),
                       check_vma=False)

    x = jnp.arange(8.0)
    out = fn(x)
    assert float(out[0]) == 28.0


def _dense_attn(q, k, v, causal):
    D = q.shape[-1]
    T = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (D ** -0.5)
    if causal:
        m = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    B, H, T, D = 2, 2, 64, 8
    onp.random.seed(1)
    q = jnp.asarray(onp.random.randn(B, H, T, D).astype("float32"))
    k = jnp.asarray(onp.random.randn(B, H, T, D).astype("float32"))
    v = jnp.asarray(onp.random.randn(B, H, T, D).astype("float32"))
    mesh = parallel.device_mesh((8,), ("sp",))
    ref = _dense_attn(q, k, v, causal)
    out = parallel.ring_attention_sharded(q, k, v, mesh=mesh, causal=causal)
    assert float(jnp.abs(out - ref).max()) < 2e-5


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_attention_matches_dense(causal):
    B, H, T, D = 2, 2, 100, 8  # non-divisible T exercises padding
    onp.random.seed(2)
    q = jnp.asarray(onp.random.randn(B, H, T, D).astype("float32"))
    k = jnp.asarray(onp.random.randn(B, H, T, D).astype("float32"))
    v = jnp.asarray(onp.random.randn(B, H, T, D).astype("float32"))
    ref = _dense_attn(q, k, v, causal)
    out = parallel.blockwise_attention(q, k, v, block_size=32, causal=causal)
    assert float(jnp.abs(out - ref).max()) < 2e-5


def test_tensor_parallel_matmul_mesh():
    """2-D mesh dp×tp: a sharded matmul under jit produces the global
    result (GSPMD inserts the collectives — SURVEY §2.3 TP row)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = parallel.device_mesh((4, 2), ("dp", "tp"))
    x = jnp.asarray(onp.random.randn(8, 16).astype("float32"))
    w = jnp.asarray(onp.random.randn(16, 32).astype("float32"))
    xs = jax.device_put(x, NamedSharding(mesh, P("dp", None)))
    ws = jax.device_put(w, NamedSharding(mesh, P(None, "tp")))
    out = jax.jit(lambda a, b: a @ b)(xs, ws)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(x @ w),
                                rtol=1e-4, atol=1e-5)


def test_data_parallel_step_advances_lr_schedule(mesh8):
    """The lr schedule must advance inside the cached compiled step: with
    FactorScheduler(step=2, factor=0.5) and SGD, the weight deltas must
    shrink by the schedule, not stay frozen at the step-0 lr."""
    net = nn.Dense(1, use_bias=False, in_units=1)
    net.initialize()
    net(mx.nd.ones((4, 1)))
    w0 = float(net.weight.data().asnumpy()[0, 0])
    sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.5)
    opt = mx.optimizer.SGD(learning_rate=1.0, lr_scheduler=sched)
    # loss = mean(w*x) with x=1 → dL/dw = 1 exactly, so each update moves
    # w by exactly the scheduled lr
    step = parallel.DataParallelStep(
        net, lambda o, l: o, opt, mesh=mesh8)
    x = mx.nd.ones((8, 1))
    y = mx.nd.zeros((8,))
    deltas = []
    prev = w0
    for _ in range(4):
        step(x, y)
        cur = float(net.weight.data().asnumpy()[0, 0])
        deltas.append(prev - cur)
        prev = cur
    # updates 1,2 at lr=1.0; updates 3,4 at lr=0.5
    onp.testing.assert_allclose(deltas, [1.0, 1.0, 0.5, 0.5], rtol=1e-5)


def test_data_parallel_step_preserves_param_dtypes():
    """bf16 params and optimizer state must stay bf16 across steps: the
    traced Adam bias correction (b2 ** t with a TRACED t) is strong f32
    and once silently rewrote every param as f32 after the first step,
    running the whole model at 2x HBM traffic from step 2 on."""
    rs = onp.random.RandomState(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(rs.rand(8, 8).astype("float32"))
    y = mx.nd.array(rs.randint(0, 4, 8).astype("float32"))
    net(x)
    net.cast("bfloat16")
    step = parallel.DataParallelStep(
        net, gloss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.Adam(learning_rate=1e-3), mesh=None)
    state_dtypes = [[str(leaf.dtype) for leaf in leaves]
                    for leaves in step._opt_states]
    for _ in range(3):
        step(x, y)
    for _, p in net.collect_params().items():
        assert p.data().dtype == onp.dtype("bfloat16"), p.name
    after = [[str(leaf.dtype) for leaf in leaves]
             for leaves in step._opt_states]
    assert after == state_dtypes, (state_dtypes, after)


def test_data_parallel_step_multi_precision_master():
    """optimizer.multi_precision carries an fp32 master for bf16 params
    (reference mp_sgd/mp_adam kernels): the working weight stays bf16,
    state (incl. master) stays f32, and training descends."""
    rs = onp.random.RandomState(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(rs.rand(8, 8).astype("float32"))
    y = mx.nd.array(rs.randint(0, 4, 8).astype("float32"))
    net(x)
    net.cast("bfloat16")
    step = parallel.DataParallelStep(
        net, gloss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.Adam(learning_rate=2e-2, multi_precision=True),
        mesh=None)
    assert all(step._mp_slots)
    assert all(str(l.dtype) == "float32"
               for lv in step._opt_states for l in lv)
    losses = [float(step(x, y).mean().asscalar()) for _ in range(25)]
    for _, p in net.collect_params().items():
        assert p.data().dtype == onp.dtype("bfloat16")
    assert all(str(l.dtype) == "float32"
               for lv in step._opt_states for l in lv)
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


def test_multi_precision_master_resyncs_on_external_set_data():
    """Externally mutated weights (checkpoint restore) must refresh the
    fp32 master, not be reverted by the next step."""
    rs = onp.random.RandomState(0)
    net = nn.Dense(4)
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(rs.rand(8, 8).astype("float32"))
    y = mx.nd.array(rs.randint(0, 4, 8).astype("float32"))
    net(x)
    net.cast("bfloat16")
    step = parallel.DataParallelStep(
        net, gloss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.Adam(learning_rate=1e-3, multi_precision=True),
        mesh=None)
    step(x, y)
    loaded = onp.full(net.weight.shape, 0.25, "float32")
    net.weight.set_data(mx.nd.array(loaded, dtype="bfloat16"))
    step(x, y)
    w = net.weight.data().asnumpy().astype("float32")
    # one small-lr step away from the loaded value, NOT the stale master
    assert onp.abs(w - loaded).max() < 0.05, w


# ---------------------------------------------------------------------------
# Pallas kernels inside a program whose batch GSPMD shards
# ---------------------------------------------------------------------------

def _sharded(mesh, *arrays):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return [jax.device_put(a, NamedSharding(mesh, P("dp"))) for a in arrays]


def test_per_batch_shard_is_a_plain_call_outside_a_scope(mesh8):
    from mxnet_tpu.parallel.mesh import batch_sharded_over, per_batch_shard
    x = jnp.arange(16.0).reshape(8, 2)
    f = lambda a, b: (a * 2, None if b is None else b.sum())
    y, s = per_batch_shard(f, (x, None))
    assert s is None and float(y.sum()) == 2 * float(x.sum())
    # a one-device axis is a plain call too
    one = parallel.device_mesh((1,), ("dp",), devices=jax.devices()[:1])
    with batch_sharded_over(one):
        y, s = per_batch_shard(f, (x, x))
    assert float(s) == float(x.sum())


@pytest.mark.parametrize("lens", [False, True], ids=["nomask", "kv_lens"])
def test_per_batch_shard_attention_matches_unsharded(mesh8, lens):
    """The flash kernels (interpret mode here) wrapped per dp shard inside a
    jitted program over batch-sharded operands give what the unsharded call
    gives, forward and backward — the wrapping the custom-vjp ops apply on
    a chip, where Mosaic kernels cannot be partitioned automatically."""
    from mxnet_tpu.ops import pallas_attention as PA
    from mxnet_tpu.parallel.mesh import batch_sharded_over, per_batch_shard
    rs = onp.random.RandomState(0)
    q, k, v, g = (jnp.asarray(rs.randn(8, 2, 128, 16).astype("float32"))
                  for _ in range(4))
    kl = jnp.asarray(rs.randint(40, 129, (8,)).astype("int32")) \
        if lens else None
    kw = dict(interpret=True, block_q=64, block_k=64)

    def fwd(q, k, v, kl):
        return PA.pallas_flash_attention(q, k, v, return_lse=True,
                                         kv_lens=kl, **kw)

    def bwd(q, k, v, out, lse, g, kl):
        return PA.pallas_flash_attention_bwd(q, k, v, out, lse, g,
                                             kv_lens=kl, **kw)

    out, lse = fwd(q, k, v, kl)
    want = (out, lse) + tuple(bwd(q, k, v, out, lse, g, kl))

    @jax.jit
    def program(q, k, v, g, kl):
        with batch_sharded_over(mesh8):
            out, lse = per_batch_shard(fwd, (q, k, v, kl))
            grads = per_batch_shard(bwd, (q, k, v, out, lse, g, kl))
        return (out, lse) + tuple(grads)

    ops = _sharded(mesh8, q, k, v, g) + \
        (_sharded(mesh8, kl) if lens else [None])
    got = program(*ops)
    assert len(got[0].sharding.device_set) == 8
    for a, b in zip(got, want):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-5, atol=1e-5)


def test_per_batch_shard_sums_the_norm_backward_reductions(mesh8):
    """The LayerNorm backward's per-channel dgamma/dbeta are partial sums
    per shard: flagged ``summed`` they come back whole and equal to the
    unsharded reduction; dx stays split by rows."""
    from mxnet_tpu.ops import pallas_layernorm as LN
    from mxnet_tpu.parallel.mesh import batch_sharded_over, per_batch_shard
    rs = onp.random.RandomState(1)
    x, ct = (jnp.asarray(rs.randn(64, 256).astype("float32"))
             for _ in range(2))
    mu, rstd = (jnp.asarray(rs.rand(64, 1).astype("float32"))
                for _ in range(2))
    g = jnp.asarray(rs.rand(256).astype("float32"))
    bwd = functools.partial(LN.pallas_layer_norm_bwd, interpret=True,
                            block_rows=8)
    want = bwd(x, g, mu, rstd, ct)

    @jax.jit
    def program(x, g, mu, rstd, ct):
        with batch_sharded_over(mesh8):
            return per_batch_shard(bwd, (x, g, mu, rstd, ct),
                                   replicated=(1,),
                                   summed=(False, True, True))

    xs, mus, rstds, cts = _sharded(mesh8, x, mu, rstd, ct)
    got = program(xs, g, mus, rstds, cts)
    for a, b in zip(got, want):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-5, atol=1e-4)


def test_resnet18_dp8_step_gives_the_one_device_losses():
    """The residual tail is plain ``jax.numpy``: under a ``dp`` mesh XLA
    partitions it and all-reduces the batch statistics and their
    gradients itself, with no per-shard wrapper — the eight-device step
    follows the one-device step's losses."""
    from mxnet_tpu.gluon.model_zoo import vision
    rs = onp.random.RandomState(0)
    x = rs.uniform(size=(16, 3, 32, 32)).astype("float32")
    y = rs.randint(0, 10, 16).astype("float32")
    L = gloss.SoftmaxCrossEntropyLoss()

    def losses(n_devices):
        onp.random.seed(7)
        mx.random.seed(7)
        net = vision.resnet18_v1(classes=10)
        net.initialize(mx.init.Xavier())
        net(mx.nd.array(x[:2]))
        mesh = parallel.device_mesh((n_devices,), ("dp",),
                                    devices=jax.devices()[:n_devices])
        step = parallel.DataParallelStep(
            net, lambda o, l: L(o, l),
            # a gentle rate: at larger ones this toy problem is fitted in
            # two steps and fp32 reassociation is amplified a thousandfold
            mx.optimizer.SGD(learning_rate=1e-5), mesh=mesh)
        return [float(step(mx.nd.array(x), mx.nd.array(y)).asscalar())
                for _ in range(4)]

    one, eight = losses(1), losses(8)
    assert one[-1] < one[0]
    onp.testing.assert_allclose(eight, one, rtol=2e-4)
