"""ZeRO-style cross-replica sharded weight update (arxiv 2004.13336).

Covers the tentpole contract: ``shard_optimizer`` OFF keeps the
replicated path; ON produces the same trained parameters while holding
only 1/N of the optimizer state per chip — including the fp32 master
under ``multi_precision`` — and composes with donation, ``scan_steps``,
uneven leaf sizes, and the 1-device degenerate mesh (so the whole
matrix runs in tier-1 on the virtual 8-device CPU mesh).
"""
import os

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.parallel import collectives as coll


@pytest.fixture
def mesh8():
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
    m = parallel.device_mesh((8,), ("dp",))
    old = parallel.get_mesh()
    parallel.set_mesh(m)
    yield m
    parallel.set_mesh(old)


# 9 in-units / 7 hidden: every weight and bias size is coprime with the
# 8-way dp axis, so each leaf exercises the zero-padded flat layout
_X = onp.random.RandomState(0).randn(16, 9).astype("float32")
_Y = onp.random.RandomState(1).randint(0, 4, 16).astype("float32")


def _build_step(mesh, shard, optimizer=None, bf16=False):
    onp.random.seed(42)
    mx.random.seed(42)
    net = nn.HybridSequential()
    net.add(nn.Dense(7, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(mx.nd.array(_X))
    if bf16:
        net.cast("bfloat16")
    L = gloss.SoftmaxCrossEntropyLoss()
    opt = optimizer() if optimizer else mx.optimizer.SGD(
        learning_rate=0.1, momentum=0.9)
    step = parallel.DataParallelStep(net, lambda o, l: L(o, l), opt,
                                     mesh=mesh, shard_optimizer=shard)
    return net, step


def _params_close(net_a, net_b, rtol=2e-5, atol=2e-6):
    # creation order, not name order: the global layer counter makes
    # "dense10_" sort before "dense9_", which mispaired the two nets
    # whenever a worker happened to cross a power of ten between them
    for (ka, pa), (kb, pb) in zip(net_a.collect_params().items(),
                                  net_b.collect_params().items()):
        onp.testing.assert_allclose(
            pa.data().asnumpy().astype("float32"),
            pb.data().asnumpy().astype("float32"), rtol=rtol, atol=atol,
            err_msg=ka)


def test_sharded_matches_replicated_k_steps(mesh8):
    """Same parameters after k steps, uneven leaf sizes included."""
    net_a, st_a = _build_step(mesh8, False)
    net_b, st_b = _build_step(mesh8, True)
    for _ in range(5):
        la = float(st_a(mx.nd.array(_X), mx.nd.array(_Y)).asscalar())
        lb = float(st_b(mx.nd.array(_X), mx.nd.array(_Y)).asscalar())
    assert abs(la - lb) < 1e-5
    _params_close(net_a, net_b)
    # every slot sharded; state leaves are flat, dp-sharded, and 1/8
    # per chip
    assert all(st_b._shard_slots)
    leaf = st_b._opt_states[0][0]
    assert leaf.ndim == 1 and leaf.shape[0] % 8 == 0
    assert leaf.addressable_shards[0].data.shape[0] == leaf.shape[0] // 8
    assert st_b.optimizer_state_bytes(per_chip=True) * 8 == \
        st_b.optimizer_state_bytes(per_chip=False)
    assert st_b.optimizer_state_bytes(per_chip=True) < \
        st_a.optimizer_state_bytes(per_chip=True)


def test_sharded_multi_precision_master_and_resync(mesh8):
    """bf16 weights keep a SHARDED fp32 master as state leaf 0: training
    matches the replicated mp path, weights stay bf16, and an external
    set_data refreshes the sharded master (not reverted next step)."""
    make = lambda: mx.optimizer.Adam(learning_rate=2e-2,  # noqa: E731
                                     multi_precision=True)
    net_a, st_a = _build_step(mesh8, False, optimizer=make, bf16=True)
    net_b, st_b = _build_step(mesh8, True, optimizer=make, bf16=True)
    assert all(st_b._mp_slots) and all(st_b._shard_slots)
    for _ in range(6):
        st_a(mx.nd.array(_X), mx.nd.array(_Y))
        st_b(mx.nd.array(_X), mx.nd.array(_Y))
    for _, p in net_b.collect_params().items():
        assert p.data().dtype == onp.dtype("bfloat16")
    assert all(str(l.dtype) == "float32"
               for lv in st_b._opt_states for l in lv)
    _params_close(net_a, net_b, rtol=2e-2, atol=2e-2)

    loaded = onp.full(net_b[0].weight.shape, 0.25, "float32")
    net_b[0].weight.set_data(mx.nd.array(loaded, dtype="bfloat16"))
    st_b(mx.nd.array(_X), mx.nd.array(_Y))
    w = net_b[0].weight.data().asnumpy().astype("float32")
    assert onp.abs(w - loaded).max() < 0.1, w


def test_sharded_scan_steps_matches_per_call(mesh8):
    """k sharded steps through one compiled lax.scan == k per-call
    sharded steps (the sharded state leaves are donated scan carries)."""
    xs = onp.random.RandomState(3).randn(3, 16, 9).astype("float32")
    ys = onp.random.RandomState(4).randint(0, 4, (3, 16)).astype("float32")
    net_a, st_a = _build_step(mesh8, True)
    net_b, st_b = _build_step(mesh8, True)
    losses = st_a.scan_steps(mx.nd.array(xs), mx.nd.array(ys))
    seq = [float(st_b(mx.nd.array(x), mx.nd.array(y)).asscalar())
           for x, y in zip(xs, ys)]
    onp.testing.assert_allclose(losses.asnumpy(), seq, rtol=1e-5,
                                atol=1e-6)
    _params_close(net_a, net_b)


def test_sharded_with_batch_donation_refeed_guard(mesh8):
    """donate_batch composes with the sharded update, and the re-feed
    guard still fires on a donated buffer."""
    net, step = _build_step(mesh8, True)
    step._donate_batch = True
    # pre-placed batches (the DevicePrefetchIter layout) are donated
    # as-is, so re-feeding the same device buffer must raise
    x = parallel.shard_batch(mx.nd.array(_X), mesh8)
    y = parallel.shard_batch(mx.nd.array(_Y), mesh8)
    step(x, y)
    with pytest.raises(RuntimeError, match="donated"):
        step(x, parallel.shard_batch(mx.nd.array(_Y), mesh8))
    # fresh buffers keep working and the state stays sharded
    step(mx.nd.array(_X), mx.nd.array(_Y))
    assert step._opt_states[0][0].addressable_shards[0].data.shape[0] \
        == step._opt_states[0][0].shape[0] // 8


def test_one_device_degenerate_mesh():
    """shard_optimizer=True on a 1-device dp mesh is a working no-op
    layout (pad-to-1, slice-of-everything) — the CPU-only degenerate."""
    mesh1 = parallel.device_mesh((1,), ("dp",),
                                 devices=jax.devices()[:1])
    net_a, st_a = _build_step(mesh1, False)
    net_b, st_b = _build_step(mesh1, True)
    assert st_b._shard_n == 1 and all(st_b._shard_slots)
    for _ in range(3):
        st_a(mx.nd.array(_X), mx.nd.array(_Y))
        st_b(mx.nd.array(_X), mx.nd.array(_Y))
    _params_close(net_a, net_b)


def _knob_mesh(kind):
    if kind == "none":
        return None
    n = int(kind[2:])
    return parallel.device_mesh((n,), ("dp",), devices=jax.devices()[:n])


# knob spelling -> the dp extent the state is sharded over on (no mesh,
# dp=1, dp=8): "auto" = on for dp>1 only; True takes any dp axis (the
# 1-device degenerate layout) and falls back without one
_KNOB_WANT = {False: (0, 0, 0), "off": (0, 0, 0), None: (0, 0, 0),
              True: (0, 1, 8), "on": (0, 1, 8), "auto": (0, 0, 8)}


@pytest.mark.parametrize("kind", ["none", "dp1", "dp8"])
@pytest.mark.parametrize("knob", list(_KNOB_WANT), ids=repr)
def test_shard_optimizer_knob_has_one_rule(knob, kind):
    """``collectives.resolve_shard_optimizer`` is the one rule:
    ``DataParallelStep`` shards over what it says, and ``Trainer``
    engages its mirror over the same extent wherever that is more than
    one replica (its weights mesh-replicated)."""
    import warnings
    from mxnet_tpu.gluon.trainer import _FusedUpdate
    want = _KNOB_WANT[knob][("none", "dp1", "dp8").index(kind)]
    mesh = _knob_mesh(kind)
    assert coll.resolve_shard_optimizer(knob, mesh) == want
    old = parallel.get_mesh()
    parallel.set_mesh(mesh)
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            net, st = _build_step(mesh, knob)
        assert st._shard_n == want
        # a forced knob with nothing to shard over says so, once
        fell_back = knob in (True, "on") and mesh is None
        assert any("shard_optimizer" in str(w.message)
                   for w in caught) == fell_back
        weights = [p.data() if mesh is None
                   else parallel.replicate(p.data(), mesh)
                   for _, p in net.collect_params().items()]
        fused = _FusedUpdate(None, shard_optimizer=knob)
        assert fused._shard_ready(weights) == (want > 1)
        assert fused._shard_n == (want if want > 1 else 0)
    finally:
        parallel.set_mesh(old)


@pytest.mark.parametrize("builder", ["resolver", "DataParallelStep",
                                     "Trainer"])
def test_unknown_shard_optimizer_knob_refused(mesh8, builder):
    from mxnet_tpu.gluon.trainer import _FusedUpdate
    with pytest.raises(ValueError, match="shard_optimizer"):
        if builder == "resolver":
            coll.resolve_shard_optimizer("sometimes", mesh8)
        elif builder == "DataParallelStep":
            _build_step(mesh8, "sometimes")
        else:
            _FusedUpdate(None, shard_optimizer="sometimes")


def test_shard_layout_telemetry(mesh8):
    """The per-chip state gauge and the collective-schedule journal
    event land at construction (docs/OBSERVABILITY.md contract)."""
    telemetry.reset()
    _, st = _build_step(mesh8, True)
    snap = telemetry.snapshot()
    per_chip = snap["gauges"]["parallel.optimizer_state_bytes_per_chip"]
    total = snap["gauges"]["parallel.optimizer_state_bytes_total"]
    assert per_chip * 8 == total
    evs = [e for e in snap["events"]
           if e["kind"] == "zero" and e["name"] == "shard_optimizer"]
    assert evs and evs[-1]["n_shards"] == 8
    assert evs[-1]["reduce_scatter_bytes"] > 0
    assert evs[-1]["all_gather_bytes"] > 0
    telemetry.reset()


# ---------------------------------------------------------------------------
# flat-layout collectives helpers
# ---------------------------------------------------------------------------

def test_flatten_pad_unflatten_roundtrip():
    for shape in ((3, 5), (7,), (), (8, 2)):
        x = onp.arange(max(1, int(onp.prod(shape))),
                       dtype="float32").reshape(shape)
        flat = coll.flatten_pad(jnp.asarray(x), 8)
        assert flat.ndim == 1 and flat.shape[0] % 8 == 0
        assert flat.shape[0] == coll.padded_size(x.size, 8)
        back = coll.unflatten(flat, shape)
        onp.testing.assert_array_equal(onp.asarray(back), x)
        # pad lanes are zero (numerics-neutral for wd/clip/moments)
        onp.testing.assert_array_equal(
            onp.asarray(flat)[x.size:], 0.0)


def test_reduce_scatter_padded_all_gather_unpad(mesh8):
    """Uneven leaf through the explicit shard_map spelling: N replicas
    each contribute, every replica ends with the summed full leaf."""
    from jax.sharding import PartitionSpec as P

    shape = (3, 7)   # 21 elements: pads to 24 over 8 replicas
    base = onp.arange(21, dtype="float32").reshape(shape)

    def f(x):
        shard = coll.reduce_scatter_padded(x, "dp", axis_size=8)
        assert shard.shape == (coll.padded_size(21, 8) // 8,)
        return coll.all_gather_unpad(shard, shape, "dp")

    fn = jax.shard_map(f, mesh=mesh8, in_specs=P("dp"), out_specs=P(),
                       check_vma=False)
    stacked = jnp.asarray(
        onp.stack([base * (r + 1) for r in range(8)]))  # (8, 3, 7)
    out = fn(stacked.reshape(8, -1))
    onp.testing.assert_allclose(onp.asarray(out), base * 36.0)

    with pytest.raises(ValueError, match="axis_size"):
        coll.reduce_scatter_padded(jnp.zeros(4), "dp")


# ---------------------------------------------------------------------------
# Trainer (_FusedUpdate) sharded path
# ---------------------------------------------------------------------------

def _trainer_setup(mesh, shard, donate_grads=False):
    onp.random.seed(42)
    mx.random.seed(42)
    net = nn.HybridSequential()
    net.add(nn.Dense(7, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(mx.nd.array(_X))
    if shard:
        for _, p in net.collect_params().items():
            p.set_data(parallel.replicate(p.data(), mesh))
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.05},
                       donate_grads=donate_grads, shard_optimizer=shard)
    return net, tr


def _trainer_epoch(net, tr, mesh, shard, k=4):
    L = gloss.SoftmaxCrossEntropyLoss()
    for _ in range(k):
        if shard:
            xb = parallel.shard_batch(mx.nd.array(_X), mesh)
            yb = parallel.shard_batch(mx.nd.array(_Y), mesh)
        else:
            xb, yb = mx.nd.array(_X), mx.nd.array(_Y)
        with mx.autograd.record():
            l = L(net(xb), yb).mean()
        l.backward()
        tr.step(1)


def test_trainer_sharded_matches_replicated(mesh8):
    """Trainer(shard_optimizer=True) with mesh-replicated params: same
    trained parameters, state mirror dp-sharded, donate_grads composes.
    The sharded leg runs under the runtime numerics sanitizer — the
    ZeRO update must keep every param/grad leaf finite and
    dtype-stable across steps (the working-dtype contract's dynamic
    half)."""
    import sys
    sys.path.insert(0, REPO) if REPO not in sys.path else None
    from tools.lint.runtime_numerics import NumericsSanitizer
    na, ta = _trainer_setup(mesh8, False)
    nb, tb = _trainer_setup(mesh8, True, donate_grads=True)
    _trainer_epoch(na, ta, mesh8, False)
    san = NumericsSanitizer().attach(tb)
    try:
        _trainer_epoch(nb, tb, mesh8, True)
    finally:
        san.detach()
    _params_close(na, nb)
    fused = tb._kv_fused or tb._local_fused
    assert fused._sharded, "sharded mirror did not engage"
    leaf = next(iter(fused._sharded.values()))[0]
    assert leaf.ndim == 1 and \
        leaf.addressable_shards[0].data.shape[0] == leaf.shape[0] // 8
    assert san.observed, "sanitizer sweep never ran"
    san.assert_all_finite()
    san.assert_no_dtype_drift()


def test_trainer_sharded_state_serialization(mesh8, tmp_path):
    """save_states gathers the mirror (same bytes as replicated
    training); load_states invalidates it and training continues."""
    na, ta = _trainer_setup(mesh8, False)
    nb, tb = _trainer_setup(mesh8, True)
    _trainer_epoch(na, ta, mesh8, False)
    _trainer_epoch(nb, tb, mesh8, True)
    fa, fb = str(tmp_path / "a.states"), str(tmp_path / "b.states")
    ta.save_states(fa)
    tb.save_states(fb)
    ua = ta._kvstore._updater if ta._update_on_kvstore else ta._updaters
    ub = tb._kvstore._updater if tb._update_on_kvstore else tb._updaters
    la, _ = jax.tree_util.tree_flatten(
        ua.states, is_leaf=lambda z: isinstance(z, mx.nd.NDArray))
    lb, _ = jax.tree_util.tree_flatten(
        ub.states, is_leaf=lambda z: isinstance(z, mx.nd.NDArray))
    assert len(la) == len(lb) and len(la) > 0
    for a, b in zip(la, lb):
        onp.testing.assert_allclose(a.asnumpy(), b.asnumpy(),
                                    rtol=2e-5, atol=1e-6)
    nc, tc = _trainer_setup(mesh8, True)
    _trainer_epoch(nc, tc, mesh8, True, k=1)
    tc.load_states(fb)
    fused = tc._kv_fused or tc._local_fused
    assert not fused._sharded       # mirror dropped; rebuilt next step
    _trainer_epoch(nc, tc, mesh8, True, k=2)


def test_trainer_unplaced_weights_keep_replicated_update(mesh8):
    """shard_optimizer=True with single-device weights must NOT engage
    (silent migration of the user's training onto the mesh): the update
    stays replicated and training still works."""
    net, tr = _trainer_setup(None, False)
    tr._shard_optimizer = True
    tr._local_fused = tr._kv_fused = None   # rebuild with the knob on
    _trainer_epoch(net, tr, mesh8, False, k=2)
    fused = tr._kv_fused or tr._local_fused
    assert fused is not None and not fused._sharded


def test_eager_grad_buffers_follow_the_data_onto_the_mesh(mesh8):
    """A Parameter's eager grad buffer lives where its data lives.  The
    sharded step re-places the data on its mesh (first call) and again on a
    re-formed mesh (``reshard``); left behind, a weight-sized buffer per
    parameter stays on the first device — what a four-chip run showed as
    uneven HBM."""
    net, step = _build_step(mesh8, True)
    params = list(net.collect_params().values())
    assert all(len(p.grad()._data.devices()) == 1 for p in params)
    step(mx.nd.array(_X), mx.nd.array(_Y))
    for p in params:
        assert p.grad()._data.sharding == p.data()._data.sharding
        assert len(p.grad()._data.devices()) == 8
    mesh4 = parallel.device_mesh((4,), ("dp",), devices=jax.devices()[:4])
    step.reshard(mesh4)
    for p in params:
        assert p.grad()._data.devices() == p.data()._data.devices() \
            == set(jax.devices()[:4])
