"""Elastic, preemption-tolerant training (parallel/elastic.py + chaos.py).

The in-process half of the chaos matrix, on the virtual 8-device CPU
mesh: a "lost chip" is simulated by re-forming the mesh over a device
subset, which exercises the REAL re-shard math — flat zero-padded ZeRO
state (fp32 master included) migrating between dp extents — the part a
multiprocess kill test cannot cover deterministically.  The
multiprocess protocol half (heartbeat detection across real OS
processes, manifest-based restart) lives in test_dist_multiprocess.py.
"""
import os
import time

import numpy as onp
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.parallel import chaos
from mxnet_tpu.parallel.elastic import ElasticContext, kv_retry

# 9 in / 7 hidden: every leaf size is coprime with the dp extents used
# here, so 8->4->2 re-sharding always crosses different pad widths
_X = onp.random.RandomState(0).randn(16, 9).astype("float32")
_Y = onp.random.RandomState(1).randint(0, 4, 16).astype("float32")


@pytest.fixture
def mesh8():
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
    m = parallel.device_mesh((8,), ("dp",))
    old = parallel.get_mesh()
    parallel.set_mesh(m)
    yield m
    parallel.set_mesh(old)


@pytest.fixture(autouse=True)
def _clear_chaos():
    chaos.clear()
    yield
    chaos.clear()


def _build_step(mesh, shard=True, optimizer=None, bf16=False):
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


def _run(step, k):
    return [float(step(mx.nd.array(_X), mx.nd.array(_Y)).asscalar())
            for _ in range(k)]


# ---------------------------------------------------------------------------
# mesh re-formation + ZeRO re-shard
# ---------------------------------------------------------------------------

def test_reshard_8_to_4_loss_parity(mesh8):
    """Kill half the mesh mid-epoch: survivors re-form, ZeRO state
    re-shards 8->4, and the loss trajectory matches an uninterrupted
    run (the update math is dp-extent-invariant)."""
    net_a, st_a = _build_step(mesh8, True)
    losses_a = _run(st_a, 6)

    net_b, st_b = _build_step(mesh8, True)
    losses_b = _run(st_b, 3)
    ctx = ElasticContext(st_b, liveness=lambda: 0)
    mesh4 = ctx.reform(devices=jax.devices()[:4], step=3)
    assert dict(mesh4.shape) == {"dp": 4}
    losses_b += _run(st_b, 3)
    onp.testing.assert_allclose(losses_a, losses_b, rtol=1e-5, atol=1e-6)
    # state really lives at the new extent: flat, padded to 4, 1/4/chip
    assert all(st_b._shard_slots)
    leaf = st_b._opt_states[0][0]
    assert leaf.ndim == 1 and leaf.shape[0] % 4 == 0
    assert leaf.addressable_shards[0].data.shape[0] == leaf.shape[0] // 4
    # journal carries the transition
    ev = [e for e in telemetry.snapshot(events=256)["events"]
          if e["kind"] == "elastic" and e["name"] == "reshard"]
    assert ev and ev[-1]["world_from"] == 8 and ev[-1]["world_to"] == 4
    assert ev[-1]["bytes"] > 0 and ev[-1]["dur_ms"] >= 0


def test_reshard_preserves_fp32_master_bitwise(mesh8):
    """The fp32 master (state leaf 0 under multi_precision) must
    migrate bitwise through a reshard — and the next step must NOT
    resync it from the bf16 weight (which would round away exactly the
    precision the master keeps)."""
    mk = lambda: mx.optimizer.Adam(learning_rate=1e-3,  # noqa: E731
                                   multi_precision=True)
    net_b, st_b = _build_step(mesh8, True, optimizer=mk, bf16=True)
    _run(st_b, 3)
    masters = [st_b._materialize_slot(s)[0].copy()
               for s in range(len(st_b._opt_states))]
    ElasticContext(st_b, liveness=lambda: 0).reform(
        devices=jax.devices()[:4])
    for s, before in enumerate(masters):
        onp.testing.assert_array_equal(before,
                                       st_b._materialize_slot(s)[0])
    # the resync-suppression pin: the next dispatch rebuilds the master
    # from the half-width weight whenever _mp_written doesn't match the
    # (re-placed) weight object — reshard must re-pin it, or the fp32
    # truth silently degrades to a bf16 round-trip
    for slot, i in enumerate(st_b._trainable):
        assert st_b._mp_written[slot] is st_b._params[i]._data._data
    _run(st_b, 1)   # masters advance from their fp32 values, not bf16
    for s, before in enumerate(masters):
        after = st_b._materialize_slot(s)[0]
        assert after.dtype == onp.float32
        assert not onp.array_equal(before, after), "master never updated"


@pytest.mark.slow
def test_reshard_auto_knob_unsharded_and_back(mesh8):
    """shard_optimizer='auto': shrinking to a 1-device mesh drops to
    the natural replicated layout; re-growing re-shards — same trained
    parameters as an uninterrupted sharded run throughout."""
    net_a, st_a = _build_step(mesh8, True)
    net_b, st_b = _build_step(mesh8, "auto")
    _run(st_a, 2), _run(st_b, 2)
    ctx = ElasticContext(st_b, liveness=lambda: 0)
    ctx.reform(devices=jax.devices()[:1])
    assert st_b._shard_n == 0 and not any(st_b._shard_slots)
    _run(st_a, 2), _run(st_b, 2)
    ctx.reform(devices=jax.devices()[:4])
    assert st_b._shard_n == 4 and all(st_b._shard_slots)
    _run(st_a, 2), _run(st_b, 2)
    for (ka, pa), (kb, pb) in zip(
            net_a.collect_params().items(),
            net_b.collect_params().items()):
        onp.testing.assert_allclose(pa.data().asnumpy(),
                                    pb.data().asnumpy(),
                                    rtol=2e-5, atol=2e-6, err_msg=ka)


@pytest.mark.slow
def test_trainer_reshard_parity(mesh8):
    """Trainer path: the ZeRO mirror gathers back bitwise, weights
    re-place on the survivors' mesh, and the fused update re-engages at
    the new dp extent — parameters keep matching an uninterrupted
    trainer.  (slow: 4 fused-update compiles across two mesh extents;
    the DataParallelStep reshard path carries the tier-1 parity
    assertion.)"""
    def setup(mesh):
        onp.random.seed(3)
        mx.random.seed(3)
        net = nn.HybridSequential()
        net.add(nn.Dense(7, activation="relu"), nn.Dense(4))
        net.initialize(mx.init.Xavier())
        net(mx.nd.array(_X))
        for _, p in net.collect_params().items():
            p.set_data(parallel.replicate(p.data(), mesh))
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9},
                           shard_optimizer=True)
        return net, tr

    L = gloss.SoftmaxCrossEntropyLoss()

    def epoch(net, tr, mesh, k):
        for _ in range(k):
            xb = parallel.shard_batch(mx.nd.array(_X), mesh)
            yb = parallel.shard_batch(mx.nd.array(_Y), mesh)
            with mx.autograd.record():
                loss = L(net(xb), yb).mean()
            loss.backward()
            tr.step(1)

    net_a, tr_a = setup(mesh8)
    net_b, tr_b = setup(mesh8)
    epoch(net_a, tr_a, mesh8, 4)
    epoch(net_b, tr_b, mesh8, 2)
    mesh4 = parallel.device_mesh((4,), ("dp",),
                                 devices=jax.devices()[:4])
    ElasticContext(tr_b, liveness=lambda: 0).reform(mesh=mesh4)
    epoch(net_b, tr_b, mesh4, 2)
    fused = tr_b._kv_fused or tr_b._local_fused
    assert fused is not None and fused._shard_n == 4
    parallel.set_mesh(mesh8)
    for (ka, pa), (kb, pb) in zip(
            net_a.collect_params().items(),
            net_b.collect_params().items()):
        onp.testing.assert_allclose(pa.data().asnumpy(),
                                    pb.data().asnumpy(),
                                    rtol=2e-5, atol=2e-6, err_msg=ka)


# ---------------------------------------------------------------------------
# detection + backoff
# ---------------------------------------------------------------------------

def test_elastic_context_detects_and_journals(mesh8):
    seq = iter([0, 0, 1, 1, 0])
    _, st = _build_step(mesh8, True)
    ctx = ElasticContext(st, liveness=lambda: next(seq))
    assert ctx.poll(step=0) is None
    assert ctx.poll(step=1) is None
    ev = ctx.poll(step=2)
    assert ev["kind"] == "departed"
    assert ev["world_from"] - ev["world_to"] == 1
    assert ctx.poll(step=3) is None        # unchanged world: no event
    ev = ctx.poll(step=4)
    assert ev["kind"] == "joined"
    kinds = [(e.get("change"), e.get("step")) for e in
             telemetry.snapshot(events=256)["events"]
             if e["kind"] == "elastic" and e["name"] == "detect"]
    assert ("departed", 2) in kinds and ("joined", 4) in kinds


def test_poll_interval_throttles_probes(mesh8):
    """poll_interval: the liveness probe is a coordinator RPC, so a
    per-step maybe_recover() must not pay one per step — throttled
    polls return None without probing."""
    calls = {"n": 0}

    def probe():
        calls["n"] += 1
        return 0

    _, st = _build_step(mesh8, True)
    ctx = ElasticContext(st, liveness=probe, poll_interval=60.0)
    assert ctx.poll(step=0) is None and calls["n"] == 1
    for i in range(5):
        assert ctx.poll(step=i + 1) is None
    assert calls["n"] == 1, "throttled polls still probed"


def test_maybe_recover_reforms_on_departure(mesh8):
    _, st = _build_step(mesh8, True)
    seq = iter([0, 1])
    ctx = ElasticContext(st, liveness=lambda: next(seq))
    assert ctx.maybe_recover(step=0) is None
    ev = ctx.maybe_recover(devices=jax.devices()[:4], step=1)
    assert ev["kind"] == "departed" and dict(ev["mesh"].shape) == {"dp": 4}
    assert st._shard_n == 4


def test_min_workers_floor_raises(mesh8):
    _, st = _build_step(mesh8, True)
    ctx = ElasticContext(st, liveness=lambda: 7, min_workers=2,
                         kvstore=None)
    ctx._world0 = 8
    with pytest.raises(MXNetError, match="min_workers"):
        ctx.poll()


def test_kv_retry_backoff_jitter_and_giveup():
    """Flaky op: retried under exponential backoff + jitter; a dead op
    re-raises after the bounded attempts (never a silent zero)."""
    delays = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("flap %d" % calls["n"])
        return 41

    import random
    r0 = telemetry.counter("elastic.kv_retries")
    out = kv_retry(flaky, retries=5, base=0.05, cap=1.0, jitter=0.5,
                   rng=random.Random(7), sleep=delays.append)
    assert out == 41 and calls["n"] == 3
    assert len(delays) == 2
    # exponential base with bounded jitter: d0 in [.05,.075], d1 in [.1,.15]
    assert 0.05 <= delays[0] <= 0.075 and 0.1 <= delays[1] <= 0.15
    assert telemetry.counter("elastic.kv_retries") - r0 == 2

    with pytest.raises(RuntimeError, match="always"):
        kv_retry(lambda: (_ for _ in ()).throw(RuntimeError("always")),
                 retries=3, sleep=delays.append)


def test_coordinator_loss_is_reported_not_fatal(mesh8):
    """A coordinator unreachable past the retry budget classifies as
    coordinator_lost (restore from the manifest is the remedy) instead
    of raising out of the training loop."""
    def dead():
        raise RuntimeError("coordination service unreachable")

    _, st = _build_step(mesh8, True)
    ctx = ElasticContext(st, liveness=dead, retries=2, backoff_base=0.0,
                         jitter=0.0)
    ev = ctx.poll(step=5)
    assert ev["kind"] == "coordinator_lost"
    det = [e for e in telemetry.snapshot(events=256)["events"]
           if e["kind"] == "elastic" and e["name"] == "detect"
           and e.get("reason") == "coordinator_unreachable"]
    assert det and det[-1]["step"] == 5


# ---------------------------------------------------------------------------
# chaos harness determinism
# ---------------------------------------------------------------------------

def test_chaos_fault_triggers_are_deterministic():
    chaos.install("kill_worker", rank=2, at_step=3)
    # wrong rank: never fires
    assert not chaos.should_fire("kill_worker", step=3, rank=1)
    # right rank, wrong step: no fire
    assert not chaos.should_fire("kill_worker", step=2, rank=2)
    assert chaos.should_fire("kill_worker", step=3, rank=2)
    assert chaos.fired("kill_worker") == 1
    chaos.clear("kill_worker")
    assert not chaos.should_fire("kill_worker", step=3, rank=2)

    chaos.install("drop_heartbeat", times=2)
    assert chaos.should_fire("drop_heartbeat")
    assert chaos.should_fire("drop_heartbeat")
    assert not chaos.should_fire("drop_heartbeat")   # times exhausted

    chaos.install("kv_garble", after_calls=1, times=1)
    assert not chaos.should_fire("kv_garble")        # warm-up call
    assert chaos.should_fire("kv_garble")


def test_chaos_kv_proxy_garbles_reads():
    class C:
        def blocking_key_value_get(self, key, t):
            return "1234.5"

        def other(self):
            return "ok"

    proxy = chaos.wrap_kv_client(C())
    assert proxy.blocking_key_value_get("k", 50) == "1234.5"
    chaos.install("kv_garble", times=1)
    garbled = proxy.blocking_key_value_get("k", 50)
    assert garbled != "1234.5"
    with pytest.raises(ValueError):
        float(garbled)          # garbled payloads must not parse
    assert proxy.blocking_key_value_get("k", 50) == "1234.5"
    assert proxy.other() == "ok"


def test_chaos_kv_proxy_stalls_reads():
    """``kv_stall`` blocks proxied reads for its ``delay`` — the
    struggling-coordinator fault the kv_retry backoff path absorbs."""
    class C:
        def blocking_key_value_get(self, key, t):
            return "1234.5"

    proxy = chaos.wrap_kv_client(C())
    chaos.install("kv_stall", times=1, delay=0.05)
    t0 = time.monotonic()
    assert proxy.blocking_key_value_get("k", 50) == "1234.5"
    assert time.monotonic() - t0 >= 0.05      # stalled, payload intact
    t0 = time.monotonic()
    assert proxy.blocking_key_value_get("k", 50) == "1234.5"
    assert time.monotonic() - t0 < 0.05       # times=1: back to fast


def test_chaos_install_from_env(monkeypatch):
    monkeypatch.setenv(chaos.ENV_VAR,
                       "kill_worker:rank=2,at_step=3;drop_heartbeat:rank=1")
    assert chaos.install_from_env(rank=2) == ["kill_worker"]
    spec = chaos.active("kill_worker")
    assert spec["rank"] == 2 and spec["at_step"] == 3
    assert chaos.active("drop_heartbeat") is None    # other rank's fault


def test_garbled_liveness_rides_retry_to_recovery(mesh8):
    """End-to-end: a liveness probe whose first reads come back garbled
    (chaos kv_garble through the heartbeat parser) retries under
    backoff and lands on the true count."""
    import time
    good = iter([None, None, 1])

    def probe():
        nxt = next(good)
        if nxt is None:
            raise ValueError("garbled heartbeat payload")
        return nxt

    _, st = _build_step(mesh8, True)
    ctx = ElasticContext(st, liveness=probe, retries=4,
                         backoff_base=0.0, jitter=0.0)
    t0 = time.monotonic()
    ev = ctx.poll()
    assert time.monotonic() - t0 < 5.0
    assert ev["kind"] == "departed" and ev["n_dead"] == 1
