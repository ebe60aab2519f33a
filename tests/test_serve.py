"""Continuous-batching inference server units (mxnet_tpu.serve):
bucket policy, AOT zero-recompile steady state, deadline propagation,
backpressure/shedding, state machine, drain, and the stablehlo bucketed
export path.  The injected-fault matrix lives in test_serve_chaos.py.
"""
import os
import sys
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve, telemetry
from mxnet_tpu.serve import (AotModel, InferenceServer, ServeConfig,
                             pad_batch, pick_bucket, plan_buckets)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

FEAT = (8,)
W = onp.arange(8 * 3, dtype="float32").reshape(8, 3) * 0.1


def _fn(x):
    import jax.numpy as jnp
    return x @ jnp.asarray(W)


def _cfg(**kw):
    base = dict(buckets=(1, 2, 4), max_queue=16, batch_wait_ms=2.0,
                default_deadline_ms=500.0, dispatch_timeout_ms=500.0,
                watchdog_interval_ms=15.0)
    base.update(kw)
    return ServeConfig(**base)


def _server(**kw):
    return InferenceServer(_fn, feature_shape=FEAT, config=_cfg(**kw))


def _rows(n):
    return [onp.full(FEAT, i, "float32") for i in range(n)]


# -- bucket policy ----------------------------------------------------------

def test_pick_bucket_smallest_covering():
    assert pick_bucket(1, (1, 2, 4)) == 1
    assert pick_bucket(3, (1, 2, 4)) == 4
    assert pick_bucket(4, (1, 2, 4)) == 4
    assert pick_bucket(5, (1, 2, 4)) is None
    assert pick_bucket(2, (1, 2, 4), quarantined=(2,)) == 4
    assert pick_bucket(4, (1, 2, 4), quarantined=(4,)) is None


def test_plan_buckets_healthy_and_degraded():
    assert plan_buckets(3, (1, 2, 4)) == [4]
    assert plan_buckets(6, (1, 2, 4)) == [4, 2]
    # quarantined big bucket: the batch degrades onto smaller buckets
    assert plan_buckets(4, (1, 2, 4), quarantined=(4,)) == [2, 2]
    assert plan_buckets(7, (1, 2, 4), quarantined=(4,)) == [2, 2, 2, 1]
    assert plan_buckets(2, (1, 2, 4), quarantined=(1, 2, 4)) is None
    assert plan_buckets(0, (1, 2)) == []


def test_pad_batch_pads_and_refuses_overflow():
    rows = _rows(2)
    out = pad_batch(rows, 4, FEAT, "float32")
    assert out.shape == (4, 8) and out.dtype == onp.float32
    onp.testing.assert_array_equal(out[1], rows[1])
    onp.testing.assert_array_equal(out[2:], 0)
    with pytest.raises(mx.MXNetError):
        pad_batch(_rows(3), 2, FEAT, "float32")


@pytest.mark.parametrize("max_batch,menu", [
    (1, [1]), (2, [1, 2]), (3, [1, 2, 4]), (8, [1, 2, 4, 8]),
    (12, [2, 4, 8, 16]), (32, [4, 8, 16, 32])])
def test_default_bucket_menu(max_batch, menu):
    """The top four powers of two up to ``max_batch`` rounded up to
    one: what ``ServeConfig(buckets="auto")`` compiles."""
    from mxnet_tpu.serve.buckets import default_bucket_menu
    assert default_bucket_menu(max_batch) == menu


@pytest.mark.parametrize("budget,dtype,menu", [
    (2 * 4096 * 7, "float32", [1, 2, 4]),   # 8 does not fit beside 1+2+4
    (2 * 2048 * 7, "bfloat16", [1, 2, 4]),  # the same rows at half width
    (1, "float32", [1])])                   # never below the smallest
def test_over_budget_menu_sheds_largest_buckets_first(budget, dtype, menu,
                                                      monkeypatch):
    """Every bucket holds its input and output batch resident: a menu
    over the serving HBM budget loses its largest buckets before any
    executable is compiled — by argument or by
    ``MXNET_SERVE_HBM_BUDGET``."""
    from mxnet_tpu.serve.buckets import default_bucket_menu
    assert default_bucket_menu(8, (1024,), dtype, budget=budget) == menu
    monkeypatch.setenv("MXNET_SERVE_HBM_BUDGET", str(budget))
    assert default_bucket_menu(8, (1024,), dtype) == menu


def test_auto_buckets_resolve_at_construction(monkeypatch):
    """``buckets="auto"`` becomes the budget-checked default menu where
    the model's feature shape is known, and is journaled once."""
    telemetry.reset()
    srv = InferenceServer(_fn, feature_shape=FEAT,
                          config=ServeConfig(buckets="auto"))
    assert srv._cfg.buckets == (1, 2, 4, 8)
    ev = [e for e in telemetry.snapshot()["events"]
          if e.get("kind") == "serve" and e.get("name") == "bucket_menu"]
    assert len(ev) == 1 and ev[0]["buckets"] == [1, 2, 4, 8]
    monkeypatch.setenv("MXNET_SERVE_HBM_BUDGET", "1")
    srv = InferenceServer(_fn, feature_shape=FEAT,
                          config=ServeConfig(buckets="auto"))
    assert srv._cfg.buckets == (1,)
    telemetry.reset()


# -- serving happy path -----------------------------------------------------

def test_serves_correct_results_zero_steady_state_recompiles():
    srv = _server()
    srv.start()
    try:
        assert srv.state() == serve.READY
        rows = _rows(11)
        handles = [srv.submit(r) for r in rows]
        outs = [h.outcome(timeout=2.0) for h in handles]
        assert all(o is not None and o[0] == "result" for o in outs)
        for r, o in zip(rows, outs):
            onp.testing.assert_allclose(o[1], r @ W, rtol=1e-5)
        # the bucketed-AOT contract: every compile happened in start(),
        # the load phase added ZERO — the recompile-detector hard gate
        assert srv.steady_state_recompiles() == {}
        counts = telemetry.compile_counts()
        menu = {k: v for k, v in counts.items()
                if k.startswith("serve.%s." % srv.name)}
        assert len(menu) == 3 and set(menu.values()) == {1}
    finally:
        srv.close()


def test_latency_and_batching_census():
    srv = _server()
    srv.start()
    try:
        h = srv.submit(_rows(1)[0])
        assert h.outcome(timeout=2.0)[0] == "result"
        assert 0.0 < h.latency_ms() < 2000.0
    finally:
        srv.close()


def test_from_block_matches_net():
    from mxnet_tpu.gluon import nn
    net = nn.Dense(4)
    net.initialize(mx.init.Xavier())
    x = onp.random.RandomState(3).randn(1, 6).astype("float32")
    net(mx.nd.array(x))            # materialize params
    want = net(mx.nd.array(x)).asnumpy()
    srv = InferenceServer(net, feature_shape=(6,),
                          config=_cfg(buckets=(1, 2)), name="dense")
    srv.start()
    try:
        got = srv.submit(x[0]).result(timeout=2.0)
        onp.testing.assert_allclose(got, want[0], rtol=1e-5)
    finally:
        srv.close()


# -- deadlines --------------------------------------------------------------

def test_expired_request_dropped_before_dispatch():
    srv = _server()
    srv.start()
    try:
        d0 = telemetry.counter("serve.dispatches")
        drops0 = telemetry.counter("serve.deadline_drops")
        h = srv.submit(_rows(1)[0], deadline_ms=0.0)
        out = h.outcome(timeout=2.0)
        assert out is not None and out[0] == "timeout"
        # the expiry resolved BEFORE an executable dispatch was wasted
        assert telemetry.counter("serve.deadline_drops") == drops0 + 1
        assert telemetry.counter("serve.dispatches") == d0
    finally:
        srv.close()


def test_batch_never_waits_past_earliest_deadline():
    # batch_wait is huge; the single request's deadline must flush the
    # batch long before the wait window closes (the margin is the
    # dispatch-time headroom the flush leaves itself)
    srv = _server(batch_wait_ms=2000.0, deadline_margin_ms=40.0)
    srv.start()
    try:
        h = srv.submit(_rows(1)[0], deadline_ms=150.0)
        out = h.outcome(timeout=2.0)
        assert out is not None and out[0] == "result"
        assert h.latency_ms() < 1000.0
    finally:
        srv.close()


# -- admission control ------------------------------------------------------

def test_bad_shape_is_immediate_reject():
    srv = _server()
    srv.start()
    try:
        h = srv.submit(onp.zeros((3,), "float32"))
        kind, _, reason = h.outcome(timeout=1.0)
        assert kind == "reject" and "bad_shape" in reason
        with pytest.raises(serve.ServeRejected):
            h.result(timeout=0.1)
    finally:
        srv.close()


def test_submit_before_start_and_after_drain_rejects():
    srv = _server()
    h = srv.submit(_rows(1)[0])
    assert h.outcome(timeout=0.5) == ("reject", None, "not_ready")
    srv.start()
    srv.drain(timeout=5.0)
    assert srv.state() == serve.DRAINING
    h2 = srv.submit(_rows(1)[0])
    assert h2.outcome(timeout=0.5) == ("reject", None, "draining")
    srv.close()


def test_priority_shedding_under_overload_then_recovery():
    # shed watermark at depth 2 of a 4-slot queue; a huge batch_wait
    # keeps the batcher from draining while the burst lands
    srv = _server(max_queue=4, shed_fraction=0.5, resume_fraction=0.9,
                  batch_wait_ms=150.0, buckets=(1, 2, 4))
    srv.start()
    try:
        handles = [srv.submit(r, priority=1, deadline_ms=2000.0)
                   for r in _rows(10)]
        outs = [h.outcome(timeout=4.0) for h in handles]
        assert all(o is not None for o in outs)
        kinds = [o[0] for o in outs]
        sheds = sum(1 for o in outs
                    if o[0] == "reject" and o[2] in ("shed",))
        assert sheds >= 1, kinds
        # priority-0 requests are NOT shed at the same depth
        h0 = srv.submit(_rows(1)[0], priority=0, deadline_ms=2000.0)
        out0 = h0.outcome(timeout=4.0)
        assert out0 is not None and out0[2] != "shed"
        # once the queue subsides the watchdog recovers DEGRADED->READY
        deadline = time.monotonic() + 3.0
        while srv.state() != serve.READY and time.monotonic() < deadline:
            time.sleep(0.02)
        assert srv.state() == serve.READY
    finally:
        srv.close()


def test_queue_full_is_reject_not_block():
    # 2-slot queue + a batcher parked on a long wait: the 20-request
    # burst must come back queue_full immediately, never block submit
    srv = _server(max_queue=2, batch_wait_ms=200.0)
    srv.start()
    try:
        t0 = time.monotonic()
        handles = [srv.submit(r, deadline_ms=2000.0) for r in _rows(20)]
        submit_s = time.monotonic() - t0
        assert submit_s < 1.0          # no blocked producer
        outs = [h.outcome(timeout=4.0) for h in handles]
        assert all(o is not None for o in outs)
        assert any(o[0] == "reject" and o[2] == "queue_full"
                   for o in outs), [o[0:3:2] for o in outs]
    finally:
        srv.close()


# -- lifecycle --------------------------------------------------------------

def test_state_machine_and_clean_drain():
    srv = _server()
    assert srv.state() == serve.STARTING
    srv.start()
    assert srv.state() == serve.READY
    handles = [srv.submit(r) for r in _rows(6)]
    drained = srv.close(timeout=10.0)
    assert drained
    # accepted requests COMPLETED through the drain (not rejected)
    outs = [h.outcome(timeout=0.5) for h in handles]
    assert all(o is not None and o[0] == "result" for o in outs), \
        [o and o[0] for o in outs]
    assert srv.state() == serve.DRAINING
    # threads stopped and joined
    for t in (srv._batcher, srv._watchdog, srv._dispatcher):
        assert t is not None and not t.is_alive()
    # idempotent
    assert srv.close(timeout=1.0)


def test_close_without_start():
    srv = _server()
    srv.close(timeout=1.0)
    assert srv.state() == serve.DRAINING


# -- stablehlo bucketed export path ----------------------------------------

def test_export_bucketed_serves_from_disk(tmp_path):
    from mxnet_tpu.contrib import stablehlo
    from mxnet_tpu.gluon import nn
    net = nn.Dense(5)
    net.initialize(mx.init.Xavier())
    x = onp.random.RandomState(7).randn(2, 6).astype("float32")
    net(mx.nd.array(x))
    want = net(mx.nd.array(x)).asnumpy()

    prefix = str(tmp_path / "served")
    paths = stablehlo.export_bucketed(prefix, net, (1, 2), (6,))
    assert [p.rsplit("/", 1)[-1] for p in paths] == \
        ["served-b1-stablehlo.bin", "served-b2-stablehlo.bin"]
    arts = stablehlo.load_bucketed(prefix)
    assert sorted(arts) == [1, 2]

    srv = InferenceServer.from_exported(prefix, name="served")
    assert srv._cfg.buckets == (1, 2)
    srv.start()
    try:
        outs = [srv.submit(x[i]).result(timeout=2.0) for i in range(2)]
        onp.testing.assert_allclose(onp.stack(outs), want, rtol=1e-5)
        assert srv.steady_state_recompiles() == {}
    finally:
        srv.close()


def test_load_bucketed_missing_raises(tmp_path):
    from mxnet_tpu.contrib import stablehlo
    with pytest.raises(mx.MXNetError):
        stablehlo.load_bucketed(str(tmp_path / "nothing"))


# -- parse_log census -------------------------------------------------------

def test_parse_log_serve_census_roundtrip(tmp_path):
    from tools.parse_log import parse_jsonl, render_jsonl
    sink = tmp_path / "serve.jsonl"
    telemetry.set_jsonl_sink(str(sink))
    try:
        srv = _server()
        srv.start()
        for r in _rows(5):
            srv.submit(r)
        srv.submit(_rows(1)[0], deadline_ms=0.0)   # one timeout row
        srv.submit(onp.zeros((3,), "float32"))     # one reject row
        time.sleep(0.2)
        srv.close()
    finally:
        telemetry.set_jsonl_sink(None)
    agg = parse_jsonl(open(str(sink)))
    census = agg["serve"]
    assert census["batches"] >= 1
    assert census["events"].get("batch", 0) >= 1
    assert census["events"].get("timeout", 0) >= 1
    assert census["events"].get("reject", 0) >= 1
    assert any(s.startswith("STARTING->READY") for s in census["states"])
    text = render_jsonl(agg)
    assert "serve journal census" in text
    assert "serve/batch" in text and "serve/timeout" in text
    assert "mean-fill" in text
