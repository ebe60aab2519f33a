"""The always-on telemetry layer (ISSUE 3): spans/counters/gauges,
recompile detection with cache-key diffs, prefetch/memory gauges in a
real Trainer run, step-hook-driven Monitor/Speedometer, exporters."""
import json
import logging

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon import loss as gloss


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Telemetry state is process-global: every test starts from a
    clean slate and leaves no step hooks behind."""
    telemetry.reset()
    telemetry.enable()
    yield
    with telemetry._lock:
        telemetry._step_hooks.clear()
    telemetry.set_jsonl_sink(None)
    telemetry.reset()


def _make_net(in_dim=6, classes=4):
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(classes))
    net.initialize()
    net(mx.nd.array(onp.zeros((2, in_dim), "float32")))
    return net


def _float_feed(n_batches=4, bs=4, dim=6):
    """DevicePrefetchIter over a tiny synthetic float32 DataIter."""
    from mxnet_tpu.io import DataBatch, DataDesc, DataIter
    from mxnet_tpu.io import DevicePrefetchIter

    rs = onp.random.RandomState(0)
    batches = [rs.randn(bs, dim).astype("float32")
               for _ in range(n_batches)]
    labels = [rs.randint(0, 4, bs).astype("float32")
              for _ in range(n_batches)]

    class F32Iter(DataIter):
        def __init__(self):
            super().__init__(bs)
            self.i = 0

        @property
        def provide_data(self):
            return [DataDesc("data", (bs, dim))]

        @property
        def provide_label(self):
            return [DataDesc("softmax_label", (bs,))]

        def reset(self):
            self.i = 0

        def next(self):
            if self.i >= len(batches):
                raise StopIteration
            b = DataBatch([mx.nd.array(batches[self.i])],
                          [mx.nd.array(labels[self.i])])
            self.i += 1
            return b

    return DevicePrefetchIter(F32Iter(), dtype="float32", depth=2)


def _count_backend_compiles(wanted):
    """The test's own ``jax.monitoring`` listener, beside the program's:
    a list that grows by one for every ``wanted`` program jax hands its
    backend from now on.  (jax keeps listeners for the life of the
    process: one that outlives its test appends to a list nobody reads.)"""
    import jax.monitoring
    seen = []

    def listener(event, seconds, fun_name=None, **_):
        if event == telemetry._BACKEND and fun_name == wanted:
            seen.append(seconds)

    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def test_span_counter_gauge_event_snapshot():
    with telemetry.span("unit.work"):
        pass
    with telemetry.span("unit.work"):
        pass
    telemetry.inc("unit.count", 3)
    telemetry.inc("unit.count")
    telemetry.gauge("unit.g", 0.5)
    telemetry.event("phase", "warmup_done", detail=1)
    snap = telemetry.snapshot()
    agg = snap["spans"]["unit.work"]
    assert agg["count"] == 2
    assert agg["total_ms"] >= agg["max_ms"] >= agg["min_ms"] >= 0
    assert snap["counters"]["unit.count"] == 4
    assert snap["gauges"]["unit.g"] == 0.5
    kinds = [(e["kind"], e["name"]) for e in snap["events"]]
    assert ("span", "unit.work") in kinds
    assert ("phase", "warmup_done") in kinds
    telemetry.reset()
    snap = telemetry.snapshot()
    assert not snap["spans"] and not snap["counters"] and not snap["events"]


def test_disabled_is_noop():
    with telemetry.disabled():
        assert not telemetry.enabled()
        with telemetry.span("off.work"):
            pass
        telemetry.inc("off.c")
        telemetry.gauge("off.g", 1)
        telemetry.event("off", "e")
        telemetry.record_compile("off.fn", {"shape": [1]})
    assert telemetry.enabled()
    snap = telemetry.snapshot()
    assert "off.work" not in snap["spans"]
    assert "off.c" not in snap["counters"]
    assert not snap["events"] and not snap["compiles"]


def test_journal_is_bounded():
    for i in range(telemetry.JOURNAL_MAXLEN + 50):
        telemetry.event("tick", "t%d" % i)
    snap = telemetry.snapshot(events=0)
    with telemetry._lock:
        assert len(telemetry._journal) == telemetry.JOURNAL_MAXLEN


# ---------------------------------------------------------------------------
# recompile detector
# ---------------------------------------------------------------------------

def test_forced_retrace_names_changed_axis():
    """The acceptance shape: the SAME jitted step called with a changed
    batch axis must journal a recompile event naming that axis."""
    net = _make_net()
    step = mx.parallel.DataParallelStep(
        net, gloss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.SGD(learning_rate=0.1), mesh=None)
    rs = onp.random.RandomState(0)
    x = mx.nd.array(rs.randn(4, 6).astype("float32"))
    y = mx.nd.array(rs.randint(0, 4, 4).astype("float32"))
    step(x, y)
    # same step, changed leading (batch) axis -> forced retrace
    x2 = mx.nd.array(rs.randn(8, 6).astype("float32"))
    y2 = mx.nd.array(rs.randint(0, 4, 8).astype("float32"))
    step(x2, y2)
    snap = telemetry.snapshot()
    # detector keys are per-instance (DataParallelStep[<id>]) so
    # unrelated steps' first compiles never read as retraces
    counts = [v for k, v in snap["compiles"].items()
              if k.startswith("DataParallelStep[")]
    assert counts == [2], snap["compiles"]
    rec = [e for e in snap["events"] if e["kind"] == "recompile"
           and e["name"].startswith("DataParallelStep[")]
    assert len(rec) == 1
    changed = rec[0]["changed"]
    assert any("data.shape[0]: 4 -> 8" in c for c in changed), changed
    # per-step spans recorded for both calls
    assert snap["spans"]["parallel.step"]["count"] == 2


def test_retrace_warning_fires(caplog):
    telemetry.record_compile("fn", {"shape": [2, 2]})
    telemetry.record_compile("fn", {"shape": [2, 3]})
    with caplog.at_level(logging.WARNING):
        changed = telemetry.record_compile("fn", {"shape": [2, 4]})
    assert changed == ["shape[1]: 3 -> 4"]
    assert any("compiled 3 times" in r.message and "shape[1]" in r.message
               for r in caplog.records)


def test_the_steps_own_placement_recompile_does_not_count_to_the_warning(
        caplog):
    """Every step compiles twice by design (its second call brings the
    carried ``t`` and ``rng`` back committed), so the third compile — an
    epoch's last partial batch, an evaluation batch size — is the FIRST
    real retrace and stays quiet; the next one warns, as it did when the
    detector saw the framework's own misses only."""
    net = _make_net()
    step = mx.parallel.DataParallelStep(
        net, gloss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.SGD(learning_rate=0.1), mesh=None)
    rs = onp.random.RandomState(0)

    def batch(n):
        return (mx.nd.array(rs.randn(n, 6).astype("float32")),
                mx.nd.array(rs.randint(0, 4, n).astype("float32")))

    with caplog.at_level(logging.WARNING):
        step(*batch(4))
        step(*batch(4))
        step(*batch(8))
    name, = [k for k in telemetry.compile_counts()
             if k.startswith("DataParallelStep[")]
    assert telemetry.compile_counts()[name] == 3
    assert not [r for r in caplog.records if "retrace" in r.message]
    with caplog.at_level(logging.WARNING):
        step(*batch(2))
    assert any("compiled 4 times" in r.message and "data.shape[0]" in
               r.message for r in caplog.records)


def test_only_the_first_placement_recompile_is_free(caplog):
    key = lambda committed: {"t": {"shape": [], "committed": committed,
                                   "sharding": None}}
    with caplog.at_level(logging.WARNING):
        telemetry.record_compile("fn", key(False))
        telemetry.record_compile("fn", key(True))
        telemetry.record_compile("fn", key(False))
    assert not caplog.records
    with caplog.at_level(logging.WARNING):
        telemetry.record_compile("fn", key(True))
    assert any("compiled 4 times" in r.message and "t.committed" in r.message
               for r in caplog.records)


def test_diff_keys_dtype_and_static_args():
    old = {"data": {"shape": [4, 6], "dtype": "float32"}, "mode": "call"}
    new = {"data": {"shape": [4, 6], "dtype": "bfloat16"}, "mode": "scan"}
    d = telemetry._diff_keys(old, new)
    assert "data.dtype: 'float32' -> 'bfloat16'" in d
    assert "mode: 'call' -> 'scan'" in d


# ---------------------------------------------------------------------------
# the acceptance run: 3-step Trainer over a prefetched feed
# ---------------------------------------------------------------------------

def test_trainer_run_snapshot_has_spans_ring_and_memory():
    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    L = gloss.SoftmaxCrossEntropyLoss()
    feed = _float_feed(n_batches=3)
    steps = 0
    fused = _count_backend_compiles("jit(fused)")
    for batch in feed:
        with autograd.record():
            loss = L(net(batch.data[0]), batch.label[0])
        loss.backward()
        trainer.step(batch.data[0].shape[0])
        steps += 1
    feed.close()
    assert steps == 3
    snap = telemetry.snapshot()
    # step spans
    assert snap["spans"]["trainer.step"]["count"] == 3
    assert snap["spans"]["trainer.step"]["mean_ms"] > 0
    # prefetch ring gauges + stage timings
    assert "prefetch.ring_occupancy" in snap["gauges"]
    assert snap["gauges"]["prefetch.ring_depth"] == 2
    assert snap["counters"]["prefetch.batches"] == 3
    assert snap["spans"]["prefetch.host"]["count"] == 3
    assert snap["spans"]["prefetch.ship"]["count"] == 3
    # memory gauge sampled at the trainer.step span boundary
    assert snap["gauges"]["mem.host_rss_bytes"] > 0
    # the detector counts what jax compiled, its own retraces of the
    # cached update included (the second call arrives with committed
    # outputs), and names the leaves that moved: no blind spot, no storm
    assert [v for k, v in snap["compiles"].items()
            if k.startswith("FusedUpdate[")] == [len(fused)]
    assert 1 <= len(fused) <= 2
    retraces = [e for e in snap["events"] if e["kind"] == "recompile"
                and e["name"].startswith("FusedUpdate[")]
    assert len(retraces) == len(fused) - 1
    for rec in retraces:
        assert any(".committed: " in c or ".sharding: " in c
                   for c in rec["changed"]), rec


# ---------------------------------------------------------------------------
# step hooks: Monitor / Speedometer without loop plumbing
# ---------------------------------------------------------------------------

def _run_steps(net, trainer, n=2, bs=4):
    L = gloss.SoftmaxCrossEntropyLoss()
    rs = onp.random.RandomState(0)
    x = mx.nd.array(rs.randn(bs, 6).astype("float32"))
    y = mx.nd.array(rs.randint(0, 4, bs).astype("float32"))
    for _ in range(n):
        with autograd.record():
            loss = L(net(x), y)
        loss.backward()
        trainer.step(bs)


def test_monitor_attach_pattern_filtering(caplog):
    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    mon = mx.monitor.Monitor(interval=1, pattern=".*weight.*",
                             monitor_all=True).attach(trainer)
    try:
        with caplog.at_level(logging.INFO):
            _run_steps(net, trainer, n=2)
    finally:
        mon.detach()
    logged = [r.message for r in caplog.records if "Batch:" in r.message]
    assert logged, "attached monitor never fired"
    assert any("weight" in m and "_grad" in m for m in logged)
    assert any("weight" in m and "_grad" not in m for m in logged)
    assert not any("bias" in m for m in logged)


def test_monitor_attach_monitor_all_false(caplog):
    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    mon = mx.monitor.Monitor(interval=1, pattern=".*",
                             monitor_all=False).attach(trainer)
    try:
        with caplog.at_level(logging.INFO):
            _run_steps(net, trainer, n=1)
    finally:
        mon.detach()
    logged = [r.message for r in caplog.records if "Batch:" in r.message]
    assert logged
    assert not any("_grad" in m for m in logged)
    assert any("bias" in m for m in logged)   # pattern .* includes biases


def test_monitor_attach_interval():
    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    seen = []
    mon = mx.monitor.Monitor(interval=2, pattern=".*weight.*")
    orig = mon._collect_trainer
    mon._collect_trainer = lambda t, i: seen.append(i) or orig(t, i)
    mon.attach(trainer)
    try:
        _run_steps(net, trainer, n=4)
    finally:
        mon.detach()
    assert seen == [0, 2]   # interval=2: steps 0 and 2 are due


def test_speedometer_attach_emits_telemetry_line(caplog):
    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    spd = mx.callback.Speedometer(batch_size=4, frequent=2).attach()
    spd.set_epoch(3)     # trainer steps carry no epoch; the loop sets it
    try:
        with caplog.at_level(logging.INFO):
            _run_steps(net, trainer, n=5)
    finally:
        spd.detach()
    lines = [r.getMessage() for r in caplog.records
             if "samples/sec" in r.getMessage()]
    assert lines, "speedometer never logged"
    # telemetry-enriched format: step span time rides on the line
    assert any("step-ms=" in ln for ln in lines)
    assert all(ln.startswith("Epoch[3]") for ln in lines)


def test_step_hook_failure_does_not_break_training(caplog):
    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})

    def bad_hook(rec):
        raise RuntimeError("observer bug")
    telemetry.add_step_hook(bad_hook)
    try:
        with caplog.at_level(logging.ERROR):
            _run_steps(net, trainer, n=1)    # must not raise
    finally:
        telemetry.remove_step_hook(bad_hook)
    assert any("step hook" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_export_jsonl_and_streaming_sink(tmp_path):
    stream = tmp_path / "stream.jsonl"
    telemetry.set_jsonl_sink(str(stream))
    with telemetry.span("exp.step"):
        pass
    telemetry.inc("exp.count", 2)
    telemetry.record_compile("exp.fn", {"shape": [4]})
    telemetry.record_compile("exp.fn", {"shape": [8]})
    telemetry.set_jsonl_sink(None)
    streamed = [json.loads(ln) for ln in
                stream.read_text().strip().splitlines()]
    assert any(r["kind"] == "span" and r["name"] == "exp.step"
               for r in streamed)
    assert any(r["kind"] == "recompile" for r in streamed)

    dump = tmp_path / "dump.jsonl"
    telemetry.export_jsonl(str(dump))
    recs = [json.loads(ln) for ln in
            dump.read_text().strip().splitlines()]
    snap_rec = [r for r in recs if r["kind"] == "snapshot"]
    assert len(snap_rec) == 1
    assert snap_rec[0]["counters"]["exp.count"] == 2
    assert snap_rec[0]["spans"]["exp.step"]["count"] == 1


# ---------------------------------------------------------------------------
# trace contexts (ISSUE 18): trace ids, sid/parent chains, rank stamps
# ---------------------------------------------------------------------------

def test_trace_ids_unique_and_pid_qualified():
    import os
    ids = {telemetry.new_trace_id() for _ in range(64)}
    assert len(ids) == 64
    assert all("%x" % (os.getpid() & 0xffffff) in i.split("-")[1]
               for i in ids)


def test_trace_context_stamps_events_and_spans():
    with telemetry.trace() as tr:
        assert telemetry.current_trace() == tr.trace_id
        telemetry.event("unit", "inside")
        with telemetry.span("unit.outer"):
            with telemetry.span("unit.inner"):
                pass
    assert telemetry.current_trace() is None
    telemetry.event("unit", "outside")
    recs = telemetry.snapshot()["events"]
    inside = [r for r in recs if r.get("name") == "inside"]
    outside = [r for r in recs if r.get("name") == "outside"]
    assert inside[0]["trace"] == tr.trace_id
    assert "trace" not in outside[0]
    spans = {r["name"]: r for r in recs if r["kind"] == "span"}
    assert spans["unit.outer"]["trace"] == tr.trace_id
    assert spans["unit.inner"]["trace"] == tr.trace_id
    # the sid/parent chain links inner -> outer causally
    assert spans["unit.inner"]["parent"] == spans["unit.outer"]["sid"]
    assert spans["unit.outer"].get("parent") is None


def test_trace_join_if_active_vs_explicit_reenter():
    with telemetry.trace() as outer:
        # no id + active trace: JOIN (same id, and exit must not
        # tear down the outer context)
        with telemetry.trace() as joined:
            assert joined.trace_id == outer.trace_id
        assert telemetry.current_trace() == outer.trace_id
    # explicit id always activates (the serve worker-thread re-enter)
    with telemetry.trace("req-42") as tr:
        assert tr.trace_id == "req-42"
        telemetry.event("unit", "reentered")
    recs = telemetry.snapshot()["events"]
    assert any(r.get("trace") == "req-42" for r in recs
               if r.get("name") == "reentered")


def test_rank_stamped_on_every_record():
    telemetry.set_rank(3)
    try:
        telemetry.event("unit", "ranked")
        with telemetry.span("unit.r"):
            pass
    finally:
        telemetry.set_rank(None)
    recs = telemetry.snapshot()["events"]
    assert all(r.get("rank") == 3 for r in recs
               if r.get("name") in ("ranked", "unit.r"))


def test_span_event_carries_explicit_trace_and_histogram():
    telemetry.span_event("unit.cross", 0.005, trace="t-1",
                         parent=7, hist=True, bucket=4)
    recs = telemetry.snapshot()["events"]
    rec = [r for r in recs if r.get("name") == "unit.cross"][0]
    assert rec["trace"] == "t-1" and rec["parent"] == 7
    assert rec["bucket"] == 4
    assert telemetry.snapshot()["spans"]["unit.cross"]["count"] == 1
    assert telemetry.histogram("unit.cross").count == 1


# ---------------------------------------------------------------------------
# online histograms: log-bucketed, fixed memory, mergeable
# ---------------------------------------------------------------------------

def test_histogram_quantiles_track_exact_within_bucket_error():
    import math
    rs = onp.random.RandomState(7)
    samples = onp.exp(rs.randn(5000) * 1.5 + 1.0)   # lognormal ms
    h = telemetry.Histogram()
    for v in samples:
        h.add(float(v))
    s = onp.sort(samples)
    for q in (0.5, 0.9, 0.99):
        exact = float(s[int(q * len(s)) - 1])
        est = h.quantile(q)
        # bucket ratio is 10**(1/10) ~ 1.26; allow 2 bucket widths
        assert abs(math.log10(est) - math.log10(exact)) < 0.2, \
            (q, est, exact)
    assert h.min == float(samples.min())
    assert h.max == float(samples.max())


def test_histogram_memory_is_fixed():
    h = telemetry.Histogram()
    h.add(1.0)
    n_after_10 = len(h.buckets)
    for v in range(10000):
        h.add(float(v) + 0.5)
    assert len(h.buckets) == n_after_10 == telemetry.Histogram.NBUCKETS
    assert h.count == 10001


def test_histogram_merge_and_roundtrip():
    a, b = telemetry.Histogram(), telemetry.Histogram()
    for v in (1.0, 2.0, 3.0):
        a.add(v)
    for v in (100.0, 200.0):
        b.add(v)
    merged = telemetry.Histogram.from_dict(a.to_dict()).merge(b)
    assert merged.count == 5
    assert merged.min == 1.0 and merged.max == 200.0
    assert merged.quantile(0.5) < 100.0 <= merged.quantile(0.95)
    # geometry mismatch is a loud error, not silent bucket garbage
    bad = a.to_dict()
    bad["bpd"] = 5
    with pytest.raises(ValueError):
        telemetry.Histogram.from_dict(bad)


def test_histogram_since_carves_a_leg():
    h = telemetry.Histogram()
    for v in (1.0, 2.0, 4.0):
        h.add(v)
    base = h.to_dict()
    for v in (50.0, 60.0, 70.0, 80.0):
        h.add(v)
    leg = h.since(base)
    assert leg.count == 4
    assert 40.0 < leg.quantile(0.5) < 100.0


def test_span_hist_feeds_named_histogram():
    with telemetry.span("unit.h", hist=True):
        pass
    with telemetry.span("unit.h", hist=True):
        pass
    h = telemetry.histogram("unit.h")
    assert h is not None and h.count == 2
    snap = telemetry.snapshot()
    assert snap["histograms"]["unit.h"]["count"] == 2


def test_export_jsonl_snapshot_carries_histograms(tmp_path):
    telemetry.hist_observe("exp.h", 5.0)
    dump = tmp_path / "dump.jsonl"
    telemetry.export_jsonl(str(dump))
    recs = [json.loads(ln) for ln in
            dump.read_text().strip().splitlines()]
    snap_rec = [r for r in recs if r["kind"] == "snapshot"][0]
    assert snap_rec["histograms"]["exp.h"]["count"] == 1
    # full mergeable form, not just the summary
    assert "buckets" in snap_rec["histograms"]["exp.h"]


# ---------------------------------------------------------------------------
# retrace-warning dedupe: one warning per (instance, changed-key family)
# ---------------------------------------------------------------------------

def test_retrace_warning_dedupes_per_key_family(caplog):
    with caplog.at_level(logging.WARNING):
        for n in (2, 4, 8, 16):
            telemetry.record_compile("fam.fn", {"shape": [2, n]})
    warns = [r for r in caplog.records if "retrace" in r.message
             and "fam.fn" in r.message]
    assert len(warns) == 1, [r.message for r in warns]
    # a DIFFERENT changed-key family on the same instance warns again
    with caplog.at_level(logging.WARNING):
        telemetry.record_compile("fam.fn", {"shape": [2, 16],
                                            "dtype": "bf16"})
    warns = [r for r in caplog.records if "retrace" in r.message
             and "fam.fn" in r.message]
    assert len(warns) == 2, [r.message for r in warns]
    # every retrace still journals an event (dedupe is log-side only)
    evs = [e for e in telemetry.snapshot()["events"]
           if e["kind"] == "recompile"]
    assert len(evs) == 4


def test_sync_clock_journals_reference_pair():
    class FakeKV:
        def __init__(self):
            self.kv = {}

        def key_value_set(self, k, v):
            self.kv[k] = v

        def blocking_key_value_get(self, k, timeout_ms):
            return self.kv[k]

    kv = FakeKV()
    ref0 = telemetry.sync_clock(kv, 0, key="t/clock")
    ref1 = telemetry.sync_clock(kv, 1, key="t/clock")
    assert ref0 is not None and abs(ref1 - ref0) < 1e-6
    clocks = [e for e in telemetry.snapshot()["events"]
              if e["kind"] == "clock"]
    assert len(clocks) == 2
    for e in clocks:
        assert e["local_wall"] is not None
        assert e["ref_wall"] is not None


# ---------------------------------------------------------------------------
# attention dispatch census
# ---------------------------------------------------------------------------

def test_attention_dispatch_counted():
    from mxnet_tpu.ops.pallas_attention import attention_dispatch
    plan = attention_dispatch(8, 8, 64, "float32", on_tpu=False)
    assert plan["kernel"] == "dense_fallback"
    assert telemetry.counter("attention.kernel.dense_fallback") == 1
    plan = attention_dispatch(2048, 2048, 64, "bfloat16", on_tpu=True)
    assert telemetry.counter("attention.kernel.%s" % plan["kernel"]) == 1
    snap = telemetry.snapshot()
    evs = [e for e in snap["events"] if e["kind"] == "attention_dispatch"]
    assert evs and evs[-1]["seq_q"] == 2048


# ---------------------------------------------------------------------------
# jax's own compile events, booked to the span they fire under (ISSUE 35)
# ---------------------------------------------------------------------------

def _compile_fresh(name="probe_fn"):
    """Hand jax a program it has not compiled in this process: a new
    function object misses ``jax.jit``'s in-memory cache whatever the
    persistent one holds, so exactly one backend event fires."""
    import jax
    import jax.numpy as jnp

    def fn(x):
        return x * 2.0 + 1.0
    fn.__name__ = fn.__qualname__ = name
    x = jnp.ones((3,), jnp.float32)      # its own small programs first
    before = telemetry.compile_totals()
    jax.jit(fn)(x).block_until_ready()
    return before


def _programs(totals, owner):
    return totals.get(owner, {}).get("programs", 0)


@pytest.mark.parametrize("span_name,fun_name,owner", [
    ("unit.build", "probe_fn", "unit.build"),
    (None, "probe_fn", "eager"),
    ("parallel.step.call", "step_fn", "parallel.step.call"),
])
def test_a_compile_is_booked_to_the_innermost_open_span(span_name, fun_name,
                                                        owner):
    if span_name is None:
        before = _compile_fresh(fun_name)
    else:
        with telemetry.span("unit.outer"):
            with telemetry.span(span_name):
                before = _compile_fresh(fun_name)
    totals = telemetry.compile_totals()
    assert _programs(totals, owner) == _programs(before, owner) + 1
    assert sum(t["programs"] for t in totals.values()) \
        == sum(t["programs"] for t in before.values()) + 1
    mine = totals[owner]
    assert set(mine) == {"programs", "trace_s", "lower_s", "backend_s",
                         "cache_hits", "cache_misses"}
    assert mine["trace_s"] > 0 and mine["lower_s"] > 0 \
        and mine["backend_s"] > 0
    snap = telemetry.snapshot()
    assert snap["compile_totals"] == totals
    # a program compiled under a span is one journal record; the
    # op-by-op programs outside every span are counted only
    recs = [e for e in snap["events"] if e["kind"] == "xla_compile"]
    if owner == "eager":
        assert not recs
    else:
        rec = recs[-1]
        assert rec["name"] == "jit(%s)" % fun_name \
            and rec["owner"] == owner
        assert rec["cache"] in ("hit", "miss", "off")
        assert rec["trace_s"] > 0 and rec["lower_s"] > 0 \
            and rec["backend_s"] > 0
    # the span closed: the next compile on this thread is eager again
    assert getattr(telemetry._tls, "name", None) is None


def test_disabled_books_no_compile():
    with telemetry.disabled():
        _compile_fresh()
        assert telemetry.compile_totals() == {}
        seq = telemetry.thread_compiles().seq
        _compile_fresh()
        assert telemetry.thread_compiles().seq == seq
    snap = telemetry.snapshot()
    assert snap["compile_totals"] == {}
    assert not [e for e in snap["events"] if e["kind"] == "xla_compile"]


def test_the_listeners_register_once():
    from jax._src import monitoring
    for _ in range(3):
        telemetry.enable()
        telemetry._listen()
        with telemetry.disabled():
            telemetry._listen()
    assert monitoring.get_event_duration_listeners().count(
        telemetry._on_jax_duration) == 1
    assert monitoring.get_event_listeners().count(
        telemetry._on_jax_event) == 1
    before = _compile_fresh()
    after = telemetry.compile_totals()
    assert _programs(after, "eager") == _programs(before, "eager") + 1


def test_reset_clears_the_compile_totals(tmp_path):
    _compile_fresh()
    assert telemetry.compile_totals()["eager"]["programs"] >= 1
    # the exporter and the flight recorder carry the totals as they are
    path = telemetry.export_jsonl(str(tmp_path / "t.jsonl"))
    with open(path) as f:
        last = json.loads(f.read().strip().splitlines()[-1])
    assert last["kind"] == "snapshot"
    assert last["compile_totals"] == telemetry.compile_totals()
    telemetry.reset()
    assert telemetry.compile_totals() == {}
    assert telemetry.snapshot()["compile_totals"] == {}


def test_a_backend_event_uses_up_what_waited_for_it():
    """Trace and lowering seconds wait on the thread for their backend
    event and go with it: a later program that jax compiles without
    tracing (another committedness of the same avals) pairs none of
    them."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: x + 1.0)
    x = jnp.ones((5,), jnp.float32)
    fn(x)
    state = telemetry.thread_compiles()
    first, seq = state.last, state.seq
    assert state.trace == {} and state.lower is None \
        and state.cache == "off"
    fn(jax.device_put(x, jax.devices()[0]))     # committed now
    assert state.seq == seq + 1 and state.last is not first
    assert state.last["name"] == first["name"] == "jit(<lambda>)"
    assert state.last["lower_s"] > 0 and state.last["backend_s"] > 0
    assert state.trace == {} and state.lower is None
