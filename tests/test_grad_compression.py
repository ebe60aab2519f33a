"""Compressed gradient collectives on the ZeRO wire (int8 / fp8-e4m3).

Covers the tentpole contract (docs/PERF.md "Compressed gradient
collectives"): per-chunk symmetric quantization with error-feedback
residuals tracks the uncompressed sharded update within the parity
band, the residual rides as the LAST dp-sharded state leaf and
round-trips BITWISE through elastic reshard and checkpoint restore,
the knob has one rule for both builders
(``compression.resolve_grad_compression``), the 1-device degenerate
quietly disables (journaled), the compressed
leg stays finite/drift-free under NumericsSanitizer, the
``grad_compress_corrupt`` chaos fault is caught as non-finite params,
and the ``compress/decision`` census round-trips through
``tools/parse_log.py --jsonl``.
"""
import os

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import mxnet_tpu as mx
from mxnet_tpu import checkpoint, gluon, parallel, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.parallel import chaos
from mxnet_tpu.parallel import compression as comp
from mxnet_tpu.parallel.elastic import ElasticContext


@pytest.fixture
def mesh8():
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
    m = parallel.device_mesh((8,), ("dp",))
    old = parallel.get_mesh()
    parallel.set_mesh(m)
    yield m
    parallel.set_mesh(old)


# 9 in / 7 hidden: every leaf size is coprime with the 8-way dp axis,
# so the residual leaf exercises the zero-padded flat layout too
_X = onp.random.RandomState(0).randn(16, 9).astype("float32")
_Y = onp.random.RandomState(1).randint(0, 4, 16).astype("float32")


def _build_step(mesh, compress, optimizer=None, bf16=False, shard=True):
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
                                     mesh=mesh, shard_optimizer=shard,
                                     grad_compression=compress)
    return net, step


def _run(step, k):
    return [float(step(mx.nd.array(_X), mx.nd.array(_Y)).asscalar())
            for _ in range(k)]


def _canonical_slots(st):
    """Slot indices in the net's graph order — two steps' name-sorted
    slot orders can differ when gluon's auto-naming counters straddle a
    digit boundary (the hazard checkpoint_state keys around)."""
    order = st._param_order()
    rank = {pi: k for k, pi in enumerate(order)}
    return sorted(range(len(st._opt_states)),
                  key=lambda s: rank[st._trainable[s]])


def _last_decision():
    evs = [e for e in telemetry.snapshot(events=256)["events"]
           if e.get("kind") == "compress" and e.get("name") == "decision"]
    return evs[-1] if evs else None


# ---------------------------------------------------------------------------
# pure wire math (no mesh)
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_error_bound_and_wire_math():
    rs = onp.random.RandomState(5)
    flat = jnp.asarray(rs.randn(1000).astype("float32"))
    for mode in comp.MODES:
        q, scales = comp.quantize_chunked(flat, mode)
        assert q.shape == (comp.num_chunks(1000), comp.CHUNK)
        assert scales.shape == (comp.num_chunks(1000),)
        back = comp.dequantize_chunked(q, scales, 1000)
        assert back.shape == (1000,)
        # per-element error bound: int8 is absolute (one integer code
        # step per chunk scale); fp8-e4m3 keeps 3 mantissa bits, so
        # its error is RELATIVE (~2^-3 worst case) plus the chunk-
        # scale floor in the near-zero region
        err = onp.abs(onp.asarray(back) - onp.asarray(flat))
        step = onp.repeat(onp.asarray(scales), comp.CHUNK)[:1000]
        bound = step if mode == "int8" \
            else onp.abs(onp.asarray(flat)) * 0.13 + step
        assert (err <= bound + 1e-7).all(), (mode, err.max())
        # zeros survive the round trip exactly (the pad-lane contract
        # the bitwise reshard of residuals rests on)
        zq, zs = comp.quantize_chunked(jnp.zeros((300,), jnp.float32),
                                       mode)
        onp.testing.assert_array_equal(
            onp.asarray(comp.dequantize_chunked(zq, zs, 300)), 0.0)
    # payload is exactly 4x narrower; scales accounted separately
    assert comp.wire_bytes(1000, None) == 4000
    assert comp.wire_bytes(1000, "int8") == 1000
    assert comp.wire_bytes(1000, "fp8") == 1000
    assert comp.wire_ratio(1000, "int8") == 4.0
    assert comp.scale_bytes(1000, "int8") == 4 * comp.num_chunks(1000)
    assert comp.scale_bytes(1000, None) == 0
    with pytest.raises(ValueError):
        comp.quantize_chunked(flat, "int4")
    with pytest.raises(ValueError):
        comp.wire_bytes(10, "int4")


def test_compress_decompose_error_feedback_exact():
    """v + new_residual == comp exactly in f32: the residual carries
    the WHOLE quantization error forward, nothing is dropped."""
    rs = onp.random.RandomState(6)
    v0 = jnp.asarray(rs.randn(500).astype("float32"))
    for mode in comp.MODES:
        v, res = comp.compress_decompose(v0, mode)
        assert v.dtype == v0.dtype and res.dtype == v0.dtype
        onp.testing.assert_allclose(
            onp.asarray(v) + onp.asarray(res), onp.asarray(v0),
            rtol=0, atol=1e-6)
        assert onp.abs(onp.asarray(res)).max() > 0  # lossy, error real
    # the chaos seam: a non-finite corrupt factor poisons chunk 0
    bad, _ = comp.compress_decompose(v0, "int8",
                                     corrupt=jnp.asarray(onp.inf))
    assert not onp.isfinite(onp.asarray(bad)[:comp.CHUNK]).all()


# ---------------------------------------------------------------------------
# training parity + residual layout (8-way dp mesh)
# ---------------------------------------------------------------------------

def test_compressed_matches_uncompressed_k_steps(mesh8):
    """int8 and fp8 legs track the uncompressed sharded run within the
    parity band; the residual rides as one EXTRA flat dp-sharded leaf
    appended last."""
    net_a, st_a = _build_step(mesh8, None)
    losses = {None: _run(st_a, 5)}
    for mode in comp.MODES:
        net_b, st_b = _build_step(mesh8, mode)
        assert st_b._compress == mode
        losses[mode] = _run(st_b, 5)
        # SGD-momentum: 1 base leaf + the residual, both flat + sharded
        for slot, leaves in enumerate(st_b._opt_states):
            assert len(leaves) == len(st_a._opt_states[slot]) + 1
            res = leaves[-1]
            assert res.ndim == 1 and res.shape[0] % 8 == 0
            assert res.addressable_shards[0].data.shape[0] \
                == res.shape[0] // 8
        # error feedback really engaged: the residual is nonzero
        assert any(onp.abs(st_b._materialize_slot(s)[-1]).max() > 0
                   for s in range(len(st_b._opt_states)))
        d = onp.abs(onp.asarray(losses[mode]) -
                    onp.asarray(losses[None])).max()
        assert d < 1e-2, (mode, d)
        for (ka, pa), (_, pb) in zip(
                net_a.collect_params().items(),
                net_b.collect_params().items()):
            onp.testing.assert_allclose(pa.data().asnumpy(),
                                        pb.data().asnumpy(),
                                        rtol=5e-2, atol=5e-3,
                                        err_msg="%s/%s" % (mode, ka))


def test_compressed_scan_steps_matches_per_call(mesh8):
    """k compressed steps through one lax.scan == k per-call compressed
    steps (the residual is a donated scan carry like any state leaf)."""
    xs = onp.random.RandomState(3).randn(3, 16, 9).astype("float32")
    ys = onp.random.RandomState(4).randint(0, 4, (3, 16)).astype(
        "float32")
    net_a, st_a = _build_step(mesh8, "int8")
    net_b, st_b = _build_step(mesh8, "int8")
    scanned = st_a.scan_steps(mx.nd.array(xs), mx.nd.array(ys))
    seq = [float(st_b(mx.nd.array(x), mx.nd.array(y)).asscalar())
           for x, y in zip(xs, ys)]
    # scan and per-call are DIFFERENT XLA programs: reduction
    # partitioning varies with thread-pool state, and a one-ulp f32
    # difference landing on a quantization bucket boundary is amplified
    # by error feedback to ~scale/127 per step — band the comparison at
    # bucket level, not float level (the bitwise guarantees live on the
    # reshard/checkpoint path, which moves bytes, never re-quantizes)
    onp.testing.assert_allclose(scanned.asnumpy(), seq, rtol=1e-2,
                                atol=1e-3)
    for qa, qb in zip(_canonical_slots(st_a), _canonical_slots(st_b)):
        ra = onp.asarray(st_a._materialize_slot(qa)[-1])
        rb = onp.asarray(st_b._materialize_slot(qb)[-1])
        assert onp.any(ra != 0.0), "scan dropped the residual carry"
        onp.testing.assert_allclose(ra, rb, rtol=0.0, atol=1e-2)


def test_multi_precision_residual_dtype_and_parity(mesh8):
    """bf16 + Adam + multi_precision: the residual leaf is f32 (it
    compensates the f32 master update, not the bf16 weight) and the
    compressed mp run tracks the uncompressed mp run."""
    mk = lambda: mx.optimizer.Adam(learning_rate=2e-2,  # noqa: E731
                                   multi_precision=True)
    net_a, st_a = _build_step(mesh8, None, optimizer=mk, bf16=True)
    net_b, st_b = _build_step(mesh8, "int8", optimizer=mk, bf16=True)
    assert all(st_b._mp_slots)
    for leaves in st_b._opt_states:
        assert str(leaves[-1].dtype) == "float32"
    la = _run(st_a, 5)
    lb = _run(st_b, 5)
    assert onp.abs(onp.asarray(la) - onp.asarray(lb)).max() < 5e-2
    for _, p in net_b.collect_params().items():
        assert p.data().dtype == onp.dtype("bfloat16")


# ---------------------------------------------------------------------------
# residual migration: elastic reshard + checkpoint, bitwise
# ---------------------------------------------------------------------------

def test_residual_bitwise_through_reshard_and_checkpoint(mesh8,
                                                         tmp_path):
    """The acceptance headline: residual-carrying state re-shards 8->4
    bitwise and round-trips through CheckpointManager bitwise — byte
    movement only, never arithmetic — and training continues finite on
    both paths."""
    net_a, st_a = _build_step(mesh8, "int8")
    _run(st_a, 3)
    checkpoint.CheckpointManager(str(tmp_path), st_a,
                                 async_write=False).save()
    res_before = [st_a._materialize_slot(s)[-1].copy()
                  for s in range(len(st_a._opt_states))]

    # checkpoint restore into a fresh compressed step: every leaf,
    # residual included, bitwise
    net_b, st_b = _build_step(mesh8, "int8")
    assert checkpoint.restore_latest(str(tmp_path), st_b) == 3
    for qa, qb in zip(_canonical_slots(st_a), _canonical_slots(st_b)):
        onp.testing.assert_array_equal(res_before[qa],
                                       st_b._materialize_slot(qb)[-1])
    assert onp.isfinite(_run(st_b, 1)[0])

    # elastic 8->4 reshard of the original: residual bitwise, layout
    # still compressed at the new extent
    ElasticContext(st_a, liveness=lambda: 0).reform(
        devices=jax.devices()[:4])
    assert st_a._shard_n == 4 and st_a._compress == "int8"
    for s, before in enumerate(res_before):
        onp.testing.assert_array_equal(before,
                                       st_a._materialize_slot(s)[-1])
    leaf = st_a._opt_states[0][-1]
    assert leaf.shape[0] % 4 == 0
    assert leaf.addressable_shards[0].data.shape[0] == leaf.shape[0] // 4
    assert onp.isfinite(_run(st_a, 1)[0])


def test_uncompressed_checkpoint_restores_into_compressed(mesh8,
                                                          tmp_path):
    """_place_slot reconciliation: a residual-less (uncompressed)
    checkpoint restores into a compressed layout — base leaves bitwise,
    residual restarts at zero — and the reverse direction drops the
    residual cleanly."""
    net_a, st_a = _build_step(mesh8, None)
    _run(st_a, 3)
    checkpoint.CheckpointManager(str(tmp_path / "plain"), st_a,
                                 async_write=False).save()
    net_b, st_b = _build_step(mesh8, "int8")
    assert checkpoint.restore_latest(str(tmp_path / "plain"), st_b) == 3
    for qa, qb in zip(_canonical_slots(st_a), _canonical_slots(st_b)):
        nat_a = st_a._materialize_slot(qa)
        nat_b = st_b._materialize_slot(qb)
        assert len(nat_b) == len(nat_a) + 1
        for la, lb in zip(nat_a, nat_b):
            onp.testing.assert_array_equal(la, lb)
        onp.testing.assert_array_equal(nat_b[-1], 0.0)
    assert onp.isfinite(_run(st_b, 1)[0])

    # compressed checkpoint -> uncompressed layout: residual dropped
    checkpoint.CheckpointManager(str(tmp_path / "comp"), st_b,
                                 async_write=False).save()
    net_c, st_c = _build_step(mesh8, None)
    checkpoint.restore_latest(str(tmp_path / "comp"), st_c)
    for qb, qc in zip(_canonical_slots(st_b), _canonical_slots(st_c)):
        assert len(st_c._materialize_slot(qc)) \
            == len(st_b._materialize_slot(qb)) - 1
    assert onp.isfinite(_run(st_c, 1)[0])


# ---------------------------------------------------------------------------
# knob resolution: degenerate layouts, validation, journal
# ---------------------------------------------------------------------------

def test_one_device_degenerate_disables_and_journals():
    mesh1 = parallel.device_mesh((1,), ("dp",),
                                 devices=jax.devices()[:1])
    old = parallel.get_mesh()
    parallel.set_mesh(mesh1)
    try:
        telemetry.reset()
        net, st = _build_step(mesh1, "int8")
        assert st._compress == ""
        ev = _last_decision()
        assert ev and ev["mode"] == "off" and ev["path"] == "disabled"
        assert ev["requested"] == "int8" and ev["dp"] == 1
        # no residual leaf, training still works
        _run(st, 2)
        # shard_optimizer off entirely: same quiet disable
        _, st2 = _build_step(mesh1, "fp8", shard=False)
        assert st2._compress == ""
    finally:
        parallel.set_mesh(old)
        telemetry.reset()


def test_invalid_knob_rejected_eagerly(mesh8):
    with pytest.raises(ValueError, match="grad_compression"):
        _build_step(mesh8, "int4")
    from mxnet_tpu.gluon.trainer import _FusedUpdate
    with pytest.raises(ValueError, match="grad_compression"):
        _FusedUpdate(None, grad_compression="2bit")


@pytest.mark.parametrize("knob,want", [
    (None, ""), ("off", ""), ("int8", "int8"), ("fp8", "fp8"),
    ("auto", ValueError), ("int4", ValueError)], ids=repr)
def test_grad_compression_knob_has_one_rule(mesh8, knob, want):
    """``compression.resolve_grad_compression`` is the one rule for
    ``DataParallelStep`` and ``Trainer``: a wire mode or off, nothing
    else — ``"auto"`` had only a cost table to ask and went with it."""
    from mxnet_tpu.gluon.trainer import _FusedUpdate
    if want is ValueError:
        for build in (lambda: comp.resolve_grad_compression(knob, 8),
                      lambda: _build_step(mesh8, knob),
                      lambda: _FusedUpdate(None, shard_optimizer=True,
                                           grad_compression=knob)):
            with pytest.raises(ValueError, match="grad_compression"):
                build()
        return
    assert comp.resolve_grad_compression(knob, 8) == want
    assert comp.resolve_grad_compression(knob, 1) == ""
    net, st = _build_step(mesh8, knob)
    assert st._compress == want
    fused = _FusedUpdate(None, shard_optimizer=True,
                         grad_compression=knob)
    weights = [parallel.replicate(p.data(), mesh8)
               for _, p in net.collect_params().items()]
    assert fused._shard_ready(weights) and fused._compress == want


def test_decision_event_and_gauges(mesh8):
    telemetry.reset()
    _, st = _build_step(mesh8, "fp8")
    ev = _last_decision()
    pcount = 9 * 7 + 7 + 7 * 4 + 4
    assert ev["mode"] == "fp8" and ev["path"] == "forced"
    assert ev["dp"] == 8 and ev["params"] == pcount
    assert ev["dtype"] == "float32"
    assert ev["f32_bytes"] == 4 * pcount
    assert ev["wire_bytes"] == pcount and ev["ratio"] == 4.0
    assert ev["scale_bytes"] == 4 * comp.num_chunks(pcount)
    # the layout report refines the gauges per LEAF (each leaf gets
    # its own chunked scale tensor; the decision event's one-flat-
    # buffer arithmetic is the pre-layout estimate)
    snap = telemetry.snapshot()
    n_leaves = len(st._opt_states)
    scale = 4 * n_leaves            # every probe leaf is < one chunk
    assert snap["gauges"]["compression.scale_bytes"] == scale
    assert snap["gauges"]["compression.bytes_saved"] \
        == 4 * pcount - pcount - scale
    zev = [e for e in telemetry.snapshot(events=64)["events"]
           if e.get("kind") == "zero"
           and e.get("name") == "shard_optimizer"][-1]
    assert zev["grad_compression"] == "fp8"
    assert zev["compressed_wire_bytes"] == pcount
    assert zev["compression_scale_bytes"] == scale
    telemetry.reset()


# ---------------------------------------------------------------------------
# sanitizer + chaos: the compressed leg's runtime numerics contract
# ---------------------------------------------------------------------------

def test_chaos_corrupt_scale_caught_as_nonfinite(mesh8):
    """grad_compress_corrupt fires on the armed step: the poisoned
    chunk-0 scale blasts the params non-finite, exactly the signal
    NumericsSanitizer polices (the --audit-chaos installing test)."""
    import sys
    sys.path.insert(0, REPO) if REPO not in sys.path else None
    from tools.lint.runtime_numerics import NumericsSanitizer
    chaos.clear()
    # the dispatch consults with a 1-based step counter
    chaos.install("grad_compress_corrupt", at_step=2, times=1)
    try:
        net, st = _build_step(mesh8, "int8")
        _run(st, 1)                   # step 1: fault not armed yet
        ok = onp.concatenate(
            [p.data().asnumpy().ravel()
             for _, p in net.collect_params().items()])
        assert onp.isfinite(ok).all()
        _run(st, 1)                   # step 2: fires
        assert chaos.fired("grad_compress_corrupt") == 1
        bad = onp.concatenate(
            [p.data().asnumpy().ravel()
             for _, p in net.collect_params().items()])
        assert not onp.isfinite(bad).all()
        san = NumericsSanitizer()
        for k, p in net.collect_params().items():
            san.observe("param:%s" % k, p.data(), role="param", step=2)
        with pytest.raises(AssertionError):
            san.assert_all_finite()
    finally:
        chaos.clear()


# ---------------------------------------------------------------------------
# Trainer (_FusedUpdate) compressed path
# ---------------------------------------------------------------------------

def _trainer_setup(mesh, compress):
    onp.random.seed(42)
    mx.random.seed(42)
    net = nn.HybridSequential()
    net.add(nn.Dense(7, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(mx.nd.array(_X))
    for _, p in net.collect_params().items():
        p.set_data(parallel.replicate(p.data(), mesh))
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.05}, shard_optimizer=True,
                       grad_compression=compress)
    return net, tr


def _trainer_epoch(net, tr, mesh, k=4):
    L = gloss.SoftmaxCrossEntropyLoss()
    for _ in range(k):
        xb = parallel.shard_batch(mx.nd.array(_X), mesh)
        yb = parallel.shard_batch(mx.nd.array(_Y), mesh)
        with mx.autograd.record():
            l = L(net(xb), yb).mean()
        l.backward()
        tr.step(1)


def test_trainer_compressed_parity_sanitizer_and_states(mesh8,
                                                        tmp_path):
    """Trainer(grad_compression='int8'): tracks the uncompressed
    sharded trainer, the sharded mirror carries one extra residual
    leaf per index, the leg stays finite/drift-free under the runtime
    numerics sanitizer, and save_states/load_states round-trips (the
    mirror-only residual is deliberately not serialized)."""
    import sys
    sys.path.insert(0, REPO) if REPO not in sys.path else None
    from tools.lint.runtime_numerics import NumericsSanitizer
    na, ta = _trainer_setup(mesh8, None)
    nb, tb = _trainer_setup(mesh8, "int8")
    _trainer_epoch(na, ta, mesh8)
    san = NumericsSanitizer().attach(tb)
    try:
        _trainer_epoch(nb, tb, mesh8)
    finally:
        san.detach()
    assert san.observed, "sanitizer sweep never ran"
    san.assert_all_finite()
    san.assert_no_dtype_drift()
    fa = ta._kv_fused or ta._local_fused
    fb = tb._kv_fused or tb._local_fused
    assert fb._compress == "int8"
    for i, leaves in fb._sharded.items():
        assert len(leaves) == len(fa._sharded[i]) + 1
        assert leaves[-1].ndim == 1 and leaves[-1].shape[0] % 8 == 0
    # Adam at lr=0.05 amplifies the per-step quantization delta more
    # than the SGD probe — the parity band here is looser than the
    # DataParallelStep test's
    for (ka, pa), (_, pb) in zip(na.collect_params().items(),
                                 nb.collect_params().items()):
        onp.testing.assert_allclose(pa.data().asnumpy(),
                                    pb.data().asnumpy(),
                                    rtol=1e-1, atol=1e-1, err_msg=ka)
    # states round-trip: the residual never reaches the .states file
    f = str(tmp_path / "c.states")
    tb.save_states(f)
    nc, tc = _trainer_setup(mesh8, "int8")
    _trainer_epoch(nc, tc, mesh8, k=1)
    tc.load_states(f)
    fused = tc._kv_fused or tc._local_fused
    assert not fused._sharded        # mirror dropped; rebuilt next step
    _trainer_epoch(nc, tc, mesh8, k=2)
    fused = tc._kv_fused or tc._local_fused
    assert fused._compress == "int8" and fused._sharded


# ---------------------------------------------------------------------------
# parse_log --jsonl census round trip
# ---------------------------------------------------------------------------

def test_parse_log_compress_census_roundtrip(mesh8, tmp_path):
    from tools.parse_log import parse_jsonl, render_jsonl
    telemetry.reset()
    sink = tmp_path / "run.jsonl"
    telemetry.set_jsonl_sink(str(sink))
    try:
        _build_step(mesh8, "int8")
        telemetry.export_jsonl(str(sink))   # trailing snapshot: gauges
    finally:
        telemetry.set_jsonl_sink(None)
        telemetry.reset()
    with open(str(sink)) as fh:
        agg = parse_jsonl(fh)
    rows = agg["compress"]
    assert rows and rows[-1]["mode"] == "int8"
    assert rows[-1]["path"] == "forced" and rows[-1]["ratio"] == 4.0
    assert rows[-1]["f32_bytes"] == 4 * rows[-1]["wire_bytes"]
    text = render_jsonl(agg)
    assert "gradient compression census:" in text
    assert "wire bytes saved/step:" in text
    assert "| int8 | int8 | forced |" in text
    tsv = render_jsonl(agg, fmt="tsv")
    assert "int8\tint8\tforced" in tsv
