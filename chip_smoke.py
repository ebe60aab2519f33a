#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one TPU chip: device, two train
                                      # phases, the server
    python chip_smoke.py --chips 4    # four chips: the dp=4 ZeRO-sharded
                                      # BERT step against the one-device step

Drives the main path once through the entry points a user calls, at the full
width of the two models the repo is benchmarked on (``BASELINE.md``):
ResNet-50 bs=128 bf16 and BERT-base MLM bs=24 S=512 bf16 through
``gluon.model_zoo`` -> ``net.cast`` -> ``reset_ctx(mx.tpu())`` ->
``parallel.DataParallelStep``, then ``serve.InferenceServer`` over ResNet-50.
Weights and data are made from ``SEED``.  One process, no subprocess: a chip
belongs to one process at a time.

Every check raises; nothing is recorded and carried past.  The LAST stdout
line of a run that passed is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and a run that did not pass exits non-zero without it — as does any run on a
host where jax finds no TPU.  Step times, compile seconds and peak memory are
printed on earlier lines as set-up information; they are not results.

``--rehearse`` swaps in toy sizes so the control flow can be run end to end on
the CPU backend (tests/test_chip_smoke.py, with the device check stubbed).
The chip run never uses it; the checks that only a TPU can meet (Pallas
custom calls in the compiled step, the attention census, device memory
statistics) read the platform jax reports, not this option.
"""
import argparse
import gc
import json
import math
import sys
import time

SEED = 0

FULL = {
    "resnet": {"model": "resnet50_v1", "batch": 128, "image": 224},
    "bert": {"arch": "base", "batch": 24, "seq": 512, "layers": 12},
    "serve": {"model": "resnet50_v1", "image": 224},
}
# rehearsal only: the same code paths at sizes the CPU backend compiles in
# seconds
REHEARSAL = {
    "resnet": {"model": "resnet18_v1", "batch": 4, "image": 32},
    "bert": {"arch": "small", "batch": 4, "seq": 128, "layers": 2},
    "serve": {"model": "resnet18_v1", "image": 32},
}

WARMUP_STEPS = 3      # donation settles buffer layouts over the first calls
STEADY_STEPS = 5

_COMPILES = {"programs": 0, "seconds": 0.0, "cache_hits": 0,
             "cache_misses": 0, "listening": False}


def say(phase, **fields):
    print("[%s] %s" % (phase, json.dumps(fields, sort_keys=True)),
          flush=True)


def check(cond, message):
    if not cond:
        raise RuntimeError("chip_smoke: " + message)


def _count_compiles():
    """Count every program jax hands its backend (compiled or reloaded from
    the persistent cache), from jax's own monitoring events."""
    import jax.monitoring

    if _COMPILES["listening"]:
        return
    _COMPILES["listening"] = True

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILES["programs"] += 1
            _COMPILES["seconds"] += seconds

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _COMPILES["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _COMPILES["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def _compile_delta(before):
    return {k: round(_COMPILES[k] - before[k], 2)
            for k in ("programs", "seconds", "cache_hits", "cache_misses")}


def _dist_version(name):
    from importlib import metadata
    try:
        return metadata.version(name)
    except metadata.PackageNotFoundError:
        return "not installed"


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def require_tpu(device):
    check(device.platform == "tpu",
          "jax.devices()[0] is %r (platform %r), not a TPU"
          % (device, device.platform))


def device_phase(chips, cache_dir):
    import jax
    import jaxlib
    from mxnet_tpu import native

    devices = jax.devices()
    require_tpu(devices[0])
    check(len(devices) >= chips,
          "--chips %d needs %d devices, jax sees %d"
          % (chips, chips, len(devices)))
    say("device", platform=devices[0].platform,
        kind=devices[0].device_kind, visible=len(devices), used=chips,
        jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=_dist_version("libtpu"), compile_cache=cache_dir,
        record_reader="native" if native.available() else "python")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": chips}


# ---------------------------------------------------------------------------
# builders (seeded; benchmark/configs/*/model.py holds its own copies)
# ---------------------------------------------------------------------------

def _seed():
    import numpy as onp
    import mxnet_tpu as mx
    mx.random.seed(SEED)
    onp.random.seed(SEED)
    return onp.random.RandomState(SEED)


def _build_vision_net(model, image, dtype="bfloat16"):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.gluon.utils import materialize_params

    net = vision.get_model(model, classes=1000)
    net.initialize(mx.init.Xavier())
    materialize_params(net, mx.nd.zeros((1, 3, image, image)))
    net.cast(dtype)
    net.collect_params().reset_ctx(mx.tpu())
    return net


def build_resnet_step(cfg):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    rs = _seed()
    batch, image = cfg["batch"], cfg["image"]
    net = _build_vision_net(cfg["model"], image)
    data = mx.nd.array(
        rs.uniform(size=(batch, 3, image, image)).astype("float32"),
        ctx=mx.tpu()).astype("bfloat16")
    label = mx.nd.array(rs.randint(0, 1000, (batch,)).astype("float32"),
                        ctx=mx.tpu())
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9, wd=1e-4,
                           rescale_grad=1.0 / batch)
    step = mx.parallel.DataParallelStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), opt, mesh=None)
    return net, step, lambda: step(data, label)


def build_bert_step(cfg, mesh=None, shard_optimizer=False):
    """BERT MLM: padded rows (``valid_length``), masked head, Adam.  With a
    mesh the batch is laid over its ``dp`` axis before the first call."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import bert_base, bert_small

    rs = _seed()
    vocab, batch, seq = 30522, cfg["batch"], cfg["seq"]
    if cfg["arch"] == "base":
        net = bert_base(vocab_size=vocab, max_length=seq, dropout=0.0,
                        use_pooler=False, use_decoder=True)
    else:
        net = bert_small(num_layers=cfg["layers"], units=128,
                         hidden_size=256, vocab_size=vocab, max_length=seq,
                         dropout=0.0, use_pooler=False, use_decoder=True)
    net.initialize(mx.init.Xavier())
    tokens = rs.randint(0, vocab, (batch, seq))
    # wikipedia-style length mix: most rows near max, a short tail
    lens = rs.randint(seq // 3, seq + 1, (batch,))
    lens[: max(1, batch // 4)] = seq
    n_pred = max(1, int(seq * 0.15))
    # standard MLM: 15% of positions per row, all inside the valid length
    pos = onp.sort(onp.stack([rs.choice(int(lens.min()), n_pred,
                                        replace=False)
                              for _ in range(batch)]), 1)
    labels = rs.randint(0, vocab, (batch, n_pred))
    # deferred shapes do not depend on the batch: one row completes them
    net(mx.nd.array(tokens[:1].astype("float32")), None, None,
        mx.nd.array(lens[:1].astype("int32"), dtype="int32"),
        mx.nd.array(pos[:1].astype("int32"), dtype="int32"))
    net.cast("bfloat16")
    net.collect_params().reset_ctx(mx.tpu())

    def put(arr, dtype):
        nd = mx.nd.array(arr.astype(dtype), ctx=mx.tpu(), dtype=dtype)
        return parallel.shard_batch(nd, mesh) if mesh is not None else nd

    batch_nd = {"tokens": put(tokens, "float32"), "lens": put(lens, "int32"),
                "pos": put(pos, "int32"), "labels": put(labels, "float32")}

    class MLMLoss(gluon.loss.Loss):
        def __init__(self):
            super().__init__(weight=None, batch_axis=0)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, outputs, lab):
            _, logits = outputs
            return self._ce(logits.reshape(-1, vocab), lab.reshape(-1))

    step = mx.parallel.DataParallelStep(
        net, MLMLoss(), mx.optimizer.Adam(learning_rate=1e-4), mesh=mesh,
        shard_optimizer=shard_optimizer)

    def run():
        return step((batch_nd["tokens"], None, None, batch_nd["lens"],
                     batch_nd["pos"]), batch_nd["labels"])
    return net, step, run, batch_nd


# ---------------------------------------------------------------------------
# train phases
# ---------------------------------------------------------------------------

def _loss_value(loss):
    loss.wait_to_read()        # block_until_ready: the step ends here
    return float(loss.asnumpy().astype("float32").mean())


def _param_arrays(net):
    return {name: p.data()._data
            for name, p in sorted(net.collect_params().items())}


def _compiled_step_text(step, run):
    """Text of the step program the device runs: the cached jitted step,
    lowered again at the shapes, dtypes and shardings of one real call
    (which also takes a step) and compiled — from the compile cache where
    the first compile was kept."""
    import jax

    (key, jitted), = step._cache.items()
    seen = {}

    def spec(a):
        # an uncommitted operand (the host-made lr vector) goes wherever
        # the committed ones are, as in the real call
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding if a.committed else None)

    def spy(*args):
        seen["specs"] = jax.tree_util.tree_map(spec, args)
        return jitted(*args)

    step._cache[key] = spy
    try:
        loss = _loss_value(run())
    finally:
        step._cache[key] = jitted
    return jitted.lower(*seen["specs"]).compile().as_text(), loss


def _pallas_calls(text):
    """(forward, backward) Pallas custom calls in a compiled program: the
    backward kernels sit under ``transpose(jvp(...))`` in the op name."""
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    bwd = sum("transpose(" in line for line in calls)
    return len(calls) - bwd, bwd


def train_phase(name, net, step, run, devices):
    """Warm up, run steady steps, and hold the run to the checks every
    train phase shares.  Returns (losses, compiled step text)."""
    import jax
    from mxnet_tpu import telemetry

    dtypes_before = {n: str(a.dtype) for n, a in _param_arrays(net).items()}
    check("bfloat16" in dtypes_before.values(),
          "%s: no bf16 parameter after net.cast" % name)
    before = dict(_COMPILES)
    t0 = time.perf_counter()
    losses = [_loss_value(run()) for _ in range(WARMUP_STEPS)]
    warm_s = time.perf_counter() - t0
    warm = _compile_delta(before)

    settled = dict(_COMPILES)
    counts_settled = telemetry.compile_counts()
    step_ms = []
    for _ in range(STEADY_STEPS):
        t0 = time.perf_counter()
        losses.append(_loss_value(run()))
        step_ms.append((time.perf_counter() - t0) * 1e3)
    check(_COMPILES["programs"] == settled["programs"]
          and telemetry.compile_counts() == counts_settled,
          "%s: compiled after warm-up (%r; %r -> %r)"
          % (name, _compile_delta(settled), counts_settled,
             telemetry.compile_counts()))

    before_text = dict(_COMPILES)
    text, loss = _compiled_step_text(step, run)
    text_compile = _compile_delta(before_text)
    losses.append(loss)
    check(all(l == l and abs(l) != float("inf") for l in losses),
          "%s: non-finite loss in %r" % (name, losses))
    check(losses[-1] < losses[0],
          "%s: loss did not fall: %r" % (name, losses))

    last = run()
    last.wait_to_read()
    arrays = _param_arrays(net)
    check({n: str(a.dtype) for n, a in arrays.items()} == dtypes_before,
          "%s: parameter dtypes drifted during training" % name)
    want = set(devices)
    for pname, arr in list(arrays.items()) + [("loss", last._data)]:
        check(set(arr.devices()) == want,
              "%s: %s lives on %r, not on %r — the step ran somewhere "
              "else" % (name, pname, sorted(map(str, arr.devices())),
                        sorted(map(str, want))))
    stats = devices[0].memory_stats()
    say(name, losses=[round(l, 4) for l in losses],
        warmup_seconds=round(warm_s, 1), warmup_compiles=warm,
        steady_step_ms=[round(t, 2) for t in step_ms],
        step_text_compile=text_compile,
        peak_bytes_in_use=(stats or {}).get("peak_bytes_in_use"),
        note="set-up information, not a result")
    if jax.devices()[0].platform == "tpu":
        check(stats is not None and stats.get("peak_bytes_in_use"),
              "%s: the TPU reports no memory statistics" % name)
    return losses, text


def resnet_phase(cfg):
    import mxnet_tpu as mx

    net, step, run = build_resnet_step(cfg)
    train_phase("train_resnet", net, step, run, [mx.tpu().jax_device])


def _attention_census():
    from mxnet_tpu import telemetry
    return {k: v for k, v in telemetry.snapshot(events=0)["counters"].items()
            if k.startswith("attention.kernel.")}


def bert_phase(cfg):
    import jax
    import mxnet_tpu as mx

    net, step, run, _ = build_bert_step(cfg)
    # the census is of the train step's trace: the builder's one-row
    # forward that completes the deferred shapes runs on the host backend
    census0 = _attention_census()
    _, text = train_phase("train_bert", net, step, run,
                          [mx.tpu().jax_device])
    census = {k: v - census0.get(k, 0)
              for k, v in _attention_census().items()
              if v - census0.get(k, 0)}
    fwd, bwd = _pallas_calls(text)
    say("train_bert", attention_census=census,
        attention_custom_calls={"forward": fwd, "backward": bwd})
    if jax.devices()[0].platform == "tpu":
        check(census.get("attention.kernel.short_seq")
              and set(census) == {"attention.kernel.short_seq"},
              "train_bert: attention census %r — expected short_seq only, "
              "never dense_fallback" % (census,))
        check(fwd >= cfg["layers"] and bwd >= cfg["layers"],
              "train_bert: %d forward / %d backward attention kernels in "
              "the compiled step for %d layers"
              % (fwd, bwd, cfg["layers"]))


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_phase(cfg):
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import serve

    rs = _seed()
    image = cfg["image"]
    net = _build_vision_net(cfg["model"], image)
    bursts = (1, 2, 3, 6)                  # a dozen requests, mixed sizes
    xs = rs.uniform(size=(sum(bursts), 3, image, image)).astype("float32")
    # the reference: ONE direct forward over all twelve inputs (eval-mode
    # outputs do not depend on what else is in the batch)
    net.hybridize()
    direct = net(mx.nd.array(xs, ctx=mx.tpu()).astype("bfloat16"))
    direct = direct.asnumpy().astype("float32")
    check(onp.isfinite(direct).all(), "serve: direct forward not finite")

    before = dict(_COMPILES)
    config = serve.ServeConfig(default_deadline_ms=20000.0,
                               dispatch_timeout_ms=20000.0)
    with serve.InferenceServer(net, feature_shape=(3, image, image),
                               dtype="bfloat16", config=config,
                               name="chip_smoke") as srv:
        warm = _compile_delta(before)
        warmed = dict(_COMPILES)
        t0 = time.perf_counter()
        outcomes = []
        at = 0
        for n in bursts:
            handles = [srv.submit(x) for x in xs[at:at + n]]
            outcomes += [h.outcome(timeout=60.0) for h in handles]
            at += n
        wall_s = time.perf_counter() - t0
        recompiles = srv.steady_state_recompiles()
        buckets = srv.stats()["buckets"]
    check(all(o is not None and o[0] == "result" for o in outcomes),
          "serve: not every request reached an ok outcome: %r"
          % ([o and (o[0], o[2]) for o in outcomes],))
    got = onp.stack([onp.asarray(o[1]).astype("float32")
                     for o in outcomes])
    check(onp.isfinite(got).all(), "serve: non-finite output")
    # bf16 outputs of two programs (bucket batch vs the batch of twelve)
    err = float(onp.abs(got - direct).max())
    scale = float(onp.abs(direct).max())
    check(err <= 0.05 * max(scale, 1.0),
          "serve: outputs differ from the direct forward by %g (scale %g)"
          % (err, scale))
    check(not recompiles and _COMPILES["programs"] == warmed["programs"],
          "serve: compiled after the buckets' warm-up: %r, %r"
          % (recompiles, _compile_delta(warmed)))
    say("serve", requests=len(outcomes), bursts=list(bursts),
        buckets=buckets, warmup_compiles=warm,
        max_abs_err_vs_direct=round(err, 5), output_scale=round(scale, 4),
        wall_seconds=round(wall_s, 3),
        note="set-up information, not a result")


# ---------------------------------------------------------------------------
# four chips: the sharded path and what it is compared with
# ---------------------------------------------------------------------------

def _one_device_losses(cfg, steps):
    run = build_bert_step(cfg)[2]
    return [_loss_value(run()) for _ in range(steps)]


def four_chip_phase(cfg, chips):
    import jax
    from mxnet_tpu import parallel

    devices = jax.devices()[:chips]
    steps = 8      # enough for a bf16 loss near 10 to move by several ulp

    # what it is compared with: the one-device step on the same batch,
    # gone from the first chip again before the sharded step is measured
    reference = _one_device_losses(cfg, steps)
    gc.collect()

    mesh = parallel.device_mesh((chips,), ("dp",), devices=devices)
    net, step, run, batch = build_bert_step(cfg, mesh=mesh,
                                            shard_optimizer=True)
    check(step._shard_n == chips,
          "sharded: optimizer sharded %r ways, not %d"
          % (step._shard_n, chips))
    before = dict(_COMPILES)
    losses = [_loss_value(run()) for _ in range(steps)]
    compiles = _compile_delta(before)
    text, _ = _compiled_step_text(step, run)

    say("sharded", one_device_losses=[round(l, 4) for l in reference],
        sharded_losses=[round(l, 4) for l in losses], compiles=compiles)
    # the loss is a bf16 scalar and the two steps reduce in different
    # orders: "equal" is to two units in its last place at step 0, and to
    # four while the trajectories track
    ulp = 2.0 ** (math.floor(math.log2(abs(reference[0]))) - 7)
    check(abs(losses[0] - reference[0]) <= 2 * ulp,
          "sharded: step-0 loss %g vs one-device %g"
          % (losses[0], reference[0]))
    for i, (a, b) in enumerate(zip(losses, reference)):
        check(abs(a - b) <= 4 * ulp,
              "sharded: step %d loss %g left the one-device %g"
              % (i, a, b))
    check(losses[-1] < losses[0], "sharded: loss did not fall: %r" % losses)

    # what stayed behind on one chip: reset_ctx(mx.tpu()) puts everything on
    # the first one before the step re-shards it.  Counted from the live
    # arrays (any backend, and before the checks below make single-device
    # views of the shards); the chips' own statistics are read at the end.
    gc.collect()
    live = jax.live_arrays()
    alone = sum(a.nbytes for a in live if len(a.devices()) == 1)
    per_device = sum(
        math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize
        for a in live if len(a.devices()) > 1)
    check(alone <= 0.01 * per_device,
          "sharded: %d bytes live on one device alone against %d per device "
          "over the mesh — something stayed on one chip"
          % (alone, per_device))

    want = set(devices)
    params = list(_param_arrays(net).values())
    state = [leaf for leaves in step._opt_states for leaf in leaves]
    for what, arrays in (("parameters", params),
                         ("optimizer state", state),
                         ("batch", [v._data for v in batch.values()])):
        check(all(set(a.devices()) == want for a in arrays),
              "sharded: %s not on all of %r"
              % (what, sorted(map(str, want))))
    for what, arrays in (("optimizer state", state),
                         ("batch", [v._data for v in batch.values()])):
        for a in arrays:
            shards = a.addressable_shards
            check(len({s.device for s in shards}) == chips
                  and all(s.data.shape[0] * chips == a.shape[0]
                          for s in shards),
                  "sharded: a %s array of shape %r is not split %d ways"
                  % (what, a.shape, chips))
    found = [op for op in ("reduce-scatter", "all-gather", "all-reduce")
             if op in text]
    check(found, "sharded: no collective in the compiled step")

    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    say("sharded", collectives=found, bytes_in_use=in_use,
        live_bytes={"one_device_alone": alone, "per_mesh_device": per_device})
    if devices[0].platform == "tpu":
        check(all(in_use), "sharded: a TPU reports no memory statistics")
        check(max(in_use) <= 1.25 * min(in_use),
              "sharded: per-device HBM in use is uneven: %r — something "
              "stayed on one chip" % (in_use,))


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the dp=4 sharded BERT step and the "
                         "one-device step it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes for a CPU rehearsal of the control "
                         "flow; never used on the chip")
    args = ap.parse_args(argv)
    sizes = REHEARSAL if args.rehearse else FULL

    from mxnet_tpu.engine import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    _count_compiles()
    start = dict(_COMPILES)
    t0 = time.perf_counter()
    device = device_phase(args.chips, cache_dir)
    if args.chips == 1:
        resnet_phase(sizes["resnet"])
        bert_phase(sizes["bert"])
        serve_phase(sizes["serve"])
    else:
        four_chip_phase(sizes["bert"], args.chips)
    say("done", wall_seconds=round(time.perf_counter() - t0, 1),
        compiles=_compile_delta(start))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
