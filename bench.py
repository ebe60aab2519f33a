#!/usr/bin/env python
"""Benchmarks vs the reference's published numbers (BASELINE.md).

Covered configs (BASELINE.json):
  * ResNet-50 train-step throughput (ref `train_imagenet.py --benchmark`,
    `/root/reference/docs/faq/perf.md:239-241`: 298.51/343.19/363.69 img/s
    for bs 32/64/128 on 1x V100).
  * ResNet-50 inference throughput (ref `benchmark_score.py`,
    `docs/faq/perf.md:183,197`: 1233.15 img/s fp32 / 2355.04 img/s fp16,
    bs=128 on 1x V100).
  * LSTM language model train step (ref `example/rnn/` cuDNN path,
    `src/operator/rnn-inl.h` — capability bench, no published img/s).
  * Attention microbench: Pallas flash attention vs dense jnp attention
    (BERT/long-context proxy, BASELINE.json config 5).

The train step is the full iteration — forward + loss + backward + SGD
momentum update — compiled as ONE donated-buffer XLA program
(`parallel.DataParallelStep`), fed synthetic on-device data (input pipeline
excluded, as in the reference's --benchmark mode).

Prints ONE JSON line:
    {"metric": ..., "value": ..., "unit": "img/s", "vs_baseline": ...,
     "detail": {...}}

Performance note (round 5, re-profiled with per-HLO xplane stats): the
ResNet-50 bf16 train step is **HBM-bandwidth-bound end to end**.  Every
top HLO in the profile — conv fusions (76% of device time), BN/residual
loop fusions (13%), copies (5%) — reports "Bound by: HBM" at a measured
600-700 GiB/s against the chip's 819 GB/s spec; aggregate physical
traffic is ~30 GB/step at bs=128 (activations ~6.5 GB written+read in
forward, re-read plus gradient traffic in backward), which at spec
bandwidth floors the step at ~37 ms before any dispatch cost.  Three
control experiments bound what is achievable:
  * a hand-rolled idealized JAX step (NHWC, dict pytree, donated, no
    framework machinery) runs the SAME speed as the framework step —
    the framework adds no measurable overhead;
  * conv dimension-number layout (NCHW vs NHWC) changes per-conv time
    by <±10% either direction — XLA TPU normalizes layouts, so
    "channels-last" is not a lever on this chip;
  * k train steps inside one compiled lax.scan (scan_steps) take the
    per-call host dispatch out of the loop, the only headroom left.
Backward-mirror remat is therefore a MEMORY knob (live_temp 4.48→3.33
GB) that *adds* HBM traffic, measured ~16% slower at bs>=128 — plain is
the default; mirror ships alongside for the record.  `compute_floor_ms`
(~14.5 ms) is the MXU-only floor and is NOT reachable while the
algorithmic byte/FLOP ratio of ResNet-50 training (~36 FLOP/byte) sits
6-7x below the chip's 240 FLOP/byte balance point.

Usage:
    python bench.py             # headline + inference, minutes
    python bench.py --full      # everything: bs sweep, LSTM, attention
    python bench.py --smoke     # tiny model, CPU-safe, seconds
"""
import argparse
import json
import sys
import time


TRAIN_BASELINES = {  # MXNet-CUDA V100 img/s (docs/faq/perf.md:239-241)
    ("resnet50_v1", 32): 298.51,
    ("resnet50_v1", 64): 343.19,
    ("resnet50_v1", 128): 363.69,
}
INFER_BASELINES = {  # docs/faq/perf.md:183 (fp32), :197 (fp16)
    ("resnet50_v1", "float32"): 1233.15,
    ("resnet50_v1", "bfloat16"): 2355.04,  # ref fp16 ~ our bf16 tier
}

# ResNet-50 fwd FLOPs per 224x224 image; train ~= 3x fwd (fwd + 2x bwd).
RESNET50_FWD_FLOPS = 4.09e9
# Published peaks of ONE chip, keyed by the ``device_kind`` jax reports.
# Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16 dense
# (393 is the int8 number), 16 GB of HBM at 819 GB/s.  A device that is
# not in the table is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def _device():
    """platform / kind / count of the devices this run uses, as jax
    reports them — stamped on every JSON line the bench prints."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peaks():
    kind = _device()["kind"]
    if kind not in DEVICE_PEAKS:
        raise RuntimeError(
            "no published peaks for device kind %r (known: %s) — a "
            "utilisation against another chip's peak is not a number"
            % (kind, sorted(DEVICE_PEAKS)))
    return DEVICE_PEAKS[kind]


def _emit(obj, log=False):
    """Print one JSON line stamped with the device it was produced on:
    a result on stdout, or (``log``) a '# '-prefixed progress line on
    stderr, cut to 2000 characters with the stamp first."""
    line = json.dumps(dict({"device": _device()}, **obj))
    if log:
        print("# " + line[:2000], file=sys.stderr)
    else:
        print(line)


def _step_cost_analysis(step, data, label, step_s=None):
    """XLA cost/memory analysis of the compiled train step + roofline
    floors.  ``xla_logical_gb`` is bytes_accessed — it counts fused
    re-reads, so it is an UPPER bound on physical HBM DMA (the r3 bench
    treated it as physical and claimed >spec sustained rates; the honest
    statement is the capped pair below).  ``live_temp_gb`` is the
    materialized intermediate set the schedule actually holds in HBM —
    the number backward-mirror remat shrinks."""
    import jax.numpy as jnp
    from mxnet_tpu import random as _random
    from mxnet_tpu.tune import search as _search
    jfn = next(iter(step._cache.values())) if step._cache else step._build()
    lrs = jnp.zeros((len(step._trainable),), jnp.float32)
    pvals = [p._data._data for p in step._params]
    lowered = jfn.lower(pvals, step._opt_states, jnp.asarray(1, jnp.int32),
                        lrs, _random.next_key(), data._data, label._data)
    cost = _search.compiled_cost(lowered)
    gb = cost["bytes_accessed"] / 1e9
    tf = cost["flops"] / 1e12
    peaks = _peaks()
    out = {
        "xla_logical_gb": round(gb, 2),
        "xla_tflops": round(tf, 3),
        "compute_floor_ms": round(
            tf / (peaks["bf16_flops"] / 1e12) * 1000, 2),
    }
    if step_s is not None:
        # sustained rate implied by logical bytes, capped at the physical
        # spec — "at least this close to saturation", never >100%
        hbm_gbs = peaks["hbm_bytes_per_s"] / 1e9
        out["hbm_util_upper_capped"] = round(
            min(gb / step_s, hbm_gbs) / hbm_gbs, 3)
    if "temp_bytes" in cost:
        out["live_temp_gb"] = round(cost["temp_bytes"] / 1e9, 3)
    return out


def _sync(x):
    """Wait until the device has finished ``x`` (a timed window ends
    here: jax returns from a dispatch before the device is done)."""
    x.wait_to_read()


def _scalar(x):
    """First element of ``x`` as a host float (a loss for the record)."""
    return float(x.asnumpy().ravel()[0])


def _build_train_step(model_name, batch_size, dtype, image_size=224,
                      mirror=None):
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.gluon.utils import materialize_params

    # init on host (cheap local initializer compiles), complete deferred
    # shapes abstractly (no kernel runs), then move everything to the chip —
    # the jitted step compiles for and runs on the TPU
    net = vision.get_model(model_name, classes=1000)
    net.initialize(mx.init.Xavier())
    materialize_params(net, mx.nd.zeros((1, 3, image_size, image_size)))
    if dtype != "float32":
        net.cast(dtype)
    net.collect_params().reset_ctx(mx.tpu())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9, wd=1e-4,
                           rescale_grad=1.0 / batch_size)
    rs = onp.random.RandomState(0)
    data = mx.nd.array(
        rs.uniform(size=(batch_size, 3, image_size, image_size)).astype(
            "float32"), ctx=mx.tpu()).astype(dtype)
    label = mx.nd.array(rs.randint(0, 1000, (batch_size,)).astype("float32"),
                        ctx=mx.tpu())
    step = mx.parallel.DataParallelStep(net, loss_fn, opt, mesh=None,
                                        mirror=mirror)
    return step, data, label


def _time_calls(fn, sync, warmup=3, iters=20, reps=3):
    """Median-of-``reps`` timing protocol.

    Each rep times ``iters`` calls bounded by one host sync; the
    per-call time is the MEDIAN across reps, which rides out one-off
    host stalls that a single timed window presents as a 2x swing (the
    round-4 artifact recorded bf16 inference at half its reproducible
    rate this way).  If the rep spread exceeds 25% of the
    median, up to two extra reps are run before re-taking the median;
    the per-rep times ship in the result for auditability."""
    if warmup:
        for _ in range(warmup):
            out = fn()
        sync(out)

    def one_rep():
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn()
        sync(r)
        return (time.perf_counter() - t0) / iters, r

    times = []
    for _ in range(max(1, reps)):
        dt, out = one_rep()
        times.append(dt)
    srt = sorted(times)
    med = srt[len(srt) // 2]
    extra = 0
    while med > 0 and (srt[-1] - srt[0]) / med > 0.25 and extra < 2:
        dt, out = one_rep()
        times.append(dt)
        extra += 1
        srt = sorted(times)
        med = srt[len(srt) // 2]
    detail = {"reps_ms": [round(t * 1e3, 2) for t in times],
              "spread": round((srt[-1] - srt[0]) / med, 3) if med else None}
    return med, out, detail


def bench_train(model_name, batch_size, dtype, iters=20, mirror=None,
                pipelined_k=0):
    """Per-call train-step throughput; with ``pipelined_k`` > 0 also
    measures the scan_steps path (k steps per dispatch — the
    framework's compiled inner loop, which amortises the per-call host
    dispatch; reported separately, never as the per-call number)."""
    step, data, label = _build_train_step(model_name, batch_size, dtype,
                                          mirror=mirror)
    step_s, loss, timing = _time_calls(lambda: step(data, label), _sync,
                                       iters=iters)
    img_s = batch_size / step_s
    out = {"bench": "train", "model": model_name, "batch_size": batch_size,
           "dtype": dtype, "mirror": step._mirror,
           "step_ms": round(step_s * 1000, 2),
           "img_per_sec": round(img_s, 2), "loss": round(_scalar(loss), 3),
           "timing": timing}
    if pipelined_k:
        import numpy as onp
        import mxnet_tpu as mx
        rs = onp.random.RandomState(1)
        shape = (pipelined_k, batch_size, 3, 224, 224)
        dk = mx.nd.array(rs.uniform(size=shape).astype("float32"),
                         ctx=mx.tpu()).astype(dtype)
        lk = mx.nd.array(
            rs.randint(0, 1000, shape[:2]).astype("float32"), ctx=mx.tpu())
        scan_s, _, scan_timing = _time_calls(
            lambda: step.scan_steps(dk, lk), _sync, warmup=2,
            iters=max(2, iters // 4))
        out["pipelined_k"] = pipelined_k
        out["pipelined_step_ms"] = round(scan_s * 1000 / pipelined_k, 2)
        out["img_per_sec_pipelined"] = round(
            batch_size * pipelined_k / scan_s, 2)
        out["pipelined_timing"] = scan_timing
        base = TRAIN_BASELINES.get((model_name, batch_size))
        if base:
            out["vs_baseline_pipelined"] = round(
                out["img_per_sec_pipelined"] / base, 3)
    if model_name.startswith("resnet50"):
        out["mfu_vs_bf16_peak"] = round(
            (3 * RESNET50_FWD_FLOPS * img_s) / _peaks()["bf16_flops"], 4)
        out.update(_step_cost_analysis(step, data, label, step_s))
    base = TRAIN_BASELINES.get((model_name, batch_size))
    if base:
        out["vs_baseline"] = round(img_s / base, 3)
    return out


def bench_inference(model_name, batch_size, dtype, iters=30, image_size=224):
    """Jitted eval-mode forward (BN uses moving stats), counterpart of the
    reference's `benchmark_score.py` (docs/faq/perf.md:183-197)."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.gluon.utils import materialize_params

    net = vision.get_model(model_name, classes=1000)
    net.initialize(mx.init.Xavier())
    materialize_params(net, mx.nd.zeros((1, 3, image_size, image_size)))
    if dtype != "float32":
        net.cast(dtype)
    net.collect_params().reset_ctx(mx.tpu())
    net.hybridize()
    rs = onp.random.RandomState(0)
    data = mx.nd.array(
        rs.uniform(size=(batch_size, 3, image_size, image_size)).astype(
            "float32"), ctx=mx.tpu()).astype(dtype)
    step_s, _, timing = _time_calls(lambda: net(data), _sync, iters=iters)
    img_s = batch_size / step_s
    out = {"bench": "inference", "model": model_name,
           "batch_size": batch_size, "dtype": dtype,
           "step_ms": round(step_s * 1000, 2),
           "img_per_sec": round(img_s, 2), "timing": timing}
    if model_name.startswith("resnet50"):
        out["mfu_vs_bf16_peak"] = round(
            (RESNET50_FWD_FLOPS * img_s) / _peaks()["bf16_flops"], 4)
    base = INFER_BASELINES.get((model_name, dtype))
    if base:
        out["vs_baseline"] = round(img_s / base, 3)
    return out


def bench_lstm_lm(batch_size=32, bptt=35, hidden=650, layers=2,
                  vocab=10000, dtype="float32", iters=20):
    """PTB-medium LSTM LM train step (ref example/rnn word_language_model,
    cuDNN RNN path src/operator/rnn.cu) via the fused lax.scan LSTM."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn, rnn

    class LM(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.embed = nn.Embedding(vocab, hidden)
            self.lstm = rnn.LSTM(hidden, num_layers=layers, layout="NTC")
            self.fc = nn.Dense(vocab, flatten=False)

        def hybrid_forward(self, F, x):
            return self.fc(self.lstm(self.embed(x)))

    net = LM()
    net.initialize(mx.init.Xavier())
    rs = onp.random.RandomState(0)
    host = mx.nd.array(rs.randint(0, vocab, (batch_size, bptt))
                       .astype("float32"))
    net(host)  # materialize deferred shapes
    if dtype != "float32":
        net.cast(dtype)
    net.collect_params().reset_ctx(mx.tpu())
    data = mx.nd.array(host.asnumpy(), ctx=mx.tpu())
    label = mx.nd.array(rs.randint(0, vocab, (batch_size, bptt))
                        .astype("float32"), ctx=mx.tpu())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.SGD(learning_rate=1.0, rescale_grad=1.0 / batch_size)
    step = mx.parallel.DataParallelStep(net, loss_fn, opt, mesh=None)
    # short steps (8-10 ms) need extra warmup or dispatch jitter dominates
    step_s, loss, _ = _time_calls(lambda: step(data, label), _sync,
                                  warmup=6, iters=iters)
    tok_s = batch_size * bptt / step_s
    return {"bench": "lstm_lm", "batch_size": batch_size, "bptt": bptt,
            "hidden": hidden, "layers": layers, "vocab": vocab,
            "dtype": dtype, "step_ms": round(step_s * 1000, 2),
            "tokens_per_sec": round(tok_s, 1),
            "samples_per_sec": round(batch_size / step_s, 2),
            "loss": round(_scalar(loss), 3)}


def bench_input_pipeline(batch_size=128, n_images=512, image_size=224,
                         iters=8, train_model="resnet50_v1",
                         workers_sweep=(1, 2, 4, 8), depth_sweep=(2, 4)):
    """Native .rec input pipeline (reference: the OMP pipeline in
    src/io/iter_image_recordio_2.cc:880) swept over decode workers x
    prefetch depth x wire format, plus the OVERLAPPED end-to-end
    rec->device->train-step rate — the --data-train counterpart of the
    synthetic --benchmark numbers.  Every stage's rate ships in the
    artifact so BENCH rounds can see WHICH leg bounds the pipeline
    (``pipeline_min_stage``) and track ``end_to_end_vs_train_step``."""
    import os
    import tempfile
    import numpy as onp
    from mxnet_tpu.io.image_record_iter import ImageRecordIter
    from mxnet_tpu import recordio

    import shutil
    d = tempfile.mkdtemp(prefix="benchrec")
    rec_path = os.path.join(d, "data.rec")
    idx_path = os.path.join(d, "data.idx")
    rec = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    rs = onp.random.RandomState(0)
    for i in range(n_images):
        img = rs.randint(0, 255, (image_size, image_size, 3),
                         dtype=onp.uint8)
        hdr = recordio.IRHeader(0, float(i % 1000), i, 0)
        rec.write_idx(i, recordio.pack_img(hdr, img, quality=90,
                                           img_fmt=".jpg"))
    rec.close()

    def fresh_iter(workers=8, u8=True):
        return ImageRecordIter(
            path_imgrec=rec_path, data_shape=(3, image_size, image_size),
            batch_size=batch_size, shuffle=True, rand_crop=True,
            rand_mirror=True, mean_r=123.68, mean_g=116.78, mean_b=103.94,
            std_r=58.4, std_g=57.12, std_b=57.38,
            preprocess_threads=workers, u8_output=u8)

    # (a) decode scaling: rec -> host batch rate (decode + augment in the
    # C++ pool, zero-copy borrow delivery) per worker count.  u8 output —
    # the production wire format — so this is pure decode+augment work.
    def decode_epoch(it):
        n = 0
        while True:
            try:
                _, _, pad, release = it.next_borrow()
            except StopIteration:
                break
            release()
            n += batch_size - pad
        it.reset()
        return n

    decode_rates = {}
    for w in workers_sweep:
        it = fresh_iter(workers=w)
        decode_epoch(it)   # warm (page cache + pool spin-up), per config
        n = 0
        t0 = time.perf_counter()
        for _ in range(2):
            n += decode_epoch(it)
        decode_rates[str(w)] = round(n / (time.perf_counter() - t0), 1)
        it.close()
    host_rate = max(decode_rates.values())
    best_workers = int(max(decode_rates, key=lambda k: decode_rates[k]))
    scaling = (round(decode_rates["4"] / decode_rates["1"], 2)
               if decode_rates.get("1") and decode_rates.get("4") else None)

    # (b) device-feed sweep: depth-K async device_put from the feeder
    # thread + pre-jitted on-device normalize, per (wire format, depth).
    # One epoch each, first batch (compile + its transfer) excluded.
    import jax
    from mxnet_tpu.io import DevicePrefetchIter

    def feed_epoch_rate(feed):
        n = 0
        last = None
        t0 = None
        for batch in feed:
            if t0 is None:  # exclude compile + first transfer
                _sync(batch.data[0])
                t0 = time.perf_counter()
                continue
            n += batch.data[0].shape[0]
            last = batch.data[0]
        if last is not None:
            _sync(last)  # one sync: transfers pipeline, real-feed style
        return n / (time.perf_counter() - t0) if n else 0.0

    feed_sweep = []
    for wire in ("uint8", "float32"):
        for depth in depth_sweep:
            feed = DevicePrefetchIter(
                fresh_iter(workers=best_workers, u8=(wire == "uint8")),
                dtype="bfloat16", depth=depth)
            rate = feed_epoch_rate(feed)
            feed.close()
            feed_sweep.append({"wire": wire, "depth": depth,
                               "img_s": round(rate, 1)})
    u8_feeds = [f for f in feed_sweep if f["wire"] == "uint8"]
    best_feed = max(u8_feeds, key=lambda f: f["img_s"])
    wire_rate = best_feed["img_s"]

    # (c) the train step itself (synthetic on-device data)
    step, data, label = _build_train_step(train_model, batch_size,
                                          "bfloat16",
                                          image_size=image_size)
    step_s, _, _ = _time_calls(lambda: step(data, label), _sync,
                               warmup=3, iters=max(4, iters))
    step_rate = batch_size / step_s

    # (d) OVERLAPPED end-to-end: .rec -> multi-worker decode (borrowed
    # slots) -> u8 wire, device_put issued depth-K ahead from the feeder
    # thread -> pre-jitted on-device normalize -> train step; one epoch,
    # one sync at the end — every leg runs concurrently, so this is the
    # sustained trainable rate, not a one-shot probe
    feed = DevicePrefetchIter(fresh_iter(workers=best_workers),
                              dtype="bfloat16", depth=best_feed["depth"])
    loss = None
    n = 0
    t0 = None
    for batch in feed:
        if t0 is None:  # first batch pays the normalize-jit compile and
            _sync(batch.data[0])  # its wire transfer precedes t0:
            t0 = time.perf_counter()     # exclude it entirely, as leg (b)
            continue
        loss = step(batch.data[0], batch.label[0])
        n += batch.data[0].shape[0]
    if loss is not None:
        _sync(loss)
    e2e_rate = n / (time.perf_counter() - t0) if (t0 and n) else 0.0
    feed.close()

    shutil.rmtree(d, ignore_errors=True)
    # Sustained throughput is the slowest overlapped leg; name it so the
    # next optimization round aims at the right stage.  Decode cannot
    # scale past the host's cores whatever the worker count —
    # decode_workers and the per-core rate ship so the reader can
    # roofline the host.
    cores = min(os.cpu_count() or 1, max(workers_sweep))
    # per-core divisor: the worker count that PRODUCED host_rate (capped
    # by physical cores), not the sweep maximum — dividing the 4-worker
    # rate by 8 cores would understate per-core decode 2x
    per_core_div = max(1, min(best_workers, os.cpu_count() or 1))
    stages = {"decode": host_rate, "device_feed": wire_rate,
              "train_step": step_rate}
    return {"bench": "input_pipeline", "batch_size": batch_size,
            "n_images": n_images, "image_size": image_size,
            "wire_format": "uint8+device_normalize",
            "decode_cores": cores,
            "decode_workers": decode_rates,
            "decode_scaling_1_to_4": scaling,
            "feed_sweep": feed_sweep,
            "prefetch_depth": best_feed["depth"],
            "rec_to_host_img_s": round(host_rate, 1),
            "rec_to_host_img_s_per_core": round(host_rate / per_core_div, 1),
            "device_feed_img_s": round(wire_rate, 1),
            "train_step_img_s": round(step_rate, 1),
            "end_to_end_img_s": round(e2e_rate, 1),
            "end_to_end_vs_train_step": round(e2e_rate / step_rate, 3),
            "pipeline_min_stage": min(stages, key=lambda k: stages[k])}


def _build_bert_step(batch_size=24, seq_len=512, dtype="bfloat16",
                     arch="base", padded=True, head="masked"):
    """Construct the bert_mlm_train step: returns ``(run, step, info)``
    where ``run()`` executes one train step and ``info`` carries the
    host-side tensors the pipelined leg restacks.  Shared by
    ``bench_bert`` and ``bench_telemetry_overhead`` (the A/B leg must
    time the SAME compiled step)."""
    if head not in ("masked", "full"):
        raise ValueError("head must be 'masked' or 'full', got %r" % head)
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import bert_base, bert_small

    vocab = 30522
    ctor = bert_base if arch == "base" else bert_small
    net = ctor(vocab_size=vocab, max_length=seq_len, dropout=0.0,
               use_pooler=False, use_decoder=True)
    net.initialize(mx.init.Xavier())
    rs = onp.random.RandomState(0)
    host_tokens = mx.nd.array(rs.randint(0, vocab, (batch_size, seq_len))
                              .astype("float32"))
    host_vl = None
    if padded:
        # wikipedia-style length mix: most rows near max, a short tail
        lens = rs.randint(seq_len // 3, seq_len + 1, (batch_size,))
        lens[: max(1, batch_size // 4)] = seq_len
        host_vl = mx.nd.array(lens.astype("int32"), dtype="int32")
    n_pred = max(1, int(seq_len * 0.15))
    host_pos = None
    if head == "masked":
        # standard MLM: 15% of positions per row, all within the valid
        # length (min vl = seq_len//3 > n_pred at every benched seq_len)
        min_vl = int(lens.min()) if padded else seq_len
        pos = onp.stack([rs.choice(min_vl, n_pred, replace=False)
                         for _ in range(batch_size)])
        host_pos = mx.nd.array(onp.sort(pos, 1).astype("int32"),
                               dtype="int32")
    if padded:
        net(host_tokens, None, None, host_vl, host_pos)  # deferred shapes
    else:
        net(host_tokens, None, None, None, host_pos)
    if dtype != "float32":
        net.cast(dtype)
    net.collect_params().reset_ctx(mx.tpu())
    tokens = mx.nd.array(host_tokens.asnumpy(), ctx=mx.tpu())
    n_lab = n_pred if head == "masked" else seq_len
    labels = mx.nd.array(rs.randint(0, vocab, (batch_size, n_lab))
                         .astype("float32"), ctx=mx.tpu())
    pos = mx.nd.array(host_pos.asnumpy(), ctx=mx.tpu(),
                      dtype="int32") if head == "masked" else None

    class MLMLoss(gluon.loss.Loss):
        def __init__(self):
            super().__init__(weight=None, batch_axis=0)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, outputs, lab):
            _, logits = outputs
            return self._ce(logits.reshape(-1, vocab), lab.reshape(-1))

    step = mx.parallel.DataParallelStep(
        net, MLMLoss(), mx.optimizer.Adam(learning_rate=1e-4), mesh=None)
    vl = mx.nd.array(host_vl.asnumpy(), ctx=mx.tpu(),
                     dtype="int32") if padded else None
    if padded or head == "masked":
        run = lambda: step((tokens, None, None, vl, pos), labels)
    else:
        run = lambda: step(tokens, labels)
    info = {"vocab": vocab, "n_pred": n_pred, "n_lab": n_lab, "rs": rs,
            "host_vl": host_vl, "host_pos": host_pos}
    return run, step, info


def bench_bert(batch_size=24, seq_len=512, dtype="bfloat16", iters=10,
               arch="base", padded=True, pipelined_k=0, head="masked"):
    """BERT pretraining-style train step (BASELINE.json config 5): MLM loss
    over a bert_base encoder whose attention runs in the Pallas flash
    kernel; fwd+loss+bwd+Adam as one donated XLA program.

    ``padded=True`` feeds realistic per-row valid lengths (the normal BERT
    batch shape) — the padding mask runs INSIDE the flash kernel's online
    softmax, so this measures the masked fused path, not a mask-free
    idealization.  tokens_per_sec counts all (padded) positions, matching
    how the reference reports throughput.

    ``head="masked"`` (the default, and the reference pretraining shape:
    GluonNLP's BERTModel decodes only ``masked_positions``) gathers the
    standard 15% of positions before the vocab projection, so the MLM
    head costs B*P rows instead of B*S.  ``head="full"`` decodes every
    position — profiling showed the full-decode softmax/CE over
    (B*S, 30522) was ~45% of the step's device time, all of it work the
    reference pipeline never does."""
    if pipelined_k and not padded:
        raise ValueError("bench_bert pipelined_k requires padded=True "
                         "(the scan stacks per-row valid lengths)")
    import numpy as onp
    import mxnet_tpu as mx

    run, step, info = _build_bert_step(batch_size, seq_len, dtype, arch,
                                       padded, head)
    vocab, n_pred, n_lab = info["vocab"], info["n_pred"], info["n_lab"]
    rs, host_vl, host_pos = info["rs"], info["host_vl"], info["host_pos"]
    # the first few calls recompile as donation settles buffer layouts
    step_s, loss, timing = _time_calls(run, _sync, warmup=4, iters=iters)
    out = {"bench": "bert_mlm_train", "arch": arch,
           "batch_size": batch_size, "seq_len": seq_len, "dtype": dtype,
           "padded": padded, "head": head,
           "step_ms": round(step_s * 1000, 2),
           "tokens_per_sec": round(batch_size * seq_len / step_s, 1),
           "loss": round(_scalar(loss), 3), "timing": timing}
    if head == "masked":
        out["masked_positions"] = n_pred
    if pipelined_k:
        # k steps per dispatch (scan_steps over stacked token batches)
        K = pipelined_k
        tk = mx.nd.array(
            rs.randint(0, vocab, (K, batch_size, seq_len)).astype("float32"),
            ctx=mx.tpu())
        lk = mx.nd.array(
            rs.randint(0, vocab, (K, batch_size, n_lab)).astype("float32"),
            ctx=mx.tpu())
        vk = mx.nd.array(
            onp.tile(host_vl.asnumpy(), (K, 1)).astype("int32"),
            ctx=mx.tpu(), dtype="int32")
        pk = mx.nd.array(
            onp.tile(host_pos.asnumpy(), (K, 1, 1)).astype("int32"),
            ctx=mx.tpu(), dtype="int32") if head == "masked" else None
        scan_s, _, scan_timing = _time_calls(
            lambda: step.scan_steps((tk, None, None, vk, pk), lk), _sync,
            warmup=2, iters=max(2, iters // 3))
        out["pipelined_k"] = K
        out["pipelined_step_ms"] = round(scan_s * 1000 / K, 2)
        out["tokens_per_sec_pipelined"] = round(
            K * batch_size * seq_len / scan_s, 1)
        out["pipelined_timing"] = scan_timing
    return out


def bench_telemetry_overhead(batch_size=24, seq_len=512, dtype="bfloat16",
                             iters=10, arch="base"):
    """A/B of the SAME compiled bert_mlm_train step with telemetry OFF
    vs ON (spans + per-step trace contexts + log-bucketed histograms +
    step hooks + recompile detector + memory-gauge stride all live).
    Telemetry is host-side only — the compiled program is identical —
    so the honest overhead is the host dispatch delta.
    ``overhead_pct`` > 2 is a HARD bench failure (_hard_failures): the
    always-on layer must stay effectively free.  The artifact proves
    the ON leg actually exercised the new layers:
    ``telemetry_hist_count`` is the delta of ``parallel.step``
    histogram observations and ``telemetry_traced`` asserts the timed
    steps ran under a live trace context.  Negative deltas are timing
    noise and clamp to 0."""
    from mxnet_tpu import telemetry

    run, _, _ = _build_bert_step(batch_size, seq_len, dtype, arch)
    with telemetry.disabled():
        off_s, _, off_t = _time_calls(run, _sync, warmup=4, iters=iters)
    # NO reset here: earlier bench jobs' telemetry must survive into the
    # artifact's telemetry_snapshot — count this leg's spans as a delta.
    # The ON leg force-enables telemetry: under MXNET_TELEMETRY=0 the
    # gate would otherwise silently measure disabled-vs-disabled.
    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        before = telemetry.snapshot(events=0)["spans"].get(
            "parallel.step", {}).get("count", 0)
        h = telemetry.histogram("parallel.step")
        hist_before = h.count if h is not None else 0
        on_s, _, on_t = _time_calls(run, _sync, warmup=2, iters=iters)
        snap = telemetry.snapshot(events=0)
        h = telemetry.histogram("parallel.step")
        hist_after = h.count if h is not None else 0
        traced = any(
            r.get("trace") for r in
            telemetry.snapshot(events=512)["events"]
            if r.get("kind") == "span" and r.get("name") == "parallel.step")
    finally:
        if not was_enabled:
            telemetry.disable()
    overhead = max(0.0, (on_s - off_s) / off_s * 100.0)
    return {"bench": "telemetry_overhead", "arch": arch,
            "batch_size": batch_size, "seq_len": seq_len, "dtype": dtype,
            "step_ms_telemetry_off": round(off_s * 1000, 3),
            "step_ms_telemetry_on": round(on_s * 1000, 3),
            "overhead_pct": round(overhead, 3),
            "overhead_ok": overhead <= 2.0,
            "timing_off": off_t, "timing_on": on_t,
            "telemetry_span_count": snap["spans"].get(
                "parallel.step", {}).get("count", 0) - before,
            "telemetry_hist_count": hist_after - hist_before,
            "telemetry_traced": bool(traced)}


def bench_zero_sharded_update(batch_size=256, hidden=2048, iters=8):
    """ZeRO-style cross-replica sharded weight update (arxiv
    2004.13336): replicated vs ``shard_optimizer=True`` legs of the
    SAME wide-MLP Adam train step over a dp mesh spanning every local
    device.  Records what the MULTICHIP artifact gates on — per-chip
    optimizer-state bytes (must drop ~N-fold) and step time (the
    sharded step trades the redundant full update for a reduce-scatter/
    all-gather pair, so it must not regress at bs>=256).  Timing is
    interleaved min-of-calls so both legs see the same host contention.
    On a single-device mesh the layout degenerates gracefully and the
    artifact records n_shards=1."""
    import time
    import numpy as onp
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn

    n = len(jax.local_devices())
    mesh = parallel.device_mesh((n,), ("dp",))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def leg(shard):
        onp.random.seed(7)
        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(hidden, activation="relu"),
                nn.Dense(hidden // 2, activation="relu"), nn.Dense(10))
        net.initialize(mx.init.Xavier())
        x = mx.nd.array(onp.random.rand(batch_size, 123).astype("float32"))
        y = mx.nd.array(
            onp.random.randint(0, 10, (batch_size,)).astype("float32"))
        net(x)
        step = parallel.DataParallelStep(
            net, lambda o, l: loss_fn(o, l),
            mx.optimizer.Adam(learning_rate=1e-3), mesh=mesh,
            shard_optimizer=shard)
        step(x, y)   # compile + first update
        return step, (x, y)

    step_rep, b_rep = leg(False)
    step_sh, b_sh = leg(True)
    ms_rep = ms_sh = None
    for _ in range(iters):
        t0 = time.perf_counter()
        step_rep(*b_rep).asnumpy()
        d = (time.perf_counter() - t0) * 1e3
        ms_rep = d if ms_rep is None else min(ms_rep, d)
        t0 = time.perf_counter()
        step_sh(*b_sh).asnumpy()
        d = (time.perf_counter() - t0) * 1e3
        ms_sh = d if ms_sh is None else min(ms_sh, d)
    bytes_rep = step_rep.optimizer_state_bytes(per_chip=True)
    bytes_sh = step_sh.optimizer_state_bytes(per_chip=True)
    return {"bench": "zero_sharded_update", "batch_size": batch_size,
            "hidden": hidden, "n_shards": n,
            "optimizer_state_bytes_per_chip_replicated": bytes_rep,
            "optimizer_state_bytes_per_chip_sharded": bytes_sh,
            "state_shrink_factor": round(bytes_rep / max(1, bytes_sh), 2),
            "step_ms_replicated": round(ms_rep, 3),
            "step_ms_sharded": round(ms_sh, 3),
            "sharded_step_ok": n <= 1 or ms_sh <= ms_rep * 1.25,
            "state_bytes_ok": n <= 1 or bytes_sh * (n - 1) < bytes_rep * n}


def bench_grad_compression(batch_size=256, hidden=1024, iters=6,
                           parity_steps=5):
    """Compressed gradient collectives A/B (parallel/compression.py):
    f32 vs int8 vs fp8 legs of the SAME sharded Adam train step over a
    dp mesh spanning every local device, interleaved min-of-calls.
    Records what MULTICHIP_r06 gates on — per-chip gradient wire bytes
    (payload must drop exactly 4x vs f32; the per-chunk max-abs scale
    side tensor is accounted separately and honestly), step time, and
    the loss-parity deltas over the first ``parity_steps`` steps
    (error-feedback quantization must track the f32 trajectory within
    the per-mode band).  A final elastic 8->4 leg reshards the int8
    leg's residual-carrying state and asserts the residuals migrated
    BITWISE (byte movement only) and training still descends.

    Gates (``_hard_failures``): ``compressed_ok: false`` — the wire
    never engaged or the payload ratio came in under 4x — and
    ``parity_ok: false`` — the compressed trajectory left the band —
    both exit the bench nonzero.  On a 1-device mesh compression
    disables by contract and the legs degenerate to the uncompressed
    step (compressed_ok records the disablement as ok)."""
    import time
    import numpy as onp
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import ElasticContext
    from mxnet_tpu.parallel import compression as comp
    from mxnet_tpu.parallel.collectives import padded_size

    n = len(jax.local_devices())
    mesh = parallel.device_mesh((n,), ("dp",))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    # parity bands ~10x the measured dp=8 deltas at this probe scale
    # (int8 ~8e-4, fp8 ~2e-4 over 5 steps): loose enough for backend
    # jitter, tight enough that a broken dequantize or a dead
    # error-feedback path blows through immediately
    tol = {"int8": 1e-2, "fp8": 5e-3}

    def leg(mode):
        onp.random.seed(7)
        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(hidden, activation="relu"),
                nn.Dense(hidden // 2, activation="relu"), nn.Dense(10))
        net.initialize(mx.init.Xavier())
        x = mx.nd.array(onp.random.rand(batch_size, 123).astype("float32"))
        y = mx.nd.array(
            onp.random.randint(0, 10, (batch_size,)).astype("float32"))
        net(x)
        step = parallel.DataParallelStep(
            net, lambda o, l: loss_fn(o, l),
            mx.optimizer.Adam(learning_rate=1e-3), mesh=mesh,
            shard_optimizer=True, grad_compression=mode)
        losses = [float(step(x, y).asscalar())
                  for _ in range(parity_steps)]
        return step, (x, y), losses

    modes = (None, "int8", "fp8")
    legs = {m: leg(m) for m in modes}
    ms = {m: None for m in modes}
    for _ in range(iters):
        for m in modes:
            step, b, _ = legs[m]
            t0 = time.perf_counter()
            step(*b).asnumpy()
            d = (time.perf_counter() - t0) * 1e3
            ms[m] = d if ms[m] is None else min(ms[m], d)

    # wire arithmetic over the flat zero-padded sharded layout — the
    # same schedule accounting _report_shard_layout journals
    step0 = legs[None][0]
    padded = sum(padded_size(int(onp.prod(step0._shard_meta[s])), n)
                 for s in range(len(step0._opt_states))
                 if step0._shard_slots[s]) if n > 1 else 0
    base_losses = legs[None][2]
    out_legs = [{"mode": "f32", "step_ms": round(ms[None], 3),
                 "grad_wire_bytes_per_chip": comp.wire_bytes(padded),
                 "scale_bytes_per_chip": 0,
                 "losses": [round(v, 6) for v in base_losses]}]
    for m in ("int8", "fp8"):
        step = legs[m][0]
        engaged = step._compress == m
        wire = comp.wire_bytes(padded, m)
        scale = comp.scale_bytes(padded, m)
        ratio = comp.wire_bytes(padded) / float(wire) if wire else 1.0
        delta = max(abs(a - b)
                    for a, b in zip(base_losses, legs[m][2]))
        out_legs.append({
            "mode": m, "step_ms": round(ms[m], 3),
            "grad_wire_bytes_per_chip": wire,
            "scale_bytes_per_chip": scale,
            "wire_ratio": round(ratio, 3),
            "parity_max_abs": round(delta, 6), "parity_tol": tol[m],
            "losses": [round(v, 6) for v in legs[m][2]],
            "engaged": engaged,
            "parity_ok": delta <= tol[m],
            "compressed_ok": n <= 1 or (engaged and ratio >= 4.0)})

    # elastic 8->4: the int8 leg's residual-carrying state re-shards;
    # residuals are the LAST state leaf per slot and must migrate
    # bitwise (reshard is byte movement, never arithmetic)
    reshard = None
    if n > 1 and legs["int8"][0]._compress == "int8":
        st = legs["int8"][0]
        res_before = [st._materialize_slot(s)[-1].copy()
                      for s in range(len(st._opt_states))]
        half = max(1, n // 2)
        ElasticContext(st, liveness=lambda: 0).reform(
            devices=jax.devices()[:half])
        bitwise = all(
            onp.array_equal(b, st._materialize_slot(s)[-1])
            for s, b in enumerate(res_before))
        after = float(st(*legs["int8"][1]).asscalar())
        parallel.set_mesh(mesh)
        reshard = {"world_from": n, "world_to": half,
                   "residual_bitwise_ok": bitwise,
                   "loss_finite_after": bool(onp.isfinite(after)),
                   "still_compressed": st._compress == "int8"}

    return {"bench": "grad_compression", "batch_size": batch_size,
            "hidden": hidden, "n_shards": n, "padded_params": padded,
            "legs": out_legs, "reshard": reshard,
            "compressed_ok": all(l.get("compressed_ok", True)
                                 for l in out_legs)
            and (reshard is None
                 or (reshard["residual_bitwise_ok"]
                     and reshard["loss_finite_after"])),
            "parity_ok": all(l.get("parity_ok", True) for l in out_legs)}


def bench_checkpoint_overhead(batch_size=256, hidden=512, iters=8,
                              every=32):
    """A/B of the SAME compiled MLP train step with async checkpointing
    OFF vs ON every ``every`` steps (``mxnet_tpu.checkpoint``): the
    step-side cost is ONE jitted device-copy dispatch + a queue put,
    the host transfer and file IO ride the background writer thread.
    Timed as interleaved min-of-``every``-step windows so both legs see
    the same host contention and every ON window contains exactly one
    snapshot.  ``overhead_pct`` > 2 is a HARD bench failure
    (_hard_failures), mirroring the telemetry-overhead gate: periodic
    durability must stay effectively free on the hot path.  Negative
    deltas are timing noise and clamp to 0.

    The default cadence (every 32 steps) is the floor of "periodic":
    the snapshot dispatch costs roughly one extra step dispatch on the
    virtual-device CPU backend (on a real chip the copy is HBM
    traffic, ~free), so sparser production cadences only lower the
    overhead."""
    import shutil
    import tempfile
    import time
    import numpy as onp
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import checkpoint, gluon, parallel, telemetry
    from mxnet_tpu.gluon import nn

    n = len(jax.local_devices())
    mesh = parallel.device_mesh((n,), ("dp",))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def leg():
        onp.random.seed(7)
        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(hidden, activation="relu"),
                nn.Dense(hidden // 2, activation="relu"), nn.Dense(10))
        net.initialize(mx.init.Xavier())
        x = mx.nd.array(onp.random.rand(batch_size, 123).astype("float32"))
        y = mx.nd.array(
            onp.random.randint(0, 10, (batch_size,)).astype("float32"))
        net(x)
        step = parallel.DataParallelStep(
            net, lambda o, l: loss_fn(o, l),
            mx.optimizer.Adam(learning_rate=1e-3), mesh=mesh,
            shard_optimizer=True)
        step(x, y)   # compile + first update
        return step, (x, y)

    step_off, b_off = leg()
    step_on, b_on = leg()
    ckpt_dir = tempfile.mkdtemp(prefix="mxtpu_bench_ckpt_")
    writes0 = telemetry.counter("ckpt.writes")
    h0 = telemetry.histogram("parallel.step")
    hist_base = h0.to_dict() if h0 is not None else {}
    mgr = checkpoint.CheckpointManager(ckpt_dir, step_on,
                                       every_n_steps=every)
    mgr.attach()
    ms_off = ms_on = None
    try:
        for _ in range(iters):
            t0 = time.perf_counter()
            for _ in range(every):
                step_off(*b_off)
            step_off(*b_off).asnumpy()
            d = (time.perf_counter() - t0) * 1e3
            ms_off = d if ms_off is None else min(ms_off, d)
            t0 = time.perf_counter()
            for _ in range(every):
                step_on(*b_on)
            step_on(*b_on).asnumpy()
            d = (time.perf_counter() - t0) * 1e3
            ms_on = d if ms_on is None else min(ms_on, d)
        flushed = mgr.flush(60.0)
    finally:
        mgr.close()
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    writes = telemetry.counter("ckpt.writes") - writes0
    stats = mgr.stats()
    overhead = max(0.0, (ms_on - ms_off) / ms_off * 100.0)
    # the bench's own steps carved out of the process-lifetime
    # histogram (earlier jobs' observations subtracted bucket-wise)
    hw = telemetry.histogram("parallel.step")
    step_hist = hw.since(hist_base) if hw is not None else None
    return {"bench": "checkpoint_overhead", "batch_size": batch_size,
            "hidden": hidden, "every_n_steps": every, "n_shards": n,
            "window_ms_ckpt_off": round(ms_off, 3),
            "window_ms_ckpt_on": round(ms_on, 3),
            "overhead_pct": round(overhead, 3),
            "overhead_ok": overhead <= 2.0,
            "step_hist": step_hist.to_dict() if step_hist else None,
            "step_hist_summary":
                step_hist.summary() if step_hist else None,
            "ckpt_writes": writes, "ckpt_flushed": bool(flushed),
            "ckpt_bytes": (stats["last_written"] or {}).get("bytes"),
            "ckpt_write_ms": round(
                (stats["last_written"] or {}).get("dur_ms") or 0.0, 3),
            "ckpt_errors": stats["last_error"]}


def bench_serving_latency(rates=(25.0, 100.0, 400.0), duration_s=2.0,
                          feature=64, hidden=256, deadline_ms=500.0,
                          batch_wait_ms=2.0):
    """Open-loop serving latency through the continuous-batching
    inference server (``mxnet_tpu.serve``): a small MLP served from
    bucketed AOT executables, driven at ``rates`` arrival rates
    (requests/s) with submissions on a FIXED schedule — open-loop, so a
    slow server cannot slow the offered load and hide its own queueing.

    Per rate: p50/p99 terminal latency over completed requests,
    throughput, and the outcome census (results/timeouts/rejects).
    Percentiles come from the server's own ``serve.request`` telemetry
    histogram (log-bucketed, fixed memory, mergeable) — each leg is the
    ``since``-delta against the histogram snapshot taken at leg start,
    so the bench reads the same digest production scraping would, not
    a private sample list.  HARD bench failures (_hard_failures):

      * ``steady_state_recompiles > 0`` — the telemetry recompile
        detector saw a serve executable compile during the load phase;
        the bucketed-AOT contract is zero recompiles at steady state;
      * ``p99 > 10 x p50`` at the LOWEST rate — an unloaded server with
        a fat tail means a scheduling/dispatch bug, not queueing;
      * any request with NO terminal outcome — the no-hangs invariant
        is the server's whole robustness contract.
    """
    import numpy as onp
    from mxnet_tpu import serve, telemetry

    rng = onp.random.RandomState(0)
    w1 = rng.randn(feature, hidden).astype("float32") * 0.05
    w2 = rng.randn(hidden, 16).astype("float32") * 0.05

    def fn(x):
        import jax.numpy as jnp
        h = jnp.maximum(x @ jnp.asarray(w1), 0.0)
        return h @ jnp.asarray(w2)

    cfg = serve.ServeConfig(buckets=(1, 2, 4, 8, 16), max_queue=128,
                            batch_wait_ms=batch_wait_ms,
                            default_deadline_ms=deadline_ms,
                            dispatch_timeout_ms=1000.0)
    # percentiles come from the live serve.request histogram — under
    # MXNET_TELEMETRY=0 force telemetry on for the bench's duration so
    # the latency gate never silently judges an empty digest
    was_enabled = telemetry.enabled()
    telemetry.enable()
    srv = serve.InferenceServer(fn, feature_shape=(feature,), config=cfg,
                                name="serving_bench")

    def _q(hist, q):
        if hist is None or hist.count == 0:
            return None
        return round(hist.quantile(q), 3)

    legs = []
    hangs = 0
    try:
        t0 = time.perf_counter()
        srv.start()
        startup_ms = (time.perf_counter() - t0) * 1e3
        x = rng.randn(feature).astype("float32")
        for _ in range(4):          # one warm dispatch before timing
            srv.submit(x).outcome(timeout=2.0)
        for rate in rates:
            n = max(8, int(rate * duration_s))
            hb = telemetry.histogram("serve.request")
            base = hb.to_dict() if hb is not None else {}
            start = time.perf_counter()
            handles = []
            for i in range(n):
                target = start + i / rate
                now = time.perf_counter()
                if target > now:
                    time.sleep(target - now)
                handles.append(srv.submit(x, deadline_ms=deadline_ms))
            outs = [h.outcome(timeout=deadline_ms / 1e3 + 2.0)
                    for h in handles]
            elapsed = time.perf_counter() - start
            kinds = {}
            for o in outs:
                k = o[0] if o is not None else "hang"
                kinds[k] = kinds.get(k, 0) + 1
            hangs += kinds.get("hang", 0)
            # this leg's completions, carved bucket-wise out of the
            # server's lifetime serve.request histogram
            hh = telemetry.histogram("serve.request")
            leg_hist = hh.since(base) if hh is not None else None
            legs.append({
                "rate_per_s": rate, "n_requests": n,
                "completed": kinds.get("result", 0),
                "timeouts": kinds.get("timeout", 0),
                "rejects": kinds.get("reject", 0),
                "hangs": kinds.get("hang", 0),
                "p50_ms": _q(leg_hist, 0.50),
                "p99_ms": _q(leg_hist, 0.99),
                "hist":
                    leg_hist.to_dict() if leg_hist is not None else None,
                "throughput_per_s": round(
                    kinds.get("result", 0) / elapsed, 1)})
        recompiles = srv.steady_state_recompiles()
        stats = srv.stats()
        hist_total = telemetry.histogram("serve.request")
        srv.close()
    finally:
        if not was_enabled:
            telemetry.disable()
    low = legs[0]
    latency_ok = bool(low["p50_ms"]) and low["p99_ms"] is not None \
        and low["p99_ms"] <= 10.0 * low["p50_ms"]
    return {"bench": "serving_latency", "feature": feature,
            "hidden": hidden, "buckets": list(cfg.buckets),
            "deadline_ms": deadline_ms, "batch_wait_ms": batch_wait_ms,
            "startup_compile_ms": round(startup_ms, 1),
            "legs": legs,
            "latency_source": "histogram",
            "latency_hist":
                hist_total.to_dict() if hist_total is not None else None,
            "latency_hist_summary":
                hist_total.summary() if hist_total is not None else None,
            "steady_state_recompiles": sum(recompiles.values()),
            "recompile_ok": not recompiles,
            "latency_ok": latency_ok,
            "terminal_ok": hangs == 0,
            "final_state": stats["state"],
            "quarantined": stats["quarantined"]}


def bench_ssd(batch_size=32, image_size=128, iters=8):
    """SSD detection train step ON-DEVICE (reference example/ssd +
    multibox_target.cu): forward + MultiBoxTarget assignment (pure
    jnp/lax) + SSD loss + backward + SGD as one jitted program — no host
    callbacks."""
    import os
    import sys
    import numpy as onp
    import mxnet_tpu as mx

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "example", "ssd"))
    import train_ssd as T

    rs = onp.random.RandomState(0)
    ratios = (1.0, 2.0, 0.5)
    sizes = ((0.2, 0.27), (0.37, 0.45), (0.54, 0.62))
    a = len(sizes[0]) + len(ratios) - 1
    num_classes = 3
    net = T.SSDNet(num_classes, a)
    net.initialize(mx.init.Xavier(), ctx=mx.tpu())
    anchors = T.build_anchors(image_size, sizes, ratios)
    x, labels = T.synthetic_batch(rs, batch_size, image_size, num_classes)
    x = x.as_in_context(mx.tpu())
    labels = labels.as_in_context(mx.tpu())
    net(x)
    step = mx.parallel.DataParallelStep(
        net, T.SSDLoss(anchors.as_in_context(mx.tpu()), num_classes),
        mx.optimizer.SGD(learning_rate=0.05, momentum=0.9), mesh=None)
    step_s, loss, _ = _time_calls(lambda: step(x, labels), _sync,
                                  iters=iters)
    return {"bench": "ssd_train", "batch_size": batch_size,
            "image_size": image_size, "anchors": int(anchors.shape[1]),
            "step_ms": round(step_s * 1000, 2),
            "img_per_sec": round(batch_size / step_s, 2),
            "loss": round(_scalar(loss), 4)}


def bench_attention(batch=8, heads=16, seqlen=2048, head_dim=64, iters=5,
                    inner=10, dtype="bfloat16", check_error=True):
    """Flash-attention (Pallas TPU kernel) vs dense jnp attention, FULL
    fwd+bwd (gradients w.r.t. q, k AND v — round-4's dq-only grad let
    XLA dead-code-eliminate the dk/dv kernel, overstating throughput
    ~2x).  Proxy for BASELINE.json config 5 (BERT pretraining attention).

    The host→chip dispatch path here costs ~3-6 ms per call, so the
    measured region runs ``inner`` chained fwd+bwd iterations inside ONE
    jitted program (lax.fori_loop with a data dependence) — kernel time,
    not dispatch time.  ``check_error`` also computes the ON-DEVICE max
    abs error of the flash fwd output and all three gradients against
    the dense path (the reference's `check_consistency` discipline,
    python/mxnet/test_utils.py:1283, run on the real chip).
    """
    import os
    import numpy as onp
    import jax
    import jax.numpy as jnp
    from jax import lax
    from mxnet_tpu.ops.pallas_attention import (flash_attention,
                                                attention_dispatch,
                                                tune_attention_blocks)
    from mxnet_tpu import tune as _tune

    rs = onp.random.RandomState(0)
    shape = (batch, heads, seqlen, head_dim)
    q, k, v = (jnp.asarray(rs.uniform(-1, 1, shape).astype("float32"),
                           dtype) for _ in range(3))
    # which kernel the dispatcher picks for this shape (short_seq |
    # streaming | dense_fallback) — recorded so BENCH rounds can see the
    # dispatch decision next to the measured speedup
    plan = attention_dispatch(seqlen, seqlen, head_dim, dtype)

    def dense(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (head_dim ** 0.5)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    def mk_loop(fn):
        grad = jax.grad(lambda q, k, v:
                        jnp.sum(fn(q, k, v).astype(jnp.float32)),
                        argnums=(0, 1, 2))

        @jax.jit
        def loop(q, k, v):
            def body(_, q):
                dq, dk, dv = grad(q, k, v)
                # data dependence on ALL THREE grads, no drift
                return q + 0.0 * (dq + dk + dv).astype(q.dtype)
            return lax.fori_loop(0, inner, body, q)
        return loop

    # true executed FLOPs per path.  flash: fwd 2 dots; backward 5 when
    # the whole K axis fits one block (the fused/single dqkv kernel
    # shares the score/dp recompute — S <= 2048 with tuned blocks) else
    # 7 (split dq + dkv kernels each recompute).  dense runs 6 (fwd 2;
    # bwd dp, dv, dq, dk — softmax residuals saved).
    dot = 2 * batch * heads * seqlen * seqlen * head_dim
    fused_bwd = seqlen <= (plan["block_k"] or 2048)
    n_dots = {"flash": 7 if fused_bwd else 9, "dense": 6}
    out = {"bench": "attention", "shape": list(shape), "dtype": dtype,
           "inner_iters": inner, "grads": "q,k,v",
           "kernel": plan["kernel"],
           "block_q": plan["block_q"], "block_k": plan["block_k"],
           "bwd_kernel": "fused_dqkv" if fused_bwd else "split",
           # where the blocks came from (table-hit | searched |
           # heuristic) and which cost table served them — the
           # artifact-side face of the autotune journal census
           "tuner_source": plan.get("tuner_source"),
           "autotune_table": _tune.table_path()
           if os.path.exists(_tune.table_path()) else None}
    for name, fn in (("flash", flash_attention), ("dense", dense)):
        loop = mk_loop(fn)
        dt, _, _ = _time_calls(
            lambda: loop(q, k, v),
            lambda x: float(jnp.asarray(x[0, 0, 0, 0])),
            warmup=1, iters=iters)
        dt /= inner
        out[name + "_ms"] = round(dt * 1000, 3)
        out[name + "_tflops"] = round(dot * n_dots[name] / dt / 1e12, 1)
    if "flash_ms" in out and "dense_ms" in out:
        out["flash_speedup"] = round(out["dense_ms"] / out["flash_ms"], 2)

    # tuned-vs-heuristic A/B leg: whenever the dispatcher's blocks did
    # NOT come from the heuristic (table hit / on-miss search), ALSO
    # time the heuristic config in the SAME run — interleaved
    # min-of-calls, the ZeRO-bench protocol, so both legs see the same
    # host contention.  A tuned config slower than the heuristic it
    # replaced is a HARD failure (_hard_failures): the table's whole
    # contract is "no shape regresses vs today's clamps".
    heur_bq, heur_bk = tune_attention_blocks(seqlen, seqlen, head_dim,
                                             dtype)
    if plan["kernel"] != "dense_fallback" and \
            (plan["block_q"], plan["block_k"]) != (heur_bq, heur_bk):
        from mxnet_tpu.tune import search as _search
        out["heuristic_config"] = {"block_q": heur_bq, "block_k": heur_bk}
        loop_t, args_t = _search.attention_loop(
            batch, heads, seqlen, seqlen, head_dim, dtype,
            {"block_q": plan["block_q"], "block_k": plan["block_k"]},
            inner=inner)
        loop_h, args_h = _search.attention_loop(
            batch, heads, seqlen, seqlen, head_dim, dtype,
            {"block_q": heur_bq, "block_k": heur_bk}, inner=inner)

        def _one(loop, args):
            t0 = time.perf_counter()
            r = loop(*args)
            float(jnp.asarray(r[0][0, 0, 0, 0]))
            return (time.perf_counter() - t0) * 1e3 / inner
        _one(loop_t, args_t)      # compile + warm both legs
        _one(loop_h, args_h)
        ms_t = ms_h = None
        for _ in range(max(2, iters)):
            d = _one(loop_t, args_t)
            ms_t = d if ms_t is None else min(ms_t, d)
            d = _one(loop_h, args_h)
            ms_h = d if ms_h is None else min(ms_h, d)
        out["tuned_ms"] = round(ms_t, 3)
        out["heuristic_ms"] = round(ms_h, 3)
        out["tuned_vs_heuristic"] = round(ms_h / ms_t, 3)
        out["tuned_ok"] = ms_t <= ms_h * 1.05

    if check_error and "flash_ms" in out and "dense_ms" in out:
        # on-chip cross-check of the custom kernels vs the dense oracle
        @jax.jit
        def errs(q, k, v):
            g = jnp.ones(shape, dtype)
            fo, f_vjp = jax.vjp(flash_attention, q, k, v)
            do_, d_vjp = jax.vjp(dense, q, k, v)
            fg = f_vjp(g)[:3]
            dg = d_vjp(g)
            def mx(a, b):
                return jnp.max(jnp.abs(a.astype(jnp.float32)
                                       - b.astype(jnp.float32)))
            return (mx(fo, do_),) + tuple(mx(a, b) for a, b in zip(fg, dg))
        e_out, e_dq, e_dk, e_dv = (float(x) for x in errs(q, k, v))
        out["max_err"] = {"out": round(e_out, 5), "dq": round(e_dq, 5),
                          "dk": round(e_dk, 5), "dv": round(e_dv, 5)}
        # bf16 inputs: online-softmax vs dense disagreement is rounding-
        # level; anything past this threshold means a broken kernel
        tol = 0.06 if dtype in ("bfloat16", "float16") else 1e-3
        out["max_err_ok"] = all(e < tol for e in (e_out, e_dq, e_dk, e_dv))
    return out


def bench_autotune_program(calls=3):
    """Whole-program schedule knobs, tuned vs heuristic, same-run A/B
    (``prog_prefetch`` depth x decode workers, the ``prog_scan``
    window, the ``prog_buckets`` serving menu; ``prog_zero`` rides the
    composition leg below).  Each family's tuned config comes through
    the SAME ``program_config`` lookup production consumers use — so
    when the committed per-platform baked table holds the entry, the
    leg measures exactly what ``DevicePrefetchIter`` / ``scan_steps``
    / ``default_bucket_menu`` would run, and records the per-shape
    provenance (table | heuristic) the journal census reports.
    Timing is interleaved min-of-calls over the real subsystem
    measures (``tune.program.default_measure``), the ZeRO-bench
    protocol; a tuned schedule slower than the heuristic it replaced
    is a HARD failure (_hard_failures) — the table's contract is "no
    shape regresses vs today's defaults"."""
    import os
    from mxnet_tpu import tune as _tune
    from mxnet_tpu.tune import program as prog
    from mxnet_tpu.tune.cost_table import baked_table_path

    legs = []
    for family in ("prog_prefetch", "prog_scan", "prog_buckets"):
        shape = prog.default_shape(family)
        heur = prog.heuristic_config(family, shape)
        cfg = prog.program_config(family, shape)
        source = cfg.pop("source", "table") if cfg else "heuristic"
        tuned = cfg or dict(heur)
        leg = {"family": family, "shape": list(shape),
               "tuner_source": source, "tuned_config": tuned,
               "heuristic_config": heur}
        if family == "prog_buckets":
            leg["tuned_menu"] = prog.menu_from_config(tuned)
            leg["heuristic_menu"] = prog.menu_from_config(heur)
        measure = prog.default_measure(family, shape)
        try:
            measure(tuned, 1)                    # compile/warm both legs
            if tuned != heur:
                measure(heur, 1)
            # min-of-2 inside each interleave round: the bucket/prefetch
            # measures are sub-millisecond on this box, and a single
            # noisy round must not decide a HARD gate
            ms_t = ms_h = None
            for _ in range(max(3, calls)):
                d = measure(tuned, 2)
                ms_t = d if ms_t is None else min(ms_t, d)
                if tuned != heur:
                    d = measure(heur, 2)
                ms_h = d if ms_h is None else min(ms_h, d)
            leg["tuned_ms"] = round(ms_t, 3)
            leg["heuristic_ms"] = round(ms_h, 3)
            leg["tuned_vs_heuristic"] = round(ms_h / ms_t, 3) if ms_t \
                else None
            # 1.15: host-side schedules on a shared box jitter more
            # than on-chip kernels (attention's gate is 1.05)
            leg["tuned_ok"] = tuned == heur or ms_t <= ms_h * 1.15
        except Exception as e:
            leg["error"] = repr(e)[:300]
            leg["tuned_ok"] = False
        legs.append(leg)
    return {"bench": "autotune_program",
            "table": _tune.table_path()
            if os.path.exists(_tune.table_path()) else None,
            "baked_table": baked_table_path(), "legs": legs,
            "tuned_ok": all(l.get("tuned_ok") for l in legs)}


def bench_autotune_composition(batch=128, hidden=512, iters=6):
    """Autotuner x ZeRO x donation composition leg: the probe MLP
    train step with every measured schedule decision live at once —
    ``shard_optimizer="auto"`` resolved from the ``prog_zero`` table
    entry, the ``scan_steps`` window from ``prog_scan``, weight/state
    buffers donated through the jitted step — against the
    all-heuristic leg (k=1 plain step, heuristic shard decision) in
    the same process, interleaved min-of-window-times.  What it
    guards: the three subsystems must COMPOSE — a tuned schedule that
    wins each knob in isolation but loses when sharding, scan windows
    and donation interact would pass every per-family leg and still
    regress production, so ``tuned_ok`` here is a HARD failure too."""
    import numpy as onp
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.tune import program as prog

    n = len(jax.local_devices())
    mesh = parallel.device_mesh((n,), ("dp",))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def make_step(shard_knob):
        onp.random.seed(7)
        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(hidden, activation="relu"),
                nn.Dense(hidden // 2, activation="relu"), nn.Dense(10))
        net.initialize(mx.init.Xavier())
        x = mx.nd.array(onp.random.rand(batch, 123).astype("float32"))
        y = mx.nd.array(
            onp.random.randint(0, 10, (batch,)).astype("float32"))
        net(x)
        step = parallel.DataParallelStep(
            net, lambda o, l: loss_fn(o, l),
            mx.optimizer.Adam(learning_rate=1e-3), mesh=mesh,
            donate=True, shard_optimizer=shard_knob)
        return step, (x, y)

    # the tuned leg's schedule decisions, via the production lookups
    k = max(1, int(prog.program_knobs("prog_scan", (batch, hidden),
                                      default=1) or 1))
    pcount = (123 * hidden + hidden) \
        + (hidden * (hidden // 2) + hidden // 2) \
        + ((hidden // 2) * 10 + 10)
    zero_cfg = prog.program_config(
        "prog_zero", (prog.canon_param_count(pcount), n), quiet=True)
    scan_cfg = prog.program_config("prog_scan", (batch, hidden),
                                   quiet=True)

    step_t, _ = make_step("auto")       # resolves shard from the table
    step_h, (xh, yh) = make_step(n > 1)  # today's heuristic: shard if
    #                                      the mesh gives >1 way
    rs = onp.random.RandomState(1)
    xs = mx.nd.array(rs.rand(k, batch, 123).astype("float32"))
    ys = mx.nd.array(onp.random.RandomState(2)
                     .randint(0, 10, (k, batch)).astype("float32"))
    step_t.scan_steps(xs, ys).asnumpy()      # compile both legs
    step_h(xh, yh).asnumpy()
    n_steps = -(-8 // k) * k                 # >= 8, a multiple of k
    ms_t = ms_h = None
    for _ in range(max(2, iters)):
        t0 = time.perf_counter()
        c = 0
        while c < n_steps:
            step_t.scan_steps(xs, ys).asnumpy()
            c += k
        d = (time.perf_counter() - t0) * 1e3 / n_steps
        ms_t = d if ms_t is None else min(ms_t, d)
        t0 = time.perf_counter()
        for _ in range(n_steps):
            step_h(xh, yh).asnumpy()
        d = (time.perf_counter() - t0) * 1e3 / n_steps
        ms_h = d if ms_h is None else min(ms_h, d)
    return {
        "bench": "autotune_composition", "batch_size": batch,
        "hidden": hidden, "params": pcount, "dp": n, "donate": True,
        "scan_k": k,
        "scan_source": (scan_cfg or {}).get("source", "heuristic"),
        "shard_tuned": bool(step_t._shard_n),
        "shard_heuristic": bool(step_h._shard_n),
        "zero_source": (zero_cfg or {}).get("source", "heuristic"),
        "auto_path": "measured" if zero_cfg is not None
        else "heuristic",
        "optimizer_state_bytes_per_chip_tuned":
            step_t.optimizer_state_bytes(per_chip=True),
        "optimizer_state_bytes_per_chip_heuristic":
            step_h.optimizer_state_bytes(per_chip=True),
        "step_ms_tuned": round(ms_t, 3),
        "step_ms_heuristic": round(ms_h, 3),
        "tuned_vs_heuristic": round(ms_h / ms_t, 3) if ms_t else None,
        # 1.25: the ZeRO-bench tolerance — both legs dispatch real
        # collectives and the tuned leg may trade step time for state
        # bytes, but it must stay in the same regime
        "tuned_ok": ms_t <= ms_h * 1.25}


def bench_autotune_census(searched_shape=(64, 256)):
    """The artifact-side face of the autotune journal census: every
    cost-table entry visible to THIS process (committed baked layer +
    runtime table) with its provenance, the learned cost model's
    training state per kernel family, and one live model-ranked search
    (layernorm, interpret mode) demonstrating the v2 contract — the
    ranked search must time STRICTLY FEWER candidates than the v1
    exhaustive budget while landing the same winner."""
    from mxnet_tpu import tune as _tune
    from mxnet_tpu.tune import model as _model
    from mxnet_tpu.tune import search as _search
    from mxnet_tpu.tune.cost_table import KERNEL_FAMILIES, baked_table_path

    table = _tune.get_table()
    entries = []
    for rec in table.entries():
        entries.append({
            "family": rec.get("family"), "shape": rec.get("shape"),
            "dtype": rec.get("dtype"), "config": rec.get("config"),
            "source": rec.get("source"),
            "interpret": bool(rec.get("interpret")),
            "baked": bool(rec.get("baked")),
            "best_ms": rec.get("best_ms")})
    models = {}
    for family in KERNEL_FAMILIES:
        m = _model.get_model(family, table=table)
        if m is None:
            models[family] = {"usable": False, "reason":
                              "untrained_or_cv"}
        else:
            models[family] = {"usable": True,
                              "n_samples": m.n_samples,
                              "cv_error": round(m.cv_error, 4)}
    out = {"bench": "autotune_census",
           "baked_table": baked_table_path(), "entries": entries,
           "model": models}
    # live ranked-vs-exhaustive demo at a shape the table has not seen
    m = _model.get_model("layernorm", table=table)
    if m is not None:
        space = len(_search.candidates("layernorm", searched_shape,
                                       "float32"))
        res = _search.search_config("layernorm", searched_shape,
                                    "float32", trials=space, calls=1,
                                    interpret=True, model=m)
        if res is not None:
            out["ranked_search"] = {
                "family": "layernorm", "shape": list(searched_shape),
                "space": res["space"], "v1_budget": space,
                "trials": res["trials"],
                "ranked": bool(res.get("ranked")),
                "config": res["config"],
                "fewer_than_v1": res["trials"] < space}
    return out


def r06_artifact(out_path):
    """Cut BENCH_r06: the autotuner-v2 round.  Three legs — per-family
    tuned-vs-heuristic program A/Bs, the autotuner x ZeRO x donation
    composition step, and the table/model/provenance census — plus the
    run's telemetry snapshot, wrapped in the BENCH_rNN series' outer
    format.  Any ``tuned_ok: false`` is a HARD failure (exit 3): a
    committed table entry that loses to the heuristic it replaced must
    be re-tuned or deleted, never shipped."""
    from mxnet_tpu import telemetry

    details = []
    for job in (bench_autotune_program, bench_autotune_composition,
                bench_autotune_census):
        details.append(job())
        _emit(details[-1], log=True)
    tsnap = telemetry.snapshot(events=0)
    details.append({
        "bench": "telemetry_snapshot",
        "counters": {k: v for k, v in tsnap["counters"].items()
                     if k.startswith(("autotune.", "donation.",
                                      "zero.", "serve."))},
        "compiles": tsnap["compiles"]})
    comp = next((d for d in details
                 if d.get("bench") == "autotune_composition"), {})
    hard = _hard_failures(details)
    inner = {"metric": "autotune_composition_step_ms_tuned",
             "value": comp.get("step_ms_tuned"), "unit": "ms",
             "vs_baseline": comp.get("tuned_vs_heuristic"),
             "detail": details}
    if hard:
        inner["hard_failures"] = hard
    summary = {k: v for k, v in inner.items() if k != "detail"}
    from mxnet_tpu.fsutil import atomic_write_path
    with atomic_write_path(out_path) as tmp_out:
        with open(tmp_out, "w") as f:
            json.dump({"n": 6, "cmd": "python bench.py --r06",
                       "device": _device(),
                       "rc": 3 if hard else 0,
                       "tail": json.dumps(summary),
                       "parsed": inner}, f, indent=1)
    _emit(summary)
    for h in hard:
        print("# HARD FAIL: %s" % h, file=sys.stderr)
    if hard:
        sys.exit(3)


def multichip_r06_artifact(out_path):
    """Cut MULTICHIP_r06: the compressed-collectives round.  One leg —
    the interleaved f32 / int8 / fp8 A/B of the sharded train step at
    dp = every local device (``bench_grad_compression``: bytes/chip,
    step ms, loss-parity deltas, and the elastic 8->4 reshard of the
    residual-carrying state) — plus the run's telemetry snapshot
    (compress/decision journal + compression gauges), wrapped in the
    BENCH_rNN series' outer format with the multichip header.  Any
    ``compressed_ok: false`` or parity breach is a HARD failure
    (exit 3): a wire that silently never narrowed, or one that
    narrowed by breaking the numerics, must never ship."""
    import jax
    from mxnet_tpu import telemetry

    details = [bench_grad_compression()]
    tsnap = telemetry.snapshot(events=256)
    details.append({
        "bench": "telemetry_snapshot",
        "counters": {k: v for k, v in tsnap["counters"].items()
                     if k.startswith(("zero.", "donation."))},
        "gauges": {k: v for k, v in tsnap["gauges"].items()
                   if k.startswith(("compression.", "parallel."))},
        "compress_decisions": [
            e for e in tsnap.get("events", [])
            if e.get("kind") == "compress"]})
    _emit(details[0], log=True)
    gc = details[0]
    hard = _hard_failures(details)
    int8_leg = next((l for l in (gc.get("legs") or [])
                     if l.get("mode") == "int8"), {})
    inner = {"metric": "grad_wire_ratio_int8",
             "value": int8_leg.get("wire_ratio"), "unit": "x",
             "vs_baseline": int8_leg.get("parity_max_abs"),
             "detail": details}
    if hard:
        inner["hard_failures"] = hard
    summary = {k: v for k, v in inner.items() if k != "detail"}
    from mxnet_tpu.fsutil import atomic_write_path
    with atomic_write_path(out_path) as tmp_out:
        with open(tmp_out, "w") as f:
            json.dump({"n": 6, "n_devices": len(jax.local_devices()),
                       "device": _device(),
                       "cmd": "python bench.py --multichip-r06",
                       "rc": 3 if hard else 0, "ok": not hard,
                       "tail": json.dumps(summary),
                       "parsed": inner}, f, indent=1)
    _emit(summary)
    for h in hard:
        print("# HARD FAIL: %s" % h, file=sys.stderr)
    if hard:
        sys.exit(3)


def smoke():
    """Seconds-scale sanity run (CPU-safe): tiny net, tiny batch."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"))
    net.add(nn.Dense(10))
    net.initialize()
    x = mx.nd.array(onp.random.rand(8, 16).astype("float32"))
    net(x)
    step = mx.parallel.DataParallelStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.SGD(learning_rate=0.1), mesh=None)
    y = mx.nd.array(onp.random.randint(0, 10, (8,)).astype("float32"))
    step_s, _, _ = _time_calls(lambda: step(x, y), _sync, warmup=2, iters=5,
                               reps=1)
    _emit({"metric": "smoke_mlp_step", "value": round(step_s * 1000, 3),
           "unit": "ms", "vs_baseline": None})


def serving_artifact(out_path):
    """Cut the SERVE artifact: the serving-latency sweep (3 open-loop
    arrival rates) + the run's telemetry snapshot, one JSON file.
    Exits nonzero on any serving HARD failure (recompiles at steady
    state, fat low-rate tail, non-terminal requests)."""
    from mxnet_tpu import telemetry

    result = bench_serving_latency()
    tsnap = telemetry.snapshot(events=0)
    details = [result,
               {"bench": "telemetry_snapshot",
                "spans": tsnap["spans"],
                "counters": {k: v for k, v in tsnap["counters"].items()
                             if k.startswith("serve.")},
                "compiles": {k: v for k, v in tsnap["compiles"].items()
                             if k.startswith("serve.")}}]
    low = (result.get("legs") or [{}])[0]
    out = {"metric": "serving_p99_ms_low_rate",
           "value": low.get("p99_ms"), "unit": "ms",
           "vs_baseline": None, "device": _device(), "detail": details}
    from mxnet_tpu.fsutil import atomic_write_path
    with atomic_write_path(out_path) as tmp_out:
        with open(tmp_out, "w") as f:
            json.dump(out, f, indent=1)
    _emit({k: v for k, v in out.items() if k != "detail"})
    hard = _hard_failures(details)
    for h in hard:
        print("# HARD FAIL: %s" % h, file=sys.stderr)
    if hard:
        sys.exit(3)


def main():
    # executable reuse across runs: the bench's wall time is dominated by
    # XLA compiles, which the persistent cache eliminates on repeats
    from mxnet_tpu.engine import enable_compilation_cache
    enable_compilation_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50_v1")
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--full", action="store_true",
                    help="bs sweep + inference + LSTM LM + attention")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--serving", action="store_true",
                    help="run just the serving-latency bench and cut the "
                         "SERVE artifact (default SERVE_r01.json)")
    ap.add_argument("--serving-out", default="SERVE_r01.json")
    ap.add_argument("--r06", action="store_true",
                    help="run just the autotuner-v2 legs (program "
                         "schedule A/Bs, ZeRO/donation composition, "
                         "table census) and cut the BENCH_r06 artifact")
    ap.add_argument("--r06-out", default="BENCH_r06.json")
    ap.add_argument("--multichip-r06", action="store_true",
                    help="run just the compressed-collectives A/B "
                         "(f32/int8/fp8 sharded step + elastic reshard "
                         "of residual state) and cut the MULTICHIP_r06 "
                         "artifact")
    ap.add_argument("--multichip-r06-out", default="MULTICHIP_r06.json")
    args = ap.parse_args()

    if args.smoke:
        smoke()
        return
    if args.serving:
        serving_artifact(args.serving_out)
        return
    if args.r06:
        r06_artifact(args.r06_out)
        return
    if args.multichip_r06:
        multichip_r06_artifact(args.multichip_r06_out)
        return

    jobs = []
    if args.full:
        for bs in (32, 64, 128, 256):
            for dt in ("float32", "bfloat16"):
                jobs.append(lambda bs=bs, dt=dt: bench_train(
                    args.model, bs, dt, iters=args.iters))
        for bs in (128, 256):
            jobs.append(lambda bs=bs: bench_train(
                args.model, bs, "bfloat16", iters=args.iters,
                mirror="mirror"))
        for dt in ("float32", "bfloat16"):
            jobs.append(lambda dt=dt: bench_inference(
                args.model, 128, dt, iters=args.iters))
        jobs.append(lambda: bench_lstm_lm(iters=args.iters))
        jobs.append(lambda: bench_lstm_lm(dtype="bfloat16", iters=args.iters))
        jobs.append(lambda: bench_attention(seqlen=512,
                                            iters=max(1, args.iters // 4)))
        jobs.append(lambda: bench_attention(iters=max(1, args.iters // 4)))
        jobs.append(lambda: bench_attention(batch=2, seqlen=4096,
                                            iters=max(1, args.iters // 4)))
        jobs.append(lambda: bench_attention(batch=1, heads=8, seqlen=8192,
                                            iters=max(1, args.iters // 4),
                                            check_error=False))
        jobs.append(lambda: bench_bert(iters=args.iters, pipelined_k=4))
        jobs.append(lambda: bench_bert(iters=max(2, args.iters // 2),
                                       head="full"))
        jobs.append(lambda: bench_ssd(iters=max(4, args.iters // 3)))
        jobs.append(lambda: bench_ssd(batch_size=16, image_size=224,
                                      iters=max(4, args.iters // 3)))
        jobs.append(lambda: bench_telemetry_overhead(
            iters=max(6, args.iters // 2)))
        jobs.append(lambda: bench_zero_sharded_update(
            iters=max(4, args.iters // 3)))
        jobs.append(lambda: bench_grad_compression(
            iters=max(3, args.iters // 4)))
        jobs.append(lambda: bench_checkpoint_overhead(
            iters=max(4, args.iters // 3)))
        # autotuner v2: program-schedule A/Bs + the autotuner x ZeRO x
        # donation composition step (tuned_ok hard gates)
        jobs.append(bench_autotune_program)
        jobs.append(lambda: bench_autotune_composition(
            iters=max(4, args.iters // 3)))
        # serving latency under open-loop load (3 arrival rates);
        # recompiles-at-steady-state / fat-tail-at-low-rate / any
        # non-terminal request are HARD failures
        jobs.append(lambda: bench_serving_latency(duration_s=1.0))
        jobs.append(bench_input_pipeline)
    else:
        # the default run covers every BASELINE.json config (the driver
        # records exactly this output), at short iteration counts:
        # 1-2) ResNet-50 train fp32/bf16.  Plain (non-mirror) is the
        # default and the headline: on this chip the step is HBM-bound
        # and mirror remat is a MEMORY knob, not a speed knob (measured
        # slower at bs>=128); it is still reported for bs=128 so both
        # numbers ship in every artifact.
        it = args.iters
        jobs.append(lambda: bench_train(args.model, args.batch_size,
                                        "float32", iters=it))
        jobs.append(lambda: bench_train(args.model, 64, "bfloat16",
                                        iters=it))
        jobs.append(lambda: bench_train(args.model, 128, "bfloat16",
                                        iters=it, pipelined_k=8))
        jobs.append(lambda: bench_train(args.model, 128, "bfloat16",
                                        iters=it, mirror="mirror"))
        jobs.append(lambda: bench_train(args.model, 256, "bfloat16",
                                        iters=it))
        # 3) ResNet-50 inference
        jobs.append(lambda: bench_inference(args.model, 128, "float32",
                                            iters=it))
        jobs.append(lambda: bench_inference(args.model, 128, "bfloat16",
                                            iters=it))
        # 4) LSTM LM train step (cuDNN-RNN capability config)
        jobs.append(lambda: bench_lstm_lm(iters=max(8, it // 2)))
        jobs.append(lambda: bench_lstm_lm(dtype="bfloat16",
                                          iters=max(8, it // 2)))
        # 5) BERT MLM train (padded, flash-masked) + attention microbench
        # at BERT's production shape (S=512), the headline S=2048, and a
        # long-context point (S=4096; smaller batch so the dense oracle
        # fits for the on-chip error check)
        jobs.append(lambda: bench_attention(seqlen=512,
                                            iters=max(2, it // 4)))
        jobs.append(lambda: bench_attention(iters=max(2, it // 4)))
        jobs.append(lambda: bench_attention(batch=2, seqlen=4096,
                                            iters=max(2, it // 4)))
        # long-seq autotune tail shape (S=8192, streaming kernel): the
        # ROADMAP item-4 success bar names S=512 and long-seq as the
        # shapes the cost table must improve; smaller batch/heads so the
        # dense comparison leg's (B,H,S,S) probabilities fit HBM, and no
        # dense-oracle error check at this extent
        jobs.append(lambda: bench_attention(batch=1, heads=8, seqlen=8192,
                                            iters=max(2, it // 4),
                                            check_error=False))
        # masked head is the headline (the reference pretraining shape:
        # decode only the 15% masked positions); the full-decode point
        # ships alongside for continuity with r1-r4 artifacts
        jobs.append(lambda: bench_bert(iters=max(6, it // 2),
                                       pipelined_k=4))
        jobs.append(lambda: bench_bert(iters=max(3, it // 4),
                                       head="full"))
        # detection train step (device-side MultiBoxTarget, no callbacks):
        # the 128px smoke config plus an SSD300-scale capability config
        # (224px -> 16.5k anchors, ~1.9x real SSD300's 8732)
        jobs.append(lambda: bench_ssd(iters=max(4, it // 3)))
        jobs.append(lambda: bench_ssd(batch_size=16, image_size=224,
                                      iters=max(4, it // 3)))
        # always-on telemetry must stay <= 2% on the hot step (hard gate)
        jobs.append(lambda: bench_telemetry_overhead(iters=max(6, it // 2)))
        # ZeRO sharded-update A/B: per-chip optimizer-state bytes +
        # step time, replicated vs shard_optimizer=True (dp mesh over
        # all local devices; n_shards=1 degenerates gracefully)
        jobs.append(lambda: bench_zero_sharded_update(
            iters=max(4, it // 3)))
        # compressed gradient collectives A/B (f32/int8/fp8 sharded
        # step): wire bytes must narrow 4x with loss parity held, and
        # the residual-carrying state must survive an elastic reshard
        # bitwise — compressed_ok/parity_ok are hard gates; the
        # standalone MULTICHIP_r06 artifact cuts from the same leg
        jobs.append(lambda: bench_grad_compression(
            iters=max(3, it // 4)))
        # async checkpointing must stay <= 2% on the hot step at the
        # default cadence (hard gate, mirroring the telemetry gate)
        jobs.append(lambda: bench_checkpoint_overhead(
            iters=max(4, it // 3)))
        # autotuner v2: program-schedule A/Bs + the autotuner x ZeRO x
        # donation composition step (tuned_ok hard gates); --r06 cuts
        # the standalone BENCH_r06 artifact from the same legs
        jobs.append(bench_autotune_program)
        jobs.append(lambda: bench_autotune_composition(
            iters=max(4, it // 3)))
        # input pipeline (rec -> host -> device -> step legs), in THIS
        # process: the chip belongs to one process at a time, so a child
        # that needs it cannot run under a parent that holds it
        jobs.append(bench_input_pipeline)
    # a job that raises fails the run: an artifact with a hole in it is
    # not a record
    details = []
    for job in jobs:
        details.append(job())
        _emit(details[-1], log=True)

    flags = _sanity_gates(details)
    for f in flags:
        print("# SANITY: %s" % f, file=sys.stderr)
    _update_history(details)

    # embed the run's telemetry in the artifact (the in-process snapshot
    # API): span aggregates, compile/retrace counts, donation/dispatch
    # counters — the observability record next to the numbers
    from mxnet_tpu import telemetry
    tsnap = telemetry.snapshot(events=0)
    details.append({"bench": "telemetry_snapshot",
                    "spans": tsnap["spans"],
                    "counters": tsnap["counters"],
                    "gauges": tsnap["gauges"],
                    "compiles": tsnap["compiles"]})

    headline = None
    for d in details:  # headline: the BASELINE train target, bf16 bs128
        if d.get("bench") == "train" and d.get("dtype") == "bfloat16" \
                and d.get("batch_size") == 128 and not d.get("mirror") \
                and "img_per_sec" in d:
            headline = d
    # headline value: the pipelined (scan_steps) throughput when measured —
    # the framework's documented training loop, and robust to per-call
    # host-dispatch jitter (rep spread ~0.3% vs ~10%); the per-call
    # number always ships alongside it in the same detail dict.
    metric = "%s_train_bs%d_%s" % (args.model, headline["batch_size"],
                                   headline["dtype"])
    if "img_per_sec_pipelined" in headline:
        out = {"metric": metric + "_pipelined",
               "value": headline["img_per_sec_pipelined"],
               "unit": "img/s",
               "vs_baseline": headline.get("vs_baseline_pipelined"),
               "detail": details}
    else:
        out = {"metric": metric,
               "value": headline["img_per_sec"],
               "unit": "img/s",
               "vs_baseline": headline.get("vs_baseline"),
               "detail": details}
    if flags:
        out["sanity_flags"] = flags
    _emit(out)
    hard = _hard_failures(details)
    if hard:
        # numerics gate: the artifact still ships (printed above), but a
        # wrong kernel or a dispatch choice that loses to dense fails the
        # run — perf runs double as correctness gates
        for h in hard:
            print("# HARD FAIL: %s" % h, file=sys.stderr)
        sys.exit(3)


def _hard_failures(details):
    """Failures that exit the bench nonzero (unlike _sanity_gates flags):

      * any ``max_err_ok: false`` — a kernel produced wrong numbers on
        chip, so every throughput number in the artifact is suspect;
      * ``flash_speedup < 1.0`` at S=512 when a kernel (not the dense
        fallback) was dispatched — the round-5 regression shape; the
        dispatcher exists precisely so this shape never loses to dense;
      * ``tuned_ok: false`` — a cost-table/searched config measured
        SLOWER than the heuristic config in the same-run A/B leg; the
        autotuner's contract is "no shape regresses vs today's clamps",
        so a regressing table entry fails the run (re-tune or delete
        the entry);
      * ``telemetry_overhead`` > 2% — the always-on telemetry layer's
        whole contract is that it is too cheap to ever turn off; the
        ON leg must also PROVE the instrumentation was live (per-step
        trace contexts observed + histogram counts advanced), else the
        budget was measured against a dead path;
      * ``checkpoint_overhead`` > 2% — async checkpointing at the
        default cadence must be effectively free on the hot step, or
        nobody leaves durability on in production;
      * ``grad_compression`` ``compressed_ok: false`` — a compressed
        leg's wire never engaged, its payload ratio came in under the
        4x contract, or the residual-carrying state failed the elastic
        reshard bitwise check — and ``parity_ok: false`` — the int8/
        fp8 trajectory left the loss-parity band vs the uncompressed
        sharded step: a wire that saves bytes by corrupting gradients
        must never cut an artifact.
    """
    hard = []
    for d in details:
        if not isinstance(d, dict):
            continue
        if d.get("bench") == "telemetry_overhead" \
                and d.get("overhead_ok") is False:
            hard.append("telemetry overhead %.2f%% > 2%% on the "
                        "bert_mlm_train step" % d.get("overhead_pct", 0))
        if d.get("bench") == "telemetry_overhead" \
                and ("telemetry_hist_count" in d
                     or "telemetry_traced" in d) \
                and not (d.get("telemetry_hist_count")
                         and d.get("telemetry_traced")):
            # the 2% budget is only meaningful if the ON leg really had
            # trace contexts + histograms live — a dead instrumentation
            # path measuring 0% overhead proves nothing
            hard.append("telemetry overhead leg ran without live "
                        "instrumentation (hist_count=%s, traced=%s) — "
                        "the 2%% gate measured a dead path"
                        % (d.get("telemetry_hist_count"),
                           d.get("telemetry_traced")))
        if d.get("bench") == "checkpoint_overhead" \
                and d.get("overhead_ok") is False:
            hard.append("async checkpoint overhead %.2f%% > 2%% at "
                        "cadence every=%s on the MLP train step"
                        % (d.get("overhead_pct", 0),
                           d.get("every_n_steps")))
        if d.get("max_err_ok") is False:
            hard.append("max_err_ok false: %s %s max_err=%s"
                        % (d.get("bench"), d.get("shape"),
                           d.get("max_err")))
        if d.get("bench") == "attention" \
                and (d.get("shape") or [None] * 3)[2] == 512 \
                and d.get("kernel") not in (None, "dense_fallback") \
                and d.get("flash_speedup") is not None \
                and d["flash_speedup"] < 1.0:
            hard.append("attention S=512 flash_speedup %.2f < 1.0 "
                        "(kernel=%s)" % (d["flash_speedup"], d["kernel"]))
        if d.get("bench") == "serving_latency":
            if d.get("recompile_ok") is False:
                hard.append(
                    "serving steady-state recompiles: %s serve "
                    "executables compiled during the load phase — the "
                    "bucketed-AOT menu must compile at startup ONLY"
                    % d.get("steady_state_recompiles"))
            if d.get("latency_ok") is False:
                low = (d.get("legs") or [{}])[0]
                hard.append(
                    "serving p99 %.3f ms > 10x p50 %.3f ms at the low "
                    "rate (%s req/s) — fat tail on an unloaded server"
                    % (low.get("p99_ms") or 0, low.get("p50_ms") or 0,
                       low.get("rate_per_s")))
            if d.get("terminal_ok") is False:
                hard.append(
                    "serving requests with NO terminal outcome — the "
                    "no-hangs invariant failed under synthetic load")
        if d.get("bench") == "attention" and d.get("tuned_ok") is False:
            hard.append(
                "attention %s tuned config (bq=%s, bk=%s, source=%s) "
                "slower than heuristic %s in the same-run A/B leg "
                "(%.3f ms vs %.3f ms)" % (
                    d.get("shape"), d.get("block_q"), d.get("block_k"),
                    d.get("tuner_source"), d.get("heuristic_config"),
                    d.get("tuned_ms", 0), d.get("heuristic_ms", 0)))
        if d.get("bench") == "autotune_program" \
                and d.get("tuned_ok") is False:
            for leg in (d.get("legs") or []):
                if leg.get("tuned_ok") is False:
                    hard.append(
                        "program schedule %s %s: tuned %s (source=%s) "
                        "lost to heuristic %s (%.3f ms vs %.3f ms) in "
                        "the same-run A/B — re-tune or delete the "
                        "table entry" % (
                            leg.get("family"), leg.get("shape"),
                            leg.get("tuned_config"),
                            leg.get("tuner_source"),
                            leg.get("heuristic_config"),
                            leg.get("tuned_ms", 0),
                            leg.get("heuristic_ms", 0)))
        if d.get("bench") == "autotune_composition" \
                and d.get("tuned_ok") is False:
            hard.append(
                "autotuner x ZeRO x donation composition: tuned leg "
                "(scan_k=%s from %s, shard=%s from %s) %.3f ms/step "
                "vs heuristic %.3f ms/step — the measured schedule "
                "regresses when the subsystems compose" % (
                    d.get("scan_k"), d.get("scan_source"),
                    d.get("shard_tuned"), d.get("zero_source"),
                    d.get("step_ms_tuned", 0),
                    d.get("step_ms_heuristic", 0)))
        if d.get("bench") == "grad_compression":
            if d.get("compressed_ok") is False:
                bad = [l for l in (d.get("legs") or [])
                       if l.get("compressed_ok") is False]
                rs = d.get("reshard") or {}
                for l in bad:
                    hard.append(
                        "grad compression %s: engaged=%s wire_ratio=%s "
                        "< 4.0 at dp=%s — the compressed wire contract "
                        "failed" % (l.get("mode"), l.get("engaged"),
                                    l.get("wire_ratio"),
                                    d.get("n_shards")))
                if rs and not (rs.get("residual_bitwise_ok")
                               and rs.get("loss_finite_after")):
                    hard.append(
                        "grad compression elastic %s->%s reshard: "
                        "residual_bitwise_ok=%s loss_finite_after=%s — "
                        "error-feedback state must migrate bitwise and "
                        "keep training" % (
                            rs.get("world_from"), rs.get("world_to"),
                            rs.get("residual_bitwise_ok"),
                            rs.get("loss_finite_after")))
            if d.get("parity_ok") is False:
                for l in (d.get("legs") or []):
                    if l.get("parity_ok") is False:
                        hard.append(
                            "grad compression %s loss parity breach: "
                            "max |dloss| %s > tol %s vs the "
                            "uncompressed sharded step" % (
                                l.get("mode"), l.get("parity_max_abs"),
                                l.get("parity_tol")))
        if d.get("bench") == "autotune_census":
            rs = d.get("ranked_search")
            if rs is not None and rs.get("fewer_than_v1") is False:
                hard.append(
                    "model-ranked search timed %s candidates at "
                    "layernorm %s — not strictly fewer than the v1 "
                    "exhaustive budget %s; the cost model bought "
                    "nothing" % (rs.get("trials"), rs.get("shape"),
                                 rs.get("v1_budget")))
    return hard


def _train_key(d):
    return (d.get("bench"), d.get("model"), d.get("batch_size"),
            d.get("dtype"), d.get("mirror") or None, d.get("image_size"))


def _sanity_gates(details):
    """Physical-plausibility and regression checks over a finished run.

    Flags (never fails the run — the artifact must still ship):
      * bf16 inference slower than fp32 at the same batch — physically
        implausible on this chip, indicates a noisy window;
      * >25% throughput drop vs the most recent local history entry for
        the same config (BENCH_HISTORY.json, appended every run).
    """
    flags = []
    inf = {d.get("dtype"): d for d in details
           if d.get("bench") == "inference"
           and str(d.get("model", "")).startswith("resnet50")
           and "img_per_sec" in d}
    if "float32" in inf and "bfloat16" in inf and \
            inf["bfloat16"]["img_per_sec"] < inf["float32"]["img_per_sec"]:
        flags.append("implausible: bf16 inference (%.0f img/s) slower than "
                     "fp32 (%.0f img/s) — rerun, this is measurement noise"
                     % (inf["bfloat16"]["img_per_sec"],
                        inf["float32"]["img_per_sec"]))
    for d in details:
        if isinstance(d, dict) and d.get("max_err_ok") is False:
            flags.append("KERNEL ERROR: %s %s on-chip max_err %s exceeds "
                         "tolerance vs the dense oracle"
                         % (d.get("bench"), d.get("shape"),
                            d.get("max_err")))
        if isinstance(d, dict) and d.get("bench") == "attention" \
                and d.get("kernel") not in (None, "dense_fallback") \
                and d.get("flash_speedup") is not None \
                and d["flash_speedup"] < 1.0:
            # on-chip dispatch contract: flash (with the dispatcher's
            # kernel choice) must never lose to dense at a benched shape
            flags.append("KERNEL REGRESSION: attention %s kernel=%s "
                         "flash_speedup %.2f < 1.0 — dispatcher picked a "
                         "kernel that loses to dense XLA"
                         % (d.get("shape"), d.get("kernel"),
                            d["flash_speedup"]))
    hist = _load_history()
    if hist:
        prev = {}
        for run in hist:
            for d in run.get("details", []):
                for fld in ("img_per_sec", "img_per_sec_pipelined"):
                    if fld in d:
                        prev[_train_key(d) + (fld,)] = d[fld]
        for d in details:
            for fld in ("img_per_sec", "img_per_sec_pipelined"):
                if fld not in d:
                    continue
                p = prev.get(_train_key(d) + (fld,))
                if p and d[fld] < 0.75 * p:
                    flags.append(
                        ">25%% regression vs last run: %s %s %.0f -> %.0f "
                        "img/s" % (_train_key(d), fld, p, d[fld]))
    return flags


def _history_path():
    import os
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_HISTORY.json")


def _load_history():
    try:
        with open(_history_path()) as f:
            return json.load(f)
    except Exception:
        return []


def _update_history(details, keep=12):
    hist = _load_history()
    hist.append({"ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
                 "details": [d for d in details
                             if isinstance(d, dict) and "error" not in d]})
    try:
        from mxnet_tpu.fsutil import atomic_write_path
        with atomic_write_path(_history_path()) as tmp_out:
            with open(tmp_out, "w") as f:
                json.dump(hist[-keep:], f)
    except Exception:
        pass


if __name__ == "__main__":
    main()
