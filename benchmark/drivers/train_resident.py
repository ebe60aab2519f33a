"""Driver ``train_resident``: a ``DataParallelStep`` on one seeded batch
that stays on the device — no input pipeline, the step is what is judged.

The mesh comes from the cell's data alone: the traffic file gives
``{"mesh": {"dp": n}, "shard_optimizer": bool}``, the configuration gives
the rows per chip, and ``n`` devices are taken from ``jax.devices()`` as
``chip_smoke.four_chip_phase`` takes them.

The loop is the one a Gluon user writes: dispatch a step, read the loss of
the step before the previous one (``IN_FLIGHT`` steps are on their way), so
the host neither serialises the device nor runs ahead without bound.  A
step's completion is when ``block_until_ready`` on its loss returns.
"""
import collections
import gc
import math
import statistics
import time

WARMUP_STEPS = 3      # donation settles buffer layouts over the first calls
IN_FLIGHT = 2


def _wait(loss):
    loss.wait_to_read()
    return time.perf_counter()


def _step_memory(step, run_step):
    """``memory_analysis()`` of the compiled step: the cached jitted step
    lowered again at the specs of one real call and compiled (a cache
    hit).  Reaches into ``step._cache`` as ``chip_smoke`` does; where a
    later program no longer has it, returns None and ``step_hbm_gb.train``
    is left out."""
    import jax

    try:
        (key, jitted), = step._cache.items()
    except (AttributeError, ValueError):
        return None
    seen = {}

    def spec(a):
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding if a.committed else None)

    def spy(*args):
        seen["specs"] = jax.tree_util.tree_map(spec, args)
        return jitted(*args)

    step._cache[key] = spy
    try:
        _wait(run_step())
    finally:
        step._cache[key] = jitted
    compiled = jitted.lower(*seen["specs"]).compile()
    mem = compiled.memory_analysis()
    return {"temp_bytes": int(mem.temp_size_in_bytes),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "generated_code_bytes": int(mem.generated_code_size_in_bytes),
            "pallas_custom_calls": compiled.as_text().count(
                'custom_call_target="tpu_custom_call"')}


def run(bench):
    import jax
    from mxnet_tpu import parallel
    from benchmark import correct, harness

    traffic, sizes = bench.traffic, bench.sizes
    dp = int(traffic.get("mesh", {}).get("dp", 1))
    if dp != len(bench.devices):
        raise RuntimeError("traffic asks for dp=%d, the cell for %d chips"
                           % (dp, len(bench.devices)))
    mesh = parallel.device_mesh((dp,), ("dp",), devices=bench.devices) \
        if dp > 1 else None
    global_batch = sizes["train"]["batch_per_chip"] * dp

    # ---- set-up: weights and batch from the seed, the reference check,
    # warm-up of the one shape this cell uses
    t = time.perf_counter()
    built = bench.model.build_train(
        sizes, bench.seed, global_batch, mesh=mesh,
        shard_optimizer=bool(traffic.get("shard_optimizer", False)))
    build_s = time.perf_counter() - t
    t = time.perf_counter()
    reference = correct.logits_agree(*built["check"]())
    check_s = time.perf_counter() - t
    t = time.perf_counter()
    run_step = built["run"]
    losses = [run_step() for _ in range(WARMUP_STEPS)]
    _wait(losses[-1])
    memory = _step_memory(built["step"], run_step)
    bench.say("setup", build_s=build_s, check_s=check_s,
              warmup_s=time.perf_counter() - t, reference_check=reference,
              step_memory=memory, compiles=bench.compiles.snapshot(),
              optimizer_shards=getattr(built["step"], "_shard_n", None))
    gc.collect()

    # ---- the window: all the steps started in --seconds, and all the
    # time until the last of them has completed
    trace_at = bench.seconds - min(bench.seconds / 2, bench.trace_seconds) \
        if bench.traced else None
    pending, done_at, window_losses = collections.deque(), [], []
    bench.window_opens()
    start = time.perf_counter()
    while True:
        elapsed = time.perf_counter() - start
        if elapsed >= bench.seconds:
            break
        if trace_at is not None and elapsed >= trace_at:
            bench.trace_start()
            trace_at = None
        with bench.span("bench.step_dispatch"):
            loss = run_step()
        pending.append(loss)
        window_losses.append(loss)
        if len(pending) > IN_FLIGHT:
            with bench.span("bench.step_wait"):
                done_at.append(_wait(pending.popleft()))
    with bench.span("bench.step_wait"):
        done_at += [_wait(loss) for loss in pending]
    end = done_at[-1]
    if bench.traced:
        bench.trace_stop()

    # ---- what the window showed
    values = [float(l.asnumpy().astype("float32").mean())
              for l in window_losses]
    failed = sum(1 for v in values if not math.isfinite(v))
    steps, window_s = len(values), end - start
    intervals = [b - a for a, b in zip(done_at, done_at[1:])]
    head, tail = values[:3], values[-3:]
    fell = steps < 6 or sum(tail) / len(tail) < sum(head) / len(head)
    bench.say("window", steps=steps, window_s=window_s,
              global_batch=global_batch, first_losses=head,
              last_losses=tail, loss_fell=fell,
              step_ms_median=statistics.median(intervals) * 1e3,
              step_ms_max=max(intervals) * 1e3,
              step_ms_min=min(intervals) * 1e3,
              samples_behind_p95=steps - 1 - math.ceil(0.95 * (steps - 1)),
              memory_stats=bench.devices[0].memory_stats())
    return {
        "correct": bool(reference["ok"] and failed == 0 and fell),
        "attempted": steps, "failed": failed,
        "end_to_end": {
            "train_samples_per_s": steps * global_batch / window_s,
            "train_step_p95_ms": harness.p95(intervals) * 1e3},
        "facts": {"steps": steps, "window_s": window_s,
                  "global_batch": global_batch,
                  "step_s_median": statistics.median(intervals),
                  "step_memory": memory},
        "memory_peak_bytes": bench.memory_peak_bytes()}
