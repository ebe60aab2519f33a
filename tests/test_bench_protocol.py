"""Bench harness protocol units (CPU-safe): the median-of-k timer, the
physical-plausibility gates, and the local history comparison.

Reference counterpart: the measurement discipline of
``benchmark/python/`` + ``example/image-classification/benchmark_score.py``
(median over multiple timed repetitions)."""
import importlib.util
import os
import sys

import pytest


def _load_bench():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(root, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    return _load_bench()


def test_time_calls_takes_median_and_reports_reps(bench):
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        return calls["n"]

    med, out, detail = bench._time_calls(fn, lambda x: None, warmup=1,
                                         iters=2, reps=3)
    # 1 warmup + 3 reps x 2 iters, plus at most 2 extra reps if the
    # (sub-microsecond, jittery) spread tripped the redo threshold
    assert calls["n"] in (7, 9, 11)
    assert 3 <= len(detail["reps_ms"]) <= 5
    assert detail["spread"] is not None


def test_time_calls_extra_reps_on_high_spread(bench, monkeypatch):
    # one artificially slow rep (>25% spread) must trigger extra reps
    seq = iter([0.0, 1.0,          # rep1: 1s/call x2... (t0, t1)
                0.0, 0.1,          # rep2
                0.0, 0.1,          # rep3
                0.0, 0.1,          # extra rep 4
                0.0, 0.1])         # extra rep 5
    monkeypatch.setattr(bench.time, "perf_counter", lambda: next(seq))
    med, _, detail = bench._time_calls(lambda: None, lambda x: None,
                                       warmup=0, iters=1, reps=3)
    assert len(detail["reps_ms"]) == 5
    assert med == pytest.approx(0.1)


def test_sanity_gate_flags_bf16_slower_than_fp32(bench):
    details = [
        {"bench": "inference", "model": "resnet50_v1", "dtype": "float32",
         "img_per_sec": 6000.0},
        {"bench": "inference", "model": "resnet50_v1", "dtype": "bfloat16",
         "img_per_sec": 5000.0},
    ]
    flags = bench._sanity_gates(details)
    assert any("implausible" in f for f in flags)
    details[1]["img_per_sec"] = 9000.0
    assert not any("implausible" in f for f in bench._sanity_gates(details))


def test_sanity_gate_flags_kernel_error(bench):
    details = [{"bench": "attention", "shape": [8, 16, 2048, 64],
                "max_err": {"out": 0.5}, "max_err_ok": False}]
    assert any("KERNEL ERROR" in f for f in bench._sanity_gates(details))
    details[0]["max_err_ok"] = True
    assert not bench._sanity_gates(details)


def test_sanity_gate_flags_flash_slower_than_dense(bench):
    """Dispatch contract: when a kernel (not the dense fallback) was
    selected, flash losing to dense at ANY benched shape is flagged."""
    d = {"bench": "attention", "shape": [8, 16, 512, 64],
         "kernel": "short_seq", "flash_speedup": 0.93, "max_err_ok": True}
    flags = bench._sanity_gates([d])
    assert any("KERNEL REGRESSION" in f for f in flags)
    assert not bench._sanity_gates([dict(d, flash_speedup=1.21)])
    # off-chip (dense fallback dispatched): speedup is meaningless
    assert not bench._sanity_gates(
        [dict(d, kernel="dense_fallback", flash_speedup=0.5)])


def test_hard_failures_gate_s512_speedup_and_numerics(bench):
    """bench exits nonzero on max_err_ok:false anywhere, and on
    flash_speedup < 1.0 at S=512 whenever a kernel ran on-chip."""
    bad_err = {"bench": "attention", "shape": [8, 16, 2048, 64],
               "kernel": "short_seq", "flash_speedup": 1.5,
               "max_err": {"out": 0.5}, "max_err_ok": False}
    assert bench._hard_failures([bad_err])
    slow512 = {"bench": "attention", "shape": [8, 16, 512, 64],
               "kernel": "short_seq", "flash_speedup": 0.9,
               "max_err_ok": True}
    assert bench._hard_failures([slow512])
    # S=2048 below 1.0 is flagged by the sanity gate but is not a hard
    # exit; S=512 via the dense fallback (off-chip) is not either
    ok2048 = dict(slow512, shape=[8, 16, 2048, 64])
    assert not bench._hard_failures([ok2048])
    assert not bench._hard_failures([dict(slow512,
                                          kernel="dense_fallback")])
    good = dict(slow512, flash_speedup=1.3)
    assert not bench._hard_failures([good])


def test_hard_failures_gate_telemetry_overhead(bench):
    """The always-on telemetry layer's 2% overhead budget is a hard
    bench failure, not a soft flag."""
    bad = {"bench": "telemetry_overhead", "overhead_pct": 3.5,
           "overhead_ok": False}
    assert any("telemetry overhead" in h
               for h in bench._hard_failures([bad]))
    good = {"bench": "telemetry_overhead", "overhead_pct": 0.4,
            "overhead_ok": True}
    assert not bench._hard_failures([good])


def test_hard_failures_require_live_instrumentation(bench):
    """ISSUE 18: the 2% budget only counts if the ON leg PROVED trace
    contexts + histograms were live — a 0% overhead from a dead
    instrumentation path is itself a hard failure."""
    live = {"bench": "telemetry_overhead", "overhead_pct": 0.4,
            "overhead_ok": True, "telemetry_hist_count": 10,
            "telemetry_traced": True}
    assert not bench._hard_failures([live])
    dead_hist = dict(live, telemetry_hist_count=0)
    assert any("dead path" in h
               for h in bench._hard_failures([dead_hist]))
    untraced = dict(live, telemetry_traced=False)
    assert any("dead path" in h
               for h in bench._hard_failures([untraced]))
    # pre-ISSUE-18 artifacts without the proof fields stay accepted
    legacy = {"bench": "telemetry_overhead", "overhead_pct": 0.4,
              "overhead_ok": True}
    assert not bench._hard_failures([legacy])


def test_hard_failures_gate_checkpoint_overhead(bench):
    """Async checkpointing's 2% overhead budget at the default cadence
    is a hard bench failure, mirroring the telemetry gate."""
    bad = {"bench": "checkpoint_overhead", "overhead_pct": 4.2,
           "overhead_ok": False, "every_n_steps": 32}
    assert any("checkpoint overhead" in h
               for h in bench._hard_failures([bad]))
    good = {"bench": "checkpoint_overhead", "overhead_pct": 0.9,
            "overhead_ok": True, "every_n_steps": 32}
    assert not bench._hard_failures([good])


def test_attention_bench_records_dispatcher_choice(bench):
    """The attention sweep ships the dispatcher's kernel choice (and its
    block tuning + tuner provenance) per shape so BENCH rounds can audit
    dispatch."""
    out = bench.bench_attention(batch=1, heads=1, seqlen=64, head_dim=8,
                                iters=1, inner=1, check_error=False)
    assert out["kernel"] in ("short_seq", "streaming", "dense_fallback")
    # this suite runs on CPU: the public op must have routed dense
    assert out["kernel"] == "dense_fallback"
    assert "block_q" in out and "block_k" in out
    # autotune provenance fields always ship (None when dense/no table)
    assert "tuner_source" in out and "autotune_table" in out


def test_hard_failures_gate_tuned_vs_heuristic(bench):
    """A cost-table config measured slower than the heuristic config in
    the same-run A/B leg is a hard bench failure — the autotuner's
    no-regression contract."""
    bad = {"bench": "attention", "shape": [8, 16, 512, 64],
           "kernel": "short_seq", "flash_speedup": 1.4, "max_err_ok": True,
           "tuner_source": "table", "block_q": 128, "block_k": 512,
           "heuristic_config": {"block_q": 512, "block_k": 512},
           "tuned_ms": 2.2, "heuristic_ms": 2.0, "tuned_ok": False}
    assert any("slower than heuristic" in h
               for h in bench._hard_failures([bad]))
    assert not bench._hard_failures([dict(bad, tuned_ok=True)])
    # no A/B leg ran (heuristic dispatch): nothing to gate
    no_ab = {"bench": "attention", "shape": [8, 16, 512, 64],
             "kernel": "short_seq", "flash_speedup": 1.4,
             "max_err_ok": True, "tuner_source": "heuristic"}
    assert not bench._hard_failures([no_ab])


def test_sanity_gate_flags_regression_vs_history(bench, tmp_path,
                                                 monkeypatch):
    hist = tmp_path / "BENCH_HISTORY.json"
    monkeypatch.setattr(bench, "_history_path", lambda: str(hist))
    run1 = [{"bench": "train", "model": "resnet50_v1", "batch_size": 128,
             "dtype": "bfloat16", "mirror": None, "img_per_sec": 2500.0}]
    bench._update_history(run1)
    run2 = [dict(run1[0], img_per_sec=1500.0)]
    flags = bench._sanity_gates(run2)
    assert any("regression" in f for f in flags)
    run3 = [dict(run1[0], img_per_sec=2400.0)]
    assert not bench._sanity_gates(run3)


def test_history_keeps_bounded_entries(bench, tmp_path, monkeypatch):
    hist = tmp_path / "BENCH_HISTORY.json"
    monkeypatch.setattr(bench, "_history_path", lambda: str(hist))
    for i in range(15):
        bench._update_history([{"bench": "train", "img_per_sec": float(i)}])
    assert len(bench._load_history()) == 12


def test_hard_failures_gate_serving_latency(bench):
    """The serving hard gates: steady-state recompiles, a fat p99 tail
    at the LOW rate, and any non-terminal request each fail the run;
    a healthy serving artifact passes."""
    good = {"bench": "serving_latency", "steady_state_recompiles": 0,
            "recompile_ok": True, "latency_ok": True, "terminal_ok": True,
            "legs": [{"rate_per_s": 25.0, "p50_ms": 4.0, "p99_ms": 8.0}]}
    assert bench._hard_failures([good]) == []
    recompiled = dict(good, steady_state_recompiles=2, recompile_ok=False)
    hard = bench._hard_failures([recompiled])
    assert len(hard) == 1 and "recompile" in hard[0]
    fat = dict(good, latency_ok=False,
               legs=[{"rate_per_s": 25.0, "p50_ms": 2.0, "p99_ms": 50.0}])
    hard = bench._hard_failures([fat])
    assert len(hard) == 1 and "p99" in hard[0]
    hung = dict(good, terminal_ok=False)
    hard = bench._hard_failures([hung])
    assert len(hard) == 1 and "terminal" in hard[0]


def test_serving_latency_percentiles_come_from_histograms(bench):
    """ISSUE 18: bench_serving_latency sources its per-leg p50/p99 from
    the mergeable ``serve.request`` histogram (since-deltas per leg)
    rather than a client-side sample list; the artifact carries the
    provenance and the merged histogram itself, and the existing
    p50/p99 gate keys keep working over histogram-derived values."""
    from mxnet_tpu import telemetry

    h = telemetry.Histogram()
    for v in (3.0, 4.0, 4.5, 40.0):
        h.add(v)
    leg = {"rate_per_s": 25.0,
           "p50_ms": round(h.quantile(0.50), 3),
           "p99_ms": round(h.quantile(0.99), 3),
           "hist": h.to_dict()}
    art = {"bench": "serving_latency", "steady_state_recompiles": 0,
           "recompile_ok": True, "latency_ok": True, "terminal_ok": True,
           "latency_source": "histogram", "latency_hist": h.to_dict(),
           "latency_hist_summary": h.summary(), "legs": [leg]}
    assert bench._hard_failures([art]) == []
    # quantiles from the log-bucketed histogram stay within bucket
    # error of the exact samples, so the 10x-p50 gate math is sound
    assert leg["p50_ms"] == pytest.approx(4.25, rel=0.15)
    assert leg["p99_ms"] == pytest.approx(40.0, rel=0.15)
    # a fat histogram-derived tail still fails through the same keys
    fat = dict(art, latency_ok=False,
               legs=[dict(leg, p99_ms=leg["p50_ms"] * 20)])
    assert any("p99" in hh for hh in bench._hard_failures([fat]))


def _gc_detail(**over):
    """A green grad_compression bench detail (the MULTICHIP_r06 leg)."""
    d = {"bench": "grad_compression", "batch_size": 256, "hidden": 1024,
         "n_shards": 8, "padded_params": 656912,
         "legs": [
             {"mode": "f32", "step_ms": 50.0,
              "grad_wire_bytes_per_chip": 2627648,
              "scale_bytes_per_chip": 0},
             {"mode": "int8", "step_ms": 60.0,
              "grad_wire_bytes_per_chip": 656912,
              "scale_bytes_per_chip": 10268, "wire_ratio": 4.0,
              "parity_max_abs": 8e-4, "parity_tol": 1e-2,
              "engaged": True, "parity_ok": True, "compressed_ok": True},
             {"mode": "fp8", "step_ms": 80.0,
              "grad_wire_bytes_per_chip": 656912,
              "scale_bytes_per_chip": 10268, "wire_ratio": 4.0,
              "parity_max_abs": 2e-4, "parity_tol": 5e-3,
              "engaged": True, "parity_ok": True, "compressed_ok": True}],
         "reshard": {"world_from": 8, "world_to": 4,
                     "residual_bitwise_ok": True,
                     "loss_finite_after": True, "still_compressed": True},
         "compressed_ok": True, "parity_ok": True}
    d.update(over)
    return d


def test_hard_failures_gate_grad_compression_wire(bench):
    """ISSUE 20: compressed_ok:false — the wire never engaged or the
    payload ratio came in under the 4x contract — is a nonzero bench
    exit; the green leg passes clean."""
    assert bench._hard_failures([_gc_detail()]) == []
    bad = _gc_detail(compressed_ok=False)
    bad["legs"] = [dict(bad["legs"][0]),
                   dict(bad["legs"][1], engaged=False, wire_ratio=1.0,
                        compressed_ok=False),
                   dict(bad["legs"][2])]
    hard = bench._hard_failures([bad])
    assert any("int8" in h and "wire_ratio" in h for h in hard)


def test_hard_failures_gate_grad_compression_parity(bench):
    """A loss-parity breach on a compressed leg is a hard failure: a
    wire that saves bytes by corrupting gradients must never cut an
    artifact."""
    bad = _gc_detail(parity_ok=False)
    bad["legs"] = [dict(bad["legs"][0]), dict(bad["legs"][1]),
                   dict(bad["legs"][2], parity_max_abs=0.5,
                        parity_ok=False)]
    hard = bench._hard_failures([bad])
    assert any("fp8" in h and "parity breach" in h for h in hard)


def test_hard_failures_gate_grad_compression_reshard(bench):
    """The elastic reshard leg's residual bitwise check gates hard:
    error-feedback state that fails to migrate byte-exact (or kills
    training) fails the run."""
    bad = _gc_detail(compressed_ok=False)
    bad["reshard"] = dict(bad["reshard"], residual_bitwise_ok=False)
    hard = bench._hard_failures([bad])
    assert any("bitwise" in h for h in hard)


def test_peaks_table_is_keyed_by_device_kind_and_refuses_others(bench):
    """One table with its source, keyed by jax's device_kind; a utilisation
    against another chip's peak is not a number, so an unknown kind (the
    CPU this suite runs on) is an error, not a default."""
    v5e = bench.DEVICE_PEAKS["TPU v5 lite"]
    assert v5e == {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert bench._device()["platform"] == "cpu"
    with pytest.raises(RuntimeError, match="no published peaks"):
        bench._peaks()


def test_every_json_line_carries_the_device(bench, capsys):
    import json
    import jax
    bench._emit({"metric": "m", "value": 1.0})
    bench._emit({"bench": "x" * 5000}, log=True)
    cap = capsys.readouterr()
    stamp = {"platform": "cpu", "kind": jax.devices()[0].device_kind,
             "count": len(jax.devices())}
    assert json.loads(cap.out)["device"] == stamp
    # a progress line is cut to size with the stamp first, so it survives
    assert cap.err.startswith('# {"device": ' + json.dumps(stamp))
    assert len(cap.err) < 2100
