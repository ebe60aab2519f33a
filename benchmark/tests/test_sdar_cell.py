"""Tests of what PR 33 added to the benchmark: the configuration
``sdar-30b-a3b-ep8share`` (every published number kept, the cut as
``BENCHMARK.json`` states it, the parameter count from shapes), the cell
``sdar-30b-a3b-train-resident`` (its rehearsal, untraced and traced, ends
in the contract's line) and the two readers
(``attention_tiles_visited_share.train``, ``flash_masked_roofline.train``).
Run with

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import json
import os
import subprocess
import sys
import types

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
from benchmark import harness  # noqa: E402

CONFIG, CELL = "sdar-30b-a3b-ep8share", "sdar-30b-a3b-train-resident"

# the catalog row's ``config`` (model-configs guide, architectures.jsonl:
# SDAR-30B-A3B-Chat), every key the file carries unchanged
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False}
REDUCED = {"num_hidden_layers": (6, 48), "num_experts": (16, 128),
           "vocab_size": (18992, 151936)}


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _model():
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "configs", CONFIG, "model.py"), "sdar_model")


def _reader(name):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"), "reader")


def test_config_keeps_every_published_number():
    config = _load("benchmark", "configs", CONFIG, "config.json")
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    # with the three reduced keys these are all the catalog row's keys
    assert len(PUBLISHED) + len(REDUCED) == 24
    assert config["train"]["block_length"] == 4
    for item in ("block_length", "noise", "row", "loss", "mask_token",
                 "router", "attention", "expert", "balancing", "init",
                 "optimizer", "tokens", "check", "bytes"):
        assert item in config["assumed"], item


def test_the_cut_agrees_with_the_benchmarks_entry():
    spec = _load("BENCHMARK.json")
    config = _load("benchmark", "configs", CONFIG, "config.json")
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/%s/config.json" % CONFIG
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) \
        == sorted(REDUCED)
    for key, (held, published) in REDUCED.items():
        assert config[key] == held and config["published"][key] == published
    deployment = config["deployment"]
    assert deployment["chips_sharing_a_layer"] == 8
    assert deployment["experts_held"] == [0, config["num_experts"]]
    assert deployment["vocabulary_rows_held"] == [0, config["vocab_size"]]
    assert 128 // deployment["expert_parallel"] == 16
    assert 151936 // deployment["vocabulary_parallel"] == 18992
    # the floors of a model_config cut
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= 151936
    cells = spec["workloads"]
    assert [w["name"] for w in cells].count(CELL) == 1
    cell = next(w for w in cells if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "train-resident", 1)
    assert config["train"]["batch_per_chip"] == 1
    assert config["seq_len"] == 4096
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    # the cell stands once in every list of train and set-up cells, the
    # moe lists among them, and not in the collectives', the scan's or the
    # causal kernels' (every attention call of its step is a masked one)
    elsewhere = {"collective_exposed_share.train", "mixer_ms.train",
                 "ssd_roofline.train", "flash_causal_roofline.train"}
    for metric in spec["end_to_end"] + spec["per_layer"]:
        named = metric["name"].startswith(("train_", "moe_")) \
            or metric["name"].endswith((".train", ".setup")) \
            and metric["name"] not in elsewhere
        if named:
            assert metric["workloads"].count(CELL) == 1, metric["name"]
        else:
            assert CELL not in metric.get("workloads", []), metric["name"]
    mine = {m["name"]: m for m in spec["per_layer"]
            if m["name"] in ("attention_tiles_visited_share.train",
                             "flash_masked_roofline.train")}
    assert len(mine) == 2
    for metric in mine.values():
        assert metric["layer"] == "Pallas kernels"
        assert metric["moves"] == "train_samples_per_s"
        assert CELL in metric["workloads"] and metric["unit"] == "%"


def test_parameters_from_shapes_fill_two_thirds_of_the_chip():
    c = _load("benchmark", "configs", CONFIG, "config.json")
    e, d = c["hidden_size"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    attention = e * (heads + 2 * kv) * d + heads * d * e + 2 * d
    layer = attention + 2 * e + e * c["published"]["num_experts"] \
        + c["num_experts"] * 3 * e * c["moe_intermediate_size"]
    assert 94.5e6 < layer < 94.8e6
    total = c["num_hidden_layers"] * layer + 2 * c["vocab_size"] * e + e
    assert 645e6 < total < 646.5e6
    assert 10.2e9 < total * 16 < 10.4e9
    # one whole layer of 128 experts would not leave room for a second
    whole = layer + (128 - 16) * 3 * e * c["moe_intermediate_size"]
    assert 9.9e9 < whole * 16 < 10.1e9


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_in_the_contracts_line(trace):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "2",
         "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True
    assert 0 <= line["failed"] < line["attempted"]
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                              "memory_peak_bytes": None, "rehearsal": True}
    if trace:
        assert {"compiles_in_window.train", "moe_load_max_over_mean.train",
                "moe_local_token_share.train"} <= set(line["metrics"])
        # on the CPU no kernel streams and nothing is traced: both new
        # metrics are left out, as on a program that lacks them
        assert "flash_masked_roofline.train" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"train_samples_per_s",
                                        "train_step_p95_ms", "setup_s"}
    for metric in line["metrics"].values():
        assert metric["value"] is None or metric["unit"] == "count"


def test_attention_flops_count_live_pairs_alone():
    sizes = _load("benchmark", "configs", CONFIG, "config.json")
    model = _model()
    assert model.live_pairs(sizes) == 16793600
    assert model.attention_flops(sizes) == 18 * 128 * 16793600 * 32 * 6
    # a dense masked square would be four times the work
    assert 4 * model.live_pairs(sizes) == pytest.approx(8192 ** 2, rel=2e-3)
    toy = dict(sizes, seq_len=8, train={"block_length": 4})
    assert model.live_pairs(toy) == 96


def test_roofline_reader():
    """7.43 TFLOP of live pairs a step over the three kernels' seconds in
    the traced window over the peak; nothing where a kernel is under the
    trace's cut, where there is no trace, or where the configuration
    counts no such operations (a program that lacks the kernels)."""
    read = _reader("flash_masked_roofline.train").read
    sizes = _load("benchmark", "configs", CONFIG, "config.json")
    model = _model()
    ops = [["fusion", 1.0], ["flash_masked_dkv [pallas]", 0.30],
           ["flash_masked_dq [pallas]", 0.25],
           ["flash_masked_fwd [pallas]", 0.15]]
    facts = {"trace": {"device_ops": ops, "window_s": 2.8, "busy_s": 2.79},
             "peaks": {"bf16_flops_per_s": 197e12}, "step_s_median": 0.35,
             "model": model, "sizes": sizes, "global_batch": 1, "chips": 1}
    want = 100 * model.attention_flops(sizes) * (2.8 / 0.35) / 0.70 / 197e12
    assert read(facts) == pytest.approx(want) and 40 < want < 45
    assert read(dict(facts, trace=dict(facts["trace"],
                                       device_ops=ops[:3]))) is None
    assert read(dict(facts, trace=None)) is None
    assert read(dict(facts, model=types.SimpleNamespace())) is None


def test_tiles_reader_reads_the_gauges(monkeypatch):
    read = _reader("attention_tiles_visited_share.train").read
    from mxnet_tpu.gluon.contrib import nn as cnn
    monkeypatch.setattr(cnn, "publish_mask_tiles", lambda: (432.0, 1152.0))
    assert read({}) == pytest.approx(37.5)
    monkeypatch.setattr(cnn, "publish_mask_tiles", lambda: (0.0, 0.0))
    assert read({}) is None
    monkeypatch.delattr(cnn, "publish_mask_tiles")
    assert read({}) is None          # a program without the count
