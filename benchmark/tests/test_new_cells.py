"""Tests of what PR 26 added to the benchmark: the ZAYA1 cell and the
dp=4 ZeRO cell in ``BENCHMARK.json``, the traffic file that went with the
latter, and the three new readers on recorded facts.  (The rehearsals of
both cells, untraced and traced, are cases of
``test_benchmark.test_rehearsal_ends_in_the_contracts_line``, which runs
every cell of the spec.)  Run with

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

ZAYA, DP4 = "zaya1-8b-train-resident", "bert-base-mlm-train-dp4-zero"


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _reader(name):
    from benchmark import harness
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"), "under_test")


def test_both_cells_report_every_train_metric():
    spec = _load("BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    assert cells[ZAYA]["chips"] == 1 and cells[DP4]["chips"] == 4
    assert sum(w["chips"] == 4 for w in cells.values()) \
        <= max(1, len(cells) // 4)
    # a metric of every other train cell is one of these two's as well
    others = set(cells) - {ZAYA, DP4}
    for metric in spec["end_to_end"] + spec["per_layer"]:
        listed = set(metric.get("workloads", cells))
        if others <= listed:
            assert {ZAYA, DP4} <= listed, metric["name"]
    mine = {m["name"]: m["workloads"] for m in spec["per_layer"]}
    for name in ("moe_load_max_over_mean.train",
                 "moe_local_token_share.train"):
        assert ZAYA in mine[name] and DP4 not in mine[name], name
    assert DP4 in mine["collective_exposed_share.train"]
    assert ZAYA not in mine["collective_exposed_share.train"]


def test_dp4_cell_is_the_rehearsed_entry_and_traffic():
    spec = _load("BENCHMARK.json")
    more = _load("benchmark", "tests", "data", "BENCHMARK.more.json")
    entry = next(w for w in spec["workloads"] if w["name"] == DP4)
    assert entry == next(w for w in more["workloads"] if w["name"] == DP4)
    assert _load("benchmark", "traffic", entry["traffic"] + ".json") \
        == _load("benchmark", "tests", "data", "traffic",
                 entry["traffic"] + ".json")


def test_zaya_config_keeps_every_published_number():
    config = _load("benchmark", "configs", "zaya1-8b-ep2share",
                   "config.json")
    entry = next(c for c in _load("BENCHMARK.json")["configs"]
                 if c["name"] == "zaya1-8b-ep2share")
    published = {"attention_bias": False, "cca_time0": 2, "cca_time1": 2,
                 "head_dim": 128, "hidden_size": 2048,
                 "moe_intermediate_size": 2048, "num_attention_heads": 8,
                 "num_key_value_heads": 2, "num_experts_per_tok": 1,
                 "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
                 "router_hidden_size": 256, "tie_word_embeddings": True,
                 "max_position_embeddings": 131072}
    for key, value in published.items():
        assert config[key] == value, key
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) \
        == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 8, 131136)
    assert config["published"] == {"num_hidden_layers": 40,
                                   "num_experts": 16, "vocab_size": 262272}
    assert config["deployment"]["chips_sharing_a_layer"] == 2
    assert len(config["layer_types"]) == 40
    assert entry["source"] == config["source"]
    # 16 bytes a parameter fill two thirds of the chip
    sizes = config
    layer = 2048 * (1024 + 256 + 256) + 1024 * 2048 + 1280 * 2 \
        + 1280 * 128 * 2 + 2 + 2 * 2048 \
        + 2048 * 256 + 256 + 2 * 256 * 256 + 256 * 16 + 1 \
        + sizes["num_experts"] * 3 * 2048 * 2048
    total = 4 * layer + sizes["vocab_size"] * 2048 + 2048
    assert 690e6 < total < 700e6


def test_routing_readers_on_recorded_counts(monkeypatch):
    """Two layers' counts as ``publish_routing_counts`` returns them: the
    worst layer's most-loaded held expert over the held mean, and the
    share of all routes that stay here."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.gluon.contrib import nn as cnn

    recorded = {
        "net_layer0_experts": {"load": [30, 10, 10, 10, 20, 20, 0, 0],
                               "rows": [30, 10, 10, 10], "held": (0, 4)},
        "net_layer1_experts": {"load": [5, 5, 5, 5, 20, 20, 20, 20],
                               "rows": [5, 5, 5, 5], "held": (0, 4)}}

    def publish():
        telemetry.gauge("moe.tokens_routed", 200)
        telemetry.gauge("moe.tokens_local", 80)
        return recorded

    monkeypatch.setattr(cnn, "publish_routing_counts", publish)
    assert _reader("moe_load_max_over_mean.train").read({}) \
        == pytest.approx(30 * 4 / 60)
    assert _reader("moe_local_token_share.train").read({}) \
        == pytest.approx(40.0)
    monkeypatch.setattr(cnn, "publish_routing_counts", dict)
    assert _reader("moe_load_max_over_mean.train").read({}) is None
    assert _reader("moe_local_token_share.train").read({}) is None


def test_collective_reader_on_a_recorded_breakdown():
    read = _reader("collective_exposed_share.train").read
    trace = {"busy_s": 2.0, "device_ops": [
        ["fusion", 1.0], ["all-gather-done", 0.1], ["reduce-scatter", 0.06],
        ["all-reduce-start", 0.04], ["flash_short_fwd [pallas]", 0.3],
        ["all-gather-fusion-not-a-collective-name", 0.0]]}
    # a fusion XLA names after a collective still starts with its name:
    # it IS the collective's compute on the TensorCore
    assert read({"trace": trace}) == pytest.approx(100 * 0.2 / 2.0)
    assert read({"trace": dict(trace, device_ops=[["fusion", 1.0]])}) is None
    assert read({"trace": None}) is None
