"""Tests of what PR 37 added to the benchmark: the configuration
``laguna-s-2.1-ep32share`` (every published number kept, the cut as
``BENCHMARK.json`` states it, the parameter count from the built net) and
the cell ``laguna-s-2.1-train-resident`` (its rehearsal, untraced and
traced, ends in the contract's line and reports every train metric).  The
cell and the lists it stands in are found by MEMBERSHIP, never by place:
later PRs append after it.  Run with

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import json
import os
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
from benchmark import harness  # noqa: E402

CONFIG, CELL = "laguna-s-2.1-ep32share", "laguna-s-2.1-train-resident"

# the catalog row's ``config`` (model-configs guide, architectures.jsonl:
# Laguna-S-2.1), every key the file carries unchanged; the four per-layer
# lists are the row's first five entries
PUBLISHED = {
    "model_type": "laguna", "hidden_size": 3072, "intermediate_size": 12288,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 512, "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    "num_attention_heads_per_layer": [48, 72, 72, 72, 48],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "gating_types": ["per_head"] * 5}
REDUCED = {"num_hidden_layers": (5, 48), "num_experts": (8, 256),
           "vocab_size": (12544, 100352)}
TRAIN_METRICS = {
    "step_dispatch_ms.train", "compiles_in_window.train", "mfu.train",
    "pallas_time_share.train", "step_hbm_gb.train", "step_call_ms.train",
    "step_self_ms.train", "moe_load_max_over_mean.train",
    "moe_local_token_share.train", "moe_blocks_side_share.train",
    "attention_tiles_visited_share.train", "flash_masked_roofline.train"}


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _model():
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "configs", CONFIG, "model.py"), "laguna_model")


def test_config_keeps_every_published_number():
    config = _load("benchmark", "configs", CONFIG, "config.json")
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    # with the three reduced keys these are all the catalog row's keys
    assert len(PUBLISHED) + len(REDUCED) == 29
    for item in ("layer", "attention", "window", "rotary", "head_gate",
                 "router", "feed_forward", "loss", "tokens", "init",
                 "optimizer", "batch_per_chip", "bytes", "check"):
        assert item in config["assumed"], item
    # the five things the config is silent on, each with its equation
    assumed = config["assumed"]
    assert "i - 512 < j <= i" in assumed["window"]
    assert "freq_i = inv_i (1 - ramp_i) + (inv_i / factor) ramp_i" \
        in assumed["rotary"]
    assert "g = sigmoid(RMSNorm(x) W_g)" in assumed["head_gate"]
    assert "w_e = 2.5 p_e / (sum over T of p" in assumed["router"]
    assert "W_down (silu(W_gate x) * W_up x)" in assumed["feed_forward"]


def test_the_cut_agrees_with_the_benchmarks_entry():
    spec = _load("BENCHMARK.json")
    config = _load("benchmark", "configs", CONFIG, "config.json")
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] \
        == "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/" \
           "config.json"
    assert entry["file"] == "benchmark/configs/%s/config.json" % CONFIG
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) \
        == sorted(REDUCED)
    for key, (held, published) in REDUCED.items():
        assert config[key] == held and config["published"][key] == published
    deployment = config["deployment"]
    assert deployment["chips_sharing_a_layer"] == 32
    assert deployment["experts_held"] == [0, config["num_experts"]]
    assert deployment["vocabulary_rows_held"] == [0, config["vocab_size"]]
    assert 256 // deployment["expert_parallel"] == 8
    assert 100352 // deployment["vocabulary_parallel"] == 12544
    # the floors of a model_config cut: the leading dense layer, then a
    # whole period and at least four layers; 8 experts; an eighth
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert config["layer_types"][1:] == ["sliding_attention"] * 3 + [
        "full_attention"]
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= 100352
    cells = [w for w in spec["workloads"] if w["name"] == CELL]
    assert len(cells) == 1
    cell = cells[0]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "train-resident", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert [w["config"] for w in spec["workloads"]].count(CONFIG) == 1
    assert config["train"]["batch_per_chip"] == 1
    assert config["seq_len"] == 4096
    cells = spec["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    # the cell stands in every list of train cells, the moe lists and the
    # two masked-attention lists among them, and not in the collectives'
    for metric in spec["end_to_end"] + spec["per_layer"]:
        named = metric["name"].startswith(("train_", "moe_")) \
            or metric["name"].endswith((".train", ".setup")) \
            and not metric["name"].startswith("collective_")
        if named:
            assert metric["workloads"].count(CELL) == 1, metric["name"]
        else:
            assert CELL not in metric.get("workloads", []), metric["name"]
    reported = {m["name"] for m in spec["per_layer"]
                if CELL in m.get("workloads", [])
                and m["name"].endswith(".train")}
    assert reported == TRAIN_METRICS


def test_bytes_of_the_built_net_are_the_issues():
    """811.0M parameters from the net the builder makes (shapes only: no
    array is allocated), within 0.5%; by block as the file states them."""
    config = _load("benchmark", "configs", CONFIG, "config.json")
    sizes = {k: v for k, v in config.items() if k != "rehearsal"}
    net = _model()._net(sizes)
    state = ("balance_bias", "expert_load", "rows_computed", "mask_tiles")
    count = {}
    for name, p in net.collect_params().items():
        if name.endswith(state):
            continue
        short = name[len(net.prefix):]
        size = 1
        for n in p.shape:
            size *= n
        block = short.split("_")[0] if short.startswith("layer") else "ends"
        count[block] = count.get(block, 0) + size
    total = sum(count.values())
    assert abs(total - 811.0e6) <= 0.005 * 811.0e6, total
    assert abs(count["ends"] - 2 * 38.5e6) < 0.2e6
    assert abs(count["layer0"] - (44.2e6 + 113.2e6)) < 0.2e6
    for i in (1, 2, 3):
        assert abs(count["layer%d" % i] - 148.9e6) < 0.2e6
    assert abs(count["layer4"] - 129.9e6) < 0.2e6
    assert 12.9e9 < total * 16 < 13.05e9 and 11.3e9 < total * 14 < 11.4e9


def test_flops_count_live_pairs_and_the_masked_calls_alone():
    sizes = _load("benchmark", "configs", CONFIG, "config.json")
    model = _model()
    band = 512 * 513 // 2 + (4096 - 512) * 512
    assert model.live_pairs(sizes, "sliding_attention") == band
    assert model.live_pairs(sizes, "full_attention") == 4096 * 4097 // 2
    # the band is an eighth of the square's pairs
    assert 0.11 < band / 4096 ** 2 < 0.12
    assert model.attention_flops(sizes) == 18 * 128 * band * 72 * 3
    # ~13.7 TFLOP a step, the attention blocks the larger part
    assert 13.3e12 < model.model_flops(sizes) < 14.1e12
    # a window that covers the row runs no masked kernel
    assert model.attention_flops(dict(sizes, seq_len=512)) == 0


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_reports_every_train_metric(trace):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "2",
         "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True
    assert 0 <= line["failed"] < line["attempted"]
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                              "memory_peak_bytes": None, "rehearsal": True}
    if trace:
        # on the CPU nothing is traced and no kernel streams, and at toy
        # sizes the held experts have no blocks of slots: the metrics read
        # from the device trace, the peaks, the tiles and the blocks are
        # left out, as on a program that lacks them; every other one is
        # there
        off_chip = {"mfu.train", "pallas_time_share.train",
                    "flash_masked_roofline.train",
                    "attention_tiles_visited_share.train",
                    "moe_blocks_side_share.train"}
        assert TRAIN_METRICS - off_chip <= set(line["metrics"])
        assert "flash_masked_roofline.train" not in line["metrics"]
        assert line["metrics"]["compiles_in_window.train"]["value"] == 0
        assert line["metrics"]["step_compiles.setup"]["value"] >= 1
    else:
        assert set(line["metrics"]) == {"train_samples_per_s",
                                        "train_step_p95_ms", "setup_s"}
    for metric in line["metrics"].values():
        assert metric["value"] is None or metric["unit"] == "count"
