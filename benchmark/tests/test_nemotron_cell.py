"""Tests of what PR 30 added to the benchmark: the configuration
``nemotron3-nano-30b-ep16share`` (every published number kept, the cut as
``BENCHMARK.json`` states it, the parameter count from shapes) and the cell
``nemotron3-nano-30b-train-resident`` (its rehearsal, untraced and traced,
ends in the contract's line).  Run with

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
CONFIG, CELL = "nemotron3-nano-30b-ep16share", \
    "nemotron3-nano-30b-train-resident"

# the catalog row's ``config`` (model-configs guide, architectures.jsonl:
# NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), every key the file carries
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True}
REDUCED = {"num_hidden_layers": (9, 52), "n_routed_experts": (8, 128),
           "vocab_size": (16384, 131072)}


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_config_keeps_every_published_number():
    config = _load("benchmark", "configs", CONFIG, "config.json")
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    # with the three reduced keys these are all the catalog row's keys
    assert len(PUBLISHED) + len(REDUCED) == 46


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
    assert deployment["chips_sharing_a_layer"] == 16
    assert deployment["experts_held"] == [0, config["n_routed_experts"]]
    assert deployment["vocabulary_rows_held"] == [0, config["vocab_size"]]
    assert 128 // deployment["expert_parallel"] == 8
    assert 131072 // deployment["vocabulary_parallel"] == 16384
    # the layers kept are the model's own first nine
    kept = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    assert kept == deployment["layers_kept"] == "MEMEM*EME"
    assert len(config["hybrid_override_pattern"]) == 52
    assert (kept.count("M"), kept.count("E"), kept.count("*")) == (4, 4, 1)
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "train-resident", 1)
    assert config["train"]["batch_per_chip"] == 1
    assert config["seq_len"] == 8192
    # at most a quarter of the cells ask for four chips
    cells = spec["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    for metric in spec["end_to_end"] + spec["per_layer"]:
        named = metric["name"].startswith(("train_", "moe_")) \
            or metric["name"].endswith(".train") \
            and not metric["name"].startswith("collective_")
        if named:
            assert metric["workloads"][-1] == CELL, metric["name"]


def test_parameters_from_shapes_fill_two_thirds_of_the_chip():
    c = _load("benchmark", "configs", CONFIG, "config.json")
    e = c["hidden_size"]
    inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    channels = inner + 2 * c["n_groups"] * c["ssm_state_size"]
    mamba = e + e * (inner + channels + c["mamba_num_heads"]) \
        + channels * (c["conv_kernel"] + 1) + 3 * c["mamba_num_heads"] \
        + inner + inner * e
    attention = e + e * (c["num_attention_heads"]
                         + 2 * c["num_key_value_heads"]) * c["head_dim"] \
        + c["num_attention_heads"] * c["head_dim"] * e
    experts = e + e * c["published"]["n_routed_experts"] \
        + c["n_routed_experts"] * 2 * e * c["moe_intermediate_size"] \
        + 2 * e * c["moe_shared_expert_intermediate_size"]
    kept = c["hybrid_override_pattern"][:c["num_hidden_layers"]]
    total = sum({"M": mamba, "*": attention, "E": experts}[k] for k in kept) \
        + 2 * c["vocab_size"] * e + e
    assert 660e6 < total < 675e6
    assert 10.5e9 < total * 16 < 10.9e9


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
    else:
        assert set(line["metrics"]) == {"train_samples_per_s",
                                        "train_step_p95_ms", "setup_s"}
    for metric in line["metrics"].values():
        assert metric["value"] is None or metric["unit"] == "count"
