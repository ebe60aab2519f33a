"""Tests of the Ling additions to the benchmark: the configuration
``ling-3.0-flash-ep64share`` (every published number kept, the cut as
``BENCHMARK.json`` states it, the parameter count from the built net), the
cell ``ling-3.0-flash-train-resident`` (its rehearsal, untraced and traced,
ends in the contract's line), the two readers of the linear-attention
layer on recorded facts, ``BLOCKS`` against the step's operations, and the
counting functions.  The cell and the lists it stands in are found by
MEMBERSHIP, never by place: later PRs append after it.  Run with

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import json
import os
import re
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
from benchmark import harness, reduce_trace  # noqa: E402

CONFIG, CELL = "ling-3.0-flash-ep64share", "ling-3.0-flash-train-resident"
REDUCED = {"num_hidden_layers": (7, 42), "num_experts": (8, 512),
           "vocab_size": (19648, 157184)}
# the metrics the cell's traced run on the chip printed
TRAIN_METRICS = {
    "step_dispatch_ms.train", "compiles_in_window.train", "mfu.train",
    "pallas_time_share.train", "step_hbm_gb.train", "step_call_ms.train",
    "step_self_ms.train", "moe_load_max_over_mean.train",
    "moe_local_token_share.train", "moe_blocks_side_share.train",
    "attention_ms.train", "experts_ms.train", "optimizer_ms.train",
    "moe_products_roofline.train", "flash_causal_roofline.train",
    "linear_attention_ms.train", "delta_scan_roofline.train"}
SETUP_METRICS = {"step_compiles.setup", "step_compile_s.setup",
                 "eager_programs.setup", "eager_compile_s.setup",
                 "compile_cache_hit_share.setup", "param_build_s.setup"}


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _model():
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "configs", CONFIG, "model.py"), "ling_model")


def _sizes():
    config = _load("benchmark", "configs", CONFIG, "config.json")
    return {k: v for k, v in config.items() if k != "rehearsal"}


def test_config_keeps_every_published_number():
    """Every key of the catalog row's ``config`` (the model-configs
    guide's ``Ling-3.0-flash``, 62 keys) is in the file with its value,
    the three reduced keys apart; the per-layer lists whole."""
    config = _load("benchmark", "configs", CONFIG, "config.json")
    published = {
        "first_k_dense_replace": 2, "head_dim": 128, "hidden_size": 2560,
        "intermediate_size": 6144, "kda_lower_bound": -5,
        "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
        "moe_intermediate_size": 768, "moe_router_enable_expert_bias": True,
        "moe_shared_expert_intermediate_size": 768, "n_group": 8,
        "no_kda_lora": True, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rope_interleave": True,
        "rope_theta": 6000000, "rotary_dim": 64,
        "routed_scaling_factor": 2.5, "score_function": "sigmoid",
        "short_conv_kernel_size": 4, "tie_word_embeddings": False,
        "topk_group": 4, "topk_method": "noaux_tc", "use_qk_norm": True,
        "v_head_dim": 128, "model_type": "bailing_hybrid",
        "mtp_loss_scaling_factor": 0, "partial_rotary_factor": 0.5}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["expert_swiglu_limit_list"] == [0] * 35 + [4] * 7
    assert config["share_expert_swiglu_limit_list"] \
        == [0] * 34 + [5] * 6 + [7] * 2
    for item in ("layer", "layer_pattern", "kda", "kda_gate", "kda_output",
                 "kda_chunks", "mla", "router", "feed_forward", "mtp",
                 "loss", "tokens", "init", "optimizer", "batch_per_chip",
                 "bytes", "check"):
        assert item in config["assumed"], item
    assumed = config["assumed"]
    assert "(l + 1) % 6 == 0" in assumed["layer_pattern"]
    assert "g_t = -5 sigmoid(exp(A_log_h) (u_t W_f + dt_bias))" \
        in assumed["kda_gate"]
    assert "S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_(t-1)" \
        in assumed["kda"]
    assert "INTERLEAVED pairs (2i, 2i + 1)" in assumed["mla"]
    assert "w_e = 2.5 s_e / (sum over T of s" in assumed["router"]
    # the kept layers' clamps are zero: none is computed
    for i in config["deployment"]["layers_kept"]:
        assert config["expert_swiglu_limit_list"][i] == 0
        assert config["share_expert_swiglu_limit_list"][i] == 0


def test_the_cut_agrees_with_the_benchmarks_entry():
    spec = _load("BENCHMARK.json")
    config = _load("benchmark", "configs", CONFIG, "config.json")
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] \
        == "https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/" \
           "config.json"
    assert entry["file"] == "benchmark/configs/%s/config.json" % CONFIG
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) \
        == sorted(REDUCED)
    for key, (held, published) in REDUCED.items():
        assert config[key] == held and config["published"][key] == published
    deployment = config["deployment"]
    assert deployment["chips_sharing_a_layer"] == 64
    assert deployment["experts_held"] == [0, config["num_experts"]]
    assert deployment["vocabulary_rows_held"] == [0, config["vocab_size"]]
    assert 512 // deployment["expert_parallel"] == 8
    assert 157184 // deployment["vocabulary_parallel"] == 19648
    # the floors of a model_config cut: the leading dense layer once, then
    # a whole period (6 layers, its one latent layer); 8 experts; an eighth
    model = _model()
    kept = deployment["layers_kept"]
    assert kept == [0, 2, 3, 4, 5, 6, 7]
    assert [model._kinds(config, i) for i in kept] == [("kda", "dense")] \
        + [("kda", "sparse")] * 3 + [("mla", "sparse")] \
        + [("kda", "sparse")] * 2
    cells = [w for w in spec["workloads"] if w["name"] == CELL]
    assert len(cells) == 1
    cell = cells[0]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "train-resident", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert config["train"]["batch_per_chip"] == 1
    assert config["seq_len"] == 4096
    cells = spec["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    reported = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == TRAIN_METRICS | SETUP_METRICS | {
        "train_samples_per_s", "train_step_p95_ms", "setup_s"}
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", []):
            assert metric["workloads"].count(CELL) == 1, metric["name"]
    mine = {m["name"]: m for m in spec["per_layer"]
            if m["name"] in ("linear_attention_ms.train",
                             "delta_scan_roofline.train")}
    assert len(mine) == 2
    for metric in mine.values():
        assert metric["layer"] == "linear attention"
        assert metric["moves"] == "train_samples_per_s"
        assert metric["workloads"] == [CELL]


def test_bytes_of_the_built_net_are_the_issues():
    """822.06M parameters from the net ``_net`` makes (shapes only: no
    array is allocated); by block as the file states them."""
    net = _model()._net(_sizes())
    state = ("balance_bias", "expert_load", "rows_computed")
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
    assert total == 822057536
    assert count["ends"] == 2 * 19648 * 2560 + 2560      # + the final norm
    assert abs(count["layer0"] - 99.84e6) < 0.01e6
    for i in (2, 3, 4, 6, 7):
        assert abs(count["layer%d" % i] - 107.05e6) < 0.01e6
    assert abs(count["layer5"] - 86.37e6) < 0.01e6
    assert 13.15e9 < total * 16 < 13.16e9 and 11.50e9 < total * 14 < 11.51e9


def test_counting_functions():
    sizes, model = _sizes(), _model()
    # a chunk of 64 in sub-chunks of 16 at d = 128: the two C x C matrices
    # 2 x 128 x 16 x (64 + 48 + 32 + 16), the solve 2016 x 256, the state
    # 2 x 64 x 128^2, the output 64 x 128^2 + 64^2 x 128
    assert model.delta_chunk_macs(sizes) == 2 * 128 * 16 * 160 \
        + 2016 * 256 + 2 * 64 * 128 ** 2 + 64 * 128 ** 2 + 64 ** 2 * 128
    assert model.delta_scan_flops(sizes) \
        == 3 * 2 * model.delta_chunk_macs(sizes) * 64 * 32 * 6
    assert 0.35e12 < model.delta_scan_flops(sizes) < 0.36e12
    pairs = 4096 * 4097 // 2
    assert model.causal_attention_flops(sizes) \
        == (10 * 192 + 8 * 128) * pairs * 32
    assert model.moe_product_flops(sizes, 100) == 9 * 2 * 2560 * 768 * 100
    # ~13.0 TFLOP a step; the delta layers the larger part
    assert 12.9e12 < model.model_flops(sizes) < 13.1e12
    fewer = dict(sizes, delta_rule={"chunk": 64, "subchunk": 16},
                 seq_len=2048)
    assert model.delta_scan_flops(fewer) * 2 == model.delta_scan_flops(sizes)


def test_blocks_name_every_part_of_the_step():
    """``BLOCKS`` puts each scope the step's operations carry under one
    kind: the rule's scopes under ``delta_scan`` though its layer's block
    holds them, the rest of a delta layer under ``linear_attention``, the
    latent layer under ``attention``, the router and experts under
    ``experts``, the dense and shared blocks under ``ffn``, embedding,
    final norm and loss under ``head``."""
    model = _model()
    blocks = {kind: [re.compile(p) for p in found]
              for kind, found in model.BLOCKS.items()}
    prefix = "jit(step_fn)/jvp(forward)/lingmodel0/"
    cases = {
        "lingmodel0_layer3/lingmodel0_layer3_kda/lingmodel0_layer3_kda_proj"
        "/dot_general": "linear_attention",
        "lingmodel0_layer3/lingmodel0_layer3_kda_norm/mul":
            "linear_attention",
        "lingmodel0_layer3/lingmodel0_layer3_kda/while/body/checkpoint/"
        "kda.inputs/conv": "linear_attention",
        "lingmodel0_layer3/lingmodel0_layer3_kda/while/body/checkpoint/"
        "delta.states/while/body/dot_general": "delta_scan",
        "lingmodel0_layer3/lingmodel0_layer3_kda/while/body/checkpoint/"
        "kda.output/mul": "linear_attention",
        "lingmodel0_layer3/lingmodel0_layer3_kda/while/body/closed_call/"
        "checkpoint/transpose(jvp(delta.intra))/triangular_solve":
            "delta_scan",
        "lingmodel0_layer3/lingmodel0_layer3_kda/while/body/closed_call/"
        "checkpoint/jvp(delta.states)/while/body/dot_general": "delta_scan",
        "lingmodel0_layer3/lingmodel0_layer3_kda/while/body/closed_call/"
        "checkpoint/rematted_computation/kda.inputs/mul": "linear_attention",
        "lingmodel0_layer3/lingmodel0_layer3_kda/kda.heads/transpose":
            "linear_attention",
        "lingmodel0_layer3/lingmodel0_layer3_kda/lingmodel0_layer3_kda_out"
        "/dot_general": "linear_attention",
        "lingmodel0_layer5/lingmodel0_layer5_mla/flash_stream_fwd":
            "attention",
        "lingmodel0_layer5/lingmodel0_layer5_mla_norm/mul": "attention",
        "lingmodel0_layer5/lingmodel0_layer5_router/logistic": "experts",
        "lingmodel0_layer5/lingmodel0_layer5_experts/moe_blocks_fwd":
            "experts",
        "lingmodel0_layer5/lingmodel0_layer5_ffn_norm/mul": "experts",
        "lingmodel0_layer5/lingmodel0_layer5_shared/lingmodel0_layer5_shared"
        "_up/dot_general": "ffn",
        "lingmodel0_layer0/lingmodel0_layer0_ffn/lingmodel0_layer0_ffn_gate"
        "/dot_general": "ffn",
        "lingmodel0_layer0/lingmodel0_layer0_ffn_norm/mul": "ffn",
        "lingmodel0_embed/take": "head",
        "lingmodel0_final_norm/mul": "head",
    }
    for name, kind in cases.items():
        assert reduce_trace.kind_of(prefix + name, blocks) == kind, name
    assert reduce_trace.kind_of("ragged-dot-none:", blocks) == "experts"
    assert reduce_trace.kind_of(
        "jit(step_fn)/transpose(jvp(forward))/tiedsoftmaxcrossentropyloss0/"
        "while/body/dot_general", blocks) == "head"


def _facts(model, blocks, kernels=()):
    sizes = _sizes()
    return {"trace": {"window_s": 2.0, "blocks": blocks,
                      "kernels": list(kernels)},
            "step_s_median": 0.2, "global_batch": 1, "chips": 1,
            "sizes": sizes, "model": model,
            "peaks": {"bf16_flops_per_s": 197e12}}


def _reader(name):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"), "under_test")


def test_linear_attention_readers_on_recorded_facts():
    """Ten steps in the window: 0.8 s of ``linear_attention`` and 0.2 s of
    ``delta_scan`` leaf time are 100 ms a step; the rule's 0.357 TFLOP in
    20 ms a step is 9.06% of 197 TFLOP/s.  No kind, no number: a program
    without the layer leaves both out."""
    model = _model()
    blocks = {"linear_attention": {"forward": 0.3, "backward": 0.5},
              "delta_scan": {"forward": 0.05, "backward": 0.15},
              "attention": {"forward": 0.1}}
    facts = _facts(model, blocks)
    ms = _reader("linear_attention_ms.train").read(facts)
    assert abs(ms - 100.0) < 1e-9
    share = _reader("delta_scan_roofline.train").read(facts)
    want = 100.0 * model.delta_scan_flops(_sizes()) / (0.02 * 197e12)
    assert abs(share - want) < 1e-9 and 8.5 < share < 9.5
    bare = _facts(model, {"attention": {"forward": 0.1}})
    assert _reader("linear_attention_ms.train").read(bare) is None
    assert _reader("delta_scan_roofline.train").read(bare) is None
    assert _reader("linear_attention_ms.train").read(
        dict(facts, trace=None)) is None
    assert _reader("delta_scan_roofline.train").read(
        dict(facts, model=object())) is None


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
        # on the CPU nothing is traced, and at toy sizes the held experts
        # have no blocks of slots: the metrics read from the device trace,
        # the peaks and the blocks are left out, as on a program that
        # lacks them; every other one is there
        off_chip = {"mfu.train", "pallas_time_share.train",
                    "moe_blocks_side_share.train", "attention_ms.train",
                    "experts_ms.train", "optimizer_ms.train",
                    "moe_products_roofline.train",
                    "flash_causal_roofline.train",
                    "linear_attention_ms.train",
                    "delta_scan_roofline.train"}
        assert TRAIN_METRICS - off_chip <= set(line["metrics"])
        assert not off_chip & {name for name, m in line["metrics"].items()
                               if m["value"] is not None}
        assert line["metrics"]["compiles_in_window.train"]["value"] == 0
        assert line["metrics"]["step_compiles.setup"]["value"] >= 1
    else:
        assert set(line["metrics"]) == {"train_samples_per_s",
                                        "train_step_p95_ms", "setup_s"}
    for metric in line["metrics"].values():
        assert metric["value"] is None or metric["unit"] == "count"


def test_the_parent_fails_the_cell_at_once_without_the_model(monkeypatch):
    """A program that lacks ``gluon.model_zoo.ling`` cannot build the
    configuration: ``_net`` raises before any device work, so the cell
    fails cleanly there."""
    model = _model()
    import mxnet_tpu.gluon.model_zoo as zoo
    monkeypatch.delattr(zoo, "ling")
    monkeypatch.setitem(sys.modules, "mxnet_tpu.gluon.model_zoo.ling", None)
    with pytest.raises(ImportError):
        model._net(_sizes())
