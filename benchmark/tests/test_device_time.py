"""Tests of the device time by block and by kernel: ``reduce_trace``'s
``kernels`` and ``blocks`` (leaf time, never nested time twice), the
configurations' ``BLOCKS`` and counting functions, and the eight readers
built on them (``attention_ms.train``, ``experts_ms.train``,
``mixer_ms.train``, ``optimizer_ms.train``, ``moe_products_roofline.train``,
``moe_combine_roofline.train``, ``ssd_roofline.train``,
``flash_causal_roofline.train``).  Run with

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
from benchmark import harness, reduce_trace as rt  # noqa: E402

RECORDING = os.path.join(HERE, "data", "laguna_step_cut.xplane.pb")
READERS = ("attention_ms.train", "experts_ms.train", "mixer_ms.train",
           "optimizer_ms.train", "moe_products_roofline.train",
           "moe_combine_roofline.train", "ssd_roofline.train",
           "flash_causal_roofline.train")
FORWARD = "jit(step_fn)/jvp(forward)/"
BACKWARD = "jit(step_fn)/transpose(jvp(forward))/"


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _sizes(config, rehearsal=False):
    from benchmark.run import _merge
    cfg = _load("benchmark", "configs", config, "config.json")
    sizes = {k: v for k, v in cfg.items() if k != "rehearsal"}
    return _merge(sizes, cfg["rehearsal"]) if rehearsal else sizes


def _model(config):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "configs", config, "model.py"), "m_" + config)


def _reader(name):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"), "reader")


def _hlo(name, pallas=False):
    return "%%%s.1 = bf16[8] custom-call()%s" % (
        name, ", " + rt.PALLAS_MARK if pallas else "")


# ---------------------------------------------------------------------------
# the reduction on hand-made events
# ---------------------------------------------------------------------------

def test_leaf_time_counts_nested_time_once():
    # a loop [0, 10) holds [2, 5) which holds [3, 4), then [6, 8); the
    # last event starts inside the loop and runs on past its end
    got = rt.leaf_times([(0, 10), (2, 5), (3, 4), (6, 8), (9, 12)])
    assert got == [4, 2, 1, 2, 3]
    assert sum(got) == 12                       # the union's length
    assert rt.leaf_times([(0, 4), (0, 1)]) == [3, 1]   # the shorter inside
    assert rt.leaf_times([]) == []


def test_phase_and_kind_from_the_op_name():
    blocks = {"attention": [re.compile(r"_layer\d+_attn$")],
              "experts": [re.compile(r"_layer\d+_experts$")]}
    recompute = (BACKWARD + "m_layer1/m_layer1_experts/transpose(transpose("
                 "jvp(forward)))/m_layer1/m_layer1_experts/jvp(jit(_take))/"
                 "gather")
    assert rt.phase_of(recompute) == "backward"
    assert rt.kind_of(recompute, blocks) == "experts"
    qkv = FORWARD + "m_layer0/m_layer0_attn/m_layer0_attn_qkv/dot_general"
    assert (rt.phase_of(qkv), rt.kind_of(qkv, blocks)) \
        == ("forward", "attention")
    adam = "jit(step_fn)/optimizer/mul"
    assert (rt.phase_of(adam), rt.kind_of(adam, blocks)) \
        == ("optimizer", "optimizer")
    # outside the step's scopes: the key's split, a bare op, no block
    for name in ("jit(step_fn)/jit(_threefry_split)/x/add",
                 "jit(step_fn)/transpose"):
        assert rt.phase_of(name) == "other"
        assert rt.kind_of(name, blocks) == "unattributed"
    # a primitive is not a block, whatever it is called
    assert rt.kind_of(FORWARD + "m_layer0_attn", blocks) == "unattributed"


def _loop_trace():
    """One chip: a ``conditional`` holding the experts' kernels, an
    attention kernel, twelve fusions of other names, Adam, a copy with no
    ``op_name``, and a kernel below the tenth operation that the window
    cuts in half."""
    events, names = [], []

    def add(hlo, start, dur, name):
        events.append((hlo, float(start), float(dur)))
        names.append(name)

    experts = BACKWARD + "m_layer1/m_layer1_experts/"
    add("%conditional.3 = (bf16[8]) conditional(pred[] %p)", 0, 400,
        experts + "cond")
    add(_hlo("moe_blocks_fwd", True), 20, 100, experts + "pallas_call")
    add(_hlo("moe_blocks_dw", True), 150, 150, experts + "pallas_call")
    add(_hlo("flash_dq", True), 400, 200,
        BACKWARD + "m_layer0/m_layer0_attn/pallas_call")
    for i in range(12):
        add("%%op%s_fusion.%d = bf16[8] fusion()" % (chr(97 + i), i),
            600 + 20 * i, 20, FORWARD + "m_layer0/m_layer0_attn/dot_general")
    add("%fusion.99 = f32[8] fusion()", 840, 60, "jit(step_fn)/optimizer/add")
    add("%copy-done.4 = bf16[8] copy-done()", 900, 20, None)
    add(_hlo("ssd_fwd", True), 990, 20,
        FORWARD + "m_layer2/m_layer2_mamba/pallas_call")
    spans = [(rt.WINDOW_SPAN, 0.0, 1000.0)]
    return {"devices": {0: events}, "spans": spans, "op_names": {0: names}}


def test_blocks_and_kernels_on_hand_made_events():
    blocks = {"attention": [r"_layer\d+_attn$"],
              "experts": [r"_layer\d+_experts$"],
              "mixer": [r"_layer\d+_mamba$"]}
    got = rt.reduce(_loop_trace(), blocks=blocks)
    # the conditional's own time is what its kernels leave: 400 - 250
    assert got["blocks"]["experts"] == {"backward": pytest.approx(400e-9)}
    assert got["blocks"]["attention"] == {
        "backward": pytest.approx(200e-9), "forward": pytest.approx(240e-9)}
    assert got["blocks"]["optimizer"] == {"optimizer": pytest.approx(60e-9)}
    assert got["blocks"]["unattributed"] == {"other": pytest.approx(20e-9)}
    assert got["blocks"]["mixer"] == {"forward": pytest.approx(10e-9)}
    total = sum(sum(p.values()) for p in got["blocks"].values())
    assert total == pytest.approx(got["busy_s"], rel=1e-12)
    # every kernel, however small, with its calls: the last one half in
    kernels = {n: (s, c) for n, s, c in got["kernels"]}
    assert kernels == {"moe_blocks_fwd": (pytest.approx(100e-9), 1.0),
                       "moe_blocks_dw": (pytest.approx(150e-9), 1.0),
                       "flash_dq": (pytest.approx(200e-9), 1.0),
                       "ssd_fwd": (pytest.approx(10e-9), 0.5)}
    assert "ssd_fwd [pallas]" not in dict(got["device_ops"])
    assert [n for n, _, _ in got["kernels"]][0] == "flash_dq"
    # the top operations are as before: nested time counted twice there
    assert dict(got["device_ops"])["conditional"] == pytest.approx(400e-9)


def test_without_op_names_there_are_kernels_but_no_blocks():
    loaded = dict(_loop_trace(), op_names=None)
    got = rt.reduce(loaded)
    assert got["blocks"] is None and len(got["kernels"]) == 4
    # no mapping: the optimizer alone is a kind
    got = rt.reduce(_loop_trace())
    assert set(got["blocks"]) == {"optimizer", "unattributed"}


def test_averages_over_the_chips():
    one = _loop_trace()
    two = dict(one, devices={0: one["devices"][0], 3: one["devices"][0]},
               op_names={0: one["op_names"][0], 3: one["op_names"][0]})
    a, b = rt.reduce(one), rt.reduce(two)
    assert a["kernels"] == b["kernels"]
    assert b["blocks"]["optimizer"]["optimizer"] \
        == pytest.approx(a["blocks"]["optimizer"]["optimizer"])


# ---------------------------------------------------------------------------
# the reduction on a recording cut from a chip run
# ---------------------------------------------------------------------------

def test_blocks_on_the_recorded_laguna_cut():
    with open(RECORDING[:-len(".xplane.pb")] + ".expect.json") as f:
        expect = json.load(f)
    loaded = rt.load(RECORDING)
    assert len(loaded["devices"][0]) == expect["events"]
    # the chip's events carry no op_name: their metadata does
    assert loaded["op_names"] is not None
    got = rt.reduce(loaded, blocks=_model(
        "laguna-s-2.1-ep32share").BLOCKS)
    # leaf time is never counted twice
    total = sum(sum(p.values()) for p in got["blocks"].values())
    assert total <= got["busy_s"] * (1 + 1e-9)
    assert total == pytest.approx(got["busy_s"], rel=1e-6)
    # the cut holds a conditional with the block kernels inside it and a
    # flash kernel, and the kernels below the tenth operation keep their
    # seconds and calls
    tops = [n for n, _ in got["device_ops"]]
    assert "conditional" in tops
    kernels = {n: (s, c) for n, s, c in got["kernels"]}
    for name in expect["kernels"]:
        assert name in kernels, name
        assert kernels[name][1] == pytest.approx(
            expect["kernels"][name], abs=1e-9), name
    below = [n for n in kernels if n + rt.PALLAS_TAG not in tops]
    assert below, "no kernel under the ten-operation cut"
    assert any(n.startswith("moe_blocks_") for n in kernels)
    assert any(n.startswith("flash_") for n in kernels)
    for kind, share in expect["block_shares"].items():
        assert sum(got["blocks"][kind].values()) / total == pytest.approx(
            share, abs=1e-6), kind
    assert sum(got["blocks"].get("unattributed", {}).values()) \
        < 0.1 * total


def _without_blocks(loaded):
    got = rt.reduce(loaded, blocks=_model("laguna-s-2.1-ep32share").BLOCKS)
    return {k: v for k, v in got.items() if k != "blocks"}, got["blocks"]


@pytest.mark.parametrize("fault", ["short_line", "unreadable", "no_tf_op"])
def test_an_unread_op_name_loses_the_blocks_alone(monkeypatch, capsys,
                                                  fault):
    whole, blocks = _without_blocks(rt.load(RECORDING))
    assert blocks is not None
    read = rt._metadata_op_names

    def faulty(path):
        if fault == "unreadable":
            raise ValueError("cannot parse")
        names = read(path)
        if fault == "short_line":
            return {chip: n[:-1] for chip, n in names.items()}
        return {chip: [None] * len(n) for chip, n in names.items()}

    monkeypatch.setattr(rt, "_metadata_op_names", faulty)
    loaded = rt.load(RECORDING)
    assert loaded["op_names"] is None
    assert "reduce_trace: no blocks" in capsys.readouterr().err
    rest, blocks = _without_blocks(loaded)
    assert blocks is None
    # busy, window, top operations, kernels and gaps are read as before
    assert rest == whole


def test_the_recording_stays_small():
    assert os.path.getsize(RECORDING) < 400 * 1024


# ---------------------------------------------------------------------------
# the configurations' mappings and counts
# ---------------------------------------------------------------------------

def test_nemotron_norms_go_with_their_layers_mixer():
    sizes = _sizes("nemotron3-nano-30b-ep16share")
    blocks = {k: [re.compile(p) for p in v] for k, v in _model(
        "nemotron3-nano-30b-ep16share").BLOCKS.items()}
    kind = {"M": "mixer", "E": "experts", "*": "attention"}
    for i, c in enumerate(sizes["hybrid_override_pattern"][
            :sizes["num_hidden_layers"]]):
        name = FORWARD + "nemotronhmodel0_layer%d/nemotronhmodel0_layer%d" \
            "_norm/mul" % (i, i)
        assert rt.kind_of(name, blocks) == kind[c], i


def test_laguna_dense_layer_is_the_ffn():
    sizes = _sizes("laguna-s-2.1-ep32share")
    blocks = {k: [re.compile(p) for p in v] for k, v in _model(
        "laguna-s-2.1-ep32share").BLOCKS.items()}
    for i, mlp in enumerate(sizes["mlp_layer_types"][
            :sizes["num_hidden_layers"]]):
        norm = FORWARD + "lagunamodel0_layer%d/lagunamodel0_layer%d" \
            "_ffn_norm/mul" % (i, i)
        assert rt.kind_of(norm, blocks) \
            == {"dense": "ffn", "sparse": "experts"}[mlp], i
    shared = BACKWARD + "lagunamodel0_layer2/lagunamodel0_layer2_shared/" \
        "lagunamodel0_layer2_shared_up/dot_general"
    assert rt.kind_of(shared, blocks) == "ffn"


@pytest.mark.parametrize("config", ["zaya1-8b-ep2share",
                                    "nemotron3-nano-30b-ep16share",
                                    "sdar-30b-a3b-ep8share",
                                    "laguna-s-2.1-ep32share"])
def test_the_compilers_ragged_products_are_the_experts(config):
    """On the chip ``lax.ragged_dot`` runs as the compiler's own kernels,
    whose ``op_name`` is their name alone (``ragged-dot-none:`` in
    ZAYA1's trace): no scope, so the configuration names them."""
    blocks = {k: [re.compile(p) for p in v]
              for k, v in _model(config).BLOCKS.items()}
    for name in ("ragged-dot-none:", "ragged-dot-metadata:"):
        assert rt.kind_of(name, blocks) == "experts", name
    # another operation outside every scope is still nobody's
    for name in ("pvals[0]:", "copy:", "jit(step_fn)/jvp(forward)/add:"):
        assert rt.kind_of(name, blocks) == "unattributed", name


def test_counts_by_hand_at_rehearsal_sizes():
    # ZAYA1: 2 layers, 4 query heads of 16, 64 tokens; gate, up, down of
    # 64 x 32
    zaya = _sizes("zaya1-8b-ep2share", rehearsal=True)
    m = _model("zaya1-8b-ep2share")
    assert m.causal_attention_flops(zaya) == 18 * 16 * (64 * 65 // 2) * 4 * 2
    assert m.moe_product_flops(zaya, 100) == 3 * (3 * 2 * 64 * 32) * 100
    # Nemotron: "ME*E" — one scan layer, one attention layer; relu2 has two
    # products
    nemo = _sizes("nemotron3-nano-30b-ep16share", rehearsal=True)
    m = _model("nemotron3-nano-30b-ep16share")
    assert m.causal_attention_flops(nemo) == 18 * 16 * 2080 * 4
    assert m.moe_product_flops(nemo, 10) == 3 * (2 * 2 * 64 * 24) * 10
    # the scan at chunks of 16 over 64 tokens, 2 groups, 8 heads of 8,
    # state 16: multiply-adds of one chunk, then x 2 x 3
    scores = 2 * 16 * 16 * 16              # C B^T, a group
    in_chunk = 8 * 16 * 16 * 8             # (L o C B^T) x, a head
    states = 8 * 16 * 16 * 8               # B^T x, a head
    output = 8 * 16 * 16 * 8               # C h, a head
    assert m.ssd_flops(nemo) == 4 * 2 * 3 * (
        scores + in_chunk + states + output)
    # Laguna: the two full layers of 6 heads; the window layers run the
    # masked kernels — unless the window covers the row
    lag = _sizes("laguna-s-2.1-ep32share", rehearsal=True)
    m = _model("laguna-s-2.1-ep32share")
    assert m.causal_attention_flops(lag) == 18 * 16 * 2080 * (6 + 6)
    assert m.causal_attention_flops(dict(lag, sliding_window=64)) \
        == 18 * 16 * 2080 * (6 + 9 + 9 + 9 + 6)
    assert m.moe_product_flops(lag, 7) == 3 * (3 * 2 * 64 * 24) * 7
    # SDAR: one row of [clean ; noised] = 128 tokens of 64, 10 held routes
    sdar = _sizes("sdar-30b-a3b-ep8share", rehearsal=True)
    m = _model("sdar-30b-a3b-ep8share")
    assert m.moe_combine_bytes(sdar, 1, 10) == 2 * 64 * (10 + 128)
    assert m.moe_product_flops(sdar, 5) == 3 * (3 * 2 * 64 * 24) * 5


def test_counts_at_the_cells_sizes():
    """The published shapes give the per-step work PERF.md section 5 prices."""
    m = _model("laguna-s-2.1-ep32share")
    assert 1.85e12 < m.causal_attention_flops(
        _sizes("laguna-s-2.1-ep32share")) < 1.86e12
    m = _model("zaya1-8b-ep2share")
    assert 2.47e12 < m.causal_attention_flops(
        _sizes("zaya1-8b-ep2share")) < 2.48e12          # a row
    m = _model("nemotron3-nano-30b-ep16share")
    nemo = _sizes("nemotron3-nano-30b-ep16share")
    assert 2.47e12 < m.causal_attention_flops(nemo) < 2.48e12
    assert 3.3e11 < m.ssd_flops(nemo) < 3.4e11


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def _facts(kernels, blocks=None, model=None, sizes=None, batch=1):
    trace = {"window_s": 2.0, "busy_s": 1.99, "kernels": kernels,
             "blocks": blocks, "device_ops": []}
    return {"trace": trace, "step_s_median": 0.25, "chips": 1,
            "global_batch": batch, "model": model, "sizes": sizes,
            "peaks": {"bf16_flops_per_s": 197e12,
                      "hbm_bytes_per_s": 819e9}}


def test_block_readers():
    blocks = {"attention": {"forward": 0.4, "backward": 0.8},
              "optimizer": {"optimizer": 0.16}}
    facts = _facts([], blocks)
    # 8 steps in the window
    assert _reader("attention_ms.train").read(facts) == pytest.approx(150.0)
    assert _reader("optimizer_ms.train").read(facts) == pytest.approx(20.0)
    assert _reader("mixer_ms.train").read(facts) is None
    assert _reader("experts_ms.train").read(dict(facts, trace=None)) is None


def test_roofline_readers(monkeypatch):
    from mxnet_tpu.gluon.contrib import nn as cnn
    counts = {"l1": {"load": [300, 100, 7, 9], "held": (0, 2)},
              "l2": {"load": [200, 0, 5, 5], "held": (0, 2)}}
    monkeypatch.setattr(cnn, "publish_routing_counts", lambda: counts)
    nemo = _sizes("nemotron3-nano-30b-ep16share")
    model = _model("nemotron3-nano-30b-ep16share")
    kernels = [["moe_blocks_fwd", 0.2, 16.0], ["moe_blocks_dx", 0.1, 8.0],
               ["moe_blocks_dw", 0.1, 8.0], ["ssd_fwd", 0.02, 32.0],
               ["ssd_states", 0.02, 32.0], ["ssd_bwd", 0.04, 32.0],
               ["flash_stream_fwd", 0.05, 8.0], ["flash_dq", 0.07, 8.0],
               ["flash_dkv", 0.08, 8.0]]
    facts = _facts(kernels, model=model, sizes=nemo)
    got = _reader("moe_products_roofline.train").read(facts)
    want = 100 * model.moe_product_flops(nemo, 600) * 8 / 0.4 / 197e12
    assert got == pytest.approx(want) and 0 < got < 100
    got = _reader("ssd_roofline.train").read(facts)
    assert got == pytest.approx(
        100 * model.ssd_flops(nemo) * 8 / 0.08 / 197e12)
    got = _reader("flash_causal_roofline.train").read(facts)
    assert got == pytest.approx(
        100 * model.causal_attention_flops(nemo) * 8 / 0.2 / 197e12)
    # the compiler's ragged products are the same work
    ragged = _facts([["ragged-dot-none", 0.4, 44.0]], model=model,
                    sizes=nemo)
    assert _reader("moe_products_roofline.train").read(ragged) \
        == pytest.approx(want)
    # a kernel that did not run: nothing
    for name in ("ssd_roofline.train", "flash_causal_roofline.train",
                 "moe_products_roofline.train"):
        assert _reader(name).read(_facts(kernels[:1] + kernels[4:5],
                                         model=model, sizes=nemo)) is None
    sdar = _sizes("sdar-30b-a3b-ep8share")
    model = _model("sdar-30b-a3b-ep8share")
    facts = _facts([["moe_combine", 0.12, 80.0]], model=model, sizes=sdar)
    got = _reader("moe_combine_roofline.train").read(facts)
    per_call = 2 * 2048 * (300 + 8192)      # the layers' mean held routes
    assert got == pytest.approx(100 * per_call * 80 / 0.12 / 819e9)
    monkeypatch.setattr(cnn, "publish_routing_counts", dict)
    assert _reader("moe_combine_roofline.train").read(facts) is None


@pytest.mark.parametrize("name", READERS)
def test_every_new_reader_returns_none_untraced(name):
    read = _reader(name).read
    assert read({"spans": {}}) is None
    sizes = _sizes("nemotron3-nano-30b-ep16share")
    untraced = dict(_facts([], model=_model("nemotron3-nano-30b-ep16share"),
                           sizes=sizes), trace=None)
    assert read(untraced) is None


@pytest.mark.parametrize("cell", ["nemotron3-nano-30b-train-resident",
                                  "sdar-30b-a3b-train-resident"])
def test_no_new_reader_reads_a_rehearsal(cell):
    """On the CPU nothing runs on a TPU and the trace is None: the eight
    readers leave their metrics out of the traced line."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "2147483777", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert not set(READERS) & set(line["metrics"])
    assert "compiles_in_window.train" in line["metrics"]


def test_the_spec_lists_each_reader_where_it_reads():
    spec = _load("BENCHMARK.json")
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    cells = {w["name"]: w["config"] for w in spec["workloads"]}
    for name in READERS:
        metric = per_layer[name]
        assert metric["source"] == "device_trace"
        assert metric["moves"] == "train_samples_per_s"
        assert metric["unit"] == ("ms" if name.endswith("_ms.train")
                                  else "%")
        assert metric["better"] == ("lower" if metric["unit"] == "ms"
                                    else "higher")
        # a roofline reads a count the cell's configuration has
        need = {"moe_products_roofline.train": "moe_product_flops",
                "moe_combine_roofline.train": "moe_combine_bytes",
                "ssd_roofline.train": "ssd_flops",
                "flash_causal_roofline.train": "causal_attention_flops"}
        for cell in metric["workloads"]:
            if name in need:
                assert hasattr(_model(cells[cell]), need[name]), (name, cell)
    # ResNet's SGD is fused into the backward whole: 0.06 ms a step on the
    # chip, so the metric would read where XLA put the update, not its cost
    assert set(per_layer["optimizer_ms.train"]["workloads"]) \
        == set(cells) - {"resnet50-train-resident"}
