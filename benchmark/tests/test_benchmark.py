"""Tests of the benchmark itself.  Not under ``tests/``: the tier-1 count
does not move.  Run with

    python -m pytest benchmark/tests -q -p no:cacheprovider

Every rehearsal is a subprocess on the CPU (``run.py --rehearse``): toy
sizes, counts only.  No test here touches a chip or prints a device metric.
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
SPEC = os.path.join(ROOT, "BENCHMARK.json")
MORE = os.path.join(HERE, "data", "BENCHMARK.more.json")
RECORDING = os.path.join(HERE, "data", "resnet_step_cut.xplane.pb")
sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _load(path):
    with open(path) as f:
        return json.load(f)


def _module(path):
    from benchmark import harness
    return harness.load_module(path, "under_test")


def _sizes(config, rehearsal=False):
    from benchmark.run import _merge
    cfg = _load(os.path.join(ROOT, "benchmark", "configs", config,
                             "config.json"))
    sizes = {k: v for k, v in cfg.items() if k != "rehearsal"}
    return _merge(sizes, cfg["rehearsal"]) if rehearsal else sizes


def _cells(path):
    return [(path, cell["name"]) for cell in _load(path)["workloads"]]


# ---------------------------------------------------------------------------
# BENCHMARK.json against the contract's characters, lengths and keys
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path", [SPEC, MORE])
def test_spec_meets_the_contract(path):
    spec = _load(path)
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(path) <= 64 * 1024
    assert 1 <= spec["run_seconds"] <= 51 \
        and isinstance(spec["run_seconds"], int)
    assert 1 <= len(spec["command"]) <= 32
    for word in spec["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/") \
            and ".." not in word
    paths = spec["paths"]
    assert 1 <= len(paths) <= 16

    def one_line(text):
        return 1 <= len(text) <= 200 and "\n" not in text \
            and "\t" not in text

    configs = {c["name"]: c for c in spec["configs"]}
    assert len(configs) == len(spec["configs"]) <= 24
    files = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"]) \
            and one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in paths)
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        assert sorted(c["reduced"]) == sorted(
            _load(os.path.join(ROOT, c["file"]))["reduced"])
    cells = [w["name"] for w in spec["workloads"]]
    assert len(set(cells)) == len(cells) <= 24
    pairs = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert one_line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert {w["config"] for w in spec["workloads"]} == set(configs)
    if path == SPEC:
        assert sum(w["chips"] == 4 for w in spec["workloads"]) \
            <= max(1, len(cells) // 4)

    end = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in end and "workloads" not in end["setup_s"]
    assert 1 <= len(end) == len(spec["end_to_end"]) <= 16
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    layer = {m["name"]: m for m in spec["per_layer"]}
    assert 1 <= len(layer) == len(spec["per_layer"]) <= 128
    assert not set(layer) & set(end)
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert one_line(m["layer"]) and m["moves"] in end
        # the metric it moves is reported wherever this one is
        moved = end[m["moves"]]
        assert set(m.get("workloads", cells)) \
            <= set(moved.get("workloads", cells))
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for cell in cells:
        mine = [m for m in spec["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2, cell
        assert any(cell in m.get("workloads", cells)
                   for m in spec["per_layer"]), cell


def test_files_under_paths_are_named_from_names():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, names in os.walk(os.path.join(ROOT, "benchmark")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in names:
            rel = os.path.relpath(os.path.join(base, name), ROOT)
            assert allowed.match(rel), rel


# ---------------------------------------------------------------------------
# rehearsals: every driver, every cell, the dp=4 cell from data alone
# ---------------------------------------------------------------------------

def _rehearse(spec, cell, trace, extra_env=None):
    env = dict(os.environ, **(extra_env or {}))
    env.pop("XLA_FLAGS", None)      # run.py asks for the devices itself
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--spec", spec, "--workload", cell, "--seed", "2147483999",
         "--seconds", "1", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("spec,cell", _cells(SPEC) + _cells(MORE))
def test_rehearsal_ends_in_the_contracts_line(spec, cell, trace):
    done = _rehearse(spec, cell, trace)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == LINE_KEYS
    # a timeout on a crowded CPU is a failed request, not a wrong one
    assert line["correct"] is True
    assert 0 <= line["failed"] < line["attempted"]
    declared = _load(spec)["per_layer" if trace else "end_to_end"]
    cells = [w["name"] for w in _load(spec)["workloads"]]
    mine = {m["name"]: m["unit"] for m in declared
            if cell in m.get("workloads", cells)}
    assert set(line["metrics"]) <= set(mine)
    if not trace:
        assert set(line["metrics"]) == set(mine)
    for name, metric in line["metrics"].items():
        assert metric["unit"] == mine[name]
        # a CPU run prints counts only
        assert metric["value"] is None or metric["unit"] == "count"
    chips = next(w["chips"] for w in _load(spec)["workloads"]
                 if w["name"] == cell)
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips, "memory_peak_bytes": None,
                              "rehearsal": True}


def test_dp4_zero_cell_is_data_alone():
    """The four-chip cell is one entry and one traffic file (those in
    ``tests/data``, and the same among the benchmark's cells and mixes):
    the driver builds the mesh, shards the batch and the optimizer from
    them."""
    done = _rehearse(MORE, "bert-base-mlm-train-dp4-zero", 0)
    assert done.returncode == 0, done.stderr[-2000:]
    setup = next(json.loads(l.split("] ", 1)[1])
                 for l in done.stdout.splitlines()
                 if l.startswith("[setup]"))
    window = next(json.loads(l.split("] ", 1)[1])
                  for l in done.stdout.splitlines()
                  if l.startswith("[window]"))
    assert setup["optimizer_shards"] == 4
    assert window["global_batch"] == 4 * _sizes(
        "bert-base-mlm", rehearsal=True)["train"]["batch_per_chip"]
    # the benchmark took the cell as it stands here: the same entry among
    # its cells, the same traffic file among its mixes
    entry = next(w for w in _load(MORE)["workloads"]
                 if w["name"] == "bert-base-mlm-train-dp4-zero")
    assert entry in _load(SPEC)["workloads"]
    traffic = entry["traffic"] + ".json"
    assert _load(os.path.join(ROOT, "benchmark", "traffic", traffic)) \
        == _load(os.path.join(HERE, "data", "traffic", traffic))


def test_without_a_tpu_there_is_no_result():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", _load(SPEC)["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "not a TPU" in done.stderr
    assert not any(l.startswith("{") for l in done.stdout.splitlines())


# ---------------------------------------------------------------------------
# the trace reduction against a recording cut from a chip run of PR 23
# ---------------------------------------------------------------------------

def test_op_name_cuts_the_hlo_line():
    from benchmark.reduce_trace import op_name
    assert op_name("%fusion.398 = (bf16[64,512,3072]{2,1,0}) fusion(bf16[2]"
                   " %p.1), kind=kOutput, calls=%fused_computation.1") \
        == "fusion"
    assert op_name("%subtract_convert_fusion.20 = (bf16[768,3072]) fusion("
                   ")") == "subtract_convert_fusion"
    assert op_name("%copy-done.45 = bf16[1] copy-done(%copy-start.45)") \
        == "copy-done"
    assert op_name('%transpose_jvp___.22 = (bf16[128,200704]{1,0}) custom-'
                   'call(bf16[1] %a = x), custom_call_target="tpu_custom_'
                   'call", operand_layout_constraints={}') \
        == "transpose_jvp___ [pallas]"
    assert op_name('%custom-call.20 = bf16[1] custom-call(), custom_call_'
                   'target="ConcatBitcast"') == "custom-call"
    assert op_name("%copy = bf16[1] copy(%x)") == "copy"


def test_reduce_on_hand_made_events():
    from benchmark import reduce_trace as rt
    pallas = '%jvp__.1 = bf16[8] custom-call(), ' + rt.PALLAS_MARK
    loaded = {
        "devices": {0: [("%fusion.1 = f32[] fusion()", 100.0, 300.0),
                        ("%fusion.2 = f32[] fusion()", 400.0, 50.0),
                        (pallas, 600.0, 200.0),
                        ("%copy.3 = f32[] copy()", 950.0, 100.0)]},
        "spans": [(rt.WINDOW_SPAN, 0.0, 1000.0),
                  ("bench.step_wait", 0.0, 480.0),
                  ("bench.step_dispatch", 480.0, 400.0)]}
    got = rt.reduce(loaded)
    assert got["window_s"] == pytest.approx(1000e-9)
    # [100,450) u [600,800) u [950,1000): the copy is clipped at the window
    assert got["busy_s"] == pytest.approx(600e-9)
    assert got["pallas_s"] == pytest.approx(200e-9)
    assert got["device_ops"][0] == ["fusion", pytest.approx(350e-9)]
    gaps = dict(got["idle_gaps"])
    # idle [0,100) under step_wait, [450,600) and [800,950): the first
    # starts under step_wait, the others under step_dispatch / nothing
    assert gaps["bench.step_wait"] == pytest.approx(250e-9)
    assert gaps["bench.step_dispatch"] == pytest.approx(150e-9)
    assert rt.reduce({"devices": {}, "spans": []}) is None


def test_reduce_on_the_recorded_xplane():
    from benchmark import reduce_trace as rt
    loaded = rt.load(RECORDING)
    assert list(loaded["devices"]) == [0]
    events = loaded["devices"][0]
    got = rt.reduce(loaded)
    with open(os.path.join(HERE, "data", "resnet_step_cut.expect.json")) as f:
        expect = json.load(f)
    assert len(events) == expect["events"]
    assert sorted({name for name, _, _ in loaded["spans"]}) \
        == expect["spans"]
    # by other arithmetic: operations of one line do not overlap, so busy
    # is the sum of their parts inside the window
    (w0, w1), = [(s, s + d) for n, s, d in loaded["spans"]
                 if n == rt.WINDOW_SPAN]
    inside = [(n, max(0.0, min(s + d, w1) - max(s, w0)))
              for n, s, d in events]
    assert got["busy_s"] == pytest.approx(
        sum(t for _, t in inside) * 1e-9, rel=1e-6)
    assert got["pallas_s"] == pytest.approx(
        sum(t for n, t in inside if "tpu_custom_call" in n) * 1e-9,
        rel=1e-6)
    assert got["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert got["pallas_s"] == pytest.approx(expect["pallas_s"], rel=1e-9)
    assert 0 < got["pallas_s"] < got["busy_s"] <= got["window_s"]
    assert [op for op, _ in got["device_ops"]][:3] == expect["top_ops"]
    # names are cut to operations: no HLO text left in them
    assert all(" = " not in op and "%" not in op
               for op, _ in got["device_ops"])
    assert any(op.endswith(rt.PALLAS_TAG) for op, _ in got["device_ops"])


# ---------------------------------------------------------------------------
# model FLOPs against hand counts
# ---------------------------------------------------------------------------

def test_bert_base_flops_by_hand():
    model = _module(os.path.join(ROOT, "benchmark", "configs",
                                 "bert-base-mlm", "model.py"))
    # multiply-adds of one 512-token row, forward:
    qkv = 512 * 768 * 2304
    out = 512 * 768 * 768
    ffn = 2 * 512 * 768 * 3072
    attention = 2 * 12 * 512 * 512 * 64          # QK^T and PV, 12 heads
    head = 76 * (768 * 768 + 768 * 30522)        # 76 = int(0.15 * 512)
    macs = 12 * (qkv + out + ffn + attention) + head
    assert macs == 50_144_716_800       # 300.9 GFLOP a row, trained
    assert model.model_flops(_sizes("bert-base-mlm")) == 6 * macs


def test_resnet50_flops_by_hand():
    model = _module(os.path.join(ROOT, "benchmark", "configs",
                                 "resnet50-v1", "model.py"))
    # (c_in, c_out, kernel, output size, how many) of He et al. Table 1,
    # stride on the first 1x1 of a stage (v1)
    table = [(3, 64, 7, 112, 1),
             # conv2_x, 56x56: first unit with projection, two more
             (64, 64, 1, 56, 1), (64, 64, 3, 56, 3), (64, 256, 1, 56, 3),
             (64, 256, 1, 56, 1), (256, 64, 1, 56, 2),
             # conv3_x, 28x28
             (256, 128, 1, 28, 1), (128, 128, 3, 28, 4),
             (128, 512, 1, 28, 4), (256, 512, 1, 28, 1),
             (512, 128, 1, 28, 3),
             # conv4_x, 14x14
             (512, 256, 1, 14, 1), (256, 256, 3, 14, 6),
             (256, 1024, 1, 14, 6), (512, 1024, 1, 14, 1),
             (1024, 256, 1, 14, 5),
             # conv5_x, 7x7
             (1024, 512, 1, 7, 1), (512, 512, 3, 7, 3),
             (512, 2048, 1, 7, 3), (1024, 2048, 1, 7, 1),
             (2048, 512, 1, 7, 2)]
    macs = sum(ci * co * k * k * o * o * n for ci, co, k, o, n in table)
    macs += 2048 * 1000
    sizes = _sizes("resnet50-v1")
    assert sum(n for *_, n in table) == len(model.conv_shapes(sizes)) == 53
    assert 3.7e9 < macs < 3.9e9          # the published "3.8 x 10^9 FLOPs"
    assert model.model_flops(sizes) == 6 * macs


def test_resnet_reference_with_batch_statistics_in_float32():
    """The chip run checks ResNet in eval mode (``model.py`` says why).  The
    reference's training-mode branch is held here instead, where rounding
    cannot hide a wrong formula: float32, toy size, batch statistics."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from benchmark import correct

    model = _module(os.path.join(ROOT, "benchmark", "configs",
                                 "resnet50-v1", "model.py"))
    sizes = dict(_sizes("resnet50-v1", rehearsal=True), dtype="float32")
    mx.random.seed(3)
    onp.random.seed(3)
    net = model._net(sizes)
    rows = onp.random.uniform(size=(8, 3, 64, 64)).astype("float32")
    params = model._params_in_graph_order(net)
    with autograd.train_mode():
        got = net(mx.nd.array(rows, ctx=mx.tpu())).asnumpy()
    want = onp.asarray(model.reference_forward(params, rows, sizes,
                                               train=True))
    verdict = correct.logits_agree(got, want)
    assert verdict["max_err"] <= 1e-3 * verdict["scale"], verdict


# ---------------------------------------------------------------------------
# readers: nothing to read, nothing returned
# ---------------------------------------------------------------------------

def test_a_reader_that_finds_nothing_returns_nothing():
    folder = os.path.join(ROOT, "benchmark", "layer_metrics")
    readers = [n for n in os.listdir(folder) if n.endswith(".py")]
    declared = {m["name"] + ".py" for path in (SPEC, MORE)
                for m in _load(path)["per_layer"]}
    assert declared <= set(readers)
    for name in readers:
        assert _module(os.path.join(folder, name)).read(
            {"spans": {}}) is None, name
