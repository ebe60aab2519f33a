"""Tests of the readers of the program's own step spans
(``step_call_ms.train``, ``step_self_ms.train``).  Not under ``tests/``: run
with

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import json
import os
import statistics
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

READERS = ["step_call_ms.train", "step_self_ms.train"]


def _reader(name):
    from benchmark import harness
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"), "under_test")


@pytest.fixture(scope="module")
def five_steps():
    """Five steps of a toy ``DataParallelStep`` and, by hand from the
    journal, each step's span with its two children."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel, telemetry
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu", in_units=6),
            nn.Dense(4, in_units=8))
    net.initialize()
    step = parallel.DataParallelStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.SGD(learning_rate=0.1))
    data = mx.nd.array(onp.ones((4, 6), "float32"))
    label = mx.nd.array(onp.zeros((4,), "float32"))
    telemetry.reset()
    telemetry.enable()
    for _ in range(5):
        step(data, label)
    spans = [e for e in telemetry.snapshot(events=4096)["events"]
             if e["kind"] == "span"]
    by_hand = []
    for parent in (e for e in spans if e["name"] == "parallel.step"):
        kids = {e["name"]: e["dur_ms"] for e in spans
                if e.get("parent") == parent["sid"]}
        by_hand.append((parent["dur_ms"], kids["parallel.step.place"],
                        kids["parallel.step.call"]))
    assert len(by_hand) == 5
    return by_hand


def test_call_reader_is_the_median_of_the_call_spans(five_steps):
    for steps in (5, 3):
        want = statistics.median(c for _, _, c in five_steps[-steps:])
        assert _reader("step_call_ms.train").read({"steps": steps}) == want


def test_self_reader_is_the_median_of_step_minus_children(five_steps):
    for steps in (5, 3):
        want = statistics.median(d - (p + c)
                                 for d, p, c in five_steps[-steps:])
        assert _reader("step_self_ms.train").read({"steps": steps}) \
            == pytest.approx(want, abs=1e-9)


@pytest.mark.parametrize("name", READERS)
def test_more_steps_than_the_program_holds_is_no_number(five_steps, name):
    assert _reader(name).read({"steps": 6}) is None
    assert _reader(name).read({}) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_spans_gives_nothing(five_steps, name,
                                                   monkeypatch):
    """The parent of the PR that added the readers: no ``recent_spans``,
    and no children under ``parallel.step``."""
    from mxnet_tpu import telemetry

    whole = telemetry.recent_spans
    monkeypatch.setattr(
        telemetry, "recent_spans",
        lambda name, n: ([dict(s, children={}) for s in whole(name, n)[0]],
                         0))
    assert _reader(name).read({"steps": 5}) is None
    monkeypatch.delattr(telemetry, "recent_spans")
    assert _reader(name).read({"steps": 5}) is None


def test_traced_rehearsal_names_both_and_withholds_the_times():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "resnet50-train-resident", "--seed", "3",
         "--seconds", "1", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    for name in READERS:
        assert line["metrics"][name] == {"value": None, "unit": "ms"}
