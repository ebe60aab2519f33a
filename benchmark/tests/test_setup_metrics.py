"""The six ``.setup`` metrics at the command line (ISSUE 35): a traced
rehearsal of the BERT cell prints the two counts as integers and withholds
the four others, as every CPU time and share is withheld.  Not under
``tests/``: the tier-1 count does not move; the readers themselves are held
in-process by ``tests/test_timeline.py``.  Run with

    python -m pytest benchmark/tests/test_setup_metrics.py -q -p no:cacheprovider
"""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "bert-base-mlm-train-resident"
COUNTS = ("step_compiles.setup", "eager_programs.setup")
WITHHELD = ("step_compile_s.setup", "eager_compile_s.setup",
            "compile_cache_hit_share.setup", "param_build_s.setup")


def test_a_traced_rehearsal_prints_the_two_counts():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    metrics = json.loads(lines[-1])["metrics"]
    for name in COUNTS:
        assert metrics[name]["unit"] == "count"
        assert isinstance(metrics[name]["value"], int), (name, metrics[name])
    # the step program: once, and once more for what its second call
    # brought back committed (1 is ROADMAP S5's target)
    assert 1 <= metrics["step_compiles.setup"]["value"] <= 2
    assert metrics["eager_programs.setup"]["value"] > 0
    for name in WITHHELD:
        assert metrics[name]["value"] is None, (name, metrics[name])
    # the program books the events the harness counts: every program of
    # the [setup] line is the model's, the check's or the step's (the
    # harness's own memory_analysis() compile of the step is served from
    # jax's in-memory cache and reaches no backend)
    setup = next(json.loads(l.split("] ", 1)[1]) for l in lines
                 if l.startswith("[setup]"))
    assert setup["compiles"]["programs"] == (
        metrics["eager_programs.setup"]["value"]
        + metrics["step_compiles.setup"]["value"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in COUNTS + WITHHELD:
        assert declared[name]["moves"] == "setup_s"
        assert CELL in declared[name]["workloads"]
