"""Runtime numerics sanitizer (tools.lint.runtime_numerics) tests.

The dynamic half of the num-* rule family, in the PR-6/7
static-vs-runtime pattern: observed dtypes must be consistent with the
static dtype-flow table, fp32 masters must stay float32, no tagged
leaf may drift dtypes or go non-finite.  The seeded-bug acceptance
here runs the SAME pristine/seeded pair of ``fx_zero_update.py``
modules the static half in tests/test_lint.py lints.
"""
import importlib.util
import logging
import os
import sys

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon import loss as gloss

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXDIR = os.path.join(REPO, "tests", "lint_fixtures")
ZPATH = os.path.join(FIXDIR, "fx_zero_update.py")

sys.path.insert(0, REPO) if REPO not in sys.path else None

from tools.lint.numerics import static_dtype_flow  # noqa: E402
from tools.lint.runtime_numerics import NumericsSanitizer  # noqa: E402

ZERO_KEY = "tests/lint_fixtures/fx_zero_update.py:zero_momentum_step.body"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# sanitizer unit tests
# ---------------------------------------------------------------------------

def test_observe_finite_and_journal():
    telemetry.reset()
    san = NumericsSanitizer()
    san.observe("t:leaf_ok", jnp.ones((4,), jnp.float32), step=0)
    san.assert_all_finite()
    san.observe("t:leaf_bad", jnp.asarray([1.0, onp.inf, onp.nan]),
                step=3)
    assert san.first_nonfinite == (3, "t:leaf_bad")
    with pytest.raises(AssertionError, match="non-finite"):
        san.assert_all_finite()
    # integer leaves record dtype only (no isfinite over ints)
    san.observe("t:leaf_int", jnp.arange(4), step=4)
    assert san.observed["t:leaf_int"]["nonfinite"] == 0
    events = [e for e in telemetry.snapshot(events=4096)["events"]
              if e.get("kind") == "numerics"]
    assert any(e["leaf"] == "t:leaf_bad" and e["nonfinite"] == 2 and
               e["step"] == 3 for e in events)


def test_dtype_drift_and_master_contract():
    san = NumericsSanitizer()
    san.observe("t:w", jnp.ones((2,), jnp.bfloat16))
    san.observe("t:w", jnp.ones((2,), jnp.bfloat16))
    san.assert_no_dtype_drift()
    san.observe("t:w", jnp.ones((2,), jnp.float32))   # live promotion
    with pytest.raises(AssertionError, match="drift"):
        san.assert_no_dtype_drift()
    san2 = NumericsSanitizer()
    san2.observe("t:m", jnp.ones((2,), jnp.float32), role="master")
    san2.assert_master_fp32()
    san2.observe("t:m2", jnp.ones((2,), jnp.bfloat16), role="master")
    with pytest.raises(AssertionError, match="master"):
        san2.assert_master_fp32()


def test_consistency_with_static_flow_table():
    flow = {"pkg/mod.py:fn": {"acc": "float32"}}
    san = NumericsSanitizer()
    san.observe("pkg/mod.py:fn:acc", jnp.ones((2,), jnp.float32))
    san.observe("pkg/mod.py:fn:other", jnp.ones((2,), jnp.bfloat16))
    san.assert_consistent_with(flow)      # unknown vars are not checked
    san.observe("pkg/mod.py:fn:acc", jnp.ones((2,), jnp.bfloat16))
    with pytest.raises(AssertionError, match="static float32"):
        san.assert_consistent_with(flow)


# ---------------------------------------------------------------------------
# seeded-bug acceptance: the SAME module pair as the static half
# ---------------------------------------------------------------------------

def _run_zero_step(mod):
    mesh = mod.make_mesh(onp.asarray(jax.devices()))
    rs = onp.random.RandomState(0)
    w = jnp.asarray(rs.randn(21).astype("float32"))
    # gradient magnitudes whose squares exceed the float16 range but
    # stay comfortably inside float32 — the fp32 upcast is what keeps
    # the grad-norm finite
    g = jnp.asarray(onp.full((21,), 300.0, "float32"))
    lr = jnp.asarray(0.1, jnp.float32)
    return mod.zero_momentum_step(mesh, w, g, lr)


def test_zero_update_pristine_consistent_with_static_flow():
    """The runtime-observed dtypes of the pristine ZeRO update match
    the static dtype-flow table of the same file, every value is
    finite, and the master shard is float32 — the PR-6/7
    static-vs-runtime contract, green on the pristine module."""
    flow = static_dtype_flow([ZPATH], root=REPO)
    assert flow[ZERO_KEY]["gnorm"] == "float32"
    assert flow[ZERO_KEY]["new_master"] == "float32"
    assert flow[ZERO_KEY]["half"] == "float16"
    mod = _load(ZPATH, "fx_zero_pristine")
    half, master, gnorm = _run_zero_step(mod)
    san = NumericsSanitizer()
    san.observe(ZERO_KEY + ":half", half, step=0)
    san.observe(ZERO_KEY + ":gnorm", gnorm, step=0)
    san.observe(ZERO_KEY + ":new_master", master, role="master", step=0)
    san.assert_all_finite()
    san.assert_no_dtype_drift()
    san.assert_master_fp32()
    san.assert_consistent_with(flow)


def test_zero_update_seeded_bug_trips_runtime_checks(tmp_path):
    """Acceptance (dynamic half): dropping the fp32 upcast — the same
    seed tests/test_lint.py proves trips num-lowprec-accum statically —
    must also trip the runtime sanitizer: the grad-norm is observed in
    float16 (inconsistent with the pristine static flow) AND overflows
    to inf (finite check)."""
    src = open(ZPATH).read()
    bugged = src.replace("g16.astype(jnp.float32)", "g16")
    assert bugged != src, "seeding site moved — update the test"
    p = tmp_path / "fx_zero_bug.py"
    p.write_text(bugged)
    flow = static_dtype_flow([ZPATH], root=REPO)   # PRISTINE contract
    mod = _load(str(p), "fx_zero_bug")
    half, master, gnorm = _run_zero_step(mod)
    san = NumericsSanitizer()
    san.observe(ZERO_KEY + ":gnorm", gnorm, step=0)
    assert san.dtypes()[ZERO_KEY + ":gnorm"] == "float16"
    with pytest.raises(AssertionError, match="static float32"):
        san.assert_consistent_with(flow)
    with pytest.raises(AssertionError, match="non-finite"):
        san.assert_all_finite()


# ---------------------------------------------------------------------------
# trainer sweep: params/grads/fp32 masters via the step hook
# ---------------------------------------------------------------------------

def _bf16_net_and_trainer():
    onp.random.seed(7)
    mx.random.seed(7)
    net = nn.HybridSequential()
    net.add(nn.Dense(5, activation="relu"), nn.Dense(3))
    net.initialize(mx.init.Xavier())
    net(mx.nd.array(onp.random.randn(4, 6).astype("float32")))
    net.cast("bfloat16")
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05,
                             "multi_precision": True})
    return net, trainer


def _steps(net, trainer, n=3):
    L = gloss.SoftmaxCrossEntropyLoss()
    rs = onp.random.RandomState(1)
    x = mx.nd.array(rs.randn(4, 6).astype("float32")).astype("bfloat16")
    y = mx.nd.array(rs.randint(0, 3, 4).astype("float32"))
    for _ in range(n):
        with autograd.record():
            loss = L(net(x), y)
        loss.backward()
        trainer.step(4)


def test_sanitizer_attach_trainer_master_fp32():
    """attach(trainer): the hook sweep observes bf16 params/grads and
    the multi_precision fp32 master leaves; the master contract, the
    no-drift contract and finiteness all hold over a real bf16
    training run."""
    net, trainer = _bf16_net_and_trainer()
    san = NumericsSanitizer().attach(trainer)
    try:
        _steps(net, trainer, n=3)
    finally:
        san.detach()
    masters = [s for s, r in san.observed.items()
               if r["role"] == "master"]
    params = [s for s, r in san.observed.items() if r["role"] == "param"]
    grads = [s for s, r in san.observed.items() if r["role"] == "grad"]
    assert masters and params and grads, san.observed
    assert all(san.dtypes()[s] == "bfloat16" for s in params)
    san.assert_all_finite()
    san.assert_no_dtype_drift()
    san.assert_master_fp32()
    # every master got re-checked across steps, not just once
    assert all(san.observed[s]["checks"] >= 2 for s in masters)


def test_sanitizer_interval_skips_steps():
    net, trainer = _bf16_net_and_trainer()
    san = NumericsSanitizer(interval=2).attach(trainer)
    try:
        _steps(net, trainer, n=4)
    finally:
        san.detach()
    # steps 0 and 2 are due: exactly 2 sweeps per site
    assert all(r["checks"] == 2 for r in san.observed.values()), \
        {s: r["checks"] for s, r in san.observed.items()}


def test_numerics_events_journal_and_render(tmp_path):
    """numerics/observed events land in the telemetry journal (first
    sighting, dtype change, non-finite count) and tools/parse_log.py
    --jsonl renders the per-leaf dtype + finite-gauge table."""
    telemetry.reset()
    san = NumericsSanitizer()
    san.observe("t:acc", jnp.ones((3,), jnp.float32), step=0)
    san.observe("t:acc", jnp.ones((3,), jnp.float32), step=1)  # no event
    san.observe("t:acc", jnp.ones((3,), jnp.bfloat16), step=2)  # drift
    san.observe("t:bad", jnp.asarray([onp.inf, 1.0]), step=5)
    obs = [e for e in telemetry.snapshot(events=4096)["events"]
           if e.get("kind") == "numerics"]
    assert len(obs) == 3, obs          # fresh, drift, nonfinite
    sink = tmp_path / "journal.jsonl"
    telemetry.export_jsonl(str(sink))
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import parse_log
    finally:
        sys.path.pop(0)
    agg = parse_log.parse_jsonl(sink.read_text().splitlines())
    assert agg["numerics"]["t:acc"]["dtypes"] == ["float32", "bfloat16"]
    assert agg["numerics"]["t:bad"]["nonfinite"] == 1
    assert agg["numerics"]["t:bad"]["first_bad_step"] == 5
    rendered = parse_log.render_jsonl(agg)
    assert "numerics/observed" in rendered
    assert "float32 -> bfloat16" in rendered
    telemetry.reset()


# ---------------------------------------------------------------------------
# Monitor nan_guard
# ---------------------------------------------------------------------------

def test_monitor_nan_guard_warns_on_first_nonfinite(caplog):
    net, trainer = _bf16_net_and_trainer()
    telemetry.reset()
    mon = mx.monitor.Monitor(interval=1000, pattern=".*",
                             nan_guard=True).attach(trainer)
    try:
        with caplog.at_level(logging.WARNING):
            _steps(net, trainer, n=1)
            assert not [r for r in caplog.records
                        if "nan_guard" in r.message]
            # poison one weight, then step again: the guard must name
            # the leaf and the step index, once
            p = next(iter(net.collect_params().values()))
            bad = onp.array(p.data().asnumpy().astype("float32"))
            bad[0] = onp.nan
            p.set_data(mx.nd.array(bad).astype(p.dtype))
            _steps(net, trainer, n=2)
    finally:
        mon.detach()
    warns = [r.message for r in caplog.records if "nan_guard" in r.message]
    assert len(warns) == 1, warns        # warn-once
    # the warning names a leaf and the first offending step (the NaN
    # spreads through the step's update before the sweep runs, so the
    # named leaf is whichever poisoned leaf of THIS net the sweep meets
    # first: the blocks' names carry a process-wide counter, and once it
    # passes 9 -> 10 between the net's two layers their order by name is
    # not their order in the net)
    assert "at step 1" in warns[0], warns[0]
    assert any("'%s'" % name in warns[0] for name in net.collect_params()), \
        (p.name, warns[0])
    # the sweep journaled the sanitizer-style numerics/observed event
    events = [e for e in telemetry.snapshot(events=4096)["events"]
              if e.get("kind") == "numerics"
              and e.get("role") == "nan_guard"]
    assert events and events[0]["nonfinite"] >= 1
