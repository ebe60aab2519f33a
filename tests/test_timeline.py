"""One timeline (ISSUE 24): the program's spans are events of the profiler's
own trace, the step has child spans and can be read back from memory, the
step program carries phase, block and kernel names."""
import ast
import collections
import glob
import os
import re

import jax
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, profiler, telemetry
from mxnet_tpu.gluon import nn

PACKAGE = os.path.dirname(os.path.abspath(mx.__file__))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    telemetry.enable()
    yield
    telemetry.reset()


def _toy_step():
    net = nn.HybridSequential(prefix="toy_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=10),
                nn.Dense(4, in_units=16))
    net.initialize()
    step = parallel.DataParallelStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.SGD(learning_rate=0.1, momentum=0.9))
    rs = onp.random.RandomState(0)
    data = mx.nd.array(rs.rand(8, 10).astype("float32"))
    label = mx.nd.array(rs.randint(0, 4, (8,)).astype("float32"))
    return step, data, label


# ---------------------------------------------------------------------------
# (1) spans in the profiler's trace
# ---------------------------------------------------------------------------

def _host_events(trace_dir):
    """``{name: [(start_ns, end_ns, stats)]}`` of the ``/host:CPU`` plane
    of the one xplane under ``trace_dir``."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events[e.name].append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return events


@pytest.fixture
def capture(tmp_path):
    """``capture(body)`` runs ``body()`` under a profiler capture with the
    benchmark harness's options and returns the host plane's events."""
    def run(body):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        return _host_events(str(tmp_path))
    return run


def test_nested_spans_are_events_of_the_profilers_trace(capture):
    def body():
        with telemetry.span("tl.outer"):
            with telemetry.span("tl.inner"):
                sum(range(1000))

    events = capture(body)
    (o0, o1, _), = events["tl.outer"]
    (i0, i1, _), = events["tl.inner"]
    assert o0 <= i0 <= i1 <= o1 and i1 > i0


def test_disabled_spans_leave_no_event_in_the_trace(capture):
    def body():
        with telemetry.span("tl.seen"):
            pass
        with telemetry.disabled():
            with telemetry.span("tl.unseen"):
                with telemetry.span("tl.unseen.child"):
                    pass

    events = capture(body)
    assert len(events["tl.seen"]) == 1
    assert not [n for n in events if n.startswith("tl.unseen")]
    assert "tl.unseen" not in telemetry.snapshot()["spans"]


def test_step_spans_are_profiler_steps_with_children_inside(capture):
    step, data, label = _toy_step()
    step(data, label).wait_to_read()      # the compile stays outside

    events = capture(lambda: [step(data, label) for _ in range(2)])
    steps = sorted(events["parallel.step"])
    assert [int(s[2]["step_num"]) for s in steps] == [1, 2]
    for child in ("parallel.step.place", "parallel.step.call"):
        kids = sorted(events[child])
        assert len(kids) == 2
        assert all(s0 <= k0 <= k1 <= s1
                   for (s0, s1, _), (k0, k1, _) in zip(steps, kids))


@pytest.mark.parametrize("telemetry_on", [True, False],
                         ids=["telemetry_on", "telemetry_off"])
def test_profiler_scope_annotates_exactly_once(capture, telemetry_on):
    """``mx.profiler`` scopes call into telemetry, whose span IS the
    annotation; with telemetry off they annotate themselves."""
    task = profiler.Domain("net").new_task("fwd")

    def body():
        with task:
            pass

    if telemetry_on:
        events = capture(body)
    else:
        with telemetry.disabled():
            events = capture(body)
    assert {n: len(v) for n, v in events.items() if "net::fwd" in n} \
        == {"profiler.net::fwd": 1}


# ---------------------------------------------------------------------------
# (2) the step's children, read back from memory
# ---------------------------------------------------------------------------

def test_three_steps_leave_three_spans_with_their_children():
    step, data, label = _toy_step()
    for _ in range(3):
        step(data, label)
    journal = [e for e in telemetry.snapshot(events=4096)["events"]
               if e["kind"] == "span"]
    parents = [e for e in journal if e["name"] == "parallel.step"]
    assert len(parents) == 3
    for parent in parents:
        kids = {e["name"]: e for e in journal
                if e.get("parent") == parent["sid"]}
        assert sorted(kids) == ["parallel.step.call", "parallel.step.place"]
        assert all(k["trace"] == parent["trace"] for k in kids.values())
        assert sum(k["dur_ms"] for k in kids.values()) <= parent["dur_ms"]

    spans, short = telemetry.recent_spans("parallel.step", 3)
    assert short == 0
    assert [s["dur_ms"] for s in spans] == [p["dur_ms"] for p in parents]
    assert all(sorted(s["children"]) == ["parallel.step.call",
                                         "parallel.step.place"]
               and sum(s["children"].values()) <= s["dur_ms"]
               for s in spans)


def test_recent_spans_reports_a_shortfall():
    step, data, label = _toy_step()
    for _ in range(3):
        step(data, label)
    spans, short = telemetry.recent_spans("parallel.step", 4)
    assert (len(spans), short) == (3, 1)
    assert telemetry.recent_spans("parallel.step", 2)[1] == 0
    assert telemetry.recent_spans("never.opened", 1) == ([], 1)


def test_recent_spans_does_not_count_a_span_whose_children_fell_off(
        monkeypatch):
    """The journal is bounded: a parent whose children may already have
    been evicted would read as all self time, so it is not held."""
    monkeypatch.setattr(telemetry, "_journal", collections.deque(maxlen=7))

    def family():
        with telemetry.trace():
            with telemetry.span("tl.parent"):
                with telemetry.span("tl.child"):
                    pass
                with telemetry.span("tl.child"):
                    pass

    family()
    family()
    spans, short = telemetry.recent_spans("tl.parent", 2)
    assert short == 0 and all(len(s["children"]) == 1 for s in spans)
    family()      # 9 records through 7 slots: the first family is cut
    spans, short = telemetry.recent_spans("tl.parent", 3)
    assert (len(spans), short) == (2, 1)
    assert all(s["children"]["tl.child"] <= s["dur_ms"] for s in spans)


# ---------------------------------------------------------------------------
# (3) names in the step program
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lowered_text():
    step, data, label = _toy_step()
    with pytest.raises(RuntimeError, match="run the step once"):
        step.lower(data, label)
    step(data, label)
    t_before = step._t
    text = step.lower(data, label).as_text(debug_info=True)
    assert step._t == t_before            # nothing ran, no state moved
    return text


@pytest.mark.parametrize("scope", ["jvp(forward)", "transpose(jvp(forward))",
                                   "optimizer", "toy_dense0", "toy_dense1"])
def test_lowered_step_carries_phase_and_block_names(lowered_text, scope):
    assert re.search(r'loc\("[^"]*\b%s[/"]' % re.escape(scope),
                     lowered_text), scope


def test_backward_operations_sit_under_their_block():
    """One scope names both passes: a block's backward reads
    ``transpose(jvp(forward))/<block>``."""
    step, data, label = _toy_step()
    step(data, label)
    text = step.lower(data, label).as_text(debug_info=True)
    assert "jvp(forward)/toy_dense0/" in text
    assert "transpose(jvp(forward))/toy_dense0/" in text


# ---------------------------------------------------------------------------
# (5) no anonymous kernel
# ---------------------------------------------------------------------------

def _pallas_call_names():
    """``[(file:line, name or None)]`` for every ``pallas_call(`` in the
    package, from the source."""
    sites = []
    for path in sorted(glob.glob(os.path.join(PACKAGE, "**", "*.py"),
                                 recursive=True)):
        with open(path) as f:
            source = f.read()
        if "pallas_call" not in source:
            continue
        for node in ast.walk(ast.parse(source)):
            if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Attribute) \
                    and node.func.attr == "pallas_call":
                name = next((kw.value.value for kw in node.keywords
                             if kw.arg == "name"
                             and isinstance(kw.value, ast.Constant)), None)
                sites.append(("%s:%d" % (os.path.relpath(path, PACKAGE),
                                         node.lineno), name))
    return sites


def test_every_pallas_call_passes_a_stable_name():
    """An unnamed kernel inherits the innermost scope of the name stack —
    ``jvp__`` before the scopes existed, a block's name since — and would
    hide in the device trace under it."""
    sites = _pallas_call_names()
    assert len(sites) >= 19
    # the scan's three kernels (PR 31) and the three under a mask given as
    # data (PR 33), by the names a trace shows
    assert {"ssd_fwd", "ssd_states", "ssd_bwd", "flash_masked_fwd",
            "flash_masked_dq", "flash_masked_dkv"} <= {n for _, n in sites}
    bad = [(where, name) for where, name in sites
           if not (isinstance(name, str)
                   and re.fullmatch(r"[a-z][a-z0-9]*(_[a-z0-9]+)+", name))]
    assert not bad, bad


def test_no_two_pallas_call_sites_share_a_name():
    counts = collections.Counter(name for _, name in _pallas_call_names())
    assert [n for n, c in counts.items() if c > 1] == []
