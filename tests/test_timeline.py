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


def _toy_step(hidden=16):
    net = nn.HybridSequential(prefix="toy_")
    with net.name_scope():
        net.add(nn.Dense(hidden, activation="relu", in_units=10),
                nn.Dense(4, in_units=hidden))
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
# (2b) the step's compiles: jax's own count, the leaves that caused the
# second, the six set-up readers (ISSUE 35)
# ---------------------------------------------------------------------------

SETUP_READERS = ("step_compiles.setup", "step_compile_s.setup",
                 "eager_programs.setup", "eager_compile_s.setup",
                 "compile_cache_hit_share.setup", "param_build_s.setup")


def _reader(name):
    import importlib.util
    path = os.path.join(os.path.dirname(PACKAGE), "benchmark",
                        "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("setup_reader", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def four_steps():
    """Four steps of a small ``DataParallelStep``, once a module: what the
    journal, the detector and the readers held afterwards (the autouse
    fixture wipes the program's telemetry before every test)."""
    import time

    import jax.monitoring
    jax_saw, walks = [], []

    def listener(event, seconds, fun_name=None, **_):
        if event == telemetry._BACKEND and fun_name == "jit(step_fn)":
            jax_saw.append(len(walks))

    real = telemetry.arg_signature

    def counted(args):
        walks.append(step._t - 1)
        return real(args)

    telemetry.reset()
    telemetry.enable()
    jax.monitoring.register_event_duration_secs_listener(listener)
    t0 = time.perf_counter()
    # a width no other test builds: its initializers and optimizer state
    # are programs this process has not compiled yet
    step, data, label = _toy_step(hidden=23)
    telemetry.arg_signature = counted
    try:
        for _ in range(4):
            step(data, label).wait_to_read()
    finally:
        telemetry.arg_signature = real
    wall_s = time.perf_counter() - t0
    seen = {
        "name": "DataParallelStep[%x]" % id(step),
        "jax_compiles": len(jax_saw), "walks": walks, "wall_s": wall_s,
        "counts": telemetry.compile_counts(),
        "events": telemetry.snapshot(events=4096)["events"],
        "recent": telemetry.recent_spans("parallel.step", 4),
        "read": {name: _reader(name).read({"steps": 4})
                 for name in SETUP_READERS},
        "read_nothing": {name: _reader(name).read({"spans": {}})
                         for name in SETUP_READERS},
    }
    jax_saw.clear()      # jax keeps the listener: leave it nothing to hold
    return seen


def test_the_detector_counts_what_jax_compiled(four_steps):
    assert four_steps["jax_compiles"] >= 1
    assert four_steps["counts"][four_steps["name"]] \
        == four_steps["jax_compiles"]
    compiles = [e for e in four_steps["events"]
                if e["kind"] in ("compile", "recompile")
                and e["name"] == four_steps["name"]]
    assert [e["n"] for e in compiles] \
        == list(range(1, four_steps["jax_compiles"] + 1))
    assert compiles[0]["kind"] == "compile"


def test_every_later_compile_names_the_leaves_that_moved(four_steps):
    events = four_steps["events"]
    records = [e for e in events if e["kind"] == "span"
               and e["name"] == "parallel.step.compile"]
    retraces = [e for e in events if e["kind"] == "recompile"
                and e["name"] == four_steps["name"]]
    assert len(records) == four_steps["jax_compiles"]
    assert len(retraces) == four_steps["jax_compiles"] - 1
    assert records[0]["step"] == 0 and records[0]["changed"] == []
    for retrace, record in zip(retraces, records[1:]):
        changed = retrace["changed"]
        assert changed and changed == record["changed"]
        # leaf paths over the step's arguments, by what jax keys apart
        assert all(re.match(
            r"(params|opt_states|t|lrs|rng|data|label)[\[.\w\]]*"
            r"\.(committed|sharding|shape\[\d+\]|dtype|weak_type): ", c)
            for c in changed), changed
        assert record["n"] == retrace["n"]
    for record in records:
        assert record["cache"] in ("hit", "miss", "off")
        assert record["lower_s"] > 0 and record["backend_s"] > 0
        assert record["dur_ms"] == pytest.approx(
            (record["trace_s"] + record["lower_s"] + record["backend_s"])
            * 1e3, abs=1e-3)


def test_a_compile_record_hangs_under_its_steps_call_span(four_steps):
    events = four_steps["events"]
    spans = [e for e in events if e["kind"] == "span"]
    steps = [e for e in spans if e["name"] == "parallel.step"]
    hooks = [e for e in events if e["kind"] == "step"]
    assert len(steps) == len(hooks) == 4
    records = [e for e in spans if e["name"] == "parallel.step.compile"]
    compiled_steps = []
    for record in records:
        call, = [e for e in spans if e.get("sid") == record["parent"]]
        assert call["name"] == "parallel.step.call"
        assert call["trace"] == record["trace"]
        parent, = [e for e in steps if e["sid"] == call["parent"]]
        assert steps.index(parent) == record["step"]
        compiled_steps.append(record["step"])
    # the step hook of exactly those steps says so
    assert [h["index"] for h in hooks if h.get("compiled")] \
        == compiled_steps
    assert all(h.get("compiled", True) is True for h in hooks)


def test_steady_steps_record_nothing_and_walk_no_signature(four_steps):
    assert four_steps["jax_compiles"] <= 2
    compiled = sorted({e["step"] for e in four_steps["events"]
                       if e.get("name") == "parallel.step.compile"})
    assert compiled == list(range(four_steps["jax_compiles"]))
    # one walk of the arguments a compile, none at steps 2 and 3
    assert four_steps["walks"] == compiled
    assert not [e for e in four_steps["events"] if e["kind"] == "step"
                and e["index"] >= 2 and e.get("compiled")]


def test_the_compile_record_leaves_the_steps_self_time_alone(four_steps):
    spans, short = four_steps["recent"]
    assert short == 0 and len(spans) == 4
    steps = [e for e in four_steps["events"] if e["kind"] == "span"
             and e["name"] == "parallel.step"]
    for span, rec in zip(spans, steps):
        assert sorted(span["children"]) == ["parallel.step.call",
                                            "parallel.step.place"]
        assert span["dur_ms"] == rec["dur_ms"]
        self_ms = span["dur_ms"] - span["children"]["parallel.step.place"] \
            - span["children"]["parallel.step.call"]
        assert 0 <= self_ms < span["dur_ms"]


@pytest.mark.parametrize("name", SETUP_READERS)
def test_setup_readers_read_the_program_in_process(four_steps, name):
    value = four_steps["read"][name]
    assert four_steps["read_nothing"][name] is None
    read = four_steps["read"]
    if name == "step_compiles.setup":
        assert value == four_steps["jax_compiles"] \
            and isinstance(value, int)
    elif name == "eager_programs.setup":
        assert isinstance(value, int) and value > 0
    elif name == "compile_cache_hit_share.setup":
        # None where the persistent cache is off
        assert value is None or 0.0 <= value <= 100.0
    else:
        assert isinstance(value, float) and value >= 0.0
        assert read["step_compile_s.setup"] \
            + read["eager_compile_s.setup"] <= four_steps["wall_s"]
        assert read["param_build_s.setup"] <= four_steps["wall_s"]


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


def test_a_window_adds_no_kernel_site():
    """The window's lower bound (PR 37) rides in the masked family as a
    third integer a query: 22 sites as before it, three of them masked."""
    names = [name for _, name in _pallas_call_names()]
    assert len(names) == 22
    assert sorted(n for n in names if n.startswith("flash_masked")) == [
        "flash_masked_dkv", "flash_masked_dq", "flash_masked_fwd"]


def test_no_two_pallas_call_sites_share_a_name():
    counts = collections.Counter(name for _, name in _pallas_call_names())
    assert [n for n, c in counts.items() if c > 1] == []
