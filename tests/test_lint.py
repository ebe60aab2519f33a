"""graftlint tier-1 gate + checker unit tests.

The gate (`test_package_gate_zero_findings`) runs the full analyzer over
``mxnet_tpu/`` and fails on ANY new unsuppressed, un-baselined finding —
the static complement of the telemetry runtime detectors.  The fixture
tests assert exact rule IDs and line numbers against the seeded
violations in ``tests/lint_fixtures/`` (``# expect: <rule>`` markers).
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXDIR = os.path.join(REPO, "tests", "lint_fixtures")

sys.path.insert(0, REPO) if REPO not in sys.path else None

from tools.lint import run_lint, all_rules  # noqa: E402
from tools.lint.core import (Finding, diff_baseline, load_baseline,  # noqa: E402
                             parse_suppressions, write_baseline)


def _expected(path):
    """Parse `# expect: rule[, rule...]` markers -> {(rule, line), ...}."""
    out = set()
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            if "# expect:" in line:
                tail = line.split("# expect:", 1)[1].strip()
                for rule in tail.split(","):
                    out.add((rule.strip(), i))
    return out


def _lint_fixture(name):
    path = os.path.join(FIXDIR, name)
    return path, run_lint([path], baseline_path=None)


@pytest.mark.parametrize("name", ["fx_trace.py", "fx_retrace.py",
                                  "fx_donation.py", "fx_pallas.py",
                                  "fx_sharding.py", "fx_concurrency.py",
                                  "fx_numerics.py", "fx_errorflow.py"])
def test_fixture_rules_and_lines(name):
    path, result = _lint_fixture(name)
    got = {(f.rule, f.line) for f in result.new}
    want = _expected(path)
    assert got == want, (
        "finding mismatch for %s\n  missing: %s\n  extra: %s"
        % (name, sorted(want - got), sorted(got - want)))


def test_donation_flags_pr3_reconstruction():
    """Acceptance: the donation checker must flag the PR 3
    use-after-donate pattern (donated train-step carries read after the
    donating call) and stay quiet on the rebinding/mark_borrowed
    variants."""
    _, result = _lint_fixture("fx_donation.py")
    by_ctx = {}
    for f in result.new:
        by_ctx.setdefault(f.context, []).append(f.rule)
    assert by_ctx.get("pr3_use_after_donate") == ["donate-use-after-donate"]
    assert by_ctx.get("refeed_donated") == ["donate-use-after-donate"]
    assert by_ctx.get("helper_returned_donation") == \
        ["donate-use-after-donate"]
    for clean in ("train_loop", "borrowed_is_safe",
                  "metadata_reads_are_safe"):
        assert clean not in by_ctx, (clean, by_ctx.get(clean))


def test_suppressions_honored_and_reasons_mandatory():
    path, result = _lint_fixture("fx_suppress.py")
    got_new = {(f.rule, f.line) for f in result.new}
    assert got_new == _expected(path), got_new
    # the two properly-suppressed syncs land in .suppressed
    src = open(path).read().splitlines()
    line_a = next(i for i, l in enumerate(src, 1) if "a = float" in l)
    line_b = next(i for i, l in enumerate(src, 1) if "b = float" in l)
    suppressed = {(f.rule, f.line) for f in result.suppressed}
    assert ("trace-host-sync", line_a) in suppressed
    assert ("trace-host-sync", line_b) in suppressed


def test_suppression_parser_reason_forms():
    sups = parse_suppressions(
        "x = 1  # graftlint: disable=trace-host-sync -- inline reason\n"
        "# graftlint: disable-next=retrace-shape-branch --\n"
        "# reason on the continuation line\n"
        "y = 2\n"
        "z = 3  # graftlint: disable=trace-host-sync\n")
    assert sups[0].line == 1 and sups[0].reason == "inline reason"
    assert sups[1].line == 4
    assert sups[1].reason == "reason on the continuation line"
    assert sups[2].reason is None


def test_reasonless_suppression_cannot_steal_next_comment():
    """An inline suppression with no `--` must stay reasonless even when
    an unrelated comment follows — otherwise it silently activates and
    dodges lint-suppression-reason."""
    sups = parse_suppressions(
        "x = float(v)  # graftlint: disable=trace-host-sync\n"
        "# TODO: clean this up later\n")
    assert sups[0].reason is None
    # bare `--` without the -next form gets no continuation either
    sups = parse_suppressions(
        "x = float(v)  # graftlint: disable=trace-host-sync --\n"
        "# unrelated comment\n")
    assert sups[0].reason is None


def test_disable_next_covers_header_not_body(tmp_path):
    """disable-next above a compound statement covers only its header:
    a same-rule violation inside the body must still fire."""
    src = (
        "import jax\n"
        "\n"
        "\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    # graftlint: disable-next=trace-tracer-branch -- header ok\n"
        "    if x.sum() > 0:\n"
        "        if x.max() > 1:\n"
        "            x = x + 1\n"
        "    return x\n")
    p = tmp_path / "mod.py"
    p.write_text(src)
    result = run_lint([str(p)], baseline_path=None)
    assert [(f.rule, f.line) for f in result.suppressed] == \
        [("trace-tracer-branch", 7)]
    assert [(f.rule, f.line) for f in result.new] == \
        [("trace-tracer-branch", 8)]


def test_parse_error_fails_the_gate(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    result = run_lint([str(p)], baseline_path=None)
    assert [f.rule for f in result.new] == ["lint-parse-error"]


def test_baseline_diff_multiplicity(tmp_path):
    f = lambda line: Finding("trace-host-sync", "pkg/m.py", line, 0,
                             "sync", "fn")
    path = str(tmp_path / "baseline.json")
    write_baseline(path, [f(10)])
    table = load_baseline(path)
    # same (file, rule, context) at a DIFFERENT line stays baselined —
    # line drift must not churn the baseline
    new, old = diff_baseline([f(99)], table)
    assert not new and len(old) == 1
    # a second instance beyond the baselined count is NEW
    new, old = diff_baseline([f(10), f(20)], table)
    assert len(new) == 1 and len(old) == 1


# THE tier-1 full-package scan fixture (`package_scan`) is
# session-scoped in tests/conftest.py — shared by the gate,
# stale-suppression and changed-mode tests here so every rule family
# (numerics included) pays for ONE scan.


def test_package_gate_zero_findings(package_scan):
    """THE tier-1 gate: zero new findings over mxnet_tpu/ (stale
    suppressions included — the audit rides the gate scan), and the run
    is journaled into telemetry (lint.findings counter + lint event)."""
    from mxnet_tpu import telemetry
    result = package_scan
    assert result.files, "package scan found no files"
    msg = "\n".join(f.render() for f in result.new)
    assert not result.new, (
        "new graftlint findings (fix, or suppress with "
        "'# graftlint: disable=<rule> -- <reason>'):\n" + msg)
    # every inline suppression must carry a reason (checked by the
    # lint-suppression-reason meta rule, which lands in .new above);
    # the gate also emits its result into the telemetry journal
    assert telemetry.counter("lint.findings") == 0
    snap = telemetry.snapshot(events=4096)
    assert any(e.get("kind") == "lint" and e.get("name") == "gate"
               for e in snap["events"])


def test_detection_op_is_callback_free():
    """Satellite regression gate: the detection ops must stay pure
    jnp/lax — no host callbacks, no host syncs in jit-reachable code
    (this platform does not support callbacks; the *_host oracles are
    exempt because they are not jit-reachable)."""
    result = run_lint([os.path.join(REPO, "mxnet_tpu", "ops",
                                    "detection.py")],
                      baseline_path=None)
    trace = [f for f in result.new + result.suppressed
             if f.rule in ("trace-host-callback", "trace-host-sync")]
    assert not trace, "\n".join(f.render() for f in trace)


def test_cli_json_and_exit_codes(tmp_path):
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    # findings -> exit 1, valid JSON with exact rule/line payload
    res = subprocess.run(
        [sys.executable, "-m", "tools.lint",
         os.path.join(FIXDIR, "fx_retrace.py"), "--no-baseline",
         "--format", "json"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert res.returncode == 1, res.stderr
    data = json.loads(res.stdout)
    got = {(f["rule"], f["line"]) for f in data["findings"]}
    assert got == _expected(os.path.join(FIXDIR, "fx_retrace.py"))
    assert data["counts"]["new"] == len(got)
    # clean input -> exit 0 (the whole-package exit-0 path is covered
    # in-process by test_package_gate_zero_findings; a second full scan
    # in a subprocess would double the gate's tier-1 cost)
    res = subprocess.run(
        [sys.executable, "-m", "tools.lint",
         os.path.join(FIXDIR, "fx_donation.py"), "--no-baseline",
         "--rules", "trace-host-callback", "--format", "json"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    data = json.loads(res.stdout)
    assert data["counts"]["new"] == 0


def test_seeded_mesh_axis_bug_fails_the_gate(tmp_path):
    """Acceptance: renaming ONE mesh axis in a pristine parallel/ file
    must trip the sharding checker.  The unmodified copy stays clean —
    the finding comes from the seeded bug, not fixture noise."""
    src = open(os.path.join(REPO, "mxnet_tpu", "parallel",
                            "moe.py")).read()
    clean = tmp_path / "moe_clean.py"
    clean.write_text(src)
    result = run_lint([str(clean)], baseline_path=None)
    assert not result.new, "\n".join(f.render() for f in result.new)

    bugged = src.replace("recv_x = lax.all_to_all(send_x, axis,",
                         'recv_x = lax.all_to_all(send_x, "dp",')
    assert bugged != src, "seeding site moved — update the test"
    bad = tmp_path / "moe_bug.py"
    bad.write_text(bugged)
    result = run_lint([str(bad)], baseline_path=None)
    rules = {f.rule for f in result.new}
    assert "shard-axis-unknown" in rules, \
        "\n".join(f.render() for f in result.new)


# pristine two-lock module shared with the runtime half of the
# acceptance test (tests/test_runtime_lockorder.py reads the SAME
# fixture, so both detectors exercise byte-identical modules).  The
# seeded-bug test inverts ONE pair and the gate must trip.
LOCKPAIR_SRC = open(os.path.join(FIXDIR, "fx_lockpair.py")).read()
LOCKPAIR_INVERSION = (
    "def pop():\n    with _a:\n        with _b:",
    "def pop():\n    with _b:\n        with _a:")


def test_seeded_lock_inversion_fails_the_gate(tmp_path):
    """Acceptance: the pristine copy (consistent a->b order on every
    path) is clean; inverting ONE with-pair seeds the ABBA shape and
    must trip conc-lock-order."""
    clean = tmp_path / "lockpair_clean.py"
    clean.write_text(LOCKPAIR_SRC)
    result = run_lint([str(clean)], baseline_path=None)
    assert not result.new, "\n".join(f.render() for f in result.new)

    bugged = LOCKPAIR_SRC.replace(*LOCKPAIR_INVERSION)
    assert bugged != LOCKPAIR_SRC, "seeding site moved — update the test"
    bad = tmp_path / "lockpair_bug.py"
    bad.write_text(bugged)
    result = run_lint([str(bad)], baseline_path=None)
    rules = {f.rule for f in result.new}
    assert "conc-lock-order" in rules, \
        "\n".join(f.render() for f in result.new)


# pristine mini ZeRO update shared with the runtime half of the
# acceptance test (tests/test_runtime_numerics.py runs the SAME
# fixture on the mesh, so both detectors exercise byte-identical
# modules).  The seeded-bug test drops the fp32 upcast and the gate
# must trip.
ZERO_UPDATE_SRC = open(os.path.join(FIXDIR, "fx_zero_update.py")).read()
ZERO_UPDATE_SEED = ("g16.astype(jnp.float32)", "g16")


def test_seeded_lowprec_accum_fails_the_gate(tmp_path):
    """Acceptance: the pristine mini ZeRO update (explicit fp32 upcast
    before the reduce-scatter) is clean; dropping the upcast seeds the
    low-precision-accumulation bug and must trip num-lowprec-accum
    (the grad-norm now sums in float16) plus num-implicit-promotion
    (the master update now mixes f32 and f16)."""
    clean = tmp_path / "zero_clean.py"
    clean.write_text(ZERO_UPDATE_SRC)
    result = run_lint([str(clean)], baseline_path=None)
    assert not result.new, "\n".join(f.render() for f in result.new)

    bugged = ZERO_UPDATE_SRC.replace(*ZERO_UPDATE_SEED)
    assert bugged != ZERO_UPDATE_SRC, "seeding site moved — update the test"
    bad = tmp_path / "zero_bug.py"
    bad.write_text(bugged)
    result = run_lint([str(bad)], baseline_path=None)
    rules = {f.rule for f in result.new}
    assert "num-lowprec-accum" in rules, \
        "\n".join(f.render() for f in result.new)
    assert "num-implicit-promotion" in rules, \
        "\n".join(f.render() for f in result.new)


def _load_copy(path, name):
    """Import a seeded module copy under the package namespace so its
    relative imports resolve."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(name, str(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_seeded_dropped_commit_fails_gate_and_tears_at_runtime(tmp_path):
    """Acceptance (errorflow): deleting the ``os.replace`` commit from a
    checkpoint.py copy's atomic_path must (a) trip res-nonatomic-write
    statically — the CM is blessed STRUCTURALLY, not by name — and
    (b) reproduce the hazard dynamically: writes through the de-fanged
    CM never reach the target.  The pristine copy is clean both ways."""
    src = open(os.path.join(REPO, "mxnet_tpu", "checkpoint.py")).read()
    clean = tmp_path / "ckpt_clean.py"
    clean.write_text(src)
    result = run_lint([str(clean)], baseline_path=None)
    assert not result.new, "\n".join(f.render() for f in result.new)

    bugged = src.replace("        os.replace(tmp, path)\n", "")
    assert bugged != src, "seeding site moved — update the test"
    bad = tmp_path / "ckpt_bug.py"
    bad.write_text(bugged)
    result = run_lint([str(bad)], baseline_path=None)
    rules = {f.rule for f in result.new}
    assert "res-nonatomic-write" in rules, \
        "\n".join(f.render() for f in result.new)

    # runtime half: the same seed, executed — the commit never lands
    good_mod = _load_copy(clean, "mxnet_tpu._seeded_ckpt_clean")
    target = tmp_path / "artifact.json"
    with good_mod.atomic_path(str(target)) as tmp:
        with open(tmp, "w") as f:
            f.write("{}")
    assert target.exists()                  # pristine copy commits
    target2 = tmp_path / "artifact2.json"
    bad_mod = _load_copy(bad, "mxnet_tpu._seeded_ckpt_bug")
    with bad_mod.atomic_path(str(target2)) as tmp:
        with open(tmp, "w") as f:
            f.write("{}")
    assert not target2.exists(), \
        "seeded copy still committed — the static finding lied"


def test_seeded_dropped_resolve_fails_gate_and_hangs_at_runtime(tmp_path):
    """Acceptance (errorflow): dropping the ``r._resolve("timeout")``
    from a serve/server.py copy's _drop_expired must (a) trip
    err-terminal-outcome statically — the var stays tracked through its
    ``done()`` guard — and (b) reproduce the hang dynamically: an
    expired request dropped by the seeded copy never gets an outcome.
    The pristine copy is clean and resolves."""
    import time
    src = open(os.path.join(REPO, "mxnet_tpu", "serve",
                            "server.py")).read()
    clean = tmp_path / "server_clean.py"
    clean.write_text(src)
    result = run_lint([str(clean)], baseline_path=None)
    assert not result.new, "\n".join(f.render() for f in result.new)

    seed_old = (
        'if r._resolve("timeout",\n'
        '                              reason="deadline expired in %s"'
        ' % stage):\n'
        '                    telemetry.inc("serve.timeouts")\n'
        '                    telemetry.inc("serve.deadline_drops")\n'
        '                    telemetry.event("serve", "timeout",'
        ' stage=stage)\n')
    seed_new = 'telemetry.inc("serve.deadline_drops")\n'
    bugged = src.replace(seed_old, seed_new)
    assert bugged != src, "seeding site moved — update the test"
    bad = tmp_path / "server_bug.py"
    bad.write_text(bugged)
    result = run_lint([str(bad)], baseline_path=None)
    findings = [f for f in result.new if f.rule == "err-terminal-outcome"]
    assert findings, "\n".join(f.render() for f in result.new)
    assert any(f.context.endswith("_drop_expired") for f in findings), \
        [f.context for f in findings]

    # runtime half: an expired request through each copy's batcher drop
    good_mod = _load_copy(clean, "mxnet_tpu.serve._seeded_server_clean")
    r = good_mod.PendingRequest(None, time.monotonic() - 1.0)
    live = good_mod.InferenceServer._drop_expired(None, [r], "queue")
    assert live == [] and r.outcome(0) is not None
    assert r.outcome(0)[0] == "timeout"     # pristine copy resolves

    bad_mod = _load_copy(bad, "mxnet_tpu.serve._seeded_server_bug")
    r = bad_mod.PendingRequest(None, time.monotonic() - 1.0)
    live = bad_mod.InferenceServer._drop_expired(None, [r], "queue")
    assert live == []
    assert r.outcome(0) is None, \
        "seeded copy still resolved — the static finding lied"


def test_changed_closure_covers_errorflow_rules(tmp_path):
    """Satellite: --changed's reverse-dependency closure must pull
    err-*/res-* findings in an IMPORTER of the changed file — the
    write-helper judgment lands at the call site, cross-module."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "helper.py").write_text(
        "class PendingRequest:\n"
        "    def _resolve(self, kind):\n"
        "        return True\n"
        "\n"
        "\n"
        "def dump(path, blob):\n"
        "    with open(path, 'w') as fh:\n"
        "        fh.write(blob)\n")
    (pkg / "worker.py").write_text(
        "from .helper import PendingRequest, dump\n"
        "\n"
        "\n"
        "def publish(blob):\n"
        "    dump('report.json', blob)\n"
        "\n"
        "\n"
        "def admit(q, blob):\n"
        "    req = PendingRequest(blob)\n"
        "    if q.full():\n"
        "        return None\n"
        "    q.put(req)\n"
        "    return req\n")
    relbase = os.path.relpath(str(pkg), REPO).replace(os.sep, "/")
    helper_rel = relbase + "/helper.py"
    worker_rel = relbase + "/worker.py"
    result = run_lint([str(tmp_path)], baseline_path=None,
                      changed_files=[helper_rel])
    assert worker_rel in result.files
    rules = {(f.path, f.rule) for f in result.new}
    assert (worker_rel, "res-nonatomic-write") in rules, sorted(rules)
    assert (worker_rel, "err-terminal-outcome") in rules, sorted(rules)


def test_changed_closure_covers_num_rules(tmp_path):
    """Satellite: --changed's reverse-dependency closure must pull a
    numerics finding in an IMPORTER of the changed file (the dtype-flow
    model resolves helpers cross-module)."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "helper.py").write_text("def scale():\n    return 2\n")
    (pkg / "worker.py").write_text(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from .helper import scale\n"
        "\n"
        "\n"
        "@jax.jit\n"
        "def reduce_loss(x):\n"
        "    h = x.astype(jnp.bfloat16)\n"
        "    return jnp.sum(h) * scale()\n")
    relbase = os.path.relpath(str(pkg), REPO).replace(os.sep, "/")
    helper_rel = relbase + "/helper.py"
    worker_rel = relbase + "/worker.py"
    result = run_lint([str(tmp_path)], baseline_path=None,
                      changed_files=[helper_rel])
    assert worker_rel in result.files
    rules = {(f.path, f.rule) for f in result.new}
    assert (worker_rel, "num-lowprec-accum") in rules, sorted(rules)


def test_changed_closure_covers_conc_rules(tmp_path):
    """Satellite: --changed's reverse-dependency closure must pull a
    concurrency finding in an IMPORTER of the changed file (the conc
    model is package-wide, not per-file)."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "helper.py").write_text("def payload():\n    return 1\n")
    (pkg / "worker.py").write_text(
        "import threading\n"
        "from .helper import payload\n"
        "\n"
        "_journal = []\n"
        "\n"
        "\n"
        "def _run():\n"
        "    _journal.append(payload())\n"
        "\n"
        "\n"
        "def spawn():\n"
        "    threading.Thread(target=_run, daemon=True).start()\n"
        "\n"
        "\n"
        "def read():\n"
        "    return list(_journal)\n")
    relbase = os.path.relpath(str(pkg), REPO).replace(os.sep, "/")
    helper_rel = relbase + "/helper.py"
    worker_rel = relbase + "/worker.py"
    result = run_lint([str(tmp_path)], baseline_path=None,
                      changed_files=[helper_rel])
    assert worker_rel in result.files
    rules = {(f.path, f.rule) for f in result.new}
    assert (worker_rel, "conc-unguarded-shared-write") in rules, \
        sorted(rules)
    assert (worker_rel, "conc-thread-lifecycle") in rules, \
        sorted(rules)


def test_changed_closure_covers_serve_stop_path(tmp_path):
    """CI/tooling satellite: a change to the serving bucket policy must
    pull the server module — the stop/drain path the conc-* rules gate
    — into the --changed reverse-dependency closure (server.py imports
    buckets.py), so an edit under serve/ can never dodge the
    thread-lifecycle analysis.  Scoped to the serve package: the
    closure property under test is intra-package (server.py imports
    buckets.py) and the full-package changed-run budget is already
    owned by test_changed_mode_matches_full_run."""
    target = "mxnet_tpu/serve/buckets.py"
    result = run_lint([os.path.join(REPO, "mxnet_tpu", "serve")],
                      baseline_path=None, changed_files=[target])
    assert target in result.files
    assert "mxnet_tpu/serve/server.py" in result.files
    assert "mxnet_tpu/serve/__init__.py" in result.files
    # and the closure run stays clean over serve/ like the full gate
    bad = [f for f in result.new
           if f.path.startswith("mxnet_tpu/serve/")]
    assert not bad, "\n".join(f.render() for f in bad)


def test_list_rules_groups_by_family():
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    res = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--list-rules"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert res.returncode == 0, res.stderr
    lines = res.stdout.splitlines()
    assert "concurrency:" in lines
    fam_of = {}
    fam = None
    for line in lines:
        if line.endswith(":") and not line.startswith(" "):
            fam = line[:-1]
        elif line.strip():
            fam_of[line.split()[0]] = fam
    for rule in ("conc-lock-order", "conc-unguarded-shared-write",
                 "conc-blocking-under-lock", "conc-thread-lifecycle",
                 "conc-condition-wait-unlooped"):
        assert fam_of.get(rule) == "concurrency", (rule, fam_of.get(rule))
    assert fam_of.get("shard-axis-unknown") == "sharding"
    assert "numerics:" in lines
    for rule in ("num-implicit-promotion", "num-lowprec-accum",
                 "num-unstable-exp", "num-master-dtype",
                 "num-collective-dtype", "num-const-downcast"):
        assert fam_of.get(rule) == "numerics", (rule, fam_of.get(rule))
    assert "errorflow:" in lines
    for rule in ("err-swallowed-exception", "res-nonatomic-write",
                 "res-leaked-handle", "err-terminal-outcome",
                 "err-incident-trigger"):
        assert fam_of.get(rule) == "errorflow", (rule, fam_of.get(rule))


def test_stale_suppression_audit(tmp_path):
    """A suppression whose rule fires is kept quiet; one whose rule no
    longer fires on its line is flagged by --audit-suppressions (and
    stays invisible without the flag — the tier-1 gate is unchanged)."""
    src = (
        "import jax\n"
        "\n"
        "\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    a = float(x)  # graftlint: disable=trace-host-sync -- used\n"
        "    b = x + 1  # graftlint: disable=trace-host-sync -- stale\n"
        "    c = float(x)  # graftlint: disable=trace-host-sync,"
        "retrace-jit-in-loop -- half\n"
        "    return a + b + c\n")
    p = tmp_path / "mod.py"
    p.write_text(src)
    quiet = run_lint([str(p)], baseline_path=None)
    assert not quiet.new, [f.render() for f in quiet.new]
    audited = run_lint([str(p)], baseline_path=None,
                       audit_suppressions=True)
    got = [(f.rule, f.line) for f in audited.new]
    # line 7: fully stale; line 8: multi-rule suppression whose
    # trace-host-sync half is live but whose retrace half is dead —
    # staleness is per RULE, not per comment
    assert got == [("lint-stale-suppression", 7),
                   ("lint-stale-suppression", 8)], got
    stale_msgs = [f.message for f in audited.new]
    assert any("retrace-jit-in-loop" in m and "trace-host-sync" not in m
               for m in stale_msgs), stale_msgs
    # a rules allowlist disables the audit (unrelated suppressions
    # would read as stale)
    filtered = run_lint([str(p)], baseline_path=None, rules=["pallas-"],
                        audit_suppressions=True)
    assert not filtered.new


def test_package_suppressions_not_stale(package_scan):
    """Satellite: every inline suppression in mxnet_tpu/ must still
    suppress a live finding — the audit re-validates what PR 4
    grandfathered by hand."""
    stale = [f for f in package_scan.new
             if f.rule == "lint-stale-suppression"]
    assert not stale, "\n".join(f.render() for f in stale)


def test_changed_mode_matches_full_run(package_scan):
    """Acceptance: a --changed run over one file reports exactly the
    findings a full-package run reports for that file (the index is
    still cross-file, only the checker pass narrows), inside the 10 s
    budget."""
    import time
    target = "mxnet_tpu/parallel/collectives.py"
    t0 = time.time()
    fast = run_lint([os.path.join(REPO, "mxnet_tpu")],
                    baseline_path=None, changed_files=[target],
                    audit_suppressions=True)
    elapsed = time.time() - t0
    assert target in fast.files
    full = package_scan

    def in_file(result):
        return sorted((f.rule, f.line) for f in
                      result.new + result.suppressed
                      if f.path == target)

    assert in_file(fast) == in_file(full)
    # the closure pulls in importers of collectives.py, but not the
    # whole package
    assert len(fast.files) < len(full.files)
    # budget 12 s (was 10): PR 11's checkpoint.py imports collectives
    # (padded_size), growing this file's reverse-dependency closure by
    # one threaded module the conc checkers walk
    assert elapsed < 12.0, "changed-mode run took %.1fs" % elapsed


def test_changed_closure_covers_telemetry_collect():
    """ISSUE 18 satellite: the cross-process collector and the flight
    recorder ride the changed-mode closure — an edit to telemetry.py
    (whose Histogram dict geometry both consume) must re-lint them —
    and a changed-run over the collector itself stays clean."""
    from tools.lint.core import collect_files, ModuleInfo
    from tools.lint.jitgraph import PackageIndex
    mods = []
    for p in collect_files([os.path.join(REPO, "mxnet_tpu")]):
        rel = os.path.relpath(p, REPO).replace(os.sep, "/")
        mods.append(ModuleInfo(p, rel, open(p).read()))
    idx = PackageIndex(mods)
    closure = idx.reverse_dependency_closure({"mxnet_tpu/telemetry.py"})
    assert "mxnet_tpu/telemetry_collect.py" in closure
    assert "mxnet_tpu/flight_recorder.py" in closure
    # and the collector passes the gate when IT is the changed file
    target = "mxnet_tpu/telemetry_collect.py"
    result = run_lint([os.path.join(REPO, "mxnet_tpu")],
                      baseline_path=None, changed_files=[target])
    assert target in result.files
    bad = [f for f in result.new if f.path == target]
    assert not bad, bad


def test_reverse_dependency_closure(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("from . import a\n")
    (pkg / "a.py").write_text("from .b import f\n")
    (pkg / "b.py").write_text("def f():\n    return 1\n")
    (pkg / "c.py").write_text("import os\n")
    from tools.lint.core import collect_files, ModuleInfo
    from tools.lint.jitgraph import PackageIndex
    mods = []
    for p in collect_files([str(tmp_path)]):
        rel = os.path.relpath(p, str(tmp_path))
        mods.append(ModuleInfo(p, rel, open(p).read()))
    idx = PackageIndex(mods)
    got = idx.reverse_dependency_closure({"pkg/b.py"})
    assert got == {"pkg/b.py", "pkg/a.py", "pkg/__init__.py"}, got
    assert idx.reverse_dependency_closure({"pkg/c.py"}) == {"pkg/c.py"}


def test_rule_catalog_documented():
    """Every rule id must appear in docs/LINTING.md."""
    doc = open(os.path.join(REPO, "docs", "LINTING.md")).read()
    for rule in all_rules():
        assert rule in doc, "rule %s missing from docs/LINTING.md" % rule
