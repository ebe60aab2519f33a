"""Test fixture: run everything on a virtual 8-device CPU mesh.

The analogue of the reference's `tools/launch.py --launcher local`
multi-process fixture (SURVEY.md §4): multi-device semantics are validated
on one host by forcing 8 XLA host-platform devices.  Must run before jax
imports anywhere.
"""
import os

# the suite runs on the CPU backend whatever the shell says; JAX reads
# the variable when it is first imported, below
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# reuse compiled executables across test runs (compiles dominate the
# suite's wall time; the cache is keyed by HLO so it is semantics-safe)
from mxnet_tpu.engine import enable_compilation_cache  # noqa: E402
enable_compilation_cache()

import numpy as onp  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _incident_sandbox(tmp_path):
    """The flight recorder is always-on (quarantine, watchdog, elastic
    departure all dump bundles): route every test's bundles into its
    tmp dir so the repo checkout never accumulates ``incidents/``, and
    reset the per-process dump cap between tests."""
    from mxnet_tpu import flight_recorder
    flight_recorder.reset()
    flight_recorder.configure(dir=str(tmp_path / "incidents"))
    yield
    flight_recorder.reset()


@pytest.fixture(autouse=True)
def _seed_rng():
    """Seeded reproducibility (reference tests/python/unittest/common.py:117
    @with_seed): default 42, overridable via MXNET_TEST_SEED — the knob
    tools/flakiness_checker.py varies per trial, like the reference's
    MXNET_TEST_SEED contract."""
    import mxnet_tpu as mx
    seed = int(os.environ.get("MXNET_TEST_SEED", "42"))
    mx.random.seed(seed)
    onp.random.seed(seed)
    yield


@pytest.fixture(scope="session")
def package_scan():
    """THE tier-1 full-package graftlint scan — baseline + suppression
    audit + telemetry in ONE run (~5 s) shared by the gate,
    stale-suppression and changed-mode tests (tests/test_lint.py).
    Session-scoped so every rule family's gate tests — the numerics
    additions included — reuse one scan instead of paying it per
    module."""
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools.lint import run_lint
    baseline = os.path.join(repo, "tools", "lint", "baseline.json")
    return run_lint([os.path.join(repo, "mxnet_tpu")],
                    baseline_path=baseline if os.path.exists(baseline)
                    else None, emit_telemetry=True,
                    audit_suppressions=True)


@pytest.fixture(scope="session")
def package_lock_graph():
    """ONE static lock graph over mxnet_tpu/ shared by every runtime
    lock-order cross-check (tests/test_concurrency_stress.py,
    tests/test_runtime_lockorder.py) — the build costs a full
    PackageIndex (~3 s), so per-file fixtures would pay it repeatedly."""
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools.lint.concurrency import static_lock_graph
    return static_lock_graph([os.path.join(repo, "mxnet_tpu")])
