"""Multi-process distributed correctness (reference
``tests/nightly/dist_sync_kvstore.py:30-60`` + ``tools/launch.py:101-116``
local mode): N real OS processes bootstrap jax.distributed through the
launcher env contract, push per-worker gradients through KVStoreTPU, and
assert the aggregate bit-matches the cross-worker sum on every rank.

Runs on the CPU backend (one device per process) so it needs no real
multi-chip hardware — the same path (global array over a process-spanning
mesh + one jitted reduction) carries DCN traffic on a real pod.
"""
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))

import launch  # noqa: E402  (tools/launch.py)

_WORKER = os.path.join(_REPO, "tests", "dist_worker.py")

import jax  # noqa: E402


@pytest.mark.parametrize("n", [2, 8])
def test_dist_sync_kvstore_multiprocess(n):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the spawned interpreters must not inherit this process's TPU client
    env.pop("XLA_FLAGS", None)
    codes = launch.launch_local(n, [sys.executable, _WORKER], env=env)
    assert codes == [0] * n, codes


def test_dist_hybrid_topology_2x4():
    """2 processes x 4 virtual devices each: DCN x ICI hybrid mesh.
    The worker asserts bitwise-exact hybrid-sharded gradient aggregation,
    ring attention over a process-spanning sp axis, and a pipeline whose
    pp axis is the process boundary (see dist_worker_hybrid.py)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    codes = launch.launch_local(
        2, [sys.executable, os.path.join(_REPO, "tests",
                                         "dist_worker_hybrid.py")], env=env)
    assert codes == [0, 0], codes


def test_dist_num_dead_node_detects_killed_worker():
    """Liveness facade (reference include/mxnet/kvstore.h:353
    get_num_dead_node): rank 2 of 3 crashes without cleanup; the
    survivors must see num_dead_node() report it (dist_worker_kill.py)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    codes = launch.launch_local(
        3, [sys.executable, os.path.join(_REPO, "tests",
                                         "dist_worker_kill.py")], env=env)
    assert codes == [0, 0, 0], codes


def test_elastic_chaos_kill_worker_mid_epoch(tmp_path):
    """Chaos matrix leg 1 (ISSUE 11): the ``kill_worker`` fault preempts
    rank 2 of 3 mid-epoch (os._exit at step 3, no cleanup); the two
    survivors' ElasticContext must detect the departure through the KV
    heartbeat liveness view, re-form their mesh, journal
    elastic/detect + elastic/reshard, and keep training with the loss
    still decreasing — no restart.  (The cross-extent ZeRO re-shard
    math itself is asserted bitwise in tests/test_elastic.py /
    test_checkpoint.py, where a real multi-device dp mesh exists.)

    ISSUE 18 rides the same run: each survivor clock-syncs against
    rank 0, exports its journal, and dumps an ``elastic_departure``
    flight-recorder bundle; the parent merges the exports with
    ``telemetry_collect`` and asserts ONE chrome trace showing the
    detect -> reshard -> resume recovery on every survivor's lane."""
    import json

    tele_dir = str(tmp_path / "telemetry")
    inc_dir = str(tmp_path / "incidents")
    os.makedirs(tele_dir)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["MXTPU_KILL_MODE"] = "elastic"
    env["MXNET_TPU_CHAOS"] = "kill_worker:rank=2,at_step=3"
    env["MXNET_TPU_HEARTBEAT_TIMEOUT"] = "2"   # fast failure detection
    env["MXTPU_TELEMETRY_DIR"] = tele_dir
    env["MXNET_TPU_INCIDENT_DIR"] = inc_dir
    codes = launch.launch_local(
        3, [sys.executable, os.path.join(_REPO, "tests",
                                         "dist_worker_kill.py")], env=env)
    # survivors exit 0; the preempted rank exits with the fault's code
    assert codes[0] == 0 and codes[1] == 0, codes
    assert codes[2] == 1, codes

    # collector-merged timeline: the dead rank never exported, the two
    # survivors' files merge onto rank 0's reference clock
    from mxnet_tpu import telemetry_collect
    exports = sorted(os.path.join(tele_dir, f)
                     for f in os.listdir(tele_dir))
    assert len(exports) == 2, exports
    merged = str(tmp_path / "merged.trace.json")
    meta = telemetry_collect.collect(exports, merged)
    assert meta["ranks"] == [0, 1]
    trace = json.load(open(merged))
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    for r in (0, 1):
        lane = {e["name"] for e in spans if e["pid"] == r}
        assert {"elastic.detect", "elastic.reshard",
                "elastic.resume"} <= lane, (r, lane)
        # one causally-linked recovery per survivor: all three spans
        # share the trace id opened by maybe_recover
        ids = {e["args"].get("trace") for e in spans
               if e["pid"] == r and e["name"].startswith("elastic.")}
        assert len(ids) == 1 and None not in ids, (r, ids)

    # each survivor froze a well-formed elastic_departure bundle
    bundles = sorted(d for d in os.listdir(inc_dir)
                     if d.endswith("-elastic_departure"))
    seen_ranks = set()
    for b in bundles:
        files = sorted(os.listdir(os.path.join(inc_dir, b)))
        assert files == ["config.json", "hbm.json", "histograms.json",
                         "journal.jsonl", "lockgraph.json",
                         "snapshot.json"], (b, files)
        cfg = json.load(open(os.path.join(inc_dir, b, "config.json")))
        assert cfg["reason"] == "elastic_departure"
        assert "world 3 -> 2" in cfg["detail"]
        seen_ranks.add(cfg["rank"])
    assert seen_ranks == {0, 1}, seen_ranks


@pytest.mark.slow
def test_checkpoint_manifest_survives_coordinator_restart(tmp_path):
    """Chaos matrix leg 3: a 2-worker job checkpoints asynchronously
    and dies abruptly (no shutdown barrier — coordinator loss); a NEW
    1-worker job restores from the committed manifest (a different
    world size), verifies the materialized optimizer state bitwise
    against a deterministic recomputation, and keeps training.

    slow: 3 spawned interpreters (~12 s); the kill test above stays
    tier-1 as the multiprocess acceptance leg, and the changed-world
    restore math is tier-1 in tests/test_checkpoint.py."""
    ckpt_dir = str(tmp_path / "ckpt")
    base = dict(os.environ)
    base["JAX_PLATFORMS"] = "cpu"
    base.pop("XLA_FLAGS", None)
    base["MXTPU_CKPT_DIR"] = ckpt_dir
    env1 = dict(base, MXTPU_KILL_MODE="ckpt_phase1")
    codes = launch.launch_local(
        2, [sys.executable, os.path.join(_REPO, "tests",
                                         "dist_worker_kill.py")],
        env=env1)
    assert codes == [0, 0], codes
    env2 = dict(base, MXTPU_KILL_MODE="ckpt_phase2")
    codes = launch.launch_local(
        1, [sys.executable, os.path.join(_REPO, "tests",
                                         "dist_worker_kill.py")],
        env=env2)
    assert codes == [0], codes


def test_dist_init_failure_is_hard():
    """With the dist env set but an unreachable coordinator, the join must
    raise (at import, where mxnet_tpu auto-joins; or at kvstore creation)
    — never fall back to silent single-process training."""
    code = subprocess.run(
        [sys.executable, "-c", """
import os, sys
os.environ['JAX_PLATFORMS'] = 'cpu'
os.environ['MXNET_TPU_COORDINATOR_ADDRESS'] = '127.0.0.1:1'
os.environ['MXNET_TPU_NUM_PROCESSES'] = '2'
os.environ['MXNET_TPU_PROCESS_ID'] = '1'
os.environ['MXNET_TPU_INIT_TIMEOUT'] = '5'
sys.path.insert(0, %r)
import jax
jax.config.update('jax_platforms', 'cpu')
try:
    from mxnet_tpu import kvstore
    kvstore.create('dist_sync')
except Exception:
    sys.exit(0)   # catchable hard failure
sys.exit(42)      # silent single-process fallback is the bug
""" % _REPO],
        timeout=240).returncode
    # 0 = Python-level raise; the coordination client may instead abort
    # the process outright — also a hard failure.  Only the sentinel 42
    # (the script reached kvstore.create and it succeeded single-process)
    # is the bug this test guards against.
    assert code != 42, "dist env set + failed join fell back to single-process"
