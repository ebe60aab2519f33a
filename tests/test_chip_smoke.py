"""chip_smoke.py rehearsed on the CPU backend, and the rules it stands on.

The script is the driver's proof that the system starts on the chip, so what
is tested here is its CONTROL FLOW: that it refuses a host with no TPU and
prints no ``ok`` line, that a phase which raises takes the run down with it,
and — with the device check stubbed — that the train, serve and four-device
phases run end to end at the rehearsal size on the eight virtual devices.
Plus the rules it leans on: where the compile cache goes, and what
``mx.tpu()`` resolves to.
"""
import json
import os
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from mxnet_tpu import context, engine  # noqa: E402


def _ok_lines(out):
    return [l for l in out.splitlines() if l.startswith("{") and '"ok"' in l]


def test_refuses_a_host_without_a_tpu(capsys):
    """JAX_PLATFORMS=cpu (this suite): the device phase raises at once and
    nothing that looks like a result is printed."""
    with pytest.raises(RuntimeError, match="not a TPU"):
        chip_smoke.main(["--rehearse"])
    assert not _ok_lines(capsys.readouterr().out)


def test_a_phase_that_raises_fails_the_run(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda device: None)
    monkeypatch.setattr(chip_smoke, "resnet_phase", lambda cfg: None)

    def broken(cfg):
        raise RuntimeError("chip_smoke: boom")
    monkeypatch.setattr(chip_smoke, "bert_phase", broken)
    with pytest.raises(RuntimeError, match="boom"):
        chip_smoke.main(["--rehearse"])
    out = capsys.readouterr().out
    assert "[device]" in out and not _ok_lines(out)


@pytest.mark.parametrize("chips", [1, 4])
def test_phases_pass_at_rehearsal_size_with_device_check_stubbed(
        monkeypatch, capsys, chips):
    """One device: ResNet and BERT train phases and the server.  Four: the
    dp=4 ZeRO-sharded BERT step against the one-device step, on four of the
    eight virtual devices.  The LAST stdout line is the ok object."""
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda device: None)
    assert chip_smoke.main(["--rehearse", "--chips", str(chips)]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                   "count": chips}}
    assert len(_ok_lines("\n".join(lines))) == 1
    phases = {l.split("]")[0][1:] for l in lines if l.startswith("[")}
    want = {"device", "done"} | (
        {"train_resnet", "train_bert", "serve"} if chips == 1
        else {"sharded"})
    assert phases == want


# --- the compile cache ------------------------------------------------------

@pytest.mark.parametrize("from_env", [True, False],
                         ids=["JAX_COMPILATION_CACHE_DIR", "unset"])
def test_compile_cache_directory(monkeypatch, tmp_path, from_env):
    """Where the variable is set JAX already holds the directory and the
    code sets none; unset, the cache is the fixed <checkout>/.jax_cache."""
    updates = {}
    real_update = jax.config.update

    def spy(key, value):
        updates[key] = value
        if "cache_dir" not in key:       # never move the suite's own cache
            real_update(key, value)
    monkeypatch.setattr(jax.config, "update", spy)
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = engine.enable_compilation_cache()
    set_in_code = [v for k, v in updates.items() if "cache_dir" in k]
    if from_env:
        assert path == str(tmp_path) and not set_in_code
    else:
        assert path == os.path.join(REPO, ".jax_cache")
        assert set_in_code == [path] and os.path.isdir(path)


# --- what mx.tpu() resolves to ------------------------------------------------

class _Dev:
    def __init__(self, platform, i=0):
        self.platform, self.id = platform, i

    def __repr__(self):
        return "%s:%d" % (self.platform, self.id)


_CPUS = [_Dev("cpu", 0), _Dev("cpu", 1)]
_TPUS = [_Dev("tpu", 0), _Dev("tpu", 1)]


@pytest.mark.parametrize("devices,platforms,want", [
    (_TPUS, None, _TPUS),                   # accelerator present -> it
    (_TPUS + _CPUS, "tpu,cpu", _TPUS),      # ... and never the host beside it
    (_CPUS, "cpu", _CPUS),                  # none, pinned to cpu -> the CPU
    (_CPUS, None, None),                    # none, not pinned -> raises
    (_CPUS, "tpu,cpu", None),
], ids=["accelerator", "accelerator_beside_cpu", "pinned_to_cpu",
        "unpinned_raises", "tpu_wanted_raises"])
def test_accelerator_resolution_rule(devices, platforms, want):
    if want is None:
        with pytest.raises(RuntimeError, match="no accelerator"):
            context._pick_accel(devices, platforms)
    else:
        assert context._pick_accel(devices, platforms) == want


def test_mx_tpu_is_the_cpu_only_because_this_process_is_pinned(monkeypatch):
    import mxnet_tpu as mx
    assert jax.config.jax_platforms == "cpu"
    assert mx.tpu().jax_device.platform == "cpu"
    # the same host, not told to use the CPU: no quiet fallback
    monkeypatch.setattr(context, "_accel_devices",
                        lambda: context._pick_accel(jax.local_devices(),
                                                    None))
    with pytest.raises(RuntimeError, match="no accelerator"):
        mx.gpu(0).jax_device


def test_on_tpu_reads_where_concrete_operands_live(monkeypatch):
    """An eager op runs where its operands are committed: a model still on
    the host backend of a TPU machine must not be handed a compiled Pallas
    kernel, whatever the default backend is."""
    import jax.numpy as jnp
    tpu_default = [_Dev("tpu")]
    monkeypatch.setattr(jax, "devices", lambda *a: tpu_default)
    assert context.on_tpu() is True
    on_host = jax.device_put(jnp.ones(3), jax.local_devices(backend="cpu")[0])
    assert context.on_tpu(on_host) is False
    assert context.on_tpu(jnp.ones(3)) is True      # uncommitted: follows
    seen = []
    jax.jit(lambda x: seen.append(context.on_tpu(x)) or x)(on_host)
    assert seen == [True]                           # a tracer cannot say


# --- one process per chip ------------------------------------------------------

def test_launch_local_refuses_workers_that_would_share_chips():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import launch
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    with pytest.raises(RuntimeError, match="CPU-only"):
        launch.launch_local(2, [sys.executable, "-c", "pass"], env=env)
    assert launch.launch_local(1, [sys.executable, "-c", "pass"],
                               env=env) == [0]
    assert launch.launch_local(
        2, [sys.executable, "-c", "pass"],
        env=dict(env, JAX_PLATFORMS="cpu")) == [0, 0]
