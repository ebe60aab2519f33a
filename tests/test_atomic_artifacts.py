"""Regressions for the phase-5 errorflow burn-down: every durable
artifact writer that used to ``open(path, "w")`` in place now rides the
tmp + ``os.replace`` discipline (``fsutil.atomic_write_path`` /
``checkpoint.atomic_path``), and the shared commit window is
fault-injectable via the ``artifact_write_crash`` chaos mode.

The contract under test, for each converted writer: a crash inside the
commit window leaves the PREVIOUS file byte-identical and leaves no
``*.tmp.*`` litter — a reader can never observe a torn artifact.
"""
import glob
import json
import os

import pytest

from mxnet_tpu import fsutil, telemetry
from mxnet_tpu.parallel import chaos


@pytest.fixture(autouse=True)
def _clear_chaos():
    chaos.clear()
    yield
    chaos.clear()


def _no_tmp_litter(directory):
    return [p for p in glob.glob(os.path.join(directory, "*"))
            if ".tmp." in os.path.basename(p)]


def test_atomic_write_path_commits_and_cleans(tmp_path):
    target = tmp_path / "artifact.json"
    with fsutil.atomic_write_path(str(target)) as tmp:
        with open(tmp, "w") as f:
            f.write('{"ok": 1}')
        assert not target.exists()          # nothing until the commit
    assert json.loads(target.read_text()) == {"ok": 1}
    assert _no_tmp_litter(str(tmp_path)) == []


def test_atomic_write_path_crash_window_preserves_old_file(tmp_path):
    target = tmp_path / "artifact.json"
    target.write_text('{"version": 1}')
    chaos.install("artifact_write_crash", times=1)
    with pytest.raises(chaos.ChaosError):
        with fsutil.atomic_write_path(str(target)) as tmp:
            with open(tmp, "w") as f:
                f.write('{"version": 2}')
    assert json.loads(target.read_text()) == {"version": 1}
    assert _no_tmp_litter(str(tmp_path)) == []
    # the window is per-write: the retry commits
    with fsutil.atomic_write_path(str(target)) as tmp:
        with open(tmp, "w") as f:
            f.write('{"version": 2}')
    assert json.loads(target.read_text()) == {"version": 2}


def test_atomic_write_path_writer_error_keeps_old_file(tmp_path):
    target = tmp_path / "artifact.bin"
    target.write_bytes(b"old")
    with pytest.raises(RuntimeError):
        with fsutil.atomic_write_path(str(target)) as tmp:
            with open(tmp, "wb") as f:
                f.write(b"partial")
            raise RuntimeError("died mid-build")
    assert target.read_bytes() == b"old"
    assert _no_tmp_litter(str(tmp_path)) == []


def test_export_jsonl_atomic_under_crash(tmp_path):
    path = tmp_path / "rank0.jsonl"
    telemetry.event("unit", "before_crash")
    telemetry.export_jsonl(str(path))
    committed = path.read_text()
    assert committed                        # baseline export landed
    chaos.install("artifact_write_crash", times=1)
    telemetry.event("unit", "lost_by_crash")
    with pytest.raises(chaos.ChaosError):
        telemetry.export_jsonl(str(path))
    assert path.read_text() == committed    # old export intact, not torn
    assert _no_tmp_litter(str(tmp_path)) == []


def test_telemetry_collect_outputs_atomic_under_crash(tmp_path):
    from mxnet_tpu import telemetry_collect
    src = tmp_path / "rank0.jsonl"
    telemetry.event("unit", "collectme")
    telemetry.export_jsonl(str(src))
    out = tmp_path / "merged.trace.json"
    telemetry_collect.collect([str(src)], str(out))
    committed = out.read_text()
    json.loads(committed)                   # a complete JSON document
    chaos.install("artifact_write_crash", times=1)
    with pytest.raises(chaos.ChaosError):
        telemetry_collect.collect([str(src)], str(out))
    assert out.read_text() == committed
    assert _no_tmp_litter(str(tmp_path)) == []


def test_recordio_idx_sidecar_atomic_under_crash(tmp_path):
    from mxnet_tpu import recordio
    rec = str(tmp_path / "data.rec")
    idx = str(tmp_path / "data.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    w.write_idx(0, b"alpha")
    w.write_idx(1, b"beta")
    w.close()
    committed = open(idx).read()
    assert len(committed.splitlines()) == 2
    # rewrite with a crash inside the idx commit window: the .rec closes
    # but the OLD sidecar must survive un-torn
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    w.write_idx(0, b"gamma")
    chaos.install("artifact_write_crash", times=1)
    with pytest.raises(chaos.ChaosError):
        w.close()
    # the crash hit INSIDE the sidecar's commit window: the old sidecar
    # survives byte-identical (never torn mid-rewrite) and no tmp leaks
    assert open(idx).read() == committed
    assert _no_tmp_litter(str(tmp_path)) == []
    w.close()                               # retry: fault exhausted
    r = recordio.MXIndexedRecordIO(idx, rec, "r")
    assert r.read_idx(0) == b"gamma"
    r.close()


def test_save_optimizer_states_atomic(tmp_path):
    """Module.save_optimizer_states goes through atomic_path now — a
    checkpoint_write_crash in the commit window keeps the old .states
    file."""
    from mxnet_tpu.module import Module

    class FakeUpdater:
        blob = b"state-blob-v1"

        def get_states(self):
            return self.blob

    fname = str(tmp_path / "opt.states")
    mod = Module.__new__(Module)
    mod._update_on_kvstore = False
    mod._kvstore = None
    mod._updater = FakeUpdater()
    mod.optimizer_initialized = True
    mod.save_optimizer_states(fname)
    assert open(fname, "rb").read() == b"state-blob-v1"
    mod._updater.blob = b"state-blob-v2"
    chaos.install("checkpoint_write_crash", times=1)
    with pytest.raises(chaos.ChaosError):
        mod.save_optimizer_states(fname)
    assert open(fname, "rb").read() == b"state-blob-v1"
    assert _no_tmp_litter(str(tmp_path)) == []


def test_legacy_save_atomic_under_crash(tmp_path):
    import numpy as onp
    from mxnet_tpu.ndarray import legacy_io

    fname = str(tmp_path / "model.params")
    legacy_io.save_legacy(fname, {"w": onp.ones((2, 2), "float32")})
    committed = open(fname, "rb").read()
    assert legacy_io.is_legacy_file(fname)
    chaos.install("checkpoint_write_crash", times=1)
    with pytest.raises(chaos.ChaosError):
        legacy_io.save_legacy(fname, {"w": onp.zeros((2, 2), "float32")})
    assert open(fname, "rb").read() == committed
    assert _no_tmp_litter(str(tmp_path)) == []
