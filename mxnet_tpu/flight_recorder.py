"""Crash flight recorder: always-on postmortem bundles (ISSUE 18).

The telemetry journal is already a bounded always-on ring of recent
events; what was missing is the step that turns it into an ARTIFACT at
the moment something dies.  ``dump_incident(reason)`` freezes the
current observability state into an ``incident-<ts>-<reason>/`` bundle:

* ``journal.jsonl``    — the journal tail (the last ``JOURNAL_MAXLEN``
  events: spans with trace ids, serve outcomes, elastic transitions,
  health-state changes, chaos fires);
* ``histograms.json``  — full mergeable histogram dicts (latency
  distributions up to the moment of death);
* ``snapshot.json``    — counters, gauges, span aggregates, and the
  LAST jit-cache key per function (what shape the program was in);
* ``lockgraph.json``   — lock-order edges observed at runtime
  (``lockorder`` journal events), for deadlock postmortems;
* ``hbm.json``         — HBM estimator events from the journal;
* ``config.json``      — reason, detail, rank, pid, ``MXNET_*`` env,
  platform, plus any ``extra`` the trigger site attached.

Triggers wired across the stack: the serve watchdog firing, dispatcher
respawn exhaustion, executable quarantine, NumericsSanitizer contract
failures, checkpoint write failures, elastic departure detection,
chaos-injected crashes — and any explicit ``dump_incident()`` call.

Discipline mirrors ``checkpoint.atomic_path``: the bundle is built in a
dot-tmp directory and published with one ``os.replace`` — a reader
never sees a half-written incident, and a crash mid-dump leaves only an
ignorable tmp.  The ``incident_write_crash`` chaos fault fires in
exactly that window (tests/test_flight_recorder.py).  ``dump_incident``
NEVER raises: it is called from error paths, and a broken recorder must
not mask the original failure.  No threads are spawned — a dump is a
synchronous bounded write on the thread that hit the wall.
"""
from __future__ import annotations

import json
import logging
import os
import platform
import shutil
import threading
import time

from . import telemetry

__all__ = ["dump_incident", "configure", "reset", "incident_dir",
           "bundles_dumped"]

_ENV_DIR = "MXNET_TPU_INCIDENT_DIR"
_ENV_ENABLE = "MXNET_TPU_FLIGHT_RECORDER"
_ENV_MAX = "MXNET_TPU_INCIDENT_MAX"

_lock = threading.Lock()
_state = {"dir": None, "max": None, "count": 0}


def _enabled():
    return os.environ.get(_ENV_ENABLE, "1") not in ("0", "false", "off")


def incident_dir():
    """Where bundles land: ``configure(dir=...)`` >
    ``MXNET_TPU_INCIDENT_DIR`` > ``./incidents``."""
    with _lock:
        if _state["dir"]:
            return _state["dir"]
    return os.environ.get(_ENV_DIR, "incidents")


def _max_bundles():
    with _lock:
        if _state["max"] is not None:
            return _state["max"]
    try:
        return int(os.environ.get(_ENV_MAX, "8"))
    except ValueError:
        return 8


def bundles_dumped():
    """How many bundles this process has committed."""
    with _lock:
        return _state["count"]


def configure(dir=None, max_bundles=None):
    """Override the bundle directory / per-process cap (tests, servers
    that own their artifact layout)."""
    with _lock:
        if dir is not None:
            _state["dir"] = dir
        if max_bundles is not None:
            _state["max"] = int(max_bundles)


def reset():
    """Back to env-driven defaults, dump counter cleared (tests)."""
    with _lock:
        _state["dir"] = None
        _state["max"] = None
        _state["count"] = 0


def _write_json(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1, default=str)


def dump_incident(reason, detail=None, extra=None):
    """Freeze the current telemetry state into an incident bundle.

    Returns the committed bundle path, or None when the recorder is
    disabled, the per-process cap is reached, or the dump itself failed
    (journaled as ``incident/dump_failed`` — never raised: this runs on
    error paths and must not mask the original failure)."""
    if not _enabled() or not telemetry.enabled():
        return None
    if bundles_dumped() >= _max_bundles():
        telemetry.event("incident", "skipped", reason=reason,
                        cap=_max_bundles())
        return None

    base = incident_dir()
    ts = time.time()
    stamp = "%d_%06d" % (int(ts), int((ts % 1) * 1e6))
    final = os.path.join(base, "incident-%s-%s" % (stamp, reason))
    tmp = os.path.join(base, ".tmp-incident-%s-%d" % (stamp, os.getpid()))
    try:
        snap = telemetry.snapshot(events=0)
        with telemetry._lock:
            journal = list(telemetry._journal)
            last_keys = {fn: ent.get("key")
                         for fn, ent in telemetry._compiles.items()}
        hists = telemetry.hist_snapshot()

        os.makedirs(tmp, exist_ok=True)
        with open(os.path.join(tmp, "journal.jsonl"), "w") as f:
            for rec in journal:
                f.write(json.dumps(rec, default=str) + "\n")
        _write_json(os.path.join(tmp, "histograms.json"), hists)
        _write_json(os.path.join(tmp, "snapshot.json"),
                    {"counters": snap["counters"],
                     "gauges": snap["gauges"],
                     "spans": snap["spans"],
                     "histograms": snap["histograms"],
                     "compiles": snap["compiles"],
                     "compile_totals": snap["compile_totals"],
                     "last_cache_keys": last_keys})
        _write_json(os.path.join(tmp, "lockgraph.json"),
                    [r for r in journal if r.get("kind") == "lockorder"])
        _write_json(os.path.join(tmp, "hbm.json"),
                    [r for r in journal if r.get("kind") == "hbm"])
        _write_json(os.path.join(tmp, "config.json"),
                    {"reason": reason, "detail": detail,
                     "ts": round(ts, 6), "pid": os.getpid(),
                     "rank": telemetry.get_rank(),
                     "platform": platform.platform(),
                     "env": {k: v for k, v in os.environ.items()
                             if k.startswith(("MXNET_", "MXTPU_",
                                              "JAX_PLATFORMS"))},
                     "extra": extra})

        # crash window under test: the fault fires AFTER the bundle is
        # fully built but BEFORE the one atomic publish — a reader must
        # never see the partial bundle (same seam checkpoint_write_crash
        # exercises in checkpoint.atomic_path)
        from .parallel import chaos
        if chaos.should_fire("incident_write_crash"):
            raise chaos.ChaosError("chaos: incident_write_crash")

        os.replace(tmp, final)
    except Exception as exc:
        logging.exception("flight_recorder: incident dump failed")
        shutil.rmtree(tmp, ignore_errors=True)
        telemetry.event("incident", "dump_failed", reason=reason,
                        error=repr(exc))
        return None
    with _lock:
        _state["count"] += 1
    telemetry.event("incident", "dumped", reason=reason, path=final)
    return final
