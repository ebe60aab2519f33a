"""Deterministic fault-injection harness for elastic-training chaos tests.

Production TPU fleets lose chips, drop heartbeats, and crash mid-write;
the recovery protocol in ``parallel/elastic.py`` + ``checkpoint.py`` is
only trustworthy if those failures can be reproduced ON DEMAND, in the
same place, every run.  This module is the single switchboard: tests
(and the multiprocess chaos workers) ``install()`` named faults with
deterministic trigger conditions — a step index, a rank, a call count —
and the instrumented seams consult ``should_fire()`` at the exact
moment the real failure would land:

* ``kill_worker``            — ``maybe_kill(step=...)`` in the training
  loop: ``os._exit`` mid-step, no cleanup (a preemption, not a clean
  shutdown).
* ``drop_heartbeat``         — the ``mxtpu-heartbeat`` publisher
  (kvstore.py) skips beats while the fault is live: the worker is alive
  but looks dead to every peer (a network partition).
* ``kv_garble`` / ``kv_stall`` — ``wrap_kv_client()`` proxies a
  coordination-service client: reads return scrambled payloads or block
  for ``delay`` seconds (a struggling/restarting coordinator).
* ``checkpoint_write_crash`` — ``checkpoint.atomic_path`` raises
  between the tmp write and the ``os.replace`` commit: the crash window
  atomicity exists to survive.
* ``grad_compress_corrupt``  — the compressed ZeRO gradient wire's
  dequantize consumes a garbled chunk-0 max-abs scale (a torn scale
  side tensor): ``DataParallelStep`` consults per dispatch and threads
  a non-finite factor into ``compression.dequantize_chunked``;
  NumericsSanitizer must catch the blast as non-finite params/drift.
* ``incident_write_crash``   — ``flight_recorder.dump_incident`` raises
  between building the bundle and its ``os.replace`` publish: same
  crash window, same discipline — a reader must never see a partial
  incident bundle and the tmp must not leak.

Serving faults (consulted by ``mxnet_tpu.serve.server`` — the chaos
matrix in tests/test_serve_chaos.py drives all four):

* ``request_burst``          — ``InferenceServer.submit`` amplifies one
  real submission into ``factor`` admissions: a deterministic traffic
  spike that must resolve through backpressure (queue-full rejects) and
  priority shedding, never a blocked producer.
* ``dispatch_stall``         — the dispatch worker sleeps ``delay``
  seconds before running the executable (a hung device dispatch): the
  watchdog must time the batch out and respawn the worker.
* ``executable_poison``      — the dispatch raises instead of running
  (optionally only for ``bucket=N``): bounded retry, then quarantine +
  fallback onto smaller buckets.
* ``deadline_storm``         — every submission's deadline collapses to
  ``deadline_ms`` (default 0): the whole queue must expire through the
  pre-dispatch drop path, wasting zero dispatches.

Artifact faults (every atomic tmp+``os.replace`` writer shares one
crash window):

* ``artifact_write_crash``   — ``fsutil.atomic_write_path`` raises
  between the tmp write and the commit: the generic-artifact twin of
  ``checkpoint_write_crash`` for telemetry exports and recordio
  indexes.

``MODES`` below is the machine-readable registry of all of the above —
``tools.lint.chaos_coverage`` parses it (as a literal, without
importing this module) and audits that every statically-enumerated
fault point consults a registered mode and every mode has an installing
test.

Everything is counter-based — no randomness, no wall-clock triggers —
so a chaos test that passes once passes every time.  All fault state
lives behind one module lock: faults are installed from the main thread
and consulted from publisher/writer threads.
"""
from __future__ import annotations

import os
import threading

__all__ = ["ChaosError", "install", "clear", "active", "fired",
           "should_fire", "maybe_kill", "maybe_stall", "garble",
           "wrap_kv_client", "install_from_env", "ENV_VAR", "MODES"]

ENV_VAR = "MXNET_TPU_CHAOS"

# The fault-mode registry: name -> the seam that consults it.  This
# dict is parsed as a LITERAL by tools.lint.chaos_coverage (so the
# audit needs no import of this package) — keep it a plain dict of
# string constants.
MODES = {
    "kill_worker": "parallel.elastic training loop (maybe_kill)",
    "drop_heartbeat": "kvstore heartbeat publisher thread",
    "kv_garble": "wrap_kv_client read proxy",
    "kv_stall": "wrap_kv_client read proxy",
    "checkpoint_write_crash": "checkpoint.atomic_path commit window",
    "grad_compress_corrupt": "compressed ZeRO wire dequantize scale "
                             "(data_parallel dispatch)",
    "incident_write_crash": "flight_recorder.dump_incident publish",
    "artifact_write_crash": "fsutil.atomic_write_path commit window",
    "request_burst": "serve.server.InferenceServer.submit",
    "dispatch_stall": "serve.server dispatch worker",
    "executable_poison": "serve.server dispatch worker",
    "deadline_storm": "serve.server.InferenceServer.submit",
}

_LOCK = threading.Lock()
_FAULTS = {}     # name -> {"rank", "at_step", "after_calls", "times",
#                           "calls", "fired", ...extra params}


class ChaosError(RuntimeError):
    """Raised by an injected fault (distinguishable from real errors)."""


def install(name, rank=None, at_step=None, after_calls=0, times=None,
            **params):
    """Arm fault ``name``.  It fires when every armed condition holds:

    * ``rank`` — only for this worker rank (None: any rank);
    * ``at_step`` — only when the consulting site passes this step;
    * ``after_calls`` — skip the first N consultations (deterministic
      "later" without wall clocks);
    * ``times`` — fire at most N times (None: unlimited).

    Extra keyword ``params`` ride along for the consuming seam
    (``delay`` for ``kv_stall``, ...).
    """
    spec = {"rank": rank, "at_step": at_step,
            "after_calls": int(after_calls),
            "times": times, "calls": 0, "fired": 0}
    spec.update(params)
    with _LOCK:
        _FAULTS[name] = spec


def clear(name=None):
    """Disarm one fault (or all of them)."""
    with _LOCK:
        if name is None:
            _FAULTS.clear()
        else:
            _FAULTS.pop(name, None)


def active(name):
    """Copy of the fault spec, or None when not armed."""
    with _LOCK:
        spec = _FAULTS.get(name)
        return dict(spec) if spec is not None else None


def fired(name):
    """How many times fault ``name`` has fired so far."""
    with _LOCK:
        spec = _FAULTS.get(name)
        return spec["fired"] if spec is not None else 0


def should_fire(name, step=None, rank=None, **_ctx):
    """Consult fault ``name`` at an instrumented seam.  Counts the
    consultation and returns True when the fault fires now."""
    with _LOCK:
        spec = _FAULTS.get(name)
        if spec is None:
            return False
        if spec["rank"] is not None and rank is not None \
                and int(rank) != int(spec["rank"]):
            return False
        spec["calls"] += 1
        if spec["calls"] <= spec["after_calls"]:
            return False
        if spec["at_step"] is not None and step != spec["at_step"]:
            return False
        if spec["times"] is not None and spec["fired"] >= spec["times"]:
            return False
        spec["fired"] += 1
        return True


def maybe_kill(step=None, rank=None):
    """``kill_worker`` consultation point for training loops: a fired
    fault is a preemption — ``os._exit``, no cleanup, no atexit, no
    coordination-service goodbye (exactly what a real chip loss looks
    like to the survivors)."""
    if should_fire("kill_worker", step=step, rank=rank):
        os._exit(int(active("kill_worker").get("exit_code") or 1))


def maybe_stall(name, default_delay=0.25):
    """Consultation point for stall-type faults (``dispatch_stall``,
    and the same idiom ``kv_stall`` uses): when fault ``name`` fires,
    sleep its ``delay`` parameter (a hung dispatch / stuck RPC as seen
    by everything downstream).  Returns True when it stalled."""
    if not should_fire(name):
        return False
    import time
    spec = active(name) or {}
    time.sleep(float(spec.get("delay") or default_delay))
    return True


def garble(payload):
    """Deterministically scramble a KV payload (a torn write / wrong
    encoding on the coordinator)."""
    if isinstance(payload, bytes):
        return payload[::-1] + b"\xff"
    return "\x00garbled:" + str(payload)[::-1]


class _KVProxy:
    """Coordination-client proxy applying ``kv_garble`` / ``kv_stall``
    to reads; every other attribute passes straight through."""

    def __init__(self, client):
        self._client = client

    def __getattr__(self, attr):
        real = getattr(self._client, attr)
        if attr not in ("blocking_key_value_get", "key_value_get"):
            return real

        def read(*args, **kwargs):
            stall = active("kv_stall")
            if stall is not None and should_fire("kv_stall"):
                import time
                time.sleep(float(stall.get("delay") or 0.2))
            out = real(*args, **kwargs)
            if should_fire("kv_garble"):
                return garble(out)
            return out

        return read


def wrap_kv_client(client):
    """Wrap a coordination-service client so armed ``kv_garble`` /
    ``kv_stall`` faults apply to its reads."""
    return _KVProxy(client)


def install_from_env(rank=None, env_var=ENV_VAR):
    """Arm faults from an env spec (the multiprocess chaos workers'
    channel): ``"kill_worker:rank=2,at_step=3;drop_heartbeat:rank=1"``.
    Faults scoped to another rank are skipped when ``rank`` is given.
    Returns the list of fault names armed."""
    spec = os.environ.get(env_var, "")
    armed = []
    for part in filter(None, (s.strip() for s in spec.split(";"))):
        name, _, argstr = part.partition(":")
        kwargs = {}
        for kv in filter(None, (a.strip() for a in argstr.split(","))):
            k, _, v = kv.partition("=")
            try:
                kwargs[k] = int(v)
            except ValueError:
                kwargs[k] = v
        if rank is not None and kwargs.get("rank") is not None \
                and int(kwargs["rank"]) != int(rank):
            continue
        install(name, **kwargs)
        armed.append(name)
    return armed
