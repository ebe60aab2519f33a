"""Profiler: chrome-trace/Perfetto capture over ``jax.profiler``.

Reference: ``python/mxnet/profiler.py`` (``set_config/set_state/dump`` +
Domain/Task/Counter/Marker object model) backed by ``src/profiler/
profiler.h:88`` (chrome://tracing JSON, per-op engine instrumentation).

TPU-native: ``jax.profiler`` captures XLA/TPU execution into an XPlane/
Perfetto trace (viewable in chrome://tracing or Perfetto UI) — per-op
instrumentation hooks become ``jax.profiler.TraceAnnotation`` scopes, and
aggregate stats come from the trace itself.  The reference's API shape is
kept: ``set_config`` picks the dump dir, ``set_state('run'/'stop')``
brackets the capture, ``dump()`` finalizes.
"""
from __future__ import annotations

import os
import time

import jax

from . import telemetry

__all__ = ["set_config", "set_state", "start", "stop", "pause", "resume",
           "dump", "dumps", "Domain", "Task", "Frame", "Event", "Counter",
           "Marker", "profiler_set_config", "profiler_set_state",
           "state"]

_CONFIG = {
    "filename": "profile.json",
    "profile_dir": "profile_output",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": True,
    "profile_api": True,
    "aggregate_stats": False,
}
_STATE = {"running": False, "paused": False, "dir": None}


def set_config(**kwargs):
    """Configure the profiler (reference profiler.py set_config).  The
    relevant knob here is ``filename``/``profile_dir`` — XLA traces profile
    everything the hardware runs; per-category switches are accepted for
    API parity."""
    _CONFIG.update(kwargs)


profiler_set_config = set_config


def _trace_dir():
    d = _CONFIG.get("profile_dir") or os.path.dirname(
        _CONFIG["filename"]) or "."
    os.makedirs(d, exist_ok=True)
    return d


def set_state(state_name="stop", profile_process="worker"):
    """'run' starts capture, 'stop' ends it (reference set_state)."""
    if state_name == "run":
        start()
    elif state_name == "stop":
        stop()
    else:
        raise ValueError("invalid profiler state %r" % state_name)


profiler_set_state = set_state


def state():
    # a paused capture is still logically in the 'run' state (the
    # reference's pause does not change the profiler state machine)
    return "run" if _STATE["running"] else "stop"


def start():
    """Begin trace capture (reference profiler.start).  Starting while
    paused resumes the SAME capture (same trace dir) — previously
    ``set_state('run')`` on a paused capture double-started a fresh
    trace over the paused one."""
    if _STATE["running"]:
        if _STATE["paused"]:
            resume()
        return
    d = _trace_dir()
    jax.profiler.start_trace(d)
    _STATE.update(running=True, paused=False, dir=d)
    telemetry.event("profiler", "start", dir=d)


def stop():
    """End trace capture (reference profiler.stop)."""
    if not _STATE["running"]:
        return
    if not _STATE["paused"]:     # a paused capture's trace is already off
        jax.profiler.stop_trace()
    _STATE.update(running=False, paused=False)
    telemetry.event("profiler", "stop", dir=_STATE["dir"])


def pause(profile_process="worker"):
    """Suspend the underlying trace without leaving the 'run' state
    (reference profiler.pause)."""
    if _STATE["running"] and not _STATE["paused"]:
        jax.profiler.stop_trace()
        _STATE["paused"] = True
        telemetry.event("profiler", "pause")


def resume(profile_process="worker"):
    """Resume a paused capture into the same trace dir (reference
    profiler.resume)."""
    if _STATE["running"] and _STATE["paused"]:
        jax.profiler.start_trace(_STATE["dir"])
        _STATE["paused"] = False
        telemetry.event("profiler", "resume")


def dump(finished=True, profile_process="worker"):
    """Finalize the capture to disk (reference profiler.dump).  With
    jax.profiler the artifact is written at ``stop_trace``; dump() stops a
    running capture and returns the trace directory."""
    if _STATE["running"]:
        stop()
    return _STATE["dir"]


def dumps(reset=False):
    """Aggregate-stats text (reference profiler.dumps).  XLA traces carry
    the per-op timeline; point the user at the artifact."""
    return "profiler traces are written to %r (open in Perfetto / " \
        "chrome://tracing)" % (_STATE["dir"] or _trace_dir())


class Domain:
    """Named grouping for profiler objects (reference profiler.py Domain)."""

    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)

    def __str__(self):
        return self.name


class _Span:
    """start/stop scope (the engine's opr_profile hook analogue,
    threaded_engine.h:85): a telemetry span ``profiler.<label>``, which
    is itself the ``TraceAnnotation`` in a running capture — the object
    model is live even when no XLA capture runs: durations land in
    ``telemetry.snapshot()`` and the journal.  Only with telemetry
    disabled, when that span is a no-op, the scope annotates the trace
    itself, so a region is never annotated twice and never lost."""

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name
        self._scope = None

    def _label(self):
        return "%s::%s" % (self.domain.name, self.name) if self.domain \
            else self.name

    def start(self):
        label = "profiler.%s" % self._label()
        self._scope = telemetry.span(label) if telemetry.enabled() \
            else jax.profiler.TraceAnnotation(label)
        self._scope.__enter__()

    def stop(self):
        if self._scope is not None:
            self._scope.__exit__(None, None, None)
            self._scope = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()
        return False

    def __str__(self):
        return self.name


class Task(_Span):
    def __init__(self, domain, name):
        super().__init__(domain, name)


class Frame(_Span):
    def __init__(self, domain, name):
        super().__init__(domain, name)


class Event(_Span):
    def __init__(self, name):
        super().__init__(None, name)


class Counter:
    """Numeric counter object (reference profiler.py Counter).  Every
    mutation mirrors into a telemetry gauge (counters here may go down,
    so they map to gauges) named ``profiler.<domain>.<name>``."""

    def __init__(self, domain, name, value=None):
        self.domain = domain
        self.name = name
        self._value = 0
        if value is not None:
            self.set_value(value)

    def _publish(self):
        telemetry.gauge("profiler.%s.%s" % (self.domain.name, self.name),
                        self._value)

    def set_value(self, value):
        self._value = value
        self._publish()

    def increment(self, delta=1):
        self._value += delta
        self._publish()

    def decrement(self, delta=1):
        self._value -= delta
        self._publish()

    def get_value(self):
        return self._value

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self

    def __str__(self):
        return "%s=%s" % (self.name, self._value)


class Marker:
    """Instant marker (reference profiler.py Marker)."""

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope="process"):
        telemetry.event("marker", "%s::%s" % (self.domain.name, self.name),
                        scope=scope)
        with jax.profiler.TraceAnnotation(
                "%s::%s" % (self.domain.name, self.name)):
            pass
