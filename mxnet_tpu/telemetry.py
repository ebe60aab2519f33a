"""Always-on runtime telemetry: spans, counters, gauges, event journal.

The reference ships engine-level per-op instrumentation as a first-class
subsystem (``src/profiler/profiler.h:88`` chrome://tracing JSON, executor
monitor callbacks, ``mxnet.callback.Speedometer``); under XLA the ops
fuse into a handful of programs, so the observable seams move to the
HOST side — step dispatch, compile-cache lookups, input-pipeline stages,
buffer donation — and that is exactly what this module instruments.

Everything here is host-side and cheap (a ``perf_counter`` pair and a
few dict writes per record, no device sync, no allocation on the hot
path beyond one small dict), so it stays ON in production runs; the
``MXNET_TELEMETRY=0`` env kills it to a near-no-op for A/B overhead
measurement.

Primitives
----------
* ``span(name)`` — ``with telemetry.span("step"): ...`` scoped wall-time
  timer; aggregates (count/total/min/max/last) live in the snapshot and
  each completed span appends a journal event.  The span is also a
  ``jax.profiler.TraceAnnotation`` for its lifetime, so in ANY profiler
  capture it is an event on the ``/host:CPU`` plane of the
  ``.xplane.pb``, on the clock of the device planes.
* ``recent_spans(name, n)`` — the last ``n`` completed spans of a name
  with their children's durations, read back from the journal.
* ``inc(name, delta)`` / ``counter(name)`` — monotonic counters.
* ``gauge(name, value)`` — last-value gauges (ring occupancy, RSS, ...).
* ``event(kind, name, **data)`` — structured entry in the bounded
  journal (a ``deque(maxlen=...)``: old events fall off, memory stays
  bounded no matter how long the run).
* ``record_compile(fn, key)`` — the recompile detector: every jit-cache
  miss — the framework's own, and ``jax.jit``'s retraces of a step the
  framework had cached — reports its key here; the detector diffs it
  against the function's previous key and journals WHICH leaf moved
  (``data.shape[0]: 8 -> 16``, ``opt_states[3][0].committed: False ->
  True``), warning on the Nth retrace (the dominant silent cost on XLA
  backends is exactly this; a step's one recompile for the placement
  of its carried state is not counted).
* ``compile_totals()`` — jax's own compile events (``jax.monitoring``:
  trace, lowering, backend compile, persistent-cache hit or miss),
  booked to an OWNER: the innermost scoped ``span`` open on the thread
  the event fires on, ``eager`` outside any.  A program compiled under
  a span is also one ``xla_compile`` journal event.
  ``thread_compiles().seq`` counts them a thread, so a step builder
  sees jax compile inside its call; ``arg_signature`` is the key it
  then hands ``record_compile``.
* ``sample_memory()`` — gauges for device ``memory_stats()`` bytes and
  host RSS; sampled automatically at ``span(..., memory=True)``
  boundaries (the trainer step does this).
* ``trace()`` / ``span_event()`` / ``set_rank()`` — trace-context
  propagation (ISSUE 18): a thread-local ``trace_id`` stamps every
  span/event inside the context, spans chain ``sid``/``parent``, and
  the distributed rank rides on every record so per-rank JSONL exports
  merge into one causally-linked timeline
  (``python -m mxnet_tpu.telemetry_collect``).
* ``hist_observe()`` / ``Histogram`` — online log-bucketed histograms:
  fixed memory forever, mergeable across processes, honest p50/p99
  without raw sample lists.

Exporters
---------
* ``snapshot()`` — in-process dict (counters, gauges, span aggregates,
  compile counts, compile totals by owner, recent events).
* the profiler's own trace — scoped spans are written into whatever
  ``jax.profiler`` capture is running (see ``span``); there is no second
  timeline file.
* ``export_jsonl(path)`` / ``set_jsonl_sink(path)`` — one-shot dump or
  streaming append of journal events as JSON lines
  (``tools/parse_log.py`` parses them back into tables).
"""
from __future__ import annotations

import json
import logging
import math
import os
import threading
import time
from collections import deque

from jax.profiler import StepTraceAnnotation, TraceAnnotation

__all__ = [
    "span", "observe", "span_event", "recent_spans", "inc", "counter",
    "gauge", "event",
    "snapshot", "reset", "enabled", "enable", "disable", "disabled",
    "trace", "current_trace", "current_span", "new_trace_id",
    "set_rank", "get_rank", "sync_clock",
    "Histogram", "hist_observe", "histogram", "hist_snapshot",
    "record_compile", "compile_counts", "compile_deltas",
    "leaf_signature", "arg_signature", "thread_compiles", "compile_totals",
    "sample_memory",
    "add_step_hook", "remove_step_hook", "emit_step",
    "export_jsonl", "set_jsonl_sink",
    "JOURNAL_MAXLEN",
]

JOURNAL_MAXLEN = int(os.environ.get("MXNET_TELEMETRY_JOURNAL", "4096"))
# warn once a function's compile count reaches this (each retrace of a
# hot jitted step costs seconds-to-minutes of XLA compile time)
_RETRACE_WARN = int(os.environ.get("MXNET_TELEMETRY_RETRACE_WARN", "3"))

_EPOCH = time.perf_counter()     # monotonic anchor for trace timestamps
_WALL0 = time.time()             # wall-clock at the anchor

_lock = threading.Lock()
_enabled = os.environ.get("MXNET_TELEMETRY", "1") not in ("0", "false",
                                                          "off")
_counters = {}
_gauges = {}
_spans = {}          # name -> [count, total_s, min_s, max_s, last_s]
_journal = deque(maxlen=JOURNAL_MAXLEN)
_compiles = {}       # fn -> {"count": int, "key": last_key, "placed": 0|1}
_compile_totals = {}  # owner -> {"programs", "trace_s", ..., "cache_misses"}
_retrace_warned = set()   # (fn, changed-leaf family) already warned
_hists = {}          # name -> Histogram
_step_hooks = []
_jsonl = {"path": None, "fh": None}
_rank = None         # distributed rank stamped on every journal record
# .trace = active trace id, .span = span id, .name = innermost scoped
# span's name, .jax = this thread's _ThreadCompiles
_tls = threading.local()
_ids = [0]           # process-local trace/span id counter (under _lock)


def _now():
    return time.perf_counter() - _EPOCH


# ---------------------------------------------------------------------------
# enable / disable
# ---------------------------------------------------------------------------

def enabled():
    return _enabled


def enable():
    global _enabled
    _enabled = True
    _listen()


def disable():
    global _enabled
    _enabled = False


class disabled:
    """``with telemetry.disabled(): ...`` — A/B overhead measurement."""

    def __enter__(self):
        self._prev = _enabled
        disable()
        return self

    def __exit__(self, *a):
        if self._prev:
            enable()
        return False


# ---------------------------------------------------------------------------
# rank / trace context
# ---------------------------------------------------------------------------

def set_rank(rank):
    """Stamp ``rank`` on every subsequent journal record.  Called once
    per process by the distributed bootstrap (``kvstore.create``) so
    per-rank JSONL exports are self-identifying to the collector."""
    global _rank
    _rank = rank


def get_rank():
    return _rank


def _next_id():
    with _lock:
        _ids[0] += 1
        return _ids[0]


def new_trace_id():
    """Process-unique trace id (pid-qualified, so ids from different
    ranks never collide in a collector merge)."""
    return "%x-%x-%x" % (int(_WALL0 * 1e3) & 0xffffffff,
                         os.getpid() & 0xffffff, _next_id())


def current_trace():
    """The trace id active on this thread, or None."""
    return getattr(_tls, "trace", None)


def current_span():
    """The span id of the innermost open traced span on this thread."""
    return getattr(_tls, "span", None)


class _ActiveTrace:
    __slots__ = ("trace_id", "_prev_trace", "_prev_span")

    def __init__(self, trace_id):
        self.trace_id = trace_id

    def __enter__(self):
        self._prev_trace = getattr(_tls, "trace", None)
        self._prev_span = getattr(_tls, "span", None)
        _tls.trace = self.trace_id
        _tls.span = None
        return self

    def __exit__(self, *a):
        _tls.trace = self._prev_trace
        _tls.span = self._prev_span
        return False


class _NoopTrace:
    __slots__ = ("trace_id",)

    def __enter__(self):
        # joining an already-active trace: expose its id
        self.trace_id = getattr(_tls, "trace", None)
        return self

    def __exit__(self, *a):
        return False


def trace(trace_id=None):
    """``with telemetry.trace(): ...`` — open a trace context on this
    thread.  Spans and events inside carry ``trace`` (and spans a
    ``sid``/``parent`` chain), so one request or one training step is
    causally linked end to end.

    With no explicit id, an already-active trace is JOINED (no-op): a
    ``DataParallelStep`` dispatched from inside ``Trainer.step`` shares
    the step's trace instead of opening a nested one.  An explicit
    ``trace_id`` always activates (serve worker threads re-enter a
    request's trace from the PendingRequest)."""
    if not _enabled:
        return _NoopTrace()
    if trace_id is None:
        if getattr(_tls, "trace", None) is not None:
            return _NoopTrace()
        trace_id = new_trace_id()
    return _ActiveTrace(trace_id)


# ---------------------------------------------------------------------------
# journal
# ---------------------------------------------------------------------------

def _emit(rec):
    """Append to the journal (and the streaming JSONL sink, if set).
    Caller holds no lock; rec must already carry ``ts``."""
    if _rank is not None:
        rec.setdefault("rank", _rank)
    with _lock:
        _journal.append(rec)
        fh = _jsonl["fh"]
        if fh is not None:
            try:
                # default=str: a non-JSON value (numpy scalar, device
                # array) degrades to its string form instead of raising
                # out of the training step
                fh.write(json.dumps(rec, default=str) + "\n")
            except (ValueError, OSError):    # closed/unwritable sink
                _jsonl["fh"] = None


def event(kind, name, **data):
    """Record a structured event in the bounded journal.  Inside an
    active trace context the record carries the trace id."""
    if not _enabled:
        return
    rec = {"ts": round(_WALL0 + _now(), 6), "kind": kind, "name": name}
    tr = getattr(_tls, "trace", None)
    if tr is not None and "trace" not in data:
        rec["trace"] = tr
    if data:
        rec.update(data)
    _emit(rec)


def sync_clock(client, rank, key="mxtpu/clock0", timeout_ms=10000):
    """Cross-process clock alignment via the coordination KV store:
    rank 0 publishes its (monotonic-anchored) wall clock; every rank
    journals a ``clock`` record pairing that reference with its own
    local clock.  ``telemetry_collect`` subtracts the pair per export
    file to de-skew all ranks onto rank 0's timeline."""
    if not _enabled:
        return None
    ref = None
    if rank == 0:
        ref = _WALL0 + _now()
        try:
            client.key_value_set(key, repr(ref))
        except Exception:
            ref = None
    else:
        try:
            ref = float(client.blocking_key_value_get(key, timeout_ms))
        except Exception:
            ref = None
    local = _WALL0 + _now()
    event("clock", "sync", rank=rank, local_wall=round(local, 6),
          ref_wall=round(ref, 6) if ref is not None else None)
    return ref


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def _record_span_agg(name, dur_s):
    with _lock:
        agg = _spans.get(name)
        if agg is None:
            _spans[name] = [1, dur_s, dur_s, dur_s, dur_s]
        else:
            agg[0] += 1
            agg[1] += dur_s
            agg[2] = min(agg[2], dur_s)
            agg[3] = max(agg[3], dur_s)
            agg[4] = dur_s


def _record_span(name, start, dur_s, journal=True, trace=None, sid=None,
                 parent=None):
    _record_span_agg(name, dur_s)
    if journal:
        rec = {"ts": round(_WALL0 + start, 6), "kind": "span",
               "name": name, "dur_ms": round(dur_s * 1e3, 4),
               "tid": threading.get_ident()}
        if trace is not None:
            rec["trace"] = trace
            if sid is not None:
                rec["sid"] = sid
            if parent is not None:
                rec["parent"] = parent
        _emit(rec)


class _Span:
    """Scoped wall-time timer.  ``duration_ms`` is readable after exit.
    Inside an active trace context the journal record carries the trace
    id plus a ``sid``/``parent`` chain (nested spans link causally).
    For the same interval the span is a profiler annotation (a step
    annotation when it was given a ``step_num``)."""

    __slots__ = ("name", "memory", "hist", "_t0", "duration_ms",
                 "_trace", "_sid", "_parent", "_outer", "_ann")

    def __init__(self, name, memory=False, hist=False, step_num=None):
        self.name = name
        self.memory = memory
        self.hist = hist
        self._t0 = None
        self.duration_ms = None
        self._trace = None
        self._sid = None
        self._parent = None
        self._ann = TraceAnnotation(name) if step_num is None \
            else StepTraceAnnotation(name, step_num=step_num)

    def __enter__(self):
        self._trace = getattr(_tls, "trace", None)
        if self._trace is not None:
            self._parent = getattr(_tls, "span", None)
            self._sid = _next_id()
            _tls.span = self._sid
        self._outer = getattr(_tls, "name", None)
        _tls.name = self.name
        self._ann.__enter__()
        self._t0 = _now()
        return self

    def __exit__(self, *a):
        dur = _now() - self._t0
        self._ann.__exit__(None, None, None)
        self.duration_ms = dur * 1e3
        if self._trace is not None:
            _tls.span = self._parent
        _tls.name = self._outer
        _record_span(self.name, self._t0, dur, trace=self._trace,
                     sid=self._sid, parent=self._parent)
        if self.hist:
            hist_observe(self.name, dur * 1e3)
        if self.memory:
            sample_memory()
        return False


class _NoopSpan:
    __slots__ = ("duration_ms",)
    name = None
    memory = False

    def __enter__(self):
        self.duration_ms = None
        return self

    def __exit__(self, *a):
        return False


def span(name, memory=False, hist=False, step_num=None):
    """``with telemetry.span("step"): ...`` — time a scope.  With
    ``hist=True`` the duration also feeds the ``name`` histogram.

    The scope is a ``jax.profiler.TraceAnnotation`` too: under any
    profiler capture (``mx.profiler``, ``jax.profiler.start_trace``) the
    span is an event of this thread's line on the trace's ``/host:CPU``
    plane, on the device planes' clock.  With ``step_num`` it is a
    ``StepTraceAnnotation`` carrying that stat (one training step).
    Disabled telemetry gives a no-op span with no annotation."""
    if not _enabled:
        return _NoopSpan()
    return _Span(name, memory=memory, hist=hist, step_num=step_num)


def recent_spans(name, n):
    """The last ``n`` completed ``name`` spans, oldest first, read from
    the journal: ``(spans, short)``.  Each span is ``{"ts", "dur_ms",
    "children"}`` with ``children`` mapping a child span's name to the
    summed ``dur_ms`` of the spans that name this one as ``parent`` —
    so self time is ``dur_ms - sum(children.values())``.  ``short`` is
    how many of the ``n`` the journal no longer (or never) held: 0 means
    the answer is whole.  A span whose children may already have fallen
    off the bounded journal is not counted as held.

    Children link through ``sid``/``parent``, which spans carry inside
    a ``trace()`` context only."""
    with _lock:
        records = list(_journal)
    if len(records) == _journal.maxlen:
        # full journal: whatever was evicted was appended before the
        # oldest record that is left, so only spans that STARTED after
        # that moment are sure to have all their children here
        oldest = records[0]
        whole_after = oldest["ts"] + oldest.get("dur_ms", 0.0) * 1e-3 \
            if oldest["kind"] == "span" else oldest["ts"]
    else:
        whole_after = None
    children, found = {}, []
    for rec in records:
        if rec["kind"] != "span":
            continue
        parent = rec.get("parent")
        if parent is not None:
            kids = children.setdefault(parent, {})
            kids[rec["name"]] = kids.get(rec["name"], 0.0) + rec["dur_ms"]
        if rec["name"] == name and (whole_after is None
                                    or rec["ts"] >= whole_after):
            found.append(rec)
    found = found[-n:] if n > 0 else []
    # a span outside any trace() has no sid, and no record names None
    spans = [{"ts": rec["ts"], "dur_ms": rec["dur_ms"],
              "children": children.get(rec.get("sid"), {})}
             for rec in found]
    return spans, n - len(spans)


def observe(name, dur_s, hist=False):
    """Record an externally-measured duration into the span aggregates
    (for stages timed by hand, e.g. inside the prefetch feeder loop).
    Aggregates only: a duration handed over after the fact is no scope,
    so it is neither journaled nor a profiler annotation."""
    if not _enabled:
        return
    _record_span(name, _now() - dur_s, dur_s, journal=False)
    if hist:
        hist_observe(name, dur_s * 1e3)


def span_event(name, dur_s, trace=None, parent=None, hist=False, **data):
    """Journal an externally-timed span with EXPLICIT trace linkage.

    The serve pipeline and the elastic runtime measure phases whose
    start and end live on different threads (queue wait, dispatch,
    detect -> reshard -> resume) — no thread-local context covers them,
    so the caller passes the trace id it carried on the request or the
    recovery event.  Updates the span aggregates like ``observe`` and,
    with ``hist=True``, the ``name`` histogram.

    Journal-only: a profiler annotation opens and closes on ONE thread,
    so a span timed across threads cannot be one and is not in the
    profiler's trace."""
    if not _enabled:
        return
    start = _now() - dur_s
    _record_span_agg(name, dur_s)
    rec = {"ts": round(_WALL0 + start, 6), "kind": "span", "name": name,
           "dur_ms": round(dur_s * 1e3, 4), "tid": threading.get_ident()}
    if trace is None:
        trace = getattr(_tls, "trace", None)
    if trace is not None:
        rec["trace"] = trace
    if parent is not None:
        rec["parent"] = parent
    if data:
        rec.update(data)
    _emit(rec)
    if hist:
        hist_observe(name, dur_s * 1e3)


# ---------------------------------------------------------------------------
# counters / gauges
# ---------------------------------------------------------------------------

def inc(name, delta=1):
    """Bump a monotonic counter."""
    if not _enabled:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + delta


def counter(name):
    """Current value of a counter (0 if never bumped)."""
    with _lock:
        return _counters.get(name, 0)


def gauge(name, value):
    """Set a last-value gauge."""
    if not _enabled:
        return
    with _lock:
        _gauges[name] = value


# ---------------------------------------------------------------------------
# online histograms
# ---------------------------------------------------------------------------

class Histogram:
    """Log-bucketed online histogram: fixed memory, mergeable.

    Buckets are logarithmic — ``BUCKETS_PER_DECADE`` per power of ten
    from ``LO`` up through ``LO * 10**DECADES`` (default 1e-3..1e7 ms,
    i.e. 1 microsecond to ~3 hours when fed milliseconds), plus one
    underflow bucket.  Relative quantile error is bounded by the bucket
    ratio (~12% at 10/decade) and exact min/max are kept, so p50/p99
    are honest without storing samples: the bucket array is allocated
    once at a fixed ``NBUCKETS`` length and NEVER grows — memory is
    byte-for-byte identical after 10 observations or 10 million.

    Two histograms with the same parameters merge by adding counts,
    which is how ``telemetry_collect`` combines per-rank exports and
    how bench diffs a leg (``since``) out of a long-lived server."""

    LO = 1e-3
    BUCKETS_PER_DECADE = 10
    DECADES = 10
    NBUCKETS = 1 + BUCKETS_PER_DECADE * DECADES   # +1 underflow

    __slots__ = ("count", "sum", "min", "max", "buckets")

    def __init__(self):
        self.count = 0
        self.sum = 0.0
        self.min = None
        self.max = None
        self.buckets = [0] * self.NBUCKETS

    def _index(self, v):
        if v < self.LO:
            return 0
        return 1 + min(self.NBUCKETS - 2,
                       int(math.log10(v / self.LO)
                           * self.BUCKETS_PER_DECADE))

    def add(self, v):
        v = float(v)
        self.count += 1
        self.sum += v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)
        self.buckets[self._index(v)] += 1

    def _bound(self, i):
        """Upper edge of bucket ``i``."""
        if i == 0:
            return self.LO
        return self.LO * 10.0 ** (i / self.BUCKETS_PER_DECADE)

    def quantile(self, q):
        """Value at quantile ``q`` in [0, 1]: the geometric midpoint of
        the bucket holding the q-th observation, clamped by the exact
        min/max.  None on an empty histogram."""
        if self.count == 0:
            return None
        target = q * self.count
        seen = 0
        for i, c in enumerate(self.buckets):
            seen += c
            if seen >= target and c:
                lo = self._bound(i - 1) if i > 0 else 0.0
                hi = self._bound(i)
                mid = math.sqrt(lo * hi) if lo > 0 else hi / 2.0
                return max(self.min, min(self.max, mid))
        return self.max

    def merge(self, other):
        """Add ``other``'s counts into this histogram (same geometry)."""
        if other.count == 0:
            return self
        self.count += other.count
        self.sum += other.sum
        self.min = other.min if self.min is None else min(self.min,
                                                          other.min)
        self.max = other.max if self.max is None else max(self.max,
                                                          other.max)
        for i, c in enumerate(other.buckets):
            self.buckets[i] += c
        return self

    def since(self, baseline):
        """A new Histogram holding only what arrived after ``baseline``
        (an earlier ``to_dict`` snapshot of THIS histogram) — bench
        carves one load leg out of a long-lived server's totals.
        min/max are the lifetime values (bounds, not leg-exact)."""
        out = Histogram()
        base = {int(k): v for k, v in baseline.get("buckets", {}).items()}
        out.count = self.count - baseline.get("count", 0)
        out.sum = self.sum - baseline.get("sum", 0.0)
        out.min, out.max = self.min, self.max
        for i, c in enumerate(self.buckets):
            out.buckets[i] = c - base.get(i, 0)
        return out

    def to_dict(self):
        """JSON form: sparse non-zero buckets + geometry for merge
        validation."""
        return {"count": self.count, "sum": round(self.sum, 6),
                "min": self.min, "max": self.max,
                "lo": self.LO, "bpd": self.BUCKETS_PER_DECADE,
                "buckets": {str(i): c for i, c in enumerate(self.buckets)
                            if c}}

    @classmethod
    def from_dict(cls, d):
        if (d.get("lo", cls.LO) != cls.LO
                or d.get("bpd", cls.BUCKETS_PER_DECADE)
                != cls.BUCKETS_PER_DECADE):
            raise ValueError("histogram geometry mismatch: %r" % d)
        h = cls()
        h.count = int(d.get("count", 0))
        h.sum = float(d.get("sum", 0.0))
        h.min = d.get("min")
        h.max = d.get("max")
        for k, c in d.get("buckets", {}).items():
            h.buckets[int(k)] = int(c)
        return h

    def summary(self):
        """Quantile digest for snapshots and parse_log tables."""
        if self.count == 0:
            return {"count": 0}
        return {"count": self.count,
                "mean": round(self.sum / self.count, 4),
                "min": round(self.min, 4), "max": round(self.max, 4),
                "p50": round(self.quantile(0.50), 4),
                "p90": round(self.quantile(0.90), 4),
                "p99": round(self.quantile(0.99), 4)}


def hist_observe(name, value_ms):
    """Feed one observation (milliseconds by convention) into the
    ``name`` histogram, creating it on first use."""
    if not _enabled:
        return
    with _lock:
        h = _hists.get(name)
        if h is None:
            h = _hists[name] = Histogram()
        h.add(value_ms)


def histogram(name):
    """The live Histogram for ``name`` (None if never observed)."""
    with _lock:
        return _hists.get(name)


def hist_snapshot():
    """``{name: full to_dict()}`` for every live histogram — the
    mergeable form the JSONL snapshot record and bench artifacts embed."""
    with _lock:
        return {name: h.to_dict() for name, h in _hists.items()}


# ---------------------------------------------------------------------------
# recompile detector
# ---------------------------------------------------------------------------

def _diff_keys(old, new, path=""):
    """Leaf-level diff of two (nested dict/tuple/list/scalar) cache keys.
    Returns human-readable ``path: old -> new`` strings — the axis (or
    dtype, or static arg) that forced the retrace."""
    if isinstance(old, dict) and isinstance(new, dict):
        out = []
        for k in sorted(set(old) | set(new)):
            p = "%s.%s" % (path, k) if path else str(k)
            if k not in old:
                out.append("%s: <absent> -> %r" % (p, new[k]))
            elif k not in new:
                out.append("%s: %r -> <absent>" % (p, old[k]))
            else:
                out.extend(_diff_keys(old[k], new[k], p))
        return out
    if isinstance(old, (tuple, list)) and isinstance(new, (tuple, list)):
        if len(old) != len(new):
            return ["%s: %r -> %r" % (path or "key", tuple(old),
                                      tuple(new))]
        out = []
        for i, (o, n) in enumerate(zip(old, new)):
            out.extend(_diff_keys(o, n, "%s[%d]" % (path, i)))
        return out
    if old != new:
        return ["%s: %r -> %r" % (path or "key", old, new)]
    return []


def record_compile(fn, key):
    """Report a jit-cache miss for ``fn`` with its cache key.

    The first compile is journaled as ``kind="compile"``; every later
    one as ``kind="recompile"`` with ``changed`` naming exactly which
    leaf of the key moved vs the previous compile.  On the
    ``MXNET_TELEMETRY_RETRACE_WARN``-th (default 3rd) compile of the
    same function a ``logging`` warning fires — a retrace storm on a
    hot step usually means an unstable shape/dtype/static-arg upstream.
    A function's FIRST recompile in which nothing but the committedness
    or sharding of leaves moved is not counted toward that threshold:
    every jitted step has one, at its second call, when the state it
    carries comes back committed (``arg_signature`` keys), and the
    warning keeps meaning two retraces beyond the expected.
    """
    if not _enabled:
        return None
    with _lock:
        ent = _compiles.get(fn)
        if ent is None:
            ent = _compiles[fn] = {"count": 0, "key": None, "placed": 0}
        ent["count"] += 1
        n = ent["count"]
        prev = ent["key"]
        ent["key"] = key
    if prev is None:
        event("compile", fn, n=n)
        return []
    changed = _diff_keys(prev, key) or ["<cache key unchanged>"]
    event("recompile", fn, n=n, changed=changed)
    if not ent["placed"] and all(
            c.split(":", 1)[0].endswith((".committed", ".sharding"))
            for c in changed):
        ent["placed"] = 1
    if n - ent["placed"] >= _RETRACE_WARN:
        # warn once per (instance, cache-key family): ``fn`` keys are
        # already instance-qualified (``serve.<name>.b<N>``,
        # ``DataParallelStep[<id>]``), and the family is the SET of key
        # leaves that moved — so two servers, or a server and a trainer
        # in one process, never suppress each other's Nth-retrace
        # warnings, while a hot loop retracing on the same axis warns
        # exactly once instead of storming the log
        family = tuple(sorted(c.split(":", 1)[0] for c in changed))
        with _lock:
            warned = (fn, family) in _retrace_warned
            if not warned:
                _retrace_warned.add((fn, family))
        if not warned:
            logging.warning(
                "telemetry: %s compiled %d times (retrace); "
                "last change: %s", fn, n, "; ".join(changed[:4]))
    return changed


def leaf_signature(v):
    """What ``jax.jit`` keys an argument leaf by beyond the tree it sits
    in: shape, dtype, weak type, and the sharding of a COMMITTED array
    (an uncommitted one goes wherever the others are).  Read from a
    donated array as well as from a live one: donation takes the buffer,
    not the aval or the sharding."""
    committed = bool(getattr(v, "committed", False))
    return {"shape": list(v.shape), "dtype": str(v.dtype),
            "weak_type": bool(getattr(v, "weak_type", False)),
            "committed": committed,
            "sharding": v.sharding if committed else None}


def arg_signature(args):
    """``leaf_signature`` of every array leaf of ``args`` (nested lists,
    tuples and dicts; ``None`` stays), shardings as text: a key for
    ``record_compile``, whose diff then names the leaf that made
    ``jax.jit`` compile a cached step again —
    ``opt_states[3][0].committed: False -> True``."""
    import jax

    def text(v):
        sig = leaf_signature(v)
        sig["sharding"] = None if sig["sharding"] is None \
            else str(sig["sharding"])
        return sig
    return jax.tree_util.tree_map(text, args)


def compile_counts():
    with _lock:
        return {k: v["count"] for k, v in _compiles.items()}


def compile_deltas(baseline):
    """``{fn: extra compiles}`` for every function whose compile count
    grew past a ``compile_counts()`` snapshot — the steady-state
    zero-recompile gate's measurement (``serve.InferenceServer``
    snapshots at start; ``steady_state_recompiles()`` reads any
    ``serve.*`` entry that appears here during the load phase)."""
    cur = compile_counts()
    return {k: v - baseline.get(k, 0) for k, v in cur.items()
            if v > baseline.get(k, 0)}


# ---------------------------------------------------------------------------
# jax's own compile events
# ---------------------------------------------------------------------------

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_listening = False


class _ThreadCompiles:
    """What one thread has seen of jax's compile events: ``seq`` programs
    handed to the backend here, the record of the ``last`` one, and the
    trace (seconds by function name), lowering and cache events still
    waiting for their backend event (they fire before it, on the same
    thread)."""

    __slots__ = ("seq", "last", "trace", "lower", "cache")

    def __init__(self):
        self.seq = 0
        self.last = None
        self.trace = {}
        self.lower = None
        self.cache = "off"


def thread_compiles():
    """This thread's ``_ThreadCompiles``.  A jitted step's builder reads
    ``.seq`` before and after its call: where it moved, jax compiled
    inside the call, whatever the builder's own cache said, and ``.last``
    is the program (``DataParallelStep``, ``_FusedUpdate``)."""
    state = getattr(_tls, "jax", None)
    if state is None:
        state = _tls.jax = _ThreadCompiles()
    return state


def _on_jax_duration(event, seconds, fun_name=None, **_):
    if not _enabled:
        return
    if event == _TRACE:
        # inner jitted functions are traced too, before the program's
        # own trace ends and while it is lowered: kept by name
        thread_compiles().trace[fun_name] = seconds
    elif event == _LOWER:
        thread_compiles().lower = (fun_name, seconds)
    elif event == _BACKEND:
        _book_compile(fun_name, seconds)


def _on_jax_event(event, **_):
    if not _enabled:
        return
    if event == _CACHE_HIT:
        thread_compiles().cache = "hit"
    elif event == _CACHE_MISS:
        thread_compiles().cache = "miss"


def _book_compile(fun_name, backend_s):
    """One program handed to the backend (compiled, or reloaded from the
    persistent cache): pair it with the trace and lowering that led to
    it, book it to its owner, journal it."""
    state = thread_compiles()
    # ``jit(step_fn)`` was traced as ``step_fn``; a program jax had traced
    # before (same avals, another sharding or committedness) is lowered
    # and compiled again without a trace
    traced = fun_name[fun_name.find("(") + 1:-1] \
        if fun_name.endswith(")") else fun_name
    trace_s = state.trace.get(traced, 0.0)
    lower, cache = state.lower, state.cache
    state.trace.clear()
    state.lower = None
    state.cache = "off"
    lower_s = lower[1] if lower and lower[0] == fun_name else 0.0
    owner = getattr(_tls, "name", None) or "eager"
    rec = {"owner": owner, "trace_s": trace_s, "lower_s": lower_s,
           "backend_s": backend_s, "cache": cache}
    state.seq += 1
    state.last = dict(rec, name=fun_name)
    with _lock:
        total = _compile_totals.setdefault(owner, {
            "programs": 0, "trace_s": 0.0, "lower_s": 0.0,
            "backend_s": 0.0, "cache_hits": 0, "cache_misses": 0})
        total["programs"] += 1
        total["trace_s"] += trace_s
        total["lower_s"] += lower_s
        total["backend_s"] += backend_s
        if cache != "off":
            total["cache_hits" if cache == "hit" else "cache_misses"] += 1
    # op-by-op programs outside every span come by the hundred in a
    # set-up: counted above, not journaled (they would push everything
    # else off a snapshot's tail of recent events)
    if owner != "eager":
        event("xla_compile", fun_name, **rec)


def _listen():
    """Register the one pair of ``jax.monitoring`` listeners, once a
    process however often it is asked; nothing while telemetry is
    disabled (``enable()`` asks again)."""
    global _listening
    if _listening or not _enabled:
        return
    import jax.monitoring
    with _lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
    jax.monitoring.register_event_listener(_on_jax_event)


def compile_totals():
    """``{owner: {"programs", "trace_s", "lower_s", "backend_s",
    "cache_hits", "cache_misses"}}``: every program jax handed its
    backend since the last ``reset()``, by the scoped span it compiled
    under (``eager`` outside any)."""
    with _lock:
        return {owner: dict(total)
                for owner, total in _compile_totals.items()}


# ---------------------------------------------------------------------------
# memory gauge
# ---------------------------------------------------------------------------

def _host_rss_bytes():
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return None


_LIVE_BUFFERS = os.environ.get("MXNET_TELEMETRY_LIVE_BUFFERS",
                               "0") not in ("0", "false", "off")


def sample_memory():
    """Gauge the device allocator and host RSS.  Device ``memory_stats``
    is absent on some backends (CPU) — those gauges are simply skipped;
    host RSS is always available on Linux.  With
    ``MXNET_TELEMETRY_LIVE_BUFFERS=1`` the sum of live jax array bytes
    is gauged too (enumerating live buffers is not free, so it is
    opt-in)."""
    if not _enabled:
        return
    rss = _host_rss_bytes()
    if rss is not None:
        gauge("mem.host_rss_bytes", rss)
    try:
        import jax
        dev = jax.local_devices()[0]
        stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
    except Exception:
        stats = None
    if stats:
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if k in stats:
                gauge("mem.device_%s" % k, int(stats[k]))
    if _LIVE_BUFFERS:
        try:
            import jax
            gauge("mem.live_buffer_bytes",
                  int(sum(a.nbytes for a in jax.live_arrays())))
        except Exception:
            pass


# ---------------------------------------------------------------------------
# step hooks
# ---------------------------------------------------------------------------

def add_step_hook(hook):
    """Register ``hook(record)`` to fire after every training step
    (``Trainer.step`` / ``DataParallelStep`` / ``Module.fit``).  The
    record is a dict: ``source``, ``index``, plus whatever the emitter
    attached (``batch_size``, ``step_ms``, ``owner``...).  This is how
    ``Monitor.attach`` and ``Speedometer.attach`` install themselves
    without manual tic/toc."""
    with _lock:
        if hook not in _step_hooks:
            _step_hooks.append(hook)
    return hook


def remove_step_hook(hook):
    with _lock:
        if hook in _step_hooks:
            _step_hooks.remove(hook)


def emit_step(source, index, **data):
    """Fire the step hooks (and journal a ``step`` event)."""
    if not _enabled:
        return
    rec = {"source": source, "index": index}
    rec.update(data)
    event("step", source, index=index,
          **{k: v for k, v in data.items()
             if isinstance(v, (int, float, str, bool, type(None)))})
    with _lock:
        hooks = list(_step_hooks)
    for h in hooks:
        try:
            h(rec)
        except Exception:        # a broken observer must not kill training
            logging.exception("telemetry: step hook %r failed", h)


# ---------------------------------------------------------------------------
# snapshot / reset
# ---------------------------------------------------------------------------

def snapshot(events=64):
    """In-process view of everything: counters, gauges, span aggregates
    (ms), compile counts, and the ``events`` most recent journal
    entries.  Cheap enough to embed per-run in BENCH artifacts."""
    totals = compile_totals()
    with _lock:
        spans = {
            name: {"count": a[0],
                   "total_ms": round(a[1] * 1e3, 3),
                   "mean_ms": round(a[1] / a[0] * 1e3, 3),
                   "min_ms": round(a[2] * 1e3, 3),
                   "max_ms": round(a[3] * 1e3, 3),
                   "last_ms": round(a[4] * 1e3, 3)}
            for name, a in _spans.items()}
        return {
            "enabled": _enabled,
            "counters": dict(_counters),
            "gauges": dict(_gauges),
            "spans": spans,
            "histograms": {name: h.summary()
                           for name, h in _hists.items()},
            "compiles": {k: v["count"] for k, v in _compiles.items()},
            "compile_totals": totals,
            "events": list(_journal)[-events:] if events else [],
        }


def reset():
    """Clear all telemetry state (tests, bench A/B legs)."""
    with _lock:
        _counters.clear()
        _gauges.clear()
        _spans.clear()
        _journal.clear()
        _compiles.clear()
        _compile_totals.clear()
        _retrace_warned.clear()
        _hists.clear()


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def set_jsonl_sink(path):
    """Stream every subsequent journal event to ``path`` as JSON lines
    (append mode).  ``None`` closes the sink."""
    with _lock:
        if _jsonl["fh"] is not None:
            try:
                _jsonl["fh"].close()
            except OSError:
                pass
        _jsonl["fh"] = open(path, "a") if path else None
        _jsonl["path"] = path


def export_jsonl(path):
    """One-shot dump: the journal plus a final ``snapshot`` record.
    The snapshot carries the FULL (mergeable) histogram dicts, not just
    summaries, so ``telemetry_collect`` can sum them across ranks."""
    snap = snapshot(events=0)
    hists = hist_snapshot()
    with _lock:
        events = list(_journal)
    rec = {"ts": round(_WALL0 + _now(), 6), "kind": "snapshot",
           "counters": snap["counters"], "gauges": snap["gauges"],
           "spans": snap["spans"], "histograms": hists,
           "compiles": snap["compiles"],
           "compile_totals": snap["compile_totals"]}
    if _rank is not None:
        rec["rank"] = _rank
    # atomic (tmp + os.replace via fsutil): a collector must never read
    # a torn export from a rank that died mid-dump
    from .fsutil import atomic_write_path
    with atomic_write_path(path) as tmp:
        with open(tmp, "w") as f:
            for r in events:
                f.write(json.dumps(r, default=str) + "\n")
            f.write(json.dumps(rec, default=str) + "\n")
    return path


# the model's first eager programs compile before any span opens
_listen()
