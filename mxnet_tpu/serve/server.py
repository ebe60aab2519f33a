"""Fault-tolerant continuous-batching inference server.

The "millions of users, heavy traffic" leg of the roadmap, built
robustness-first on TF-Serving's design (arxiv 1605.08695: bounded
batching queues with deadline-aware scheduling) over the bucketed-shape
AOT discipline in :mod:`mxnet_tpu.serve.buckets`.  The contract is the
failure envelope, not just the happy path:

* **Every submitted request reaches a terminal outcome** — ``result``,
  ``timeout`` or ``reject`` — no hangs, no silent drops.  The chaos
  matrix (``parallel/chaos.py`` faults ``request_burst``,
  ``dispatch_stall``, ``executable_poison``, ``deadline_storm``) proves
  it under injected failure.
* **Deadlines propagate** from enqueue through dispatch: an expired
  request is dropped *before* it wastes a TPU dispatch, and a batch
  never waits past its earliest member's deadline.
* **Backpressure, never blocking**: the request queue is bounded and
  admission uses ``put_nowait`` — a full queue is an immediate
  ``reject(queue_full)``, never a blocked producer, never an unbounded
  queue.
* **Watchdog + quarantine**: a dispatch that hangs past
  ``dispatch_timeout_ms`` is timed out by the watchdog (its requests
  resolve, a replacement dispatcher takes over, the stale worker's late
  result is discarded); an executable that *fails* is retried a bounded
  number of times and then quarantined — subsequent batches degrade
  onto smaller buckets (:func:`buckets.plan_buckets`).
* **Health state machine** ``STARTING -> READY -> DEGRADED ->
  DRAINING``: DEGRADED (overload watermark crossed, or a quarantine /
  watchdog fire) sheds low-priority requests at admission and recovers
  to READY when the queue subsides; DRAINING rejects new work, lets
  accepted work finish, then stops and joins every thread.

Request lifecycle, shed/degrade semantics and the overload runbook:
docs/SERVING.md.  Journal events (``serve/*``) render as a census via
``tools/parse_log.py --jsonl``.
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as onp

from .. import flight_recorder, telemetry
from ..base import MXNetError
from ..parallel import chaos
from .buckets import AotModel, pad_batch, plan_buckets

__all__ = ["InferenceServer", "ServeConfig", "PendingRequest",
           "ServeError", "ServeRejected", "ServeTimeout",
           "STARTING", "READY", "DEGRADED", "DRAINING"]

STARTING = "STARTING"
READY = "READY"
DEGRADED = "DEGRADED"
DRAINING = "DRAINING"


class ServeError(MXNetError):
    """A request failed inside the server (poisoned executable with no
    fallback bucket left)."""


class ServeRejected(ServeError):
    """Admission control refused the request (queue_full / shed /
    draining / not_ready / bad_shape)."""


class ServeTimeout(ServeError):
    """The request's deadline expired before a result (queue wait,
    pre-dispatch drop, or a watchdog-killed dispatch)."""


class ServeConfig:
    """Serving knobs.  Times are milliseconds; everything is bounded by
    construction — there is no unbounded queue or wait anywhere."""

    def __init__(self, buckets=(1, 2, 4, 8), max_queue=64,
                 batch_wait_ms=2.0, deadline_margin_ms=5.0,
                 default_deadline_ms=1000.0, dispatch_timeout_ms=1000.0,
                 watchdog_interval_ms=25.0, max_retries=1,
                 shed_fraction=0.75, resume_fraction=0.25,
                 max_respawns=4, poll_ms=20.0):
        if isinstance(buckets, str):
            if buckets != "auto":
                raise MXNetError("ServeConfig: buckets must be ints or "
                                 "'auto', got %r" % (buckets,))
            # resolved at InferenceServer construction, where the
            # model's feature shape (the HBM-validation input) is known
            self.buckets = "auto"
        else:
            self.buckets = tuple(sorted(set(int(b) for b in buckets)))
            if not self.buckets or self.buckets[0] < 1:
                raise MXNetError("ServeConfig: buckets must be >= 1")
        self.max_queue = int(max_queue)
        if self.max_queue < 1:
            # queue.Queue(maxsize=0) means UNBOUNDED — the exact thing
            # this server promises never to have
            raise MXNetError("ServeConfig: max_queue must be >= 1 "
                             "(got %d)" % self.max_queue)
        self.batch_wait_s = float(batch_wait_ms) / 1e3
        self.margin_s = float(deadline_margin_ms) / 1e3
        self.default_deadline_s = float(default_deadline_ms) / 1e3
        self.dispatch_timeout_s = float(dispatch_timeout_ms) / 1e3
        self.watchdog_s = float(watchdog_interval_ms) / 1e3
        self.max_retries = int(max_retries)
        self.shed_depth = max(1, int(self.max_queue * float(shed_fraction)))
        self.resume_depth = int(self.max_queue * float(resume_fraction))
        self.max_respawns = int(max_respawns)
        self.poll_s = float(poll_ms) / 1e3


class PendingRequest:
    """Client handle: resolves exactly once to a terminal outcome.

    ``outcome(timeout)`` returns ``("result", value, None)``,
    ``("timeout", None, reason)``, ``("reject", None, reason)`` or
    ``("error", None, reason)`` — or None if the outcome has not
    arrived within ``timeout``.  ``result(timeout)`` unwraps, raising
    the typed exception.  First resolution wins (the watchdog and a
    late-returning stalled dispatch may race; the client sees ONE
    outcome).
    """

    def __init__(self, x, deadline, priority=0, synthetic=False):
        self.x = x
        self.deadline = deadline            # time.monotonic() absolute
        self.priority = int(priority)
        self.synthetic = bool(synthetic)
        self.arrival = time.monotonic()
        # the trace id follows this request across batcher -> dispatch
        # -> terminal outcome: every journal record stamped with it is
        # one causally-linked story in the collector's merged timeline
        self.trace_id = telemetry.new_trace_id()
        self._lock = threading.Lock()
        self._done = threading.Event()
        self._outcome = None
        self._done_ts = None

    def _resolve(self, kind, value=None, reason=None):
        """Record the terminal outcome; False if already resolved."""
        with self._lock:
            if self._outcome is not None:
                return False
            self._outcome = (kind, value, reason)
            done_ts = self._done_ts = time.monotonic()
        self._done.set()
        lat_ms = (done_ts - self.arrival) * 1e3
        if kind == "result" and not self.synthetic:
            telemetry.hist_observe("serve.request", lat_ms)
        telemetry.event("serve", "outcome", trace=self.trace_id,
                        outcome=kind, reason=reason,
                        latency_ms=round(lat_ms, 3))
        return True

    def done(self):
        return self._done.is_set()

    def outcome(self, timeout=None):
        if not self._done.wait(timeout):
            return None
        with self._lock:
            out = self._outcome
        return out

    def latency_ms(self):
        """submit -> terminal-outcome latency, or None while pending."""
        with self._lock:
            ts = self._done_ts
        return None if ts is None else (ts - self.arrival) * 1e3

    def result(self, timeout=None):
        out = self.outcome(timeout)
        if out is None:
            raise ServeTimeout("no outcome within %.3fs client wait"
                               % (timeout or 0))
        kind, value, reason = out
        if kind == "result":
            return value
        if kind == "timeout":
            raise ServeTimeout(reason or "deadline exceeded")
        if kind == "reject":
            raise ServeRejected(reason or "rejected")
        raise ServeError(reason or "serving error")


class InferenceServer:
    """Continuous-batching server over per-bucket AOT executables.

    ::

        srv = serve.InferenceServer(fn, feature_shape=(64,),
                                    config=serve.ServeConfig())
        srv.start()                       # STARTING -> READY
        h = srv.submit(x, deadline_ms=50)
        y = h.result(timeout=1.0)         # or h.outcome(...)
        srv.close()                       # DRAINING -> stopped

    ``model`` is a jax-traceable callable, an :class:`AotModel`, or a
    gluon HybridBlock (functionalized via the stablehlo export path);
    :meth:`from_exported` serves per-bucket StableHLO artifacts.
    """

    def __init__(self, model, feature_shape=None, dtype="float32",
                 config=None, name="model"):
        self._cfg = config or ServeConfig()
        if isinstance(model, AotModel):
            self._model = model
        elif callable(model) and not hasattr(model, "collect_params"):
            if feature_shape is None:
                raise MXNetError("InferenceServer: feature_shape is "
                                 "required for a callable model")
            self._model = AotModel(fn=model, feature_shape=feature_shape,
                                   dtype=dtype, name=name)
        else:
            if feature_shape is None:
                raise MXNetError("InferenceServer: feature_shape is "
                                 "required for a block model")
            self._model = AotModel.from_block(
                model, feature_shape=feature_shape, dtype=dtype,
                name=name)
        self.name = self._model.name
        if self._cfg.buckets == "auto":
            # the default menu, checked against the serving HBM budget
            # (buckets.default_bucket_menu)
            from .buckets import default_bucket_menu
            self._cfg.buckets = tuple(default_bucket_menu(
                feature_shape=self._model.feature_shape,
                dtype=self._model.dtype))
            telemetry.event("serve", "bucket_menu", model=self.name,
                            buckets=list(self._cfg.buckets))
        self._lock = threading.Lock()
        self._q = queue.Queue(maxsize=self._cfg.max_queue)
        self._dq = queue.Queue(maxsize=2)
        self._stop = threading.Event()
        self._draining = threading.Event()
        self._state = STARTING
        self._started = False
        self._batcher = None
        self._watchdog = None
        self._dispatcher = None
        self._retired = []
        self._gen = 0
        self._respawns = 0
        self._dispatcher_gone = False
        self._pending_n = 0
        self._inflight = {}          # id -> {"start", "reqs", "bucket"}
        self._inflight_seq = 0
        self._quarantined = set()
        self._synthetic = []         # request_burst clones (chaos tests)
        self._compile_baseline = {}

    @classmethod
    def from_exported(cls, prefix, epoch=0, config=None, name=None):
        """Serve per-bucket StableHLO artifacts written by
        ``contrib.stablehlo.export_bucketed`` — the cross-process
        deployment path.  The config's bucket menu defaults to the
        artifact set."""
        model = AotModel.from_exported(prefix, epoch=epoch, name=name)
        cfg = config or ServeConfig(buckets=model.exported_buckets)
        return cls(model, config=cfg)

    # -- lifecycle -------------------------------------------------------
    def start(self):
        """Compile every bucket executable (STARTING), snapshot the
        compile counts (the steady-state zero-recompile baseline), flip
        READY and start the batcher/dispatcher/watchdog threads."""
        with self._lock:
            if self._started:
                return self
            self._started = True
        self._model.compile_all(self._cfg.buckets)
        baseline = telemetry.compile_counts()
        b = threading.Thread(target=self._batch_loop,
                             name="mxtpu-serve-batcher", daemon=True)
        w = threading.Thread(target=self._watchdog_loop,
                             name="mxtpu-serve-watchdog", daemon=True)
        with self._lock:
            self._compile_baseline = baseline
            self._batcher = b
            self._watchdog = w
            self._gen += 1
            gen = self._gen
        self._set_state(READY)
        self._spawn_dispatcher(gen)
        b.start()
        w.start()
        return self

    def __enter__(self):
        return self.start()

    def __exit__(self, *a):
        self.close()
        return False

    def drain(self, timeout=10.0):
        """DRAINING: new submissions reject, accepted requests complete.
        Returns True when queue + batcher + dispatch all went quiet
        within ``timeout``."""
        self._draining.set()
        self._set_state(DRAINING)
        deadline = time.monotonic() + float(timeout)
        while time.monotonic() < deadline:
            with self._lock:
                busy = self._pending_n or self._inflight
            if not busy and self._q.qsize() == 0 and self._dq.qsize() == 0:
                return True
            time.sleep(0.005)
        return False

    def close(self, timeout=10.0):
        """Drain, stop and join every thread; any request still
        unresolved after the drain window gets a terminal
        ``reject(shutdown)`` / ``timeout(shutdown)``.  Idempotent."""
        drained = True
        with self._lock:
            started = self._started
        if started:
            drained = self.drain(timeout)
        else:
            self._draining.set()
            self._set_state(DRAINING)
        self._stop.set()
        with self._lock:
            b, w, d = self._batcher, self._watchdog, self._dispatcher
            retired = list(self._retired)
        if b is not None and b.is_alive():
            b.join(timeout)
        if w is not None and w.is_alive():
            w.join(timeout)
        if d is not None and d.is_alive():
            d.join(timeout)
        for t in retired:
            if t.is_alive():
                t.join(timeout)
        self._fail_leftovers()
        return drained

    def _fail_leftovers(self):
        """Terminal outcomes for anything a hard (timed-out) close left
        behind: queued requests reject, in-flight dispatches time out.
        The no-hangs invariant must hold even when shutdown does not go
        cleanly."""
        leftovers = []
        for q in (self._dq, self._q):
            while True:
                try:
                    item = q.get_nowait()
                except queue.Empty:
                    break
                leftovers.extend(item if isinstance(item, list)
                                 else [item])
        for r in leftovers:
            if r._resolve("reject", reason="shutdown"):
                telemetry.inc("serve.rejects")
                telemetry.event("serve", "reject", reason="shutdown")
        with self._lock:
            stuck = [rec for rec in self._inflight.values()]
            self._inflight.clear()
        for rec in stuck:
            for r in rec["reqs"]:
                if r._resolve("timeout", reason="shutdown"):
                    telemetry.inc("serve.timeouts")
                    telemetry.event("serve", "timeout", stage="shutdown")

    # -- state machine ---------------------------------------------------
    def state(self):
        with self._lock:
            return self._state

    def _set_state(self, new):
        with self._lock:
            old = self._state
            if old == new or (old == DRAINING and new != DRAINING):
                return
            self._state = new
        telemetry.event("serve", "state", state_from=old, state_to=new)
        telemetry.gauge("serve.state", new)

    # -- admission (backpressure, shedding) ------------------------------
    def submit(self, x, deadline_ms=None, priority=0):
        """Submit one request; returns a :class:`PendingRequest` that
        ALWAYS reaches a terminal outcome (possibly already resolved as
        a reject when admission refuses it).  ``priority`` 0 is the
        highest; under DEGRADED/overload, ``priority > 0`` requests are
        shed at this door.  Never blocks: a full queue is an immediate
        reject."""
        storm = chaos.active("deadline_storm")
        if storm is not None and chaos.should_fire("deadline_storm"):
            deadline_ms = float(storm.get("deadline_ms") or 0.0)
        if deadline_ms is None:
            deadline_s = self._cfg.default_deadline_s
        else:
            deadline_s = float(deadline_ms) / 1e3
        arr = onp.asarray(x)
        feat = self._model.feature_shape
        req = PendingRequest(arr, time.monotonic() + deadline_s,
                             priority=priority)
        telemetry.inc("serve.requests")
        telemetry.event("serve", "request", trace=req.trace_id,
                        deadline_ms=round(deadline_s * 1e3, 3),
                        priority=priority)
        if tuple(arr.shape) != feat:
            self._reject(req, "bad_shape: %r != %r"
                         % (tuple(arr.shape), feat))
            return req
        if arr.dtype != self._model.dtype:
            req.x = arr.astype(self._model.dtype)
        self._admit(req)
        burst = chaos.active("request_burst")
        if burst is not None and chaos.should_fire("request_burst"):
            clones = []
            for _ in range(max(0, int(burst.get("factor") or 8) - 1)):
                clone = PendingRequest(req.x, req.deadline,
                                       priority=priority, synthetic=True)
                telemetry.inc("serve.requests")
                self._admit(clone)
                clones.append(clone)
            with self._lock:
                self._synthetic.extend(clones)
        return req

    def _admit(self, req):
        with self._lock:
            st = self._state
        if st == STARTING:
            self._reject(req, "not_ready")
            return req
        if st == DRAINING:
            self._reject(req, "draining")
            return req
        depth = self._q.qsize()
        overloaded = depth >= self._cfg.shed_depth
        if overloaded and st == READY:
            self._set_state(DEGRADED)
            st = DEGRADED
        if (st == DEGRADED or overloaded) and req.priority > 0:
            self._shed(req)
            return req
        try:
            self._q.put_nowait(req)
        except queue.Full:
            self._reject(req, "queue_full")
            return req
        if self._draining.is_set():
            # drain() raced us between the state check and the enqueue:
            # the batcher may already have taken its final look at the
            # queue and exited, so this request would sit unresolved
            # until close().  Resolve it as a drain reject NOW — if the
            # batcher IS still running it simply skips the resolved
            # request (_drop_expired filters done() requests), and
            # either way the no-hangs invariant holds on drain() alone.
            self._reject(req, "draining")
            return req
        telemetry.inc("serve.accepted")
        return req

    def _reject(self, req, reason):
        if req._resolve("reject", reason=reason):
            telemetry.inc("serve.rejects")
            telemetry.event("serve", "reject", reason=reason,
                            priority=req.priority)

    def _shed(self, req):
        if req._resolve("reject", reason="shed"):
            telemetry.inc("serve.sheds")
            telemetry.event("serve", "shed", priority=req.priority,
                            queue_depth=self._q.qsize())

    # -- batcher thread --------------------------------------------------
    def _drop_expired(self, reqs, stage):
        """Deadline propagation: expired requests resolve as timeouts
        HERE — before a bucket slot, a dispatch or a padded row is
        spent on them."""
        now = time.monotonic()
        live = []
        for r in reqs:
            if r.deadline <= now:
                if r._resolve("timeout",
                              reason="deadline expired in %s" % stage):
                    telemetry.inc("serve.timeouts")
                    telemetry.inc("serve.deadline_drops")
                    telemetry.event("serve", "timeout", stage=stage)
            elif not r.done():
                live.append(r)
        return live

    def _batch_loop(self):
        cfg = self._cfg
        max_bucket = cfg.buckets[-1]
        pending = []
        first = None
        while True:
            stopped = self._stop.is_set()
            if not stopped:
                if pending:
                    flush_at = min(
                        first + cfg.batch_wait_s,
                        min(r.deadline for r in pending) - cfg.margin_s)
                    wait = max(0.0, flush_at - time.monotonic())
                else:
                    wait = cfg.poll_s
                try:
                    req = self._q.get(timeout=wait)
                except queue.Empty:
                    req = None
                if req is not None:
                    if not pending:
                        first = time.monotonic()
                    pending.append(req)
            pending = self._drop_expired(pending, "queue")
            if not pending:
                first = None
            now = time.monotonic()
            flush = bool(pending) and (
                stopped or self._draining.is_set()
                or len(pending) >= max_bucket
                or now >= first + cfg.batch_wait_s
                or now >= min(r.deadline for r in pending) - cfg.margin_s)
            if flush:
                batch, pending = pending[:max_bucket], pending[max_bucket:]
                first = now if pending else None
                self._hand_to_dispatch(batch)
            with self._lock:
                self._pending_n = len(pending)
            if stopped:
                leftovers = pending
                while True:
                    try:
                        leftovers.append(self._q.get_nowait())
                    except queue.Empty:
                        break
                for r in leftovers:
                    if r._resolve("reject", reason="shutdown"):
                        telemetry.inc("serve.rejects")
                return
            if self._draining.is_set() and not pending \
                    and self._q.qsize() == 0:
                return

    def _hand_to_dispatch(self, batch):
        """Bounded handoff to the dispatch queue.  While dispatch is
        busy (maxsize 2), expired members keep getting dropped — a
        stalled executable must not let queued requests rot past their
        deadlines unresolved."""
        while batch:
            try:
                self._dq.put(batch, timeout=0.05)
                return
            except queue.Full:
                batch = self._drop_expired(batch, "queue")
                if self._stop.is_set():
                    for r in batch:
                        if r._resolve("reject", reason="shutdown"):
                            telemetry.inc("serve.rejects")
                    return

    # -- dispatch thread -------------------------------------------------
    def _spawn_dispatcher(self, gen):
        t = threading.Thread(target=self._dispatch_loop, args=(gen,),
                             name="mxtpu-serve-dispatch", daemon=True)
        with self._lock:
            self._dispatcher = t
        t.start()

    def _dispatch_loop(self, gen):
        while not self._stop.is_set():
            with self._lock:
                cur, gone = self._gen, self._dispatcher_gone
            if gen != cur or gone:
                # superseded by a watchdog respawn — or the respawn
                # budget is exhausted (this worker was written off as
                # wedged; even if it revives, the watchdog is the
                # consumer of record now, so exit instead of racing it)
                return
            try:
                batch = self._dq.get(timeout=0.05)
            except queue.Empty:
                continue
            self._run_batch(batch)

    def _run_batch(self, reqs):
        """Plan the batch onto available buckets and dispatch each
        chunk.  Also the quarantine-fallback path: _dispatch_chunk
        re-enters here after quarantining a bucket, and the re-plan
        (which now excludes it) degrades onto smaller buckets."""
        reqs = self._drop_expired(reqs, "dispatch")
        if not reqs:
            return
        with self._lock:
            quarantined = set(self._quarantined)
        plan = plan_buckets(len(reqs), self._cfg.buckets, quarantined)
        if plan is None:
            self._fail_requests(reqs, "no executable available "
                                      "(all buckets quarantined)")
            return
        i = 0
        for b in plan:
            part = reqs[i:i + b]
            i += len(part)
            if part:
                self._dispatch_chunk(part, b)

    def _register_inflight(self, part, bucket):
        with self._lock:
            self._inflight_seq += 1
            did = self._inflight_seq
            self._inflight[did] = {"start": time.monotonic(),
                                   "reqs": part, "bucket": bucket}
        return did

    def _unregister_inflight(self, did):
        """Pop the dispatch record; None means the watchdog already
        abandoned it (this worker stalled past the timeout) and its
        requests are resolved — the late result must be discarded."""
        with self._lock:
            return self._inflight.pop(did, None)

    def _dispatch_chunk(self, part, bucket):
        part = self._drop_expired(part, "dispatch")
        if not part:
            return
        attempts = 0
        while True:
            did = self._register_inflight(part, bucket)
            t0 = time.monotonic()
            try:
                chaos.maybe_stall("dispatch_stall")
                poison = chaos.active("executable_poison")
                if poison is not None and \
                        poison.get("bucket") in (None, bucket) and \
                        chaos.should_fire("executable_poison"):
                    raise chaos.ChaosError(
                        "executable_poison injected for bucket %d"
                        % bucket)
                xp = pad_batch([r.x for r in part], bucket,
                               self._model.feature_shape,
                               self._model.dtype)
                out = onp.asarray(self._model.run(bucket, xp))
            except Exception as e:       # noqa: BLE001 — fault boundary
                abandoned = self._unregister_inflight(did) is None
                attempts += 1
                telemetry.inc("serve.dispatch_errors")
                telemetry.event("serve", "dispatch_error", bucket=bucket,
                                attempt=attempts, error=repr(e),
                                traces=[r.trace_id for r in part])
                if abandoned:
                    return
                if attempts <= self._cfg.max_retries:
                    telemetry.inc("serve.retries")
                    part = self._drop_expired(part, "dispatch")
                    if not part:
                        return
                    continue
                self._quarantine(bucket, e)
                self._run_batch(part)     # re-plan minus the bucket
                return
            abandoned = self._unregister_inflight(did) is None
            if abandoned:
                return                   # watchdog resolved these already
            dispatch_s = time.monotonic() - t0
            n = 0
            for j, r in enumerate(part):
                if r._resolve("result", value=out[j]):
                    n += 1
            # per-request queue-wait phase (trace-linked) + the shared
            # execute phase: with the terminal outcome event these make
            # one request's submit -> wait -> execute -> outcome story
            for r in part:
                telemetry.span_event("serve.queue_wait",
                                     max(0.0, t0 - r.arrival),
                                     trace=r.trace_id, hist=True,
                                     bucket=bucket)
            telemetry.span_event("serve.dispatch", dispatch_s, hist=True,
                                 bucket=bucket, n=len(part),
                                 traces=[r.trace_id for r in part])
            depth = self._q.qsize()
            telemetry.inc("serve.dispatches")
            telemetry.inc("serve.results", n)
            telemetry.gauge("serve.queue_depth", depth)
            telemetry.event(
                "serve", "batch", bucket=bucket, n=len(part),
                fill_pct=round(100.0 * len(part) / bucket, 1),
                queue_depth=depth,
                wait_ms=round((t0 - min(r.arrival for r in part)) * 1e3,
                              3),
                dispatch_ms=round(dispatch_s * 1e3, 3))
            return

    def _fail_requests(self, reqs, reason):
        for r in reqs:
            if r._resolve("error", reason=reason):
                telemetry.inc("serve.errors")
        telemetry.event("serve", "error", reason=reason, n=len(reqs))

    def _quarantine(self, bucket, error):
        with self._lock:
            fresh = bucket not in self._quarantined
            self._quarantined.add(bucket)
        if fresh:
            telemetry.inc("serve.quarantines")
            telemetry.event("serve", "quarantine", bucket=bucket,
                            error=repr(error))
        self._set_state(DEGRADED)
        if fresh:
            # postmortem artifact AFTER the journal records the
            # quarantine + DEGRADED transition: the bundle's journal
            # tail holds the dispatch_error events (with the affected
            # requests' trace ids), the failing bucket and the
            # state change — the poisoned-executable story, recoverable
            # offline
            flight_recorder.dump_incident(
                "serve_quarantine",
                detail="bucket %d quarantined: %r" % (bucket, error),
                extra={"model": self.name, "bucket": bucket})

    def reset_quarantine(self):
        """Operator knob (overload runbook): re-admit quarantined
        buckets after the underlying executable/driver issue is
        resolved."""
        with self._lock:
            had = sorted(self._quarantined)
            self._quarantined.clear()
        if had:
            telemetry.event("serve", "quarantine_reset", buckets=had)
        return had

    # -- watchdog thread -------------------------------------------------
    def _watchdog_loop(self):
        cfg = self._cfg
        while not self._stop.wait(cfg.watchdog_s):
            now = time.monotonic()
            stuck = []
            with self._lock:
                for did in list(self._inflight):
                    rec = self._inflight[did]
                    if now - rec["start"] >= cfg.dispatch_timeout_s:
                        stuck.append(self._inflight.pop(did))
            for rec in stuck:
                self._on_stuck_dispatch(rec, now)
            self._drain_if_dispatcherless()
            self._maybe_recover()

    def _drain_if_dispatcherless(self):
        """Once the respawn budget is exhausted there is no consumer
        left for the dispatch queue — batches the batcher keeps handing
        over would otherwise sit there unresolved until close().  The
        watchdog becomes the consumer of record: every tick it drains
        the queue and gives the requests a terminal error — the server
        fails FAST in its permanent-DEGRADED tail (operator runbook:
        drain and restart the replica), and the no-hangs invariant
        holds without a close()."""
        with self._lock:
            gone = self._dispatcher_gone
        if not gone:
            return
        while True:
            try:
                batch = self._dq.get_nowait()
            except queue.Empty:
                return
            self._fail_requests(
                batch, "no dispatcher available "
                       "(watchdog respawn budget exhausted)")

    def _on_stuck_dispatch(self, rec, now):
        """A dispatch exceeded dispatch_timeout: resolve its requests
        (the client never hangs on a hung executable), respawn a fresh
        dispatcher (bounded) so the queue keeps draining, and degrade."""
        n = 0
        for r in rec["reqs"]:
            if r._resolve("timeout", reason="dispatch watchdog"):
                n += 1
        telemetry.inc("serve.timeouts", n)
        telemetry.inc("serve.watchdog_fires")
        with self._lock:
            can_respawn = self._respawns < self._cfg.max_respawns
            if can_respawn:
                self._respawns += 1
                self._gen += 1
                gen = self._gen
                old = self._dispatcher
                if old is not None:
                    self._retired.append(old)
            else:
                self._dispatcher_gone = True
        telemetry.event(
            "serve", "watchdog", bucket=rec["bucket"], n=n,
            age_ms=round((now - rec["start"]) * 1e3, 3),
            respawned=bool(can_respawn))
        if can_respawn:
            self._spawn_dispatcher(gen)
        self._set_state(DEGRADED)
        flight_recorder.dump_incident(
            "serve_respawn_exhausted" if not can_respawn
            else "serve_watchdog",
            detail="dispatch stuck %.1f ms on bucket %d"
                   % ((now - rec["start"]) * 1e3, rec["bucket"]),
            extra={"model": self.name, "bucket": rec["bucket"],
                   "timed_out_requests": n,
                   "traces": [r.trace_id for r in rec["reqs"]],
                   "respawned": bool(can_respawn)})

    def _maybe_recover(self):
        """DEGRADED -> READY once the queue subsides below the resume
        watermark, no bucket is quarantined, and a dispatcher exists
        (a server past its respawn budget fails fast until restarted —
        READY would be a lie)."""
        with self._lock:
            st = self._state
            quarantined = bool(self._quarantined)
            gone = self._dispatcher_gone
        if st == DEGRADED and not quarantined and not gone \
                and self._q.qsize() <= self._cfg.resume_depth:
            self._set_state(READY)

    # -- introspection ---------------------------------------------------
    def steady_state_recompiles(self):
        """``{fn: extra compiles}`` for every ``serve.*`` executable
        whose compile count moved since :meth:`start` — the
        zero-recompile hard gate's measurement.  Empty dict == healthy
        steady state."""
        with self._lock:
            baseline = dict(self._compile_baseline)
        deltas = telemetry.compile_deltas(baseline)
        return {k: v for k, v in deltas.items()
                if k.startswith("serve.%s." % self.name)}

    def stats(self):
        with self._lock:
            return {"state": self._state,
                    "queue_depth": self._q.qsize(),
                    "batcher_pending": self._pending_n,
                    "inflight": len(self._inflight),
                    "quarantined": sorted(self._quarantined),
                    "respawns": self._respawns,
                    "buckets": list(self._cfg.buckets)}
