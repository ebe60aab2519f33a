"""Depth-K asynchronous host->device prefetch over any DataIter.

TPU-native counterpart of the reference's ``PrefetchingIter`` +
per-GPU ``_load_data`` scatter (``python/mxnet/io/io.py`` PrefetchingIter,
``executor_group.py:451``), rebuilt as a real pipeline stage: a background
feeder thread pulls host batches and issues ``jax.device_put`` up to
``depth`` batches ahead into a bounded queue — the device-side ring.  By
the time the consumer asks for batch N, its transfer (and, in uint8 wire
mode, its on-device normalize) was dispatched while batches N-1..N-depth
were being computed, so the host->device leg overlaps BOTH host decode and
device compute instead of running between them.

Wire formats:

* ``uint8`` (``ImageRecordIter(u8_output=True)``): raw pixels move 4x
  fewer bytes than normalized float32 and ``(x - mean) / std`` runs
  on-device in ONE jitted kernel built at construction — never
  re-traced per batch, fused by XLA into the consumer when possible.
  The right split for any bandwidth-constrained host->device link.
* ``float32``: the host-normalized batch ships as-is and is cast to
  ``dtype`` on-device (also a single pre-built jit).

Placement composes with SPMD training: pass ``mesh=`` (or an explicit
``sharding=``) and every batch is laid out as ``NamedSharding(mesh,
P(axis, None, ...))`` — per-replica shards land directly on their target
devices, so ``DataParallelStep`` sees pre-placed operands and skips its
own scatter.

Host buffers are staged through a small ring of reusable arrays (sized
``depth + 2``) on accelerator backends, and the native iterator's
``next_borrow`` zero-copy path is used when available — decode slots go
straight to the staging copy with no intermediate allocation.
"""
from __future__ import annotations

import queue
import threading
import time
import weakref

import numpy as onp

from .. import telemetry
from .io import DataBatch, DataDesc, DataIter

__all__ = ["DevicePrefetchIter"]

_BATCH, _END, _ERR = 0, 1, 2


class DevicePrefetchIter(DataIter):
    """Wrap ``base`` so batches arrive device-resident, ``depth`` ahead.

    Parameters
    ----------
    base : DataIter
        Source of host batches.  Iterators exposing ``next_host`` /
        ``next_borrow`` (the native ``ImageRecordIter``) feed raw numpy
        straight through; anything else is unwrapped from its DataBatch.
    dtype : str, default "bfloat16"
        On-device data dtype (labels stay float32).
    mean, std : array-like, optional
        Per-channel normalize constants for uint8 wire batches, defaulted
        from the base iterator's attributes.
    device : jax.Device, optional
        Single-device placement target (default ``jax.devices()[0]``).
    depth : int, default 2
        Number of batches kept in flight ahead of the consumer.
    mesh : jax.sharding.Mesh, optional
        Place every batch sharded over ``axis`` of this mesh instead of
        on one device (per-replica shards go straight to their devices).
    axis : str, default "dp"
        Mesh axis the leading (batch) dimension is sharded over.
    sharding : jax.sharding.Sharding, optional
        Explicit placement for the DATA array (overrides device/mesh);
        labels use the analogous leading-axis sharding.
    """

    def __init__(self, base, dtype="bfloat16", mean=None, std=None,
                 device=None, depth=2, mesh=None, axis="dp", sharding=None):
        super().__init__(getattr(base, "batch_size", 0))
        import jax

        self._base = base
        self._dtype = dtype
        self._depth = max(1, int(depth))
        self._device = device or jax.devices()[0]
        self._mesh = mesh
        self._axis = axis
        self._sharding = sharding
        mean = mean if mean is not None else getattr(base, "mean", None)
        std = std if std is not None else getattr(base, "std", None)
        self._mean = None if mean is None else onp.asarray(mean, "float32")
        self._std = None if std is None else onp.asarray(std, "float32")
        self._norm_fn = self._build_norm()
        self._cast_fn = None
        # host staging ring (reused on accelerator backends; the CPU
        # backend may alias numpy memory into jax arrays, so there every
        # stage is a fresh copy).  Each slot carries the device arrays
        # its last transfer produced: reuse blocks on them first, so a
        # buffer is never rewritten under an in-flight device_put.
        self._ring = [None] * (self._depth + 2)
        self._ring_guard = [None] * (self._depth + 2)
        self._ring_i = 0
        self._stage_idx = None
        self._reuse_host = self._device.platform != "cpu"
        self._q = None
        self._stop = threading.Event()
        # serializes feeder lifecycle transitions.  Reentrant, held
        # across the WHOLE stop->start pair in reset()/close(): two
        # racing resets interleaving as stop,stop,start,start would
        # otherwise orphan a live feeder on the shared ring.  Feeder
        # and consumers never take it on the hot path, so holding it
        # over the (drain-bounded) join cannot deadlock them.
        self._lifecycle = threading.RLock()
        self._thread = None
        self._exhausted = False
        # GC safety net: a dropped iterator must not leave a feeder
        # thread blocked on the queue.  The holder (not ``self`` — the
        # finalizer must hold no strong reference to it) names the live
        # thread; the feeder itself only touches ``self`` through a
        # weakref between blocking points, so GC of the iterator fires
        # this and the thread unwinds.
        self._holder = {"thread": None}
        self._finalizer = weakref.finalize(
            self, DevicePrefetchIter._shutdown_thread,
            self._stop, self._holder)
        self._start_feeder()

    # ------------------------------------------------------------------
    # construction-time jits (one trace each, donated input buffers)
    # ------------------------------------------------------------------
    def _build_norm(self):
        import jax
        import jax.numpy as jnp

        mean = jnp.zeros((3,), jnp.float32) if self._mean is None \
            else jnp.asarray(self._mean)
        std = jnp.ones((3,), jnp.float32) if self._std is None \
            else jnp.asarray(self._std)
        dt = jnp.dtype(self._dtype)

        def norm(x):
            xf = x.astype(jnp.float32)
            y = (xf - mean.reshape(1, -1, 1, 1)) / std.reshape(1, -1, 1, 1)
            # graftlint: disable-next=retrace-closure-array -- mean/std/
            # dtype are fixed per iterator; norm is jitted exactly once
            return y.astype(dt)

        # no donate: the u8 input and the widened output differ in byte
        # size, so XLA could never reuse the buffer anyway
        return jax.jit(norm)

    def _cast(self, dev):
        import jax
        import jax.numpy as jnp
        if str(dev.dtype) == str(jnp.dtype(self._dtype)):
            return dev
        if self._cast_fn is None:
            dt = jnp.dtype(self._dtype)
            self._cast_fn = jax.jit(lambda x: x.astype(dt))
        return self._cast_fn(dev)

    # ------------------------------------------------------------------
    # placement
    # ------------------------------------------------------------------
    def _target(self, ndim):
        """Placement for an ndim-dimensional batch array."""
        if self._sharding is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            s = self._sharding
            if isinstance(s, NamedSharding) and len(s.spec) != ndim:
                # rank-adapt for labels / non-4D batches: keep the
                # leading (batch) axis placement, replicate the rest
                lead = s.spec[0] if len(s.spec) else None
                return NamedSharding(
                    s.mesh, PartitionSpec(lead, *([None] * (ndim - 1))))
            return s
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            spec = PartitionSpec(self._axis, *([None] * (ndim - 1)))
            return NamedSharding(self._mesh, spec)
        return self._device

    # ------------------------------------------------------------------
    # host side
    # ------------------------------------------------------------------
    def _stage(self, view):
        """A stable host copy of ``view`` the transfer can own: through
        the reusable ring off-CPU, a fresh array on the CPU backend."""
        if not self._reuse_host:
            self._stage_idx = None
            return onp.array(view)
        i = self._ring_i
        guard = self._ring_guard[i]
        if guard is not None:
            # by the time the ring wraps (depth+2 batches later) this
            # transfer is long done — the block is a cheap no-op guard
            for a in guard:
                try:
                    a.block_until_ready()
                except RuntimeError:
                    # a donating consumer (DataParallelStep with
                    # donate_batch=True) already consumed-and-freed the
                    # array — the transfer it derived from is necessarily
                    # complete, so the slot is safe to rewrite
                    pass
            self._ring_guard[i] = None
        buf = self._ring[i]
        if buf is None or buf.shape != view.shape or buf.dtype != view.dtype:
            buf = onp.empty_like(view)
            self._ring[i] = buf
        self._ring_i = (i + 1) % len(self._ring)
        self._stage_idx = i
        onp.copyto(buf, view)
        return buf

    def _next_host(self):
        """(data_np, label_np, pad) with the fewest copies: borrow the
        native decode slot when the base supports it (zero-copy loan,
        staged + released here), else ``next_host`` raw numpy, else
        unwrap a DataBatch."""
        nb = getattr(self._base, "next_borrow", None)
        if nb is not None:
            data_v, lab_v, pad, release = nb()
            try:
                data = self._stage(data_v)
                lab = onp.array(lab_v)
            finally:
                release()
            return data, lab, pad
        nh = getattr(self._base, "next_host", None)
        if nh is not None:
            return nh()
        batch = self._base.next()
        host = batch.data[0]
        lab = batch.label[0]
        return (host.asnumpy() if hasattr(host, "asnumpy")
                else onp.asarray(host),
                lab.asnumpy() if hasattr(lab, "asnumpy")
                else onp.asarray(lab),
                batch.pad)

    # ------------------------------------------------------------------
    # feeder thread
    # ------------------------------------------------------------------
    def _ship(self, host_np, lab_np, pad):
        """Dispatch one batch's async host->device transfer and (u8
        wire) its on-device normalize; runs ON THE FEEDER THREAD so the
        per-batch dispatch latency is hidden behind the consumer."""
        import jax

        lab_np = onp.asarray(lab_np)
        dev, dev_lab = jax.device_put(
            (host_np, lab_np),
            (self._target(host_np.ndim), self._target(lab_np.ndim)))
        if host_np.dtype == onp.uint8:
            dev = self._norm_fn(dev)
        else:
            dev = self._cast(dev)
        if self._stage_idx is not None:
            # dev derives from the staged buffer's transfer: readiness of
            # dev implies the ring slot is safe to rewrite (see _stage)
            self._ring_guard[self._stage_idx] = (dev, dev_lab)
            self._stage_idx = None
        return dev, dev_lab, pad

    @staticmethod
    def _feed(wref, q, stop):
        """Feeder loop.  Holds the iterator only through ``wref`` and
        drops it before every blocking queue put, so an abandoned
        (garbage-collected) iterator's finalizer can fire and stop the
        thread instead of leaking it."""
        def put(item):
            while True:
                try:
                    q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    if stop.is_set():
                        return False

        while not stop.is_set():
            it = wref()
            if it is None:
                return
            try:
                t0 = time.perf_counter()
                host = it._next_host()
                host_s = time.perf_counter() - t0
            except StopIteration:
                it = None
                put((_END, None))
                return
            except Exception as e:          # pragma: no cover - passthrough
                it = None
                put((_ERR, e))
                return
            if stop.is_set():               # drop the in-flight batch
                return
            try:
                t0 = time.perf_counter()
                shipped = it._ship(*host)
                ship_s = time.perf_counter() - t0
                # per-stage rate gauges: host decode (rec -> staged
                # numpy) and ship (device_put dispatch + on-device
                # normalize dispatch) img/s for the LAST batch — the
                # numbers the bench sweep derives, now live at runtime
                n = host[0].shape[0]
                telemetry.observe("prefetch.host", host_s, hist=True)
                telemetry.observe("prefetch.ship", ship_s, hist=True)
                if host_s > 0:
                    telemetry.gauge("prefetch.host_rate_img_s",
                                    round(n / host_s, 1))
                if ship_s > 0:
                    telemetry.gauge("prefetch.ship_rate_img_s",
                                    round(n / ship_s, 1))
            except Exception as e:
                it = None
                put((_ERR, e))
                return
            it = None
            if not put((_BATCH, shipped)):
                return

    def _start_feeder(self):
        with self._lifecycle:
            self._q = queue.Queue(maxsize=self._depth)
            self._stop.clear()
            self._exhausted = False
            self._thread = threading.Thread(
                target=DevicePrefetchIter._feed,
                args=(weakref.ref(self), self._q, self._stop),
                name="DevicePrefetchIter-feeder", daemon=True)
            self._holder["thread"] = self._thread
            self._thread.start()

    @staticmethod
    def _shutdown_thread(stop, holder):
        stop.set()
        t = holder.get("thread")
        if t is not None and t.is_alive():
            t.join(timeout=5.0)

    def _stop_feeder(self):
        # the join stays INSIDE the transition lock: reset() must not
        # be able to start a successor feeder while the old one is
        # still unwinding (a second concurrent stop sees None and
        # skips)
        with self._lifecycle:
            self._stop.set()
            t, q = self._thread, self._q
            self._thread = None
            self._holder["thread"] = None
            self._q = None
            if t is not None and t is not threading.current_thread():
                while t.is_alive():
                    try:                    # unblock a feeder stuck in put
                        q.get_nowait()
                    except queue.Empty:
                        pass
                    # graftlint: disable-next=conc-blocking-under-lock --
                    # the transition mutex must span stop->join->restart
                    # (interleaved stop,stop,start,start would orphan a
                    # feeder); feeder and consumer hot paths never take
                    # it, and the drain above bounds the join to one
                    # in-flight decode
                    t.join(timeout=0.05)
            if q is not None:
                # wake any consumer still blocked in next()'s q.get() —
                # the feeder is dead and will never put again; consumers
                # chain the sentinel onward (see next()) so every
                # waiter unblocks.  The sentinel MUST land: a full queue
                # can still have blocked consumers racing for its items
                # (feeder's final put vs the drain), so on Full we
                # discard a stale item and retry — only consumers pop
                # concurrently, which helps, so this terminates
                while True:
                    try:
                        q.put_nowait((_END, None))
                        break
                    except queue.Full:
                        try:
                            q.get_nowait()
                        except queue.Empty:
                            pass

    # ------------------------------------------------------------------
    # DataIter surface
    # ------------------------------------------------------------------
    @property
    def provide_data(self):
        # report the POST-normalize dtype: that is what the consumer sees
        # (bfloat16 resolves through ml_dtypes when jax registered it
        # with numpy; otherwise float32 is the closest host-side truth)
        try:
            dt = onp.dtype(self._dtype)
        except TypeError:
            dt = onp.dtype("float32")
        descs = self._base.provide_data
        return [DataDesc(d.name, d.shape, dtype=dt) if i == 0 else d
                for i, d in enumerate(descs)]

    @property
    def provide_label(self):
        return self._base.provide_label

    def reset(self):
        # one atomic stop->start transition: a racing reset()/close()
        # serializes behind the whole pair instead of interleaving
        with self._lifecycle:
            self._stop_feeder()
            self._base.reset()
            self._start_feeder()

    def next(self):
        # snapshot the queue ONCE: a concurrent close()/reset() nulls
        # self._q, and re-reading it after the liveness check would turn
        # that race into an AttributeError (or a get() on a fresh
        # post-reset queue)
        q = self._q
        if self._exhausted or q is None:
            raise StopIteration
        # ring occupancy BEFORE the blocking get: 0 here means the
        # consumer is about to stall on the pipeline (the "stalled
        # prefetch ring" signature); depth alongside so occupancy reads
        # as a fraction
        telemetry.gauge("prefetch.ring_occupancy", q.qsize())
        telemetry.gauge("prefetch.ring_depth", self._depth)
        t0 = time.perf_counter()
        kind, payload = q.get()
        telemetry.observe("prefetch.consumer_wait",
                          time.perf_counter() - t0, hist=True)
        if kind == _BATCH:
            telemetry.inc("prefetch.batches")
        if kind in (_END, _ERR):
            # a sentinel from a SUPERSEDED queue (this consumer lost a
            # race against reset()) ends only this call — it must not
            # mark the freshly-started epoch exhausted.  Check-and-set
            # under the transition lock: an unlocked check could pass
            # just before reset() swaps the queue and then poison the
            # new epoch
            with self._lifecycle:
                if q is self._q:
                    self._exhausted = True
            # chain a sentinel to the next blocked consumer (N threads
            # may wait on one ring; the feeder/stop/error paths put
            # only ONE); a full queue means nobody is blocked.  Errors
            # chain _END: one consumer surfaces the exception, the
            # rest see a clean end-of-stream
            try:
                q.put_nowait((_END, None))
            except queue.Full:
                pass
            if kind == _ERR:
                raise payload
            raise StopIteration
        from ..ndarray.ndarray import _wrap
        dev, dev_lab, pad = payload
        return DataBatch([_wrap(dev)], [_wrap(dev_lab)], pad=pad)

    def close(self):
        with self._lifecycle:
            self._stop_feeder()
            self._finalizer.detach()
            close = getattr(self._base, "close", None)
            if close:
                close()

    def __del__(self):
        try:
            self._stop_feeder()
        except Exception:                   # pragma: no cover
            pass
