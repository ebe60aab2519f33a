"""Device context abstraction over JAX devices.

Replaces the reference's ``python/mxnet/context.py:29`` (``Context``,
``cpu()/gpu()/cpu_pinned()``).  TPU-first: ``mx.tpu()`` is the first-class
accelerator context; ``mx.gpu()`` is kept as an alias that resolves to the
host's accelerator (so reference training scripts run unmodified on TPU).

A Context maps to a concrete ``jax.Device``.  NDArrays carry a Context;
placement is realised with ``jax.device_put``.  There is no per-device stream
or worker-thread state here — XLA + JAX async dispatch schedule the work
(reference engine equivalence documented in SURVEY.md §2.3 last row).
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context", "num_gpus", "num_tpus", "gpu_memory_info"]


class Context:
    """A device context (device_type, device_id).

    Reference: ``python/mxnet/context.py:29``.  Usable as a ``with`` scope to
    set the default context for array creation.
    """

    # Keep the reference's numeric codes, extended with tpu.
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "cpu_shared", 5: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 4, "tpu": 5}

    _default = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in self.devstr2type:
            raise ValueError("unknown device type %r" % (device_type,))
        self.device_type = device_type
        self.device_id = int(device_id)
        self._old_ctx: Optional[Context] = None

    # -- identity ---------------------------------------------------------
    @property
    def device_typeid(self) -> int:
        return self.devstr2type[self.device_type]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    # -- jax mapping ------------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        """Resolve to a concrete jax.Device.

        ``tpu``/``gpu`` both resolve to the default (accelerator) backend so
        reference scripts written against ``mx.gpu()`` run on TPU unchanged.
        ``cpu``/``cpu_pinned`` resolve to host CPU devices.
        """
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = _cpu_devices()
        else:
            devs = _accel_devices()
        if self.device_id >= len(devs):
            # Mirror the reference's lenient behaviour: out-of-range ids only
            # fail at first use; here we fail fast with a clear message.
            raise RuntimeError(
                "context %s out of range: only %d %s device(s) present"
                % (self, len(devs), self.device_type)
            )
        return devs[self.device_id]

    # -- scoping ----------------------------------------------------------
    def __enter__(self):
        self._old_ctx = getattr(Context._default, "value", None)
        Context._default.value = self
        return self

    def __exit__(self, *args):
        Context._default.value = self._old_ctx
        return False

    def empty_cache(self):
        """Parity with ``Context.empty_cache`` (reference context.py): no-op —
        XLA owns the device allocator."""


def _cpu_devices():
    # local (addressable) devices only: in a multi-process job
    # jax.devices() is the GLOBAL list and placing onto another process's
    # device is an error
    return jax.local_devices(backend="cpu")


def _pick_accel(devs, platforms):
    """The accelerator devices among ``devs``, or raise.

    The one exception is a process that was TOLD to use the CPU
    (``platforms``, i.e. ``jax.config.jax_platforms``, is ``"cpu"`` — what
    ``JAX_PLATFORMS=cpu`` sets for the test suite): there the CPU is what
    was asked for and ``mx.tpu()`` names it.  Anywhere else a missing
    accelerator is an error, never a silent run on the host."""
    accel = [d for d in devs if d.platform != "cpu"]
    if accel:
        return accel
    if platforms == "cpu":
        return list(devs)
    raise RuntimeError(
        "no accelerator device: jax sees only %r and the process was not "
        "pinned to the CPU (JAX_PLATFORMS=cpu)" % (list(devs),))


def _accel_devices():
    return _pick_accel(jax.local_devices(), jax.config.jax_platforms)


def on_tpu(*arrays) -> bool:
    """True when the computation runs on a TPU — THE platform probe for
    kernel dispatch (Pallas vs composed XLA).

    An eager op runs where its operands are committed, and a TPU machine
    has a host CPU backend too: a model that has not been moved yet
    (``current_context()`` defaults to ``cpu(0)``) computes there, where a
    compiled Pallas kernel cannot go.  So concrete committed ``arrays``
    answer for themselves; tracers — and no arrays — answer with the
    default backend.  A backend that fails to initialise raises out of
    here: it must not turn into a quiet dense fallback."""
    for a in arrays:
        if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer) \
                and a.committed:
            return all(d.platform == "tpu" for d in a.devices())
    return jax.devices()[0].platform == "tpu"


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def gpu(device_id: int = 0) -> Context:
    """Accelerator context. On TPU hosts this is the TPU chip (alias of tpu())."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def num_gpus() -> int:
    """Number of accelerator devices visible (reference: context.num_gpus)."""
    devs = [d for d in jax.local_devices() if d.platform != "cpu"]
    return len(devs)


def num_tpus() -> int:
    return num_gpus()


def gpu_memory_info(device_id: int = 0):
    """(free, total) bytes for the accelerator, when the backend reports it."""
    dev = gpu(device_id).jax_device
    stats = getattr(dev, "memory_stats", lambda: None)()
    if not stats:
        return (0, 0)
    total = stats.get("bytes_limit", 0)
    used = stats.get("bytes_in_use", 0)
    return (total - used, total)


def current_context() -> Context:
    """The default context (innermost ``with Context`` scope, else cpu(0)...

    TPU-first default: if an accelerator is present we still default to cpu to
    match the reference's semantics (mx.cpu() is the default); users opt in
    with ``with mx.tpu():`` or explicit ctx arguments.
    """
    ctx = getattr(Context._default, "value", None)
    return ctx if ctx is not None else Context("cpu", 0)
