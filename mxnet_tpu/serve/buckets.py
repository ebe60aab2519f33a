"""Bucketed-shape policy + AOT-compiled executables for the serving stack.

TPU inference latency is predictable exactly when the served program
never recompiles (arxiv 2605.25645): XLA specializes on shapes, so a
server that pads every dynamic batch onto a small fixed menu of batch
*buckets* and AOT-compiles one executable per bucket does all of its
compilation at startup and ZERO at steady state.  This module owns that
discipline:

* :func:`pick_bucket` / :func:`plan_buckets` — the shape policy: a
  request batch of ``n`` runs on the smallest available bucket ``>= n``;
  when that bucket is quarantined (a poisoned executable,
  ``server.InferenceServer``) the batch *degrades* onto a cover of
  smaller buckets instead of failing;
* :func:`pad_batch` — zero-pads ``n`` feature rows up to the bucket
  extent (results are sliced back to ``n`` after dispatch);
* :class:`AotModel` — the executable registry: per bucket,
  ``jax.jit(fn).lower(spec).compile()`` at :meth:`compile_all` time.
  Every compile reports to the telemetry recompile detector under a
  per-bucket key (``serve.<name>.b<N>``), so a steady-state recompile
  is *observable* — ``telemetry.compile_deltas`` over a post-start
  snapshot is the hard gate (``steady_state_recompiles()``).
  A compiled executable REFUSES a wrong shape (raises, never retraces),
  so the zero-recompile property cannot silently erode.

Model sources: a plain jax-traceable callable, a gluon HybridBlock
(functionalized through the ``contrib.stablehlo`` export path), or
per-bucket StableHLO artifacts on disk
(``contrib.stablehlo.export_bucketed`` / ``load_bucketed``) — the
deployment story where the exporter and the server are different
processes.
"""
from __future__ import annotations

import os
import time
from typing import Optional, Sequence

import numpy as onp

from .. import telemetry
from ..base import MXNetError

__all__ = ["pick_bucket", "plan_buckets", "pad_batch", "AotModel",
           "default_bucket_menu", "validate_menu"]

# per-process de-dup of model display names: two AotModel instances
# sharing a name would share recompile-detector keys, so the second
# server's startup compiles would read as retraces of the first
_NAME_SEQ = {}


def _unique_name(name):
    seq = _NAME_SEQ.get(name, 0) + 1
    _NAME_SEQ[name] = seq
    return name if seq == 1 else "%s#%d" % (name, seq)


def default_bucket_menu(max_batch: int = 8, feature_shape=(),
                        dtype="float32", budget=None):
    """The bucket menu for a served max batch of ``max_batch``: the top
    four powers of two up to ``max_batch`` rounded up to one (8 ->
    ``[1, 2, 4, 8]``, 32 -> ``[4, 8, 16, 32]``), checked against the
    serving HBM budget (:func:`validate_menu`) BEFORE any executable is
    compiled — an over-budget menu sheds its largest buckets here, not
    at compile time."""
    mb = 1 << max(0, (int(max_batch) - 1).bit_length())
    menu = [mb >> i for i in range(min(4, mb.bit_length()))]
    return validate_menu(menu, feature_shape, dtype, budget=budget)


def validate_menu(menu: Sequence[int], feature_shape: Sequence[int],
                  dtype="float32", budget: Optional[int] = None) -> list:
    """Drop menu buckets whose padded batch I/O cannot fit the serving
    HBM budget, using the static estimator's arithmetic
    (``tools.lint.hbm.dtype_itemsize``): each bucket's executable
    holds its input and output batch resident, and every bucket's
    buffers coexist at startup (compile_all touches them all).  Budget:
    ``MXNET_SERVE_HBM_BUDGET`` bytes, default 2 GiB — deliberately a
    fraction of a chip, since the model's own weights are not ours to
    spend.  Largest buckets are dropped first; the menu never empties
    below its smallest bucket."""
    try:
        from tools.lint.hbm import dtype_itemsize
        item = dtype_itemsize(dtype)
    except Exception:
        item = onp.dtype(dtype).itemsize
    if budget is None:
        try:
            budget = int(os.environ.get("MXNET_SERVE_HBM_BUDGET",
                                        2 * 1024 ** 3))
        except ValueError:
            budget = 2 * 1024 ** 3
    feat = 1
    for d in feature_shape:
        feat *= int(d)
    menu = sorted(set(int(b) for b in menu if int(b) >= 1))

    def total(m):
        return sum(2 * b * feat * item for b in m)   # in + out per bucket

    while len(menu) > 1 and total(menu) > budget:
        menu.pop()          # largest first
    return menu


def pick_bucket(n: int, buckets: Sequence[int],
                quarantined: Sequence[int] = ()) -> Optional[int]:
    """Smallest available (non-quarantined) bucket ``>= n``; None when
    every covering bucket is quarantined (or ``n`` exceeds the menu)."""
    for b in sorted(buckets):
        if b >= n and b not in quarantined:
            return b
    return None


def plan_buckets(n: int, buckets: Sequence[int],
                 quarantined: Sequence[int] = ()) -> Optional[list]:
    """Bucket cover for ``n`` requests: ``[smallest covering bucket]``
    in the healthy case, a largest-available-first split when the
    covering buckets are quarantined (graceful degradation: a poisoned
    b=8 executable turns one 6-request batch into a [4, 2] dispatch
    pair).  None when no bucket is available at all."""
    avail = sorted(b for b in set(buckets) if b not in set(quarantined))
    if not avail or n <= 0:
        return None if not avail else []
    plan = []
    left = n
    while left > 0:
        b = pick_bucket(left, avail)
        if b is not None:
            plan.append(b)
            break
        plan.append(avail[-1])
        left -= avail[-1]
    return plan


def pad_batch(rows: Sequence[onp.ndarray], bucket: int,
              feature_shape: tuple, dtype) -> onp.ndarray:
    """Zero-padded ``(bucket,) + feature_shape`` batch from ``rows``
    (``len(rows) <= bucket``).  Padding rows are zeros — the executable
    computes them and the dispatcher slices them off; wasted FLOPs are
    the price of a fixed shape menu (journaled as ``fill_pct``)."""
    if len(rows) > bucket:
        raise MXNetError("pad_batch: %d rows exceed bucket %d"
                         % (len(rows), bucket))
    out = onp.zeros((bucket,) + tuple(feature_shape), dtype)
    for i, r in enumerate(rows):
        out[i] = r
    return out


def _aot_compile(fn, *specs):
    """The whole AOT pipeline for one bucket: jit -> lower at the
    bucket avals -> compile.  One callable, one compile, and the
    returned executable never traces again — which is why constructing
    the jit wrapper here (once per bucket, outside any loop) is not a
    retrace hazard: the wrapper's own cache is never exercised."""
    import jax

    return jax.jit(fn).lower(*specs).compile()


class AotModel:
    """Per-bucket AOT-compiled executables of one model function.

    ``fn(x: [B, *feature_shape] array) -> array`` must be
    jax-traceable.  With ``params`` (a list of arrays) the signature is
    ``fn(params, x)`` and the parameters are ARGUMENTS of every bucket
    executable: one copy on the device, shared by the whole menu.
    Closed over instead they are baked into each executable as
    constants — for ResNet-50 a 173 MB compile-cache entry and a copy of
    the weights in HBM per bucket.  After :meth:`compile_all`,
    :meth:`run` dispatches a padded bucket batch with no tracing on the
    path — a shape outside the compiled menu raises immediately.
    """

    def __init__(self, fn=None, feature_shape=(), dtype="float32",
                 name="model", fn_for_bucket=None, params=None):
        if fn is None and fn_for_bucket is None:
            raise MXNetError("AotModel needs fn or fn_for_bucket")
        self._fn = fn
        self._fn_for_bucket = fn_for_bucket
        self._params = params
        self.feature_shape = tuple(int(d) for d in feature_shape)
        self.dtype = onp.dtype(dtype)
        self.name = _unique_name(str(name))
        self._compiled = {}

    # -- constructors ----------------------------------------------------
    @classmethod
    def from_block(cls, net, feature_shape, dtype="float32",
                   name="model"):
        """Serve a gluon HybridBlock in-process: the eval-mode forward
        is functionalized exactly as ``contrib.stablehlo.export_block``
        traces it (training=False; the parameter values of this moment
        are what every later request sees)."""
        from ..contrib.stablehlo import _functional_eval_forward
        fn, params = _functional_eval_forward(net)
        if not params:
            raise MXNetError("AotModel.from_block: net has no "
                             "initialized parameters")
        return cls(fn=fn, feature_shape=feature_shape, dtype=dtype,
                   name=name, params=[p._data._data for p in params])

    @classmethod
    def from_exported(cls, prefix, epoch=0, name=None):
        """Serve per-bucket StableHLO artifacts from disk
        (``contrib.stablehlo.export_bucketed``).  The bucket menu IS
        the artifact set — :meth:`compile_all` may only be called with
        buckets the exporter shipped."""
        from ..contrib.stablehlo import load_bucketed
        arts = load_bucketed(prefix, epoch=epoch)
        feat = None
        makers = {}
        for b, (exported, pvals) in sorted(arts.items()):
            aval = exported.in_avals[-1]
            if feat is None:
                feat, dt = tuple(aval.shape[1:]), aval.dtype
            makers[b] = (lambda ex, pv: lambda x: ex.call(pv, x))(
                exported, pvals)
        model = cls(fn_for_bucket=lambda b: makers[b],
                    feature_shape=feat, dtype=dt,
                    name=name or prefix.rsplit("/", 1)[-1])
        model.exported_buckets = sorted(makers)
        return model

    # -- compile ---------------------------------------------------------
    def compile_all(self, buckets: Sequence[int]):
        """AOT-compile one executable per bucket (idempotent per
        bucket).  Each compile is reported to the telemetry recompile
        detector under ``serve.<name>.b<bucket>`` — at steady state
        these counts must never move again."""
        import jax

        for b in sorted(set(int(b) for b in buckets)):
            if b in self._compiled:
                continue
            exported = getattr(self, "exported_buckets", None)
            if exported is not None and b not in exported:
                raise MXNetError(
                    "AotModel %r: bucket %d has no exported artifact "
                    "(menu: %r)" % (self.name, b, exported))
            spec = jax.ShapeDtypeStruct((b,) + self.feature_shape,
                                        self.dtype)
            t0 = time.perf_counter()
            fn = self._fn if self._fn is not None \
                else self._fn_for_bucket(b)
            if self._params is None:
                self._compiled[b] = _aot_compile(fn, spec)
            else:
                self._compiled[b] = _aot_compile(
                    fn, [jax.ShapeDtypeStruct(v.shape, v.dtype,
                                              sharding=v.sharding)
                         for v in self._params], spec)
            dur_ms = round((time.perf_counter() - t0) * 1e3, 3)
            telemetry.record_compile(
                "serve.%s.b%d" % (self.name, b),
                {"bucket": b, "shape": [b] + list(self.feature_shape),
                 "dtype": str(self.dtype)})
            telemetry.event("serve", "compile", bucket=b, dur_ms=dur_ms,
                            model=self.name)
        return self

    @property
    def buckets(self):
        return sorted(self._compiled)

    def run(self, bucket: int, x):
        """Dispatch one padded bucket batch through the AOT executable.
        No tracing happens here; a bucket outside the compiled menu is
        an error, never a recompile."""
        compiled = self._compiled.get(int(bucket))
        if compiled is None:
            raise MXNetError("AotModel %r: bucket %d was never compiled"
                             % (self.name, bucket))
        if self._params is None:
            return compiled(x)
        return compiled(self._params, x)
