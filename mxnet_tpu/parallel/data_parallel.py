"""Fused SPMD train step: forward + loss + backward + all-reduce + update.

The TPU answer to the reference's whole per-batch machinery —
``DataParallelExecutorGroup`` scatter (executor_group.py:282-311,451),
GraphExecutor Forward/Backward (graph_executor.cc:78,91), kvstore
push/pull (model.py:150-160), and the optimizer engine ops — compiled into
ONE XLA program:

* the batch is sharded over the mesh's ``dp`` axis (shard_batch);
* parameters are replicated (or sharded for ZeRO-style layouts);
* the loss mean over the *global* batch makes GSPMD insert the gradient
  all-reduce (psum) on ICI — communication is scheduled/overlapped by XLA,
  which the reference approximates with engine priority hints
  (model.py:146);
* the optimizer update is the optimizer's pure ``make_step`` traced into
  the same program, with buffers donated so updates are in-place.

The reference needs ~4 subsystems and 2 process boundaries for this; the
mesh + jit formulation is the entire implementation.
"""
from __future__ import annotations

from typing import Optional

import numpy as onp

import jax
import jax.numpy as jnp

from .. import autograd
from .. import random as _random
from .. import telemetry
from ..optimizer import optimizer as _opt
from ..ndarray import NDArray
from ..ndarray.ndarray import _wrap
from .mesh import batch_sharded_over, get_mesh

__all__ = ["DataParallelStep"]


def _resolve_mirror(mirror):
    """Normalise the backward-mirror knob.

    TPU-native equivalent of the reference's gradient-mirroring pass
    (``MXNET_BACKWARD_DO_MIRROR``, graph_executor.cc:351-374 /
    docs/faq/env_var.md:181-186): instead of marking node outputs for
    recompute in a graph pass, the whole forward is wrapped in
    ``jax.checkpoint`` with a save-policy.  ``"mirror"`` (env value 1)
    keeps MXU outputs (conv results, matmul dots, BN stats — tagged via
    ``checkpoint_name``) and recomputes the cheap elementwise chain
    (BN apply / ReLU / residual adds) in the backward, trading idle MXU
    FLOPs for HBM activation traffic.  ``"full"`` (env value 2) saves
    nothing but the step inputs — maximum memory saving.
    """
    from_env = mirror is None
    if from_env:
        import os
        mirror = os.environ.get("MXNET_BACKWARD_DO_MIRROR", "")
    if mirror in (False, None, "", "0", 0):
        return None
    if mirror in (True, 1, "1", "mirror"):
        return "mirror"
    if mirror in (2, "2", "full"):
        return "full"
    if from_env:
        # env-var typos degrade to off (matching the reference's lenient
        # boolean env parsing) — only the explicit mirror= arg hard-fails
        import warnings
        warnings.warn("ignoring unrecognized MXNET_BACKWARD_DO_MIRROR=%r "
                      "(expected 0/1/2)" % (mirror,))
        return None
    raise ValueError("mirror must be one of None/'mirror'/'full', got %r"
                     % (mirror,))


def _mirror_wrap(fn, mode):
    """Wrap ``fn`` in jax.checkpoint per the mirror mode (None = no-op)."""
    if not mode:
        return fn
    if mode == "full":
        return jax.checkpoint(fn)
    from jax import checkpoint_policies as _cp
    policy = _cp.save_from_both_policies(
        _cp.save_only_these_names("conv_out", "bn_stats"),
        _cp.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn, policy=policy)


def _grad_follows(param, sharding):
    """Re-place a Parameter's eager grad buffer where the step has just
    re-placed its data.  The buffer lives where the data lives —
    ``reset_ctx`` and ``cast`` keep it so — and the step, which never
    reads it, must not break that: left behind, a weight-sized array of
    zeros per parameter stays on the first chip while everything else
    spreads over the mesh (seen as uneven HBM on four chips), or on the
    devices of a mesh that elastic recovery has just abandoned.  A buffer
    nobody has read yet holds nothing to move (``gluon.parameter``)."""
    grad = param._grad
    if grad is not None:
        grad._data = jax.device_put(grad._data, sharding)


class DataParallelStep:
    """Compile a Gluon block + loss + optimizer into one jitted train step.

    Usage::

        step = DataParallelStep(net, loss_fn, optimizer, mesh=mesh)
        for data, label in batches:
            loss = step(data, label)      # params updated in place

    The net must be initialized (run one eager forward first if it uses
    deferred shapes).

    ``shard_optimizer=True|False|"auto"`` enables the ZeRO-style
    cross-replica sharded weight update (arxiv 2004.13336): optimizer
    state and update compute shard over the ``dp`` axis — reduce-scatter
    grads, update the local 1/N shard, all-gather params — cutting
    per-chip optimizer-state memory ~N-fold.  See docs/PERF.md.

    ``grad_compression="int8"|"fp8"|None`` narrows the sharded
    path's gradient wire (parallel/compression.py): the flat padded
    gradient is chunk-quantized to a 1-byte payload before the
    reduce-scatter and dequantized-with-error-feedback on the local
    shard — the residual rides as an extra dp-sharded state leaf, so
    it re-shards and checkpoints with the rest of the ZeRO state.
    Requires the sharded update — on a 1-device or unsharded layout
    compression quietly disables.
    """

    def __init__(self, net, loss_fn, optimizer, mesh=None, donate=True,
                 mirror=None, donate_batch=False, shard_optimizer=False,
                 grad_compression=None):
        self._net = net
        self._loss = loss_fn
        self._opt = optimizer
        self._mesh = mesh if mesh is not None else get_mesh()
        self._donate = donate
        # shard_optimizer: ZeRO-style cross-replica sharding of the
        # weight update (arxiv 2004.13336).  Instead of every chip
        # holding the full optimizer state and redundantly computing the
        # full update, each state leaf lives in a flat zero-padded layout
        # sharded over the ``dp`` axis; gradients are reduce-scattered,
        # the update runs on the local 1/N shard, and the updated
        # parameters are all-gathered back to replicated — all inside
        # the one jitted program, so XLA overlaps the collectives with
        # backprop.  ``False`` (default) keeps today's replicated path
        # bit-identical; ``"auto"`` turns it on when the mesh has a dp
        # axis of size > 1; ``True`` forces it (size-1 dp degenerates to
        # a no-op layout, handy for CPU tests).  The raw knobs are kept
        # for elastic re-formation: reshard() re-resolves both against
        # the NEW mesh's dp extent.
        self._shard_knob = shard_optimizer
        # compressed gradient wire (parallel/compression.py): "" (off)
        # or a compression.MODES member.  Only meaningful on the sharded
        # update.
        self._compress_knob = grad_compression
        self._resolve_knobs()
        # donate_batch additionally donates the data/label buffers: the
        # step is their last reader (a fresh batch arrives every call),
        # so XLA reuses their HBM pages for step outputs instead of
        # holding them live — part of the pure-copy elimination.  Safety:
        # buffers marked borrowed (``NDArray.mark_borrowed()`` — e.g. a
        # batch a pipeline stage will hand out again) are passed as
        # copies, and re-feeding a buffer a previous step donated raises
        # instead of silently reading freed memory (on backends where
        # donation is a no-op the raise is the only guard).
        self._donate_batch = donate_batch
        # ring of recently-donated batch buffers (strong refs keep the
        # identity check stable; on TPU the donated shells are already
        # freed device-side, so holding them is cheap) — bounded so a
        # long training loop doesn't accumulate host-backed arrays
        from collections import deque
        self._donated_batch = deque(maxlen=64)
        self._mirror = _resolve_mirror(mirror)
        params = [p for _, p in sorted(net.collect_params().items())
                  if p._data is not None]
        self._params = params
        self._trainable = [i for i, p in enumerate(params)
                           if p.grad_req != "null"]
        # optimizer state pytrees per trainable param (flattened to
        # leaves).  With optimizer.multi_precision, half-width (bf16/
        # fp16) weights carry an fp32 MASTER copy as the first state
        # leaf (reference mp_sgd/mp_adam kernels): the forward runs the
        # half weight, the update applies to the master in fp32, and
        # the half weight is re-quantized from it each step — small
        # updates accumulate instead of rounding away.
        # chaos: device-resident grad_compress_corrupt operands (1.0 =
        # clean, inf = garbled chunk-0 scale), lazily built per process
        self._corrupt_ok_dev = None
        self._corrupt_fire_dev = None
        # NOTE: the flattened leaf lists below are NOT covered by the
        # optimizer's own state treedef — multi-precision slots carry the
        # fp32 master as an EXTRA leaf 0 prepended after flattening, and
        # sharded slots store every leaf in the flat padded layout.  Any
        # state (de)serializer must strip/re-prepend the master and
        # ``unflatten`` sharded leaves before unflattening the pytree.
        self._opt_states = []
        self._mp_slots = []
        self._shard_slots = []   # per-slot: flat-sharded layout in use?
        self._shard_meta = []    # per-slot: natural (master) shape
        self._base_leaves = []   # per-slot: leaf count sans residual
        self._mp_written = {}   # slot -> last weight array THIS step wrote
        mp = bool(getattr(optimizer, "multi_precision", False))
        # a scoped span: it owns the small programs compiled here (casts,
        # zeros, placements), so they read as state, not as `eager`
        with telemetry.span("parallel.state_init"):
            self._init_states(optimizer, params, mp)
        self._report_shard_layout()
        self._t = optimizer.begin_num_update
        self._cache = {}
        # device-resident per-call operands: every tiny host->device
        # transfer is a dispatch of its own on the step's critical path, so
        # the lr vector is cached (re-uploaded only when the schedule moves),
        # and the step counter and RNG key live on-device, threaded
        # through the jitted step as donated carry values
        self._lrs_key = None
        self._lrs_dev = None
        self._t_dev = None
        self._rng_dev = None
        self._rng_epoch = None
        # one jitted copy-program for checkpoint snapshots (see
        # checkpoint_state)
        self._ckpt_copier = None

    def _init_states(self, optimizer, params, mp):
        """Master copies, ``create_state`` / ``_create_sharded_state`` for
        every trainable slot."""
        for slot, i in enumerate(self._trainable):
            wdata = params[i].data()
            use_mp = mp and onp.dtype(wdata.dtype).itemsize < 4
            self._mp_slots.append(use_mp)
            if use_mp:
                wdata = wdata.astype("float32")   # master (state dtype f32)
            self._shard_meta.append(tuple(wdata.shape))
            if self._shard_n:
                leaves = self._create_sharded_state(optimizer, slot, wdata)
                if leaves is not None:
                    self._shard_slots.append(True)
                    self._base_leaves.append(
                        len(leaves) - (1 if self._compress else 0))
                    self._opt_states.append(leaves)
                    continue
            self._shard_slots.append(False)
            st = optimizer.create_state(slot, wdata)
            leaves, _ = jax.tree_util.tree_flatten(
                st, is_leaf=lambda x: isinstance(x, NDArray))
            if use_mp:
                leaves = [wdata] + leaves     # master rides as leaf 0
            self._base_leaves.append(len(leaves))
            # commit state buffers to the weight's device so the first call
            # and post-donation calls see identical arg shardings (one
            # compile, not two)
            wdev = None
            devs = getattr(params[i].data()._data, "devices", None)
            if devs is not None and params[i].data()._data.committed:
                wdev = next(iter(params[i].data()._data.devices()))
            self._opt_states.append(
                [jax.device_put(l._data, wdev) if wdev is not None
                 else l._data for l in leaves])

    # ------------------------------------------------------------------
    # ZeRO-style sharded weight update (arxiv 2004.13336)
    # ------------------------------------------------------------------
    def _resolve_knobs(self):
        """``_shard_n`` (the dp extent the state is sharded over, 0 =
        replicated path, untouched) and ``_compress`` ("" or a wire
        mode) from the two knobs and the mesh, by the rules ``Trainer``
        shares (``collectives.resolve_shard_optimizer``,
        ``compression.resolve_grad_compression``); a knob that asks for
        a wire mode journals one ``compress/decision`` event."""
        from . import compression as _comp
        from .collectives import SHARD_FORCED, resolve_shard_optimizer
        self._shard_n = resolve_shard_optimizer(self._shard_knob,
                                                self._mesh)
        if not self._shard_n and self._shard_knob in SHARD_FORCED:
            import warnings
            warnings.warn("shard_optimizer=True needs a mesh with a 'dp' "
                          "axis; falling back to the replicated update")
        self._compress = _comp.resolve_grad_compression(
            self._compress_knob, self._shard_n)
        if self._compress_knob in _comp.MODES:
            _comp.journal_decision(self._compress_knob, self._compress,
                                   self._shard_n,
                                   *self._trainable_param_stats())

    def _trainable_param_stats(self):
        """(param count, dominant dtype string) of the trainable set —
        what the ``compress/decision`` record sizes the wire by."""
        pcount, dtype = 0, "float32"
        try:
            for _, p in sorted(self._net.collect_params().items()):
                if p._data is None or p.grad_req == "null":
                    continue
                if pcount == 0:
                    dtype = str(onp.dtype(p._data.dtype))
                pcount += int(onp.prod(p._data.shape))
        except Exception:
            pcount = 0
        return pcount, dtype

    def _shard_sharding(self, replicated=False):
        import jax.sharding as jsh
        spec = jsh.PartitionSpec() if replicated else jsh.PartitionSpec("dp")
        return jsh.NamedSharding(self._mesh, spec)

    def _shard_put(self, value):
        """Eagerly place a natural-shape value into the flat padded
        layout, sharded over dp (the layout every sharded state leaf
        lives in between steps)."""
        from .collectives import flatten_pad
        return jax.device_put(flatten_pad(value, self._shard_n),
                              self._shard_sharding())

    def _create_sharded_state(self, optimizer, slot, wdata):
        """Create slot ``slot``'s optimizer state directly in the flat
        sharded layout via ``create_state_flat`` — state leaves are born
        as 1/N shards (plus the fp32 master as leaf 0 under
        multi-precision), so the full replicated leaf never
        materializes.  Returns None when the state is not elementwise
        (a leaf that is not weight-shaped), in which case the slot
        falls back to the replicated layout."""
        from ..ndarray.ndarray import _wrap
        wflat = self._shard_put(wdata._data if isinstance(wdata, NDArray)
                                else wdata)
        st = optimizer.create_state_flat(slot, _wrap(wflat))
        leaves, _ = jax.tree_util.tree_flatten(
            st, is_leaf=lambda x: isinstance(x, NDArray))
        vals = []
        for l in leaves:
            v = l._data if isinstance(l, NDArray) else jnp.asarray(l)
            if tuple(v.shape) != tuple(wflat.shape):
                return None    # structured state: keep slot replicated
            vals.append(jax.device_put(v, self._shard_sharding()))
        if self._mp_slots[slot]:
            vals = [wflat] + vals    # master rides as leaf 0, sharded too
        if self._compress:
            # error-feedback residual: LAST leaf, zero-initialized, in
            # the grad-wire dtype (f32 under mp).  Living inside the
            # dp-sharded state means elastic.reshard and the checkpoint
            # path carry it bitwise for free.
            rdt = jnp.float32 if self._mp_slots[slot] else wflat.dtype
            vals.append(jax.device_put(jnp.zeros(wflat.shape, rdt),
                                       self._shard_sharding()))
        return vals

    def optimizer_state_bytes(self, per_chip=True):
        """Logical optimizer-state footprint in bytes.  With
        ``per_chip=True`` this is what ONE replica holds: sharded leaves
        count padded_size/N, replicated leaves count full — the number
        the ZeRO sharding shrinks N-fold."""
        total = 0
        for slot, leaves in enumerate(self._opt_states):
            for l in leaves:
                n = int(l.nbytes)
                if per_chip and self._shard_slots[slot]:
                    n //= self._shard_n
                total += n
        return total

    def _report_shard_layout(self):
        """Gauge the per-chip state footprint (both layouts — the
        replicated number is what the ZeRO sharding shrinks) and, when
        sharded, journal the collective schedule the update compiles to
        (the collectives run inside XLA, so the journal records the
        schedule, not per-step host timings)."""
        per_chip = self.optimizer_state_bytes(per_chip=True)
        total = self.optimizer_state_bytes(per_chip=False)
        telemetry.gauge("parallel.optimizer_state_bytes_per_chip",
                        per_chip)
        telemetry.gauge("parallel.optimizer_state_bytes_total", total)
        if not self._shard_n:
            return
        from . import compression as _comp
        rs_bytes = ag_bytes = wire_bytes = scale_bytes = 0
        for slot, i in enumerate(self._trainable):
            if not self._shard_slots[slot]:
                continue
            w = self._params[i].data()
            nelem = 1
            for d in self._shard_meta[slot]:
                nelem *= int(d)
            itemsize = onp.dtype(w.dtype).itemsize
            rs_bytes += (4 if self._mp_slots[slot] else itemsize) * nelem
            ag_bytes += itemsize * nelem
            if self._compress:
                wire_bytes += _comp.wire_bytes(nelem, self._compress)
                scale_bytes += _comp.scale_bytes(nelem, self._compress)
        telemetry.event(
            "zero", "shard_optimizer", axis="dp", n_shards=self._shard_n,
            sharded_slots=sum(self._shard_slots),
            replicated_slots=len(self._shard_slots)
            - sum(self._shard_slots),
            state_bytes_per_chip=per_chip, state_bytes_total=total,
            reduce_scatter_bytes=rs_bytes, all_gather_bytes=ag_bytes,
            grad_compression=self._compress or "off",
            compressed_wire_bytes=wire_bytes,
            compression_scale_bytes=scale_bytes)
        if self._compress:
            telemetry.gauge("compression.bytes_saved",
                            max(0, rs_bytes - wire_bytes - scale_bytes))
            telemetry.gauge("compression.scale_bytes", scale_bytes)

    def hbm_estimate(self, activations=()):
        """Static per-chip HBM estimate of this step's resident leaves
        (params, optimizer state, batch), computed from shapes/dtypes
        and the per-slot layout flags via ``tools.lint.hbm`` — the SAME
        arithmetic graftlint uses, independently of
        what the runtime allocated (cross-checked against the
        ``optimizer_state_bytes_per_chip`` gauges in
        ``tests/test_hbm_estimator.py``).

        ``activations``: ``(shape, dtype)`` pairs for the dp-sharded
        batch leaves of one jitted signature.  Returns a dict of
        per-chip byte counts, or None when ``tools.lint`` is not
        importable (installed package without the repo's tools/).
        """
        try:
            from tools.lint import hbm
        except ImportError:
            return None
        n = self._shard_n or 1
        # the batch is dp-sharded whenever the mesh has a dp axis —
        # independent of whether the ZeRO state sharding is on
        dp = 1
        if self._mesh is not None and \
                "dp" in getattr(self._mesh, "axis_names", ()):
            dp = int(self._mesh.shape["dp"])
        params_b = 0
        for p in self._params:
            d = p.data()
            params_b += hbm.leaf_bytes_per_chip(
                tuple(d.shape), str(d.dtype), hbm.REPLICATED, n)
        state_b = 0
        for slot, leaves in enumerate(self._opt_states):
            layout = hbm.DP_SHARDED if self._shard_slots[slot] \
                else hbm.REPLICATED
            w = self._params[self._trainable[slot]].data()
            sdtype = "float32" if self._mp_slots[slot] else str(w.dtype)
            state_b += len(leaves) * hbm.leaf_bytes_per_chip(
                self._shard_meta[slot], sdtype, layout, n)
        act_b = 0
        for shape, dtype in activations:
            nelem = 1
            for d in shape:
                nelem *= int(d)
            act_b += nelem * hbm.dtype_itemsize(dtype) // dp
        return {"params_bytes_per_chip": params_b,
                "opt_state_bytes_per_chip": state_b,
                "activation_bytes_per_chip": act_b,
                "total_bytes_per_chip": params_b + state_b + act_b,
                "n_shards": n}

    def _journal_hbm_estimate(self, dval, lval, scan):
        """One ``hbm/estimate`` journal event per jitted program (fires
        with the cache-miss, so every compiled signature gets its
        bytes-per-chip record; rendered by tools/parse_log.py)."""
        leaves = list(dval) if isinstance(dval, tuple) else [dval]
        leaves.append(lval)
        acts = [(tuple(v.shape), str(v.dtype)) for v in leaves
                if v is not None]
        est = self.hbm_estimate(activations=acts)
        if est is not None:
            telemetry.event("hbm", "estimate",
                            program="DataParallelStep[%x]" % id(self),
                            mode="scan" if scan else "call", **est)

    # ------------------------------------------------------------------
    # elastic re-formation + checkpoint state (parallel/elastic.py,
    # mxnet_tpu/checkpoint.py)
    # ------------------------------------------------------------------
    def _materialize_slot(self, slot):
        """Natural-shape HOST copies of one slot's state leaves (the
        fp32 master first under multi-precision) — the ZeRO checkpoint
        gather, done in numpy so it is pure byte movement: drop the
        flat layout's pad lanes, restore the master shape, never touch
        a value."""
        shape = self._shard_meta[slot]
        n = 1
        for d in shape:
            n *= int(d)
        out = []
        for l in self._opt_states[slot]:
            host = onp.asarray(l)
            if self._shard_slots[slot]:
                host = host.ravel()[:n].reshape(shape)
            out.append(host)
        return out

    def _place_slot(self, slot, nat_leaves):
        """Place natural-shape (host) state leaves into the CURRENT
        layout: flat zero-padded dp-sharded when the step shards and
        every leaf is weight-shaped (the ``create_state_flat``
        elementwise contract), replicated otherwise.  Updates the
        per-slot layout flag.

        Error-feedback residuals reconcile HERE, the single seam both
        elastic reshard and checkpoint restore pass through: a leaf
        set carrying a residual this layout doesn't use drops it, and
        a compressed layout restoring residual-less leaves (e.g. an
        uncompressed checkpoint) starts one at zero — error feedback
        restarts cleanly, nothing else is touched."""
        shape = tuple(self._shard_meta[slot])
        nat_leaves = list(nat_leaves)
        will_shard = bool(self._shard_n) and all(
            tuple(onp.shape(l)) == shape for l in nat_leaves)
        want = self._base_leaves[slot] + (
            1 if (self._compress and will_shard) else 0)
        if len(nat_leaves) == want + 1:
            nat_leaves = nat_leaves[:-1]
        elif len(nat_leaves) == want - 1:
            rdt = onp.float32 if self._mp_slots[slot] else \
                onp.dtype(self._params[self._trainable[slot]]
                          .data().dtype)
            nat_leaves.append(onp.zeros(shape, rdt))
        if self._shard_n and all(tuple(onp.shape(l)) == shape
                                 for l in nat_leaves):
            self._shard_slots[slot] = True
            self._opt_states[slot] = [
                self._shard_put(jnp.asarray(l)) for l in nat_leaves]
            return
        self._shard_slots[slot] = False
        wdev = None
        i = self._trainable[slot]
        devs = getattr(self._params[i].data()._data, "devices", None)
        if devs is not None and self._params[i].data()._data.committed:
            wdev = next(iter(self._params[i].data()._data.devices()))
        self._opt_states[slot] = [
            jax.device_put(jnp.asarray(l), wdev) if wdev is not None
            else jnp.asarray(l) for l in nat_leaves]

    def reshard(self, mesh):
        """Re-form this step onto a new mesh (elastic recovery: the dp
        extent changed under us).  Parameters are re-placed replicated
        on the survivors' mesh and every ZeRO state leaf — the fp32
        master included — migrates through its natural shape onto the
        new flat zero-padded dp extent, bitwise-preserved (byte
        movement only, no arithmetic).  The jit cache is invalidated;
        the next call recompiles against the new layout and training
        resumes mid-epoch.  Returns the bytes moved."""
        naturals = [self._materialize_slot(slot)
                    for slot in range(len(self._opt_states))]
        self._mesh = mesh
        # both knobs re-resolve against the NEW layout ("auto" follows
        # the dp extent; losing the sharded update disables the wire) —
        # _place_slot reconciles residual leaves either way
        self._resolve_knobs()
        moved = 0
        repl = self._shard_sharding(replicated=True) \
            if mesh is not None else None
        with autograd.pause():
            for p in self._params:
                host = onp.asarray(p._data._data)
                moved += host.nbytes
                p._data._data = jax.device_put(host, repl) \
                    if repl is not None else jnp.asarray(host)
                if repl is not None:
                    _grad_follows(p, repl)
        for slot, nat in enumerate(naturals):
            self._place_slot(slot, nat)
            moved += sum(int(l.nbytes) for l in nat)
        for slot, i in enumerate(self._trainable):
            if self._mp_slots[slot]:
                # the re-placed weight is a NEW array object; without
                # this the next dispatch's master-resync would rebuild
                # the fp32 master from the half-width weight, rounding
                # away exactly the precision the master exists to keep
                self._mp_written[slot] = self._params[i]._data._data
        # device-resident carries migrate off the old mesh; the lr
        # vector re-uploads lazily
        if self._t_dev is not None:
            self._t_dev = jnp.asarray(onp.asarray(self._t_dev))
        if self._rng_dev is not None:
            self._rng_dev = jnp.asarray(onp.asarray(self._rng_dev))
        self._lrs_key = None
        self._lrs_dev = None
        self._cache.clear()
        self._report_shard_layout()
        return moved

    def checkpoint_state(self):
        """Snapshot for ``checkpoint.CheckpointManager`` — device-side
        COPIES of the param/state arrays (async dispatch, no host
        sync): the train step donates its buffers, so a
        reference-only snapshot would race the next step's donation
        and read freed memory.  All copies run as ONE jitted
        ``optimization_barrier`` program (bit-exact identity that
        cannot alias its inputs; per-array ``.copy()`` dispatch
        overhead would dominate) ordered before the donation by the
        runtime; the writer thread does the host transfer at its
        leisure."""
        vals = [p._data._data for p in self._params]
        for leaves in self._opt_states:
            vals.extend(leaves)
        if self._ckpt_copier is None:
            # retraces automatically when shapes/shardings move
            # (reshard): the cache key is jit's own
            self._ckpt_copier = jax.jit(
                lambda xs: jax.lax.optimization_barrier(xs))
        vals = list(self._ckpt_copier(vals))
        params, vals = vals[:len(self._params)], vals[len(self._params):]
        slots = []
        for slot, leaves in enumerate(self._opt_states):
            copies, vals = vals[:len(leaves)], vals[len(leaves):]
            slots.append({"leaves": copies,
                          "sharded": bool(self._shard_slots[slot]),
                          "shape": tuple(self._shard_meta[slot]),
                          "mp": bool(self._mp_slots[slot])})
        # params/slots are POSITIONAL in the net's GRAPH order: gluon's
        # global auto-naming counters make raw names differ between
        # otherwise-identical nets, and name-SORTED order (self._params)
        # flips when a counter crosses a digit boundary (dense9_ sorts
        # after dense10_) — graph order is architecture-stable.  Names
        # ride along as metadata only.
        order = self._param_order()
        slot_rank = {pi: k for k, pi in enumerate(order)}
        slot_order = sorted(range(len(slots)),
                            key=lambda s: slot_rank[self._trainable[s]])
        return {"step": int(self._t), "dp": int(self._shard_n or 1),
                "params": [params[i] for i in order],
                "param_names": [self._params[i].name for i in order],
                "slots": [slots[s] for s in slot_order]}

    def _param_order(self):
        """Canonical checkpoint permutation: position k -> index into
        ``self._params`` of the k-th parameter in the net's GRAPH
        (insertion) order — stable across processes regardless of
        where gluon's auto-naming counters stand.  Both save and load
        apply the same rule, so positional payloads align between
        identically-structured nets."""
        try:
            rank = {n: i for i, n in
                    enumerate(self._net.collect_params().keys())}
        except Exception:
            return list(range(len(self._params)))
        return sorted(range(len(self._params)),
                      key=lambda i: rank.get(self._params[i].name, i))

    def load_checkpoint_state(self, state):
        """Restore a checkpoint saved at ANY world size: natural-shape
        leaves re-shard onto this step's current layout
        (``_place_slot``), parameters re-place replicated, and the
        step/optimizer clocks resume where the checkpoint stopped.
        The RNG stream is NOT part of the checkpoint (re-seed with
        ``mx.random.seed`` for bit-reproducible dropout)."""
        from ..base import MXNetError
        order = self._param_order()
        # validate EVERYTHING before mutating anything: a caller that
        # catches a mismatch error must find the step exactly as it
        # was, never half-restored (checkpoint weights over stale
        # optimizer state is silent corruption)
        if len(state["params"]) != len(self._params):
            raise MXNetError(
                "checkpoint has %d parameters, step has %d"
                % (len(state["params"]), len(self._params)))
        if len(state["slots"]) != len(self._opt_states):
            raise MXNetError(
                "checkpoint has %d optimizer slots, step has %d"
                % (len(state["slots"]), len(self._opt_states)))
        for k, arr in enumerate(state["params"]):
            p = self._params[order[k]]
            if tuple(onp.shape(arr)) != tuple(p._data.shape):
                raise MXNetError(
                    "checkpoint parameter %r has shape %s, step "
                    "expects %s" % (p.name, tuple(onp.shape(arr)),
                                    tuple(p._data.shape)))
        repl = self._shard_sharding(replicated=True) \
            if self._mesh is not None else None
        with autograd.pause():
            for k, arr in enumerate(state["params"]):
                p = self._params[order[k]]
                val = jnp.asarray(onp.asarray(arr))
                p._data._data = jax.device_put(val, repl) \
                    if repl is not None else val
        slot_rank = {pi: k for k, pi in enumerate(order)}
        slot_order = sorted(range(len(self._opt_states)),
                            key=lambda s: slot_rank[self._trainable[s]])
        for k, rec in enumerate(state["slots"]):
            self._place_slot(slot_order[k],
                             [onp.asarray(l) for l in rec["leaves"]])
        for slot, i in enumerate(self._trainable):
            if self._mp_slots[slot]:
                # master restored from the checkpoint IS the truth —
                # suppress the dispatch-time resync from the half weight
                self._mp_written[slot] = self._params[i]._data._data
        self._t = int(state["step"])
        self._opt.num_update = max(self._opt.num_update, self._t)
        self._t_dev = None       # next dispatch resumes at t+1
        self._lrs_key = None
        self._lrs_dev = None
        self._report_shard_layout()

    # ------------------------------------------------------------------
    def __call__(self, data, label):
        return self._dispatch(data, label, scan=False)

    def scan_steps(self, data, label):
        """Run ``k`` consecutive optimizer steps in ONE compiled program.

        ``data``/``label`` carry a leading steps dimension ``(k, batch,
        …)``; the program is a ``lax.scan`` over that dimension with the
        parameters, optimizer state, step counter and RNG key as donated
        carries.  Returns the per-step losses as an NDArray of shape
        ``(k,)``.

        This is the TPU-idiomatic inner training loop (the reference's
        per-epoch batch loop, ``Module.fit`` / model.py:150-160, driven
        by the engine's async queue): one dispatch per ``k`` steps
        amortises the host's per-call dispatch.  The learning-rate
        schedule is sampled once per window (schedules move per-epoch,
        not per-step; the step counter still advances per step inside
        the program).
        """
        return self._dispatch(data, label, scan=True)

    def _dispatch(self, data, label, scan):
        """Shared prologue/epilogue for the per-call and scan paths:
        batch placement, compile-cache lookup, lr/step/RNG refresh, and
        the parameter/opt-state writeback."""
        # memory is sampled on a stride, not per step: device
        # memory_stats() is a runtime call, and this step is the hot
        # path the 2% telemetry-overhead gate protects
        idx = self._t   # 0-based index of THIS step (inner advances it)
        # trace() JOINS an enclosing trace (a Trainer-driven step) and
        # opens a fresh one per step otherwise, so every step's spans
        # and step event are causally linked either way
        with telemetry.trace():
            with telemetry.span("parallel.step", hist=True,
                                memory=(idx % 32 == 0),
                                step_num=idx) as _sp:
                out, compiled = self._dispatch_inner(data, label, scan)
            # a step in which jax compiled says so: a stall in the step
            # times can be put down to its step from the journal alone
            telemetry.emit_step("parallel", idx, step_ms=_sp.duration_ms,
                                owner=self,
                                **({"compiled": True} if compiled else {}))
        return out

    def _batch_sharding(self, ndim, scan):
        """Where a batch array of ``ndim`` dimensions lives on the mesh:
        its batch dimension over ``dp``."""
        import jax.sharding as jsh
        # under scan the leading dim is the step axis; the batch (dim 1)
        # is the one sharded over dp
        lead = (None, "dp") if scan else ("dp",)
        spec = jsh.PartitionSpec(*lead, *([None] * (ndim - len(lead))))
        return jsh.NamedSharding(self._mesh, spec)

    @staticmethod
    def _cache_key(dval, lval, scan):
        """The key of the step program for a batch: mode, shapes and
        dtypes (of arrays or of ``ShapeDtypeStruct``s)."""
        sig = lambda v: (None if v is None
                         else (tuple(v.shape), str(v.dtype)))
        return ("scan" if scan else "call",
                tuple(sig(d) for d in dval) if isinstance(dval, tuple)
                else sig(dval), sig(lval))

    def lower(self, data, label, scan=False):
        """The step program a call with this batch runs, as
        ``jax.stages.Lowered``: ``.as_text(debug_info=True)`` shows the
        scopes (``jvp(forward)``, ``transpose(jvp(forward))``,
        ``optimizer``, the blocks' names), ``.compile()`` gives
        ``memory_analysis()`` and the optimised text.  Nothing runs and
        no state moves.

        Lowered at the specs of the NEXT call — parameters, optimizer
        state and the device-side carries as the last step left them — so
        the step must have run once at these shapes; compiling it then
        hits the cache of the steady-state executable."""
        def spec(v):
            return jax.ShapeDtypeStruct(
                v.shape, v.dtype,
                sharding=telemetry.leaf_signature(v)["sharding"])

        def batch_spec(x):
            if x is None:
                return None
            val = x._data if isinstance(x, NDArray) else jnp.asarray(x)
            if self._mesh is None:
                return spec(val)
            return jax.ShapeDtypeStruct(
                val.shape, val.dtype,
                sharding=self._batch_sharding(val.ndim, scan))

        dspec = (tuple(batch_spec(d) for d in data)
                 if isinstance(data, (tuple, list)) else batch_spec(data))
        lspec = batch_spec(label)
        jfn = self._cache.get(self._cache_key(dspec, lspec, scan))
        if jfn is None:
            raise RuntimeError(
                "lower(): no step program for this batch yet — run the "
                "step once at these shapes first")
        state = [[p._data._data for p in self._params], self._opt_states,
                 self._t_dev, self._lrs_dev, self._rng_dev]
        if self._compress:
            state.append(self._corrupt_ok_dev)
        state = jax.tree_util.tree_map(spec, state)
        return jfn.lower(*state[:5], dspec, lspec, *state[5:])

    def _dispatch_inner(self, data, label, scan):
        def prep(x):
            if x is None:
                return None
            val = x._data if isinstance(x, NDArray) else jnp.asarray(x)
            if self._donate_batch:
                if any(val is d for d in self._donated_batch):
                    raise RuntimeError(
                        "batch buffer was donated by a previous step "
                        "(donate_batch=True) and may already be freed — "
                        "feed a fresh batch, or mark_borrowed() buffers "
                        "the caller keeps reusing")
                if isinstance(x, NDArray) and getattr(x, "_borrowed",
                                                      False):
                    # opt-out: the caller still holds this buffer, so
                    # donate a private copy instead of the original
                    val = jnp.array(val, copy=True)
            if self._mesh is not None:
                target = self._batch_sharding(val.ndim, scan)
                # batches pre-placed by the input pipeline
                # (``DevicePrefetchIter(mesh=...)`` lays per-replica
                # shards directly on their target devices) skip even the
                # no-op device_put dispatch
                if getattr(val, "sharding", None) == target:
                    return val
                val = jax.device_put(val, target)
            return val

        # data may be a tuple of forward inputs (None entries allowed),
        # e.g. (tokens, token_types, mask, valid_length) for BERT
        with telemetry.span("parallel.step.place"):
            dval = (tuple(prep(d) for d in data)
                    if isinstance(data, (tuple, list)) else prep(data))
            lval = prep(label)
        if scan:
            first = (next(d for d in dval if d is not None)
                     if isinstance(dval, tuple) else dval)
            lead = first.shape[0]
        else:
            lead = 1
        key = self._cache_key(dval, lval, scan)
        jfn = self._cache.get(key)
        missed = jfn is None
        if missed:
            self._journal_hbm_estimate(dval, lval, scan)
            jfn = self._build(scan=scan)
            self._cache[key] = jfn
        self._t += lead
        # advance the optimizer's clock and read the *current* scheduled lr
        # per slot — passed traced so warmup/decay advance inside the cached
        # compiled step (the reference re-reads the schedule per update too)
        self._opt.num_update = max(self._opt.num_update, self._t)
        lr_vals = tuple(self._opt._get_lrs(list(range(len(self._trainable)))))
        if lr_vals != self._lrs_key:
            self._lrs_dev = jnp.asarray(lr_vals, jnp.float32)
            self._lrs_key = lr_vals
        if self._t_dev is None:
            # the FIRST update must run with t=1 (Adam-family bias
            # correction divides by 1-beta**t, which is 0 at t=0)
            self._t_dev = jnp.asarray(self._t - lead + 1, jnp.int32)
        if self._rng_dev is None or self._rng_epoch != _random.seed_epoch():
            # (re-)draw from the global stream — a fresh mx.random.seed()
            # must restart this step's dropout trajectory too
            self._rng_dev = _random.next_key()
            self._rng_epoch = _random.seed_epoch()
        pvals = [p._data._data for p in self._params]
        if self._shard_n:
            # the sharded program mixes dp-sharded state with the params
            # in ONE jit call, so every param must be committed to the
            # mesh (replicated).  Identity is preserved for already-
            # placed arrays — the step's own outputs — so this only
            # copies on the first call and after an external set_data
            # (where the master-resync below must fire anyway).
            repl = self._shard_sharding(replicated=True)
            def _onmesh(v):
                sh = getattr(v, "sharding", None)
                try:
                    if sh is not None and sh.is_equivalent_to(repl, v.ndim):
                        return v
                except Exception:
                    pass
                return jax.device_put(v, repl)
            placed = [_onmesh(v) for v in pvals]
            for p, was, now in zip(self._params, pvals, placed):
                if now is not was:
                    _grad_follows(p, repl)
            pvals = placed
        # multi-precision master resync: the fp32 master (state leaf 0)
        # is the source of truth for the update, so an externally
        # mutated weight (load_parameters / set_data after construction)
        # must refresh it — otherwise the next step would silently
        # restore the stale master's value
        for slot, i in enumerate(self._trainable):
            if self._mp_slots[slot] and \
                    self._mp_written.get(slot) is not pvals[i]:
                master = jnp.asarray(pvals[i], jnp.float32)
                if self._shard_slots[slot]:
                    # sharded masters live flat-padded over dp
                    master = self._shard_put(master)
                self._opt_states[slot][0] = master
        argv = [pvals, self._opt_states, self._t_dev, self._lrs_dev,
                self._rng_dev, dval, lval]
        if self._compress:
            # grad_compress_corrupt chaos seam: consulted host-side per
            # dispatch; the fired/clean outcome rides into the program
            # as a traced scalar multiplied into chunk 0's wire scale
            # inside the dequantize (compression.dequantize_chunked) —
            # same compiled program either way, no retrace
            from . import chaos
            if self._corrupt_ok_dev is None:
                self._corrupt_ok_dev = jnp.asarray(1.0, jnp.float32)
                self._corrupt_fire_dev = jnp.asarray(onp.inf, jnp.float32)
            argv.append(self._corrupt_fire_dev if chaos.should_fire(
                "grad_compress_corrupt", step=self._t)
                else self._corrupt_ok_dev)
        # steady state pays this integer read and one compare: jax's
        # listener counts the programs it hands the backend on this thread
        compiles = telemetry.thread_compiles()
        seq = compiles.seq
        with telemetry.span("parallel.step.call"):
            new_pvals, new_states, self._t_dev, self._rng_dev, loss = \
                jfn(*argv)
            compiled = missed or compiles.seq != seq
            if compiled:
                self._report_compile(
                    scan, argv, self._t - lead,
                    compiles.last if compiles.seq != seq else None)
        if self._donate_batch:
            # remember this call's donated buffers so re-feeding one
            # raises in prep — accumulated (not replaced) so a buffer
            # donated several steps ago is still caught; these store
            # the donated SHELLS for the re-feed identity guard in
            # prep(), no buffer contents are read
            donated = [d for d in (dval if isinstance(dval, tuple)
                                   else (dval,)) if d is not None]
            self._donated_batch.extend(donated)
            if lval is not None:
                self._donated_batch.append(lval)
                donated.append(lval)
            telemetry.inc("donation.batch_buffers", len(donated))
        for p, v in zip(self._params, new_pvals):
            with autograd.pause():
                p._data._data = v
        for slot, i in enumerate(self._trainable):
            if self._mp_slots[slot]:
                self._mp_written[slot] = new_pvals[i]
        self._opt_states = new_states
        return _wrap(loss), compiled

    _ARG_NAMES = ("params", "opt_states", "t", "lrs", "rng", "data",
                  "label", "corrupt")

    def _report_compile(self, scan, argv, step, program):
        """A call in which the step program was compiled: the framework's
        own cache missed, or ``jax.jit`` compiled the cached step again
        for arguments it keys apart (committedness, sharding — same
        shapes).  Either way the recompile detector gets the signature
        of what the call was handed, read from the retained ``argv``
        (donation leaves avals and shardings), so its diff names the
        leaves; and the journal gets a ``parallel.step.compile`` record
        under this call's span, with the ``step`` index the profiler's
        step annotation carries.  ``program`` is jax's record of the
        compile (None while telemetry was off)."""
        key = dict(zip(self._ARG_NAMES, telemetry.arg_signature(argv)),
                   mode="scan" if scan else "call")
        # per-INSTANCE detector key: first compiles of unrelated steps
        # (a bench builds ~10) must not read as retraces of one function
        # and trip the warning on each other
        name = "DataParallelStep[%x]" % id(self)
        changed = telemetry.record_compile(name, key)
        if program is None:
            return
        telemetry.span_event(
            "parallel.step.compile",
            program["trace_s"] + program["lower_s"] + program["backend_s"],
            parent=telemetry.current_span(), step=step,
            n=telemetry.compile_counts()[name], changed=changed,
            **{k: program[k] for k in ("trace_s", "lower_s", "backend_s",
                                       "cache")})

    # ------------------------------------------------------------------
    def _build(self, scan=False):
        net, loss_fn, optimizer = self._net, self._loss, self._opt
        params = self._params
        trainable = self._trainable
        mp_slots = self._mp_slots
        shard_slots = self._shard_slots
        shard_meta = self._shard_meta
        shard_n = self._shard_n
        compress = self._compress
        if shard_n:
            from .collectives import zero_sharded_update
            SHARD = self._shard_sharding()
            REPL = self._shard_sharding(replicated=True)
        trainset = set(trainable)
        steps = [optimizer.make_step(slot) for slot, _ in enumerate(trainable)]

        def sharded_update(slot, i, w, g, t, lrs, st_leaves,
                           corrupt=None):
            """ZeRO-style update of one slot (arxiv 2004.13336): the
            gradient's producer is the global-batch mean, so its shard
            constraint lowers to a reduce-scatter; the optimizer math
            runs on the local 1/N shard and the updated weight all-
            gathers back in the working dtype.  State leaves stay
            sharded across steps — 1/N of the replicated footprint per
            chip.  With ``compress`` the wire leg is chunk-quantized
            and the slot's LAST leaf carries the error-feedback
            residual.  The numerics live in
            collectives.zero_sharded_update (shared with the Trainer's
            fused path)."""
            return zero_sharded_update(
                steps[slot], w, g, st_leaves, t, lrs[slot],
                shape=shard_meta[slot], mp=mp_slots[slot],
                axis_size=shard_n, shard=SHARD, repl=REPL,
                compress=compress or None, corrupt=corrupt)

        def run_forward(pvals, rng, dval, lval):
            """Swap traced values into the blocks' parameters, run the
            user's (NDArray-level) forward+loss, restore — the same
            functionalization trick as gluon's _CachedGraph."""
            saved = [(p._data._data, p._data._ag) for p in params]
            for p, v in zip(params, pvals):
                p._data._data = v
                p._data._ag = None
            try:
                prev_rec = autograd.set_recording(False)
                prev_train = autograd.set_training(True)
                try:
                    with _random.key_supply(rng):
                        if isinstance(dval, tuple):
                            args = [None if d is None else _wrap(d)
                                    for d in dval]
                            out = net.forward(*args)
                        else:
                            out = net.forward(_wrap(dval))
                        loss = loss_fn(out, _wrap(lval))
                finally:
                    autograd.set_recording(prev_rec)
                    autograd.set_training(prev_train)
                loss_val = jnp.mean(loss._data)
                # aux params mutated in-forward (BN running stats)
                mutated = {}
                for i, (p, (old, _)) in enumerate(zip(params, saved)):
                    if p._data._data is not pvals[i] and i not in trainset:
                        mutated[i] = p._data._data
                return loss_val, mutated
            finally:
                for p, (old, ag) in zip(params, saved):
                    p._data._data = old
                    p._data._ag = ag

        fwd = _mirror_wrap(run_forward, self._mirror)

        def step_fn(pvals, opt_states, t, lrs, rng, dval, lval,
                    corrupt=None):
            # the step counter and RNG key are device-resident carries:
            # advanced inside the program, returned for the next call (no
            # per-step host->device transfer)
            use_key, next_key = jax.random.split(rng)
            train_vals = [pvals[i] for i in trainable]

            def loss_of(tvals):
                full = list(pvals)
                for i, v in zip(trainable, tvals):
                    full[i] = v
                # one scope names both passes: jvp(forward) on forward
                # operations, transpose(jvp(forward)) on backward ones
                with jax.named_scope("forward"):
                    return fwd(full, use_key, dval, lval)

            # a Pallas kernel in the forward or backward has to know
            # that GSPMD shards this program's batch over the dp axis
            with batch_sharded_over(self._mesh):
                (loss_val, mutated), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(train_vals)

            new_pvals = list(pvals)
            new_states = []
            with jax.named_scope("optimizer"):
                for slot, (i, g) in enumerate(zip(trainable, grads)):
                    st_leaves = opt_states[slot]
                    if shard_slots[slot]:
                        new_pvals[i], new_st = sharded_update(
                            slot, i, pvals[i], g, t, lrs, st_leaves,
                            corrupt)
                        new_states.append(new_st)
                        continue
                    if mp_slots[slot]:
                        # fp32 master path (reference mp_* kernels): update
                        # the master, re-quantize the working weight from it
                        master, rest = st_leaves[0], st_leaves[1:]
                        res = steps[slot](master, g.astype(jnp.float32), t,
                                          lrs[slot], *rest)
                        new_master, new_rest = _opt.pin_update_dtypes(
                            res, master, rest)
                        new_pvals[i] = new_master.astype(pvals[i].dtype)
                        new_states.append([new_master] + new_rest)
                        continue
                    # graftlint: disable-next=retrace-closure-array -- step
                    # fns are per-slot constants; step_fn is jitted once per
                    # (mode, shapes) cache key by design
                    res = steps[slot](
                        pvals[i], g, t, lrs[slot].astype(pvals[i].dtype),
                        *st_leaves)
                    # see optimizer.pin_update_dtypes: traced-t bias
                    # corrections are strong f32 and once silently rewrote
                    # bf16 params as f32 from step 2 on
                    new_pvals[i], new_st = _opt.pin_update_dtypes(
                        res, pvals[i], st_leaves)
                    new_states.append(new_st)
            for i, v in mutated.items():
                new_pvals[i] = v
            return new_pvals, new_states, t + 1, next_key, loss_val

        donate = (0, 1, 2, 4) if self._donate else ()
        if self._donate_batch:
            donate = donate + (5, 6)
        if not scan:
            return jax.jit(step_fn, donate_argnums=donate)

        from jax import lax

        def scan_fn(pvals, opt_states, t, lrs, rng, dseq, lseq,
                    corrupt=None):
            def body(carry, xs):
                pv, st, tt, key = carry
                d, l = xs
                npv, nst, tt, key, loss = step_fn(pv, st, tt, lrs, key,
                                                  d, l, corrupt)
                return (npv, nst, tt, key), loss
            (pvals, opt_states, t, rng), losses = lax.scan(
                body, (pvals, opt_states, t, rng), (dseq, lseq))
            return pvals, opt_states, t, rng, losses

        return jax.jit(scan_fn, donate_argnums=donate)
