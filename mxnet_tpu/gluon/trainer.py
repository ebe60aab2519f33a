"""Gluon Trainer: applies an Optimizer to a set of Parameters.

Reference: ``python/mxnet/gluon/trainer.py`` (495 LoC) — ``step`` (:305) =
``_allreduce_grads`` (kvstore push/pull :356-365) + ``_update`` (:399);
kvstore selection logic ``_init_kvstore`` (:169).

TPU-native behavior: with one logical (possibly mesh-sharded) array per
parameter, gradient all-reduce is either implicit (global-view jit) or an
ICI psum via ``KVStoreTPU`` — the kvstore round-trip shrinks to at most one
collective per parameter, and the optimizer update runs as a pure fused XLA
op per parameter (``optimizer.py _apply``).
"""
from __future__ import annotations

import numpy as onp

from .. import autograd
from .. import kvstore as kvs
from .. import telemetry
from .. import optimizer as opt
from ..optimizer.optimizer import pin_update_dtypes as _pin_update_dtypes
from ..base import MXNetError
from ..ndarray import NDArray
from .parameter import Parameter, ParameterDict

__all__ = ["Trainer"]


class _FusedUpdate:
    """Every parameter's optimizer update as ONE jitted XLA program.

    The reference batches tiny per-weight update kernels with aggregated
    multi-weight ops (``optimizer.py:46`` aggregate_num,
    ``model.py:130-148`` ``_update_params_on_kvstore_nccl``,
    ``MXNET_UPDATE_AGGREGATION_SIZE``) to amortize launch overhead.  Here
    the whole update sweep — all weights, all optimizer states — compiles
    into a single donated-buffer XLA call: one dispatch instead of
    O(n_params), with the per-weight elementwise updates fused/scheduled by
    XLA.  States live in the owning ``Updater`` (same objects), so
    ``save_states``/``load_states`` serialize exactly what this path
    updates.

    Multi-precision runs IN the fused program (reference ``mp_sgd`` /
    ``mp_adam`` kernels): for half-width weights under
    ``optimizer.multi_precision`` the fp32 master rides as state leaf 0 —
    the update applies to the master in fp32 and the working weight is
    re-quantized from it each step, all inside the same jitted call.

    Falls back (returns False) only when the optimizer has no pure
    ``make_step``, holds non-NDArray state, or a gradient is parts-backed
    row-sparse — the caller then runs the eager per-parameter loop.
    """

    def __init__(self, updater, donate_grads=False, shard_optimizer=False,
                 grad_compression=None):
        self._updater = updater
        self._donate_grads = donate_grads
        self._cache = {}
        self._unavailable = False
        # ZeRO-style weight-update sharding (arxiv 2004.13336, see
        # parallel/data_parallel.py for the SPMD-step variant): when the
        # weights live REPLICATED on a mesh with a dp axis, the
        # optimizer state migrates into a flat zero-padded dp-sharded
        # mirror and the fused program updates only the local 1/N shard
        # of every weight, all-gathering the result.  The updater's own
        # state objects become stale while the mirror is live —
        # ``materialize_states()`` gathers them back (Trainer.save_states
        # does this), ``invalidate_sharded()`` drops the mirror after an
        # external state load.
        # The knob is validated eagerly; it resolves against the live
        # process mesh in _shard_ready, by the rule DataParallelStep
        # shares (parallel.collectives.resolve_shard_optimizer).
        from ..parallel.collectives import (SHARD_OFF,
                                            resolve_shard_optimizer)
        resolve_shard_optimizer(shard_optimizer, None)
        self._shard_opt = shard_optimizer not in SHARD_OFF
        self._shard_knob = shard_optimizer
        self._sharded = {}       # index -> flat dp-sharded state leaves
        self._shard_mesh = None
        self._shard_n = 0
        self._shard_skip_reported = False
        # Compressed gradient wire for the sharded leg (see
        # parallel/compression.py): the knob is validated eagerly, the
        # MODE resolves once sharding engages (_shard_ready) — it needs
        # the live dp extent.  Error-feedback residuals ride as one
        # extra flat leaf at the END of each index's sharded mirror;
        # they are mirror-only (materialize_states' zip-shortest drops
        # them, so Trainer.save_states never sees them — a restore
        # simply restarts error feedback from zero, which is
        # numerics-safe).
        from ..parallel.compression import resolve_grad_compression
        resolve_grad_compression(grad_compression)
        self._compress_knob = grad_compression
        self._compress = ""

    def __getstate__(self):
        # the jitted executables are not picklable (and are cheap to
        # rebuild); Trainer state serialization reaches here via
        # optimizer.param_dict → Parameter._trainer.  The sharded
        # mirror cannot travel either (device-committed arrays) — but
        # the updater's natural-shape states it shadows are STALE while
        # it is live, so gather it back first or the pickle carries
        # step-0 moments
        self.materialize_states()
        state = self.__dict__.copy()
        state["_cache"] = {}
        state["_sharded"] = {}
        state["_shard_mesh"] = None
        state["_shard_n"] = 0
        # mesh-dependent: re-resolved (and re-journaled) when sharding
        # re-engages on the unpickled trainer
        state["_compress"] = ""
        return state

    # -- ZeRO sharded-state mirror --------------------------------------
    def _shard_ready(self, weights):
        """Engage sharding iff every weight is committed replicated to
        ONE mesh with a ``dp`` axis of size > 1 — the eager global-view
        training layout (params broadcast via ``parallel.replicate``).
        Unplaced (single-device) weights keep the replicated update:
        migrating them implicitly would move the user's training onto
        the mesh behind their back."""
        if not self._shard_opt:
            return False
        if self._shard_mesh is not None:
            return True
        from ..parallel import compression as _comp
        from ..parallel.collectives import resolve_shard_optimizer
        from ..parallel.mesh import get_mesh
        import jax.sharding as jsh
        mesh = get_mesh()
        n = resolve_shard_optimizer(self._shard_knob, mesh)
        if n < 2:
            return False
        repl = jsh.NamedSharding(mesh, jsh.PartitionSpec())
        for w in weights:
            sh = getattr(w._data, "sharding", None)
            try:
                if sh is None or not sh.is_equivalent_to(repl, w._data.ndim):
                    if not self._shard_skip_reported:
                        # once, not per step: a 10k-step run would
                        # otherwise evict every other journal event
                        self._shard_skip_reported = True
                        telemetry.event("zero", "trainer_shard_skipped",
                                        reason="weights not "
                                               "mesh-replicated")
                    return False
            except Exception:
                return False
        self._shard_mesh = mesh
        self._shard_n = n
        self._compress = _comp.resolve_grad_compression(
            self._compress_knob, n)
        if self._compress:
            _comp.journal_decision(
                self._compress_knob, self._compress, n,
                sum(int(onp.prod(w.shape)) for w in weights),
                str(onp.dtype(weights[0].dtype)) if weights
                else "float32")
        return True

    def _shard_sharding(self, replicated=False):
        import jax.sharding as jsh
        spec = jsh.PartitionSpec() if replicated else jsh.PartitionSpec("dp")
        return jsh.NamedSharding(self._shard_mesh, spec)

    def _sharded_leaves(self, i, leaves, weight):
        """The flat dp-sharded mirror of index ``i``'s state leaves
        (built from the updater's natural-shape leaves on first use).
        Under grad compression one extra leaf — the zero-initialized
        error-feedback residual, flat padded like the weight — is
        appended LAST; it has no natural-shape shell in the updater
        (mirror-only, see ``__init__``)."""
        import jax
        import jax.numpy as jnp
        from ..parallel.collectives import flatten_pad, padded_size
        got = self._sharded.get(i)
        if got is not None:
            return got
        spec = self._shard_sharding()
        flat = [jax.device_put(flatten_pad(l._data, self._shard_n), spec)
                for l in leaves]
        if self._compress:
            mp = self._updater.optimizer.multi_precision \
                and onp.dtype(weight.dtype).itemsize < 4
            rdt = jnp.float32 if mp else weight.dtype
            n = padded_size(int(onp.prod(weight.shape)), self._shard_n)
            flat.append(jax.device_put(jnp.zeros((n,), rdt), spec))
        self._sharded[i] = flat
        return flat

    def materialize_states(self):
        """Gather the sharded mirror back into the updater's natural-
        shape state NDArrays (the ZeRO checkpoint gather) — call before
        serializing states.  The mirror stays live afterwards."""
        from ..parallel.collectives import unflatten
        if not self._sharded:
            return
        is_nd = lambda x: isinstance(x, NDArray)  # noqa: E731
        import jax
        for i, flat in self._sharded.items():
            shells, _ = jax.tree_util.tree_flatten(
                self._updater.states[i], is_leaf=is_nd)
            with autograd.pause():
                # zip-shortest: the compressed mirror carries one extra
                # trailing leaf (the error-feedback residual) with no
                # natural-shape shell — it stays mirror-only and is
                # deliberately NOT serialized
                for shell, fl in zip(shells, flat):
                    shell._data = unflatten(fl, shell.shape)

    def invalidate_sharded(self):
        """Drop the mirror (externally loaded states take over)."""
        self._sharded.clear()

    def reset_mesh(self):
        """Elastic re-formation: gather the mirror back (its shards are
        about to be re-laid-out), drop it, and forget the mesh — the
        next step re-probes ``_shard_ready`` against the NEW process
        mesh and rebuilds the mirror at the new dp extent.  The jitted
        executables are compiled against the old mesh's shardings, so
        the cache goes too."""
        self.materialize_states()
        self.invalidate_sharded()
        self._shard_mesh = None
        self._shard_n = 0
        self._shard_skip_reported = False
        # compression re-resolves at the NEW dp extent (the journaled
        # wire arithmetic depends on it); residuals restart from zero —
        # numerics-safe, the error-feedback carry is a convergence
        # refinement, not state correctness
        self._compress = ""
        self._cache.clear()

    def __call__(self, indices, grads, weights):
        if self._unavailable:
            return False
        import jax
        import jax.numpy as jnp
        from ..ndarray.sparse import RowSparseNDArray
        optimizer = self._updater.optimizer
        if any(isinstance(g, RowSparseNDArray) and g.has_parts
               for g in grads):
            # parts-backed sparse grads must reach the optimizer's lazy
            # row-sparse branch; the fused dense step would densify them
            # (and decay momentum on every row).  If the sharded mirror
            # is live, the eager path must not read the stale updater
            # states — gather the mirror back first and retire it.
            if self._sharded:
                self.materialize_states()
                self.invalidate_sharded()
                self._shard_opt = False
                telemetry.event("zero", "trainer_shard_disabled",
                                reason="parts-backed sparse gradient")
            return False
        states = self._updater.states
        for i, w in zip(indices, weights):
            if i not in states:
                states[i] = optimizer.create_state_multi_precision(i, w)
                self._updater.states_synced[i] = True
        is_nd = lambda x: isinstance(x, NDArray)  # noqa: E731
        leaves_per = []
        for i in indices:
            lv, _ = jax.tree_util.tree_flatten(states[i], is_leaf=is_nd)
            if any(not isinstance(l, NDArray) for l in lv):
                self._unavailable = True
                return False
            leaves_per.append(lv)
        # make_step closures bake every scalar hyperparameter except lr/t at
        # trace time, so the cache key must cover them — scalar attrs
        # (momentum/betas/eps/wd/...; counters excluded) plus the resolved
        # per-index wds (covers wd_mult / param_dict mutation)
        fingerprint = tuple(sorted(
            (k, v) for k, v in vars(optimizer).items()
            if isinstance(v, (int, float, bool, str, type(None)))
            and k not in ("num_update", "begin_num_update")))
        # per-weight multi-precision flags are static at trace time; the
        # weight-dtype tuple in the key covers them
        mp_flags = [optimizer.multi_precision
                    and onp.dtype(w.dtype).itemsize < 4 for w in weights]
        sharded = self._shard_ready(weights)
        key = (tuple(indices), fingerprint,
               tuple(optimizer._get_wds(list(indices))),
               tuple((w.shape, str(w.dtype)) for w in weights),
               self._shard_n if sharded else 0,
               self._compress if sharded else "")
        jfn = self._cache.get(key)
        missed = jfn is None
        if missed:
            try:
                steps = [optimizer.make_step(i) for i in indices]
            except NotImplementedError:
                self._unavailable = True
                return False

            if sharded:
                from ..parallel.collectives import zero_sharded_update
                SHARD = self._shard_sharding()
                REPL = self._shard_sharding(replicated=True)
                shard_n = self._shard_n
                wshapes = [tuple(w.shape) for w in weights]
                compress = self._compress or None

            def fused(wvals, gvals, svals, t, lr_vec):
                new_w, new_s = [], []
                # graftlint: disable-next=retrace-closure-array -- step
                # fns are per-slot constants; fused is jitted once per
                # (shapes, lr-schedule) cache key by design
                for k, step in enumerate(steps):
                    if sharded:
                        # ZeRO-sharded update (numerics shared with
                        # DataParallelStep): replicated grad/weight
                        # slice to the local flat shard for free, the
                        # update runs on 1/N elements, only the new
                        # weight all-gathers back (working dtype);
                        # state leaves arrive and stay dp-sharded
                        # graftlint: disable-next=retrace-closure-array -- wshapes:
                        # per-slot shape tuples fixed at build; fused
                        # is jitted once per cache key
                        nw, ns = zero_sharded_update(
                            step, wvals[k], gvals[k], svals[k], t,
                            lr_vec[k], shape=wshapes[k],
                            mp=mp_flags[k], axis_size=shard_n,
                            shard=SHARD, repl=REPL, compress=compress)
                        new_w.append(nw)
                        new_s.append(ns)
                        continue
                    # graftlint: disable-next=retrace-closure-array --
                    # mp_flags: per-slot Python bools fixed at build
                    if mp_flags[k]:
                        # fp32 master path (reference mp_* kernels):
                        # state leaf 0 is the master; update it in f32
                        # and re-quantize the working weight from it
                        master, rest = svals[k][0], svals[k][1:]
                        res = step(master, gvals[k].astype(jnp.float32),
                                   t, lr_vec[k], *rest)
                        nm, ns = _pin_update_dtypes(res, master, rest)
                        new_w.append(nm.astype(wvals[k].dtype))
                        new_s.append([nm] + ns)
                        continue
                    res = step(wvals[k], gvals[k], t,
                               lr_vec[k].astype(wvals[k].dtype), *svals[k])
                    # traced-t bias corrections are strong f32; pin the
                    # carry (see optimizer.pin_update_dtypes)
                    nw, ns = _pin_update_dtypes(res, wvals[k], svals[k])
                    new_w.append(nw)
                    new_s.append(ns)
                return new_w, new_s

            # donate weights + states: the update is in-place at the XLA
            # level, matching the reference's kWriteInplace update ops.
            # Gradients join the donation only on request (Trainer
            # donate_grads=True): the step is their last reader — the
            # next backward rebinds fresh buffers — but a caller reading
            # param.grad() between step() and backward() would see a
            # freed buffer, so the default keeps them live.
            donate = (0, 1, 2) if self._donate_grads else (0, 2)
            jfn = jax.jit(fused, donate_argnums=donate)
            self._cache[key] = jfn
        # count the step only once the fused path is committed to running —
        # the eager fallback does its own counting
        optimizer._update_count(list(indices))
        lrs = optimizer._get_lrs(list(indices))
        wvals = [w._data for w in weights]
        gvals = [g._data for g in grads]
        if sharded:
            svals = [self._sharded_leaves(i, lv, w)
                     for i, lv, w in zip(indices, leaves_per, weights)]
            telemetry.gauge(
                "trainer.optimizer_state_bytes_per_chip",
                sum(int(l.nbytes) // self._shard_n
                    for sv in svals for l in sv))
        else:
            svals = [[l._data for l in lv] for lv in leaves_per]
        t = jnp.asarray(optimizer.num_update, jnp.int32)
        lr_vec = jnp.asarray(lrs, jnp.float32)
        compiles = telemetry.thread_compiles()
        seq = compiles.seq
        new_w, new_s = jfn(wvals, gvals, svals, t, lr_vec)
        if missed or compiles.seq != seq:
            # this cache's miss, or jax.jit compiling the cached update
            # again for arguments it keys apart: the detector's diff
            # names the hyperparameter or the leaf (its shape, dtype,
            # committedness or sharding) that moved
            # graftlint: disable-next=donate-use-after-donate -- avals and
            # shardings only: donation takes the buffer, not these
            args = {"weights": wvals, "grads": gvals, "states": svals}
            telemetry.record_compile(
                "FusedUpdate[%x]" % id(self),
                dict(telemetry.arg_signature(args), indices=list(indices),
                     hyperparams=dict(fingerprint), wds=list(key[2])))
        if self._donate_grads:
            telemetry.inc("donation.grad_buffers", len(gvals))
        with autograd.pause():
            for w, nv in zip(weights, new_w):
                w._data = nv
            if sharded:
                # the updater's natural-shape shells stay stale while
                # the mirror is live; materialize_states() gathers them
                for i, nlv in zip(indices, new_s):
                    self._sharded[i] = nlv
            else:
                for lv, nlv in zip(leaves_per, new_s):
                    for l, nl in zip(lv, nlv):
                        l._data = nl
        return True


class Trainer:
    """Optimizer driver (reference trainer.py:45).

    Parameters
    ----------
    params : ParameterDict | dict | list of Parameter
    optimizer : str or Optimizer
    optimizer_params : dict
    kvstore : str or KVStore or None — 'device' (default), 'local', 'tpu',
        'dist_sync' … (reference kvstore arg)
    update_on_kvstore : bool, default None — kept for API parity; updates
        always run through the store's updater (the reference's
        update_on_kvstore=True semantics, which its dist path requires too).
    donate_grads : bool, default False — also donate the gradient buffers
        into the fused update program (pure-copy elimination).  Opt-in:
        after ``step()`` the old gradient buffers are consumed, so the
        caller must not read ``param.grad()`` until the next
        ``backward()`` rebinds them.
    grad_compression : {"int8", "fp8", None}, default None —
        narrow the ZeRO gradient wire when ``shard_optimizer`` engages
        (``parallel/compression.py``: per-chunk symmetric quantization
        with error-feedback residuals carried as an extra dp-sharded
        mirror leaf).  Without the sharded update the knob is inert
        (there is no gradient reduce-scatter to narrow).
        Distinct from ``compression_params`` (the reference kvstore
        2-bit push/pull compression API).
    """

    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None,
                 donate_grads=False, shard_optimizer=False,
                 grad_compression=None):
        param_list = []
        if isinstance(params, (dict, ParameterDict)):
            for key in sorted(list(params.keys())):
                param_list.append(params[key])
            params = param_list
        if not isinstance(params, (list, tuple)):
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                "got %s." % (type(params)))
        self._params = []
        self._param2idx = {}
        for i, param in enumerate(params):
            if not isinstance(param, Parameter):
                raise ValueError(
                    "First argument must be a list or dict of Parameters, "
                    "got list of %s." % (type(param)))
            self._param2idx[param.name] = i
            self._params.append(param)
            param._set_trainer(self)
        self._compression_params = compression_params
        optimizer_params = optimizer_params if optimizer_params else {}
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))
        self._init_optimizer(optimizer, optimizer_params)
        self._kvstore_params = {
            "kvstore": kvstore, "update_on_kvstore": update_on_kvstore}
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None
        self._params_to_init = []
        self._donate_grads = donate_grads
        self._shard_optimizer = shard_optimizer
        self._grad_compression = grad_compression
        self._kv_fused = None
        self._local_fused = None
        self._step_count = 0
        self._reset_kvstore()

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            assert not optimizer_params, \
                "optimizer_params must be None if optimizer is an Optimizer " \
                "instance"
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updaters = opt.get_updater(self._optimizer)

    def _reset_kvstore(self):
        if self._kvstore and "dist" in self._kvstore.type:
            raise RuntimeError("Cannot reset distributed KVStore.")
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None
        self._params_to_init = [param for param in self._params]

    def _init_kvstore(self):
        """(reference trainer.py:169) Pick and set up the kvstore."""
        config = self._kvstore_params
        kvstore = config["kvstore"]
        update_on_kvstore = config["update_on_kvstore"]
        if kvstore is None:
            self._kvstore = None
            self._update_on_kvstore = False
        else:
            if isinstance(kvstore, str):
                kvstore = kvs.create(kvstore)
            self._kvstore = kvstore
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            self._update_on_kvstore = True if update_on_kvstore is None \
                else update_on_kvstore
            if self._update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
        self._kv_initialized = True

    def _init_params(self):
        assert self._kv_initialized, \
            "Cannot initialize parameters in KVStore when KVStore is not " \
            "initialized."
        params_to_init = []
        if self._kvstore:
            for param in self._params_to_init:
                if param._deferred_init:
                    params_to_init.append(param)
                else:
                    idx = self._param2idx[param.name]
                    self._kvstore.init(idx, param.data())
        self._params_to_init = params_to_init

    @property
    def learning_rate(self):
        if not isinstance(self._optimizer, opt.Optimizer):
            raise UserWarning("Optimizer has to be defined before its learning "
                              "rate can be accessed.")
        return self._optimizer.learning_rate if hasattr(
            self._optimizer, "learning_rate") else self._optimizer.lr

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        if not isinstance(self._optimizer, opt.Optimizer):
            raise UserWarning("Optimizer has to be defined before its learning "
                              "rate is mutated.")
        self._optimizer.lr = lr

    def allreduce_grads(self):
        """Explicit grad all-reduce, for when update is done manually
        (reference trainer.py:336)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        assert not (self._kvstore and self._update_on_kvstore), \
            "allreduce_grads() when parameters are updated on kvstore " \
            "is not supported. Try setting `update_on_kvstore` " \
            "to False when creating trainer."
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore and not self._update_on_kvstore:
            from .. import parallel
            for i, param in enumerate(self._params):
                if param.grad_req != "null":
                    g = parallel.allreduce(param.grad())
                    g.copyto(param.grad())

    def step(self, batch_size, ignore_stale_grad=False):
        """One optimization step over recorded gradients (reference
        trainer.py:305).  The step runs inside a telemetry span (per-step
        wall time + a memory gauge at the boundary) and fires the
        registered step hooks — Monitor/Speedometer attach there instead
        of requiring manual tic/toc."""
        # memory sampled on a stride (first step always): the allocator
        # query is a runtime call, not worth paying on every fast step
        # — one trace per step: nested spans (fused update, checkpoint
        # save from the step hook) and events share the step's trace id
        with telemetry.trace():
            with telemetry.span("trainer.step", hist=True,
                                memory=(self._step_count % 8 == 0)) as _sp:
                self._step_impl(batch_size, ignore_stale_grad)
            telemetry.emit_step("trainer", self._step_count,
                                batch_size=batch_size,
                                step_ms=_sp.duration_ms, owner=self)
        self._step_count += 1

    def _step_impl(self, batch_size, ignore_stale_grad):
        rescale_grad = self._scale / batch_size
        self._check_and_rescale_grad(rescale_grad)
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        if self._kvstore and self._update_on_kvstore:
            if self._fused_on_kvstore():
                return
            # push grads, pull updated weights (reference _update_params_on_kvstore)
            for i, param in enumerate(self._params):
                if param.grad_req == "null":
                    continue
                if not ignore_stale_grad:
                    self._check_fresh(param)
                self._kvstore.push(i, param.grad())
                self._kvstore.pull(i, out=param.data())
            return
        self._allreduce_grads()
        self._update(ignore_stale_grad)

    def _fused_on_kvstore(self):
        """Run the whole update as one jitted program through the store's
        updater when the store is in-process (local/device, or the tpu store
        in a single process, where eager push's all-reduce is a
        re-replication XLA performs anyway inside the fused program)."""
        store = self._kvstore
        if not isinstance(store, kvs.KVStoreLocal) or store._updater is None:
            return False
        if isinstance(store, kvs.KVStoreTPU):
            import jax
            if jax.process_count() > 1:
                return False
        if self._kv_fused is None or self._kv_fused._updater is not store._updater:
            self._kv_fused = _FusedUpdate(
                store._updater, donate_grads=self._donate_grads,
                shard_optimizer=self._shard_optimizer,
                grad_compression=self._grad_compression)
        indices, grads, weights = [], [], []
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            indices.append(i)
            grads.append(param.grad())
            weights.append(param.data())
        if not indices:
            return True
        ok = self._kv_fused(indices, grads, weights)
        if ok:
            # keep the store's pull view coherent with the updated weights
            for i, w in zip(indices, weights):
                store._store[i] = w
        return ok

    def _check_and_rescale_grad(self, scale):
        if self._update_on_kvstore and self._kvstore and self._kv_initialized:
            if self._optimizer.rescale_grad != scale:
                raise UserWarning(
                    "Possible change in the `batch_size` from previous "
                    "`step` detected. Optimizer gradient normalizing "
                    "factor will not change w.r.t new batch_size when "
                    "update_on_kvstore=True")
        self._optimizer.rescale_grad = scale

    def _check_fresh(self, param):
        pass  # freshness tracking is a no-op: grads are written by backward()

    def update(self, batch_size, ignore_stale_grad=False):
        """Manual update step (reference trainer.py:378) — requires
        allreduce_grads() to have been called when using a kvstore."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        assert not (self._kvstore and self._update_on_kvstore), \
            "update() when parameters are updated on kvstore " \
            "is not supported. Try setting `update_on_kvstore` " \
            "to False when creating trainer."
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        if self._local_fused is None or \
                self._local_fused._updater is not self._updaters:
            self._local_fused = _FusedUpdate(
                self._updaters, donate_grads=self._donate_grads,
                shard_optimizer=self._shard_optimizer,
                grad_compression=self._grad_compression)
        indices, grads, weights = [], [], []
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            indices.append(i)
            grads.append(param.grad())
            weights.append(param.data())
        if not indices:
            return
        if self._local_fused(indices, grads, weights):
            return
        for i, g, w in zip(indices, grads, weights):
            self._updaters(i, g, w)

    def reshard(self, mesh):
        """Re-form this trainer onto a new mesh after an elastic
        transition (``parallel/elastic.py``): the ZeRO mirrors gather
        back into the updater's natural-shape states (bitwise) and are
        dropped, every weight/gradient/state leaf re-places onto the
        survivors' mesh (replicated — the eager training layout), and
        the fused update re-engages its dp-sharded mirror at the NEW
        extent on the next step.  Returns the bytes moved."""
        import jax
        import jax.numpy as jnp
        from .. import parallel
        from ..parallel import NamedSharding, P
        for fused in (self._kv_fused, self._local_fused):
            if fused is not None:
                fused.reset_mesh()
        parallel.set_mesh(mesh)
        repl = NamedSharding(mesh, P()) if mesh is not None else None
        moved = 0

        def _replace(shell):
            nonlocal moved
            host = onp.asarray(shell._data)
            moved += host.nbytes
            shell._data = jax.device_put(host, repl) \
                if repl is not None else jnp.asarray(host)

        is_nd = lambda x: isinstance(x, NDArray)  # noqa: E731
        with autograd.pause():
            for param in self._params:
                if param._data is not None:
                    _replace(param._data)
                if getattr(param, "_grad", None) is not None:
                    _replace(param._grad)
            # natural-shape updater states follow (they feed the next
            # fused program; stale old-mesh placements would force a
            # second migration inside jit)
            seen = set()
            for fused in (self._kv_fused, self._local_fused):
                if fused is None or id(fused._updater) in seen:
                    continue
                seen.add(id(fused._updater))
                for st in fused._updater.states.values():
                    leaves, _ = jax.tree_util.tree_flatten(
                        st, is_leaf=is_nd)
                    for l in leaves:
                        if isinstance(l, NDArray):
                            _replace(l)
        return moved

    def _sync_sharded_states(self, invalidate=False):
        """ZeRO mirror maintenance around state (de)serialization: the
        fused updates keep dp-sharded flat state mirrors that make the
        updater's natural-shape states stale — gather them back before a
        save, and drop the mirrors after a load (the loaded states are
        now the truth)."""
        for fused in (self._kv_fused, self._local_fused):
            if fused is None:
                continue
            if invalidate:
                fused.invalidate_sharded()
            else:
                fused.materialize_states()

    def save_states(self, fname):
        """(reference trainer.py:440).  The write is atomic (tmp +
        ``os.replace`` via ``checkpoint.atomic_path``): a crash
        mid-write leaves the previous states file intact instead of a
        torn pickle — regression-tested with the chaos
        ``checkpoint_write_crash`` fault."""
        assert self._optimizer is not None
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        self._sync_sharded_states()
        from ..checkpoint import atomic_path
        if self._update_on_kvstore and self._kvstore:
            self._kvstore.save_optimizer_states(fname, dump_optimizer=True)
        else:
            with atomic_path(fname) as tmp:
                with open(tmp, "wb") as fout:
                    fout.write(self._updaters.get_states(
                        dump_optimizer=True))

    def load_states(self, fname):
        """(reference trainer.py:463)"""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        self._sync_sharded_states(invalidate=True)
        if self._update_on_kvstore and self._kvstore:
            self._kvstore.load_optimizer_states(fname)
            self._optimizer = self._kvstore._updater.optimizer
        else:
            with open(fname, "rb") as f:
                states = f.read()
            self._updaters.set_states(states)
            self._optimizer = self._updaters.optimizer
        self._optimizer.param_dict = {
            i: param for i, param in enumerate(self._params)}
