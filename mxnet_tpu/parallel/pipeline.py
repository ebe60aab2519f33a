"""Pipeline parallelism: GPipe-style microbatch schedule over a ``pp``
mesh axis.

Reference capability: absent upstream (SURVEY.md §2.3 marks pipeline
parallelism optional — the reference's closest notion is ``group2ctx``
device placement).  TPU-native design: each pipeline stage lives on one
slice of the ``pp`` axis; microbatches stream through the ring with
``lax.ppermute`` neighbour exchanges inside ONE compiled program — no
host scheduling, and XLA overlaps each tick's compute with the shift.

    mesh = Mesh(devices.reshape(pp,), ("pp",))
    out = pipeline_apply(stage_fn, stacked_params, microbatches, mesh)

``stage_fn(params, x) -> y`` is the per-stage computation (all stages
share one program; per-stage behaviour comes from the stacked params).
``stacked_params`` is a pytree whose leaves have leading dim = number of
stages (sharded over ``pp``); ``microbatches`` is (num_micro, mb, ...).
The schedule runs ``num_micro + num_stages - 1`` ticks (the classic GPipe
fill+drain); outputs are returned replicated.  Differentiable: the whole
schedule is a ``lax.scan``, so ``jax.grad`` through it yields the 1F1B-
equivalent backward for free.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply", "pipeline_train_step", "PipelineTrainer"]


from ..optimizer.optimizer import pin_update_dtypes as _pin_update_dtypes  # noqa: E402


def pipeline_apply(stage_fn, stacked_params, microbatches, mesh: Mesh,
                   axis: str = "pp"):
    """Run the pipeline; returns (num_micro, mb, ...) outputs.

    Output structure must match the input microbatch structure (stages map
    activations to activations of the same shape — true for transformer
    blocks and most residual stages; reshape layers belong inside a stage).
    """
    nstage = mesh.shape[axis]
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != nstage:
            raise ValueError(
                "stacked_params leading dim %d must equal the %r mesh axis "
                "size %d (one stage per device)" % (leaf.shape[0], axis,
                                                   nstage))
    n_micro = microbatches.shape[0]
    ticks = n_micro + nstage - 1
    fwd_perm = [(i, (i + 1) % nstage) for i in range(nstage)]

    def per_shard(params_blk, xs):
        # params_blk leaves have leading dim 1 (this stage); xs is the
        # full microbatch stream (replicated)
        params = jax.tree_util.tree_map(lambda p: p[0], params_blk)
        stage = lax.axis_index(axis)
        is_first = stage == 0
        is_last = stage == nstage - 1

        act0 = jnp.zeros_like(xs[0])

        def tick(carry, t):
            act = carry
            # stage 0 ingests microbatch t while valid; later stages use
            # the activation shifted in last tick
            feed_idx = jnp.minimum(t, n_micro - 1)
            inp = jnp.where(is_first, xs[feed_idx], act)
            out = stage_fn(params, inp)
            # the last stage emits microbatch t-(nstage-1) at this tick;
            # psum over the ring broadcasts it (other stages contribute 0)
            emit_valid = (t >= nstage - 1) & is_last
            emitted = lax.psum(
                jnp.where(emit_valid, out, jnp.zeros_like(out)), axis)
            act_next = lax.ppermute(out, axis, fwd_perm)
            return act_next, emitted

        _, outs = lax.scan(tick, act0, jnp.arange(ticks))
        return outs[nstage - 1:]          # drop the fill phase

    in_specs = (jax.tree_util.tree_map(lambda _: P(axis), stacked_params),
                P())
    fn = jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                       out_specs=P(), check_vma=False)
    return fn(stacked_params, microbatches)


# ---------------------------------------------------------------------------
# heterogeneous stages: a model (embedding / blocks / head) trains pipelined
# ---------------------------------------------------------------------------

def pipeline_train_step(stage_fns, params, inputs, labels, mesh: Mesh,
                        axis: str = "pp"):
    """Mean loss of a heterogeneous GPipe pipeline — differentiable.

    Unlike :func:`pipeline_apply` (one shared ``stage_fn`` over stacked
    params), stages here are arbitrary per-stage functions with their own
    parameter pytrees, so an embedding→blocks→head model runs end-to-end:

    * ``stage_fns[0](params[0], x_mb) -> act`` — ingests a microbatch of
      raw inputs (e.g. token ids), emits the wire activation;
    * ``stage_fns[i](params[i], act) -> act`` — middle stages; every
      stage's output must share ONE wire shape (the ppermute payload);
    * ``stage_fns[-1](params[-1], act, y_mb) -> scalar`` — the head:
      per-microbatch mean loss.

    Each device runs only its own stage (``lax.switch`` on the stage
    index); microbatches stream through the ``ppermute`` ring with the
    classic fill+drain schedule, losses leave through a ``psum``.  The
    returned scalar is the mean loss over all ``n_micro`` microbatches,
    replicated — so ``jax.grad`` through this function yields, via
    shard_map's replicated-input transpose, full parameter gradients
    (each device contributes exactly its stage's terms).

    ``params`` is a tuple of per-stage pytrees, replicated over the mesh
    (the memory-scaled layout for *homogeneous* stacks remains
    ``pipeline_apply``, whose stacked params live one-stage-per-device).
    ``inputs``/``labels`` are ``(n_micro, mb, ...)`` streams.
    """
    nstage = mesh.shape[axis]
    if len(stage_fns) != nstage:
        raise ValueError("need exactly %d stage fns (one per %r slice), "
                         "got %d" % (nstage, axis, len(stage_fns)))
    # graftlint: disable-next=retrace-shape-branch -- stage-count
    # validation: raises on mismatch, no per-shape code paths
    if len(params) != nstage:
        raise ValueError("need %d per-stage param trees, got %d"
                         % (nstage, len(params)))
    n_micro = inputs.shape[0]
    ticks = n_micro + nstage - 1
    fwd_perm = [(i, (i + 1) % nstage) for i in range(nstage)]
    act_shape = jax.eval_shape(stage_fns[0], params[0], inputs[0])

    def per_shard(params, xs, ys):
        stage = lax.axis_index(axis)
        is_last = stage == nstage - 1

        def mk_branch(i):
            if i == 0:
                return lambda op: (stage_fns[0](params[0], op[1]),
                                   jnp.float32(0.0))
            if i == nstage - 1:
                return lambda op: (
                    jnp.zeros(act_shape.shape, act_shape.dtype),
                    stage_fns[-1](params[-1], op[0],
                                  op[2]).astype(jnp.float32))
            return lambda op: (stage_fns[i](params[i], op[0]),
                               jnp.float32(0.0))

        branches = [mk_branch(i) for i in range(nstage)]
        act0 = jnp.zeros(act_shape.shape, act_shape.dtype)

        def tick(act, t):
            feed = jnp.minimum(t, n_micro - 1)
            lab = jnp.clip(t - (nstage - 1), 0, n_micro - 1)
            out, loss = lax.switch(stage, branches,
                                   (act, xs[feed], ys[lab]))
            emit = ((t >= nstage - 1) & is_last).astype(jnp.float32)
            loss_t = lax.psum(loss * emit, axis)
            return lax.ppermute(out, axis, fwd_perm), loss_t

        _, losses = lax.scan(tick, act0, jnp.arange(ticks))
        return jnp.sum(losses) / n_micro

    in_specs = (jax.tree_util.tree_map(lambda _: P(), params), P(), P())
    fn = jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                       out_specs=P(), check_vma=False)
    return fn(params, inputs, labels)


class PipelineTrainer:
    """Train a heterogeneous-stage model pipelined over a ``pp`` mesh axis.

    The Trainer-shaped consumer of :func:`pipeline_train_step`: holds the
    per-stage params, compiles ONE jitted program per input signature
    (value_and_grad through the pipeline + an mxnet-style optimizer
    update on every leaf, buffers donated), and steps in place::

        trainer = PipelineTrainer(stage_fns, params,
                                  mx.optimizer.SGD(learning_rate=0.1), mesh)
        loss = trainer.step(micro_inputs, micro_labels)   # params updated
    """

    def __init__(self, stage_fns, params, optimizer, mesh: Mesh,
                 axis: str = "pp"):
        self._fns = list(stage_fns)
        self._mesh = mesh
        self._axis = axis
        self._opt = optimizer
        from ..ndarray import NDArray
        from ..ndarray.ndarray import _wrap
        leaves, self._treedef = jax.tree_util.tree_flatten(tuple(params))
        # own copies: step() donates its param buffers, which must never
        # invalidate the caller's arrays
        self.params = [jnp.array(l, copy=True) for l in leaves]
        leaves = self.params
        self._states = []
        for i, leaf in enumerate(leaves):
            st = optimizer.create_state(i, _wrap(jnp.asarray(leaf)))
            st_leaves, _ = jax.tree_util.tree_flatten(
                st, is_leaf=lambda x: isinstance(x, NDArray))
            self._states.append([s._data if isinstance(s, NDArray) else s
                                 for s in st_leaves])
        self._t = 0
        self._jitted = {}
        self._lr_key = None
        self._lr_dev = None
        self._t_dev = None

    def _build(self):
        fns, treedef, axis, mesh = (self._fns, self._treedef, self._axis,
                                    self._mesh)
        opt = self._opt
        steps = [opt.make_step(i) for i in range(len(self.params))]

        def step_fn(leaves, states, t, lr, xs, ys):
            def loss_of(leaves):
                params = jax.tree_util.tree_unflatten(treedef, leaves)
                return pipeline_train_step(fns, params, xs, ys, mesh, axis)

            loss, grads = jax.value_and_grad(loss_of)(leaves)
            new_leaves, new_states = [], []
            for i, (w, g) in enumerate(zip(leaves, grads)):
                # graftlint: disable-next=retrace-closure-array -- step
                # fns are per-slot constants; step_fn is jitted once per
                # trainer build by design
                res = steps[i](w, g, t, lr.astype(w.dtype), *states[i])
                # traced-t bias corrections are strong f32; pin the
                # carry (see optimizer.pin_update_dtypes)
                nw, ns = _pin_update_dtypes(res, w, states[i])
                new_leaves.append(nw)
                new_states.append(ns)
            return new_leaves, new_states, t + 1, loss

        return jax.jit(step_fn, donate_argnums=(0, 1, 2))

    def step(self, inputs, labels):
        key = (tuple(inputs.shape), str(inputs.dtype),
               tuple(labels.shape), str(labels.dtype))
        jfn = self._jitted.get(key)
        if jfn is None:
            jfn = self._jitted[key] = self._build()
        self._t += 1
        self._opt.num_update = max(self._opt.num_update, self._t)
        # device-resident lr/step-counter (no tiny per-call uploads on
        # the step's critical path; see DataParallelStep)
        lr_val = float(self._opt._get_lrs([0])[0])
        if lr_val != self._lr_key:
            self._lr_dev = jnp.asarray(lr_val, jnp.float32)
            self._lr_key = lr_val
        if self._t_dev is None:
            self._t_dev = jnp.asarray(self._t, jnp.int32)
        self.params, self._states, self._t_dev, loss = jfn(
            self.params, self._states, self._t_dev, self._lr_dev,
            inputs, labels)
        return loss

    def stage_params(self):
        """The current params as the per-stage tuple-of-pytrees."""
        return jax.tree_util.tree_unflatten(self._treedef, self.params)
