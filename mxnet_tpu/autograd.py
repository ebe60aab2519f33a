"""Imperative autograd: record/pause scopes, gradient tape, backward.

TPU-native replacement for the reference's ``Imperative`` runtime tape
(``src/imperative/imperative.cc:193`` RecordOp / ``:280`` Backward; Python API
``python/mxnet/autograd.py:122-368``).  The reference builds an nnvm grad
graph from per-op FGradient attributes and executes it on the dependency
engine; here every recorded op is a *pure JAX function*, so backward is a
reverse-topological sweep calling ``jax.vjp`` per node — XLA differentiates
the kernels, the tape only routes cotangents.

Key semantics preserved from the reference:
* ``record()/pause()`` scopes with ``train_mode`` flags (``is_training``).
* ``attach_grad(grad_req)`` on NDArray; grad_req in {write, add, null}.
* ``backward(head_grads)`` accumulates into ``.grad`` buffers.
* ``grad(heads, variables, create_graph)`` for higher-order gradients —
  with ``create_graph=True`` the vjp computations are themselves recorded
  ops, so they can be differentiated again (reference
  ``tests/python/unittest/test_higher_order_grad.py`` strategy).
* asynchronous-exception parity is not needed: JAX raises at dispatch.
"""
from __future__ import annotations

import threading
from typing import List, Optional, Sequence

import jax

__all__ = [
    "record", "pause", "train_mode", "predict_mode",
    "is_recording", "is_training", "set_recording", "set_training",
    "mark_variables", "backward", "grad", "get_symbol", "Function",
]


class _State(threading.local):
    def __init__(self):
        self.recording = False
        self.training = False


_STATE = _State()


def is_recording() -> bool:
    return _STATE.recording


def is_training() -> bool:
    return _STATE.training


def set_recording(flag: bool) -> bool:
    prev, _STATE.recording = _STATE.recording, bool(flag)
    return prev


def set_training(flag: bool) -> bool:
    prev, _STATE.training = _STATE.training, bool(flag)
    return prev


class _Scope:
    def __init__(self, recording: Optional[bool], training: Optional[bool]):
        self._rec, self._train = recording, training

    def __enter__(self):
        if self._rec is not None:
            self._prev_rec = set_recording(self._rec)
        if self._train is not None:
            self._prev_train = set_training(self._train)
        return self

    def __exit__(self, *a):
        if self._rec is not None:
            set_recording(self._prev_rec)
        if self._train is not None:
            set_training(self._prev_train)
        return False


def record(train_mode: bool = True) -> _Scope:
    """Scope in which executed ops are recorded on the tape (reference
    autograd.py:122)."""
    return _Scope(True, train_mode)


def pause(train_mode: bool = False) -> _Scope:
    return _Scope(False, train_mode)


def train_mode() -> _Scope:
    return _Scope(None, True)


def predict_mode() -> _Scope:
    return _Scope(None, False)


# ---------------------------------------------------------------------------
# Tape structure
# ---------------------------------------------------------------------------

class AGInfo:
    """Tape metadata attached to an NDArray (reference
    ``include/mxnet/imperative.h:42-79`` AGInfo).

    Either a *variable* (leaf with a grad buffer: node is None) or an output
    slot of a recorded op node.
    """

    __slots__ = ("node", "index", "_grad", "grad_req", "array_ref")

    def __init__(self, node: Optional["Node"], index: int = 0,
                 grad=None, grad_req: str = "write", array_ref=None):
        self.node = node
        self.index = index
        self.grad = grad          # NDArray grad buffer (variables only)
        self.grad_req = grad_req  # write | add | null
        self.array_ref = array_ref

    @property
    def grad(self):
        """The grad buffer; given as a function, it is made at first use."""
        if callable(self._grad):
            self._grad = self._grad()
        return self._grad

    @grad.setter
    def grad(self, value):
        self._grad = value


class Node:
    """A recorded op invocation.

    Stores the pure function, the input *values at record time* (so later
    in-place mutation of the input NDArrays can't corrupt the tape — the
    reference achieves the same with engine var versioning), and the AGInfo
    links of the inputs for cotangent routing.
    """

    __slots__ = ("fn", "in_values", "in_ag", "n_outputs", "out_shapes", "name")

    def __init__(self, fn, in_values, in_ag, n_outputs, name=""):
        self.fn = fn
        self.in_values = list(in_values)
        self.in_ag = list(in_ag)  # AGInfo | None per input
        self.n_outputs = n_outputs
        self.name = name

    def __repr__(self):
        return "Node(%s)" % (self.name,)


def record_op(fn, input_arrays, output_arrays, name: str = "") -> None:
    """Record one op call on the tape. Called by the dispatcher when
    ``is_recording()`` (reference Imperative::RecordOp imperative.cc:193)."""
    in_ag = [getattr(x, "_ag", None) for x in input_arrays]
    if not any(a is not None for a in in_ag):
        return  # nothing upstream requires grad — skip (tape stays small)
    node = Node(fn, [x._data for x in input_arrays], in_ag,
                len(output_arrays), name=name)
    for i, out in enumerate(output_arrays):
        out._ag = AGInfo(node, i)


def mark_variables(variables, gradients, grad_reqs="write") -> None:
    """Attach grad buffers to arrays (reference imperative.cc:123
    MarkVariables; Python mark_variables)."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for var, g, req in zip(variables, gradients, grad_reqs):
        var._ag = AGInfo(None, 0, grad=g, grad_req=req, array_ref=var)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _toposort(heads_ag) -> List[Node]:
    order: List[Node] = []
    seen = set()
    # iterative DFS (tapes can be deep: RNN steps)
    stack = [(ag.node, False) for ag in heads_ag if ag is not None and ag.node is not None]
    while stack:
        node, processed = stack.pop()
        if processed:
            order.append(node)
            continue
        if id(node) in seen:
            continue
        seen.add(id(node))
        stack.append((node, True))
        for ag in node.in_ag:
            if ag is not None and ag.node is not None and id(ag.node) not in seen:
                stack.append((ag.node, False))
    return order  # already reverse-finished = topological order of completion


def backward(heads, head_grads=None, retain_graph: bool = False,
             train_mode: bool = True, create_graph: bool = False):
    """Run backward from ``heads``, accumulating into variables' ``.grad``.

    Reference: ``Imperative::Backward`` (imperative.cc:280) building the grad
    graph + RunGraph (:517).  Here: reverse-topo per-node ``jax.vjp``.
    """
    from .ndarray.ndarray import NDArray, _wrap  # late import (cycle)
    import jax.numpy as jnp

    if isinstance(heads, NDArray):
        heads = [heads]
        if head_grads is not None and not isinstance(head_grads, (list, tuple)):
            head_grads = [head_grads]
    if head_grads is None:
        head_grads = [None] * len(heads)

    heads_ag = []
    for h in heads:
        ag = getattr(h, "_ag", None)
        if ag is None:
            raise ValueError(
                "cannot differentiate a head that is not the output of a "
                "recorded computation (did you forget autograd.record()?)")
        heads_ag.append(ag)

    # cotangent accumulators: id(node) -> [per-output cotangent or None]
    cotan = {}
    var_acc = {}  # id(AGInfo) -> accumulated grad value
    var_ag = {}   # id(AGInfo) -> AGInfo

    def _acc_slot(store, key, idx, n, value):
        lst = store.get(key)
        if lst is None:
            lst = [None] * n
            store[key] = lst
        lst[idx] = value if lst[idx] is None else lst[idx] + value

    def _acc_var(ag, value):
        from .ndarray.sparse import RowSparseNDArray as _RS, \
            merge_row_sparse as _merge
        k = id(ag)
        var_ag[k] = ag
        if k not in var_acc:
            var_acc[k] = value
            return
        prev = var_acc[k]
        prev_sp = isinstance(prev, _RS) and prev.has_parts
        val_sp = isinstance(value, _RS) and value.has_parts
        # graftlint: disable-next=trace-tracer-branch -- _RS part flags
        # are Python bools on the wrapper, not traced values
        if prev_sp and val_sp:
            var_acc[k] = _merge(prev, value)
        # graftlint: disable-next=trace-tracer-branch -- _RS part flags
        # are Python bools on the wrapper, not traced values
        elif prev_sp or val_sp:
            # mixed sparse+dense: correctness first — densify
            pd = prev._data if isinstance(prev, NDArray) else prev
            vd = value._data if isinstance(value, NDArray) else value
            var_acc[k] = pd + vd
        else:
            var_acc[k] = prev + value

    for h, hg, ag in zip(heads, head_grads, heads_ag):
        if hg is not None:
            g = hg if create_graph else hg._data
        else:
            g = jnp.ones(h.shape, h.dtype)
            if create_graph:
                from .ndarray.ndarray import _wrap as __wrap
                g = __wrap(g)
        if ag.node is None:
            _acc_var(ag, g)
        else:
            _acc_slot(cotan, id(ag.node), ag.index, ag.node.n_outputs, g)

    order = _toposort(heads_ag)

    for node in reversed(order):
        outs_ct = cotan.pop(id(node), None)
        if outs_ct is None:
            continue
        sparse_vjp = getattr(node.fn, "_sparse_vjp", None)
        if create_graph:
            in_grads = _vjp_recorded(node, outs_ct)
        elif sparse_vjp is not None:
            # sparse-gradient op (Embedding(sparse_grad=True)): the weight
            # gradient comes back as a parts-backed RowSparseNDArray whose
            # size scales with the batch's live rows, not the table
            in_grads = sparse_vjp(node.in_values, outs_ct)
        else:
            primals, vjp_fn = jax.vjp(node.fn, *node.in_values)
            # fill missing cotangents with zeros of the primal out shape
            if isinstance(primals, (tuple, list)):
                full = [c if c is not None else jnp.zeros(p.shape, p.dtype)
                        for c, p in zip(outs_ct, primals)]
                in_grads = vjp_fn(tuple(full))
            else:
                in_grads = vjp_fn(outs_ct[0])
        for ag, g in zip(node.in_ag, in_grads):
            if ag is None or g is None:
                continue
            from .ndarray.sparse import RowSparseNDArray as _RS
            # graftlint: disable-next=trace-tracer-branch -- has_parts
            # is a Python bool on the sparse wrapper, not traced
            if isinstance(g, _RS) and g.has_parts and ag.node is None:
                # stays sparse through accumulation — leaves only: a
                # cotangent routed into another recorded node must be a
                # plain array for that node's jax.vjp
                gval = g
            # graftlint: disable-next=trace-tracer-branch -- has_parts
            # is a Python bool on the sparse wrapper, not traced
            elif isinstance(g, _RS) and g.has_parts:
                gval = g._data  # non-leaf target: densify
            else:
                # keep NDArrays (with tape links) for grad-of-grad graphs
                gval = g if (create_graph and isinstance(g, NDArray)) else (
                    g._data if isinstance(g, NDArray) else g)
            if ag.node is None:  # variable leaf
                if ag.grad_req == "null":
                    continue
                _acc_var(ag, gval)
            else:
                _acc_slot(cotan, id(ag.node), ag.index, ag.node.n_outputs, gval)

    # write/add into grad buffers
    from .ndarray.sparse import RowSparseNDArray as _RSW, \
        make_row_sparse_inplace as _mk_rs
    for k, ag in var_ag.items():
        if ag.grad is None:
            continue
        accum = var_acc[k]
        # graftlint: disable-next=trace-tracer-branch -- has_parts is a
        # Python bool on the sparse wrapper, not traced
        if isinstance(accum, _RSW) and accum.has_parts:
            if ag.grad_req == "add":
                # accumulate-into-buffer requires dense arithmetic
                ag.grad._data = ag.grad._data + accum._data
            else:
                _mk_rs(ag.grad, accum.__dict__["_sp_values"],
                       accum.__dict__["_sp_indices"], accum.shape)
            continue
        if isinstance(accum, NDArray):
            # create_graph: transfer both value and tape link so the grad
            # buffer itself is differentiable (higher-order autograd)
            if ag.grad_req == "add":
                ag.grad._data = ag.grad._data + accum._data
            else:
                ag.grad._data = accum._data.astype(ag.grad.dtype).reshape(ag.grad.shape)
            ag.grad._ag = getattr(accum, "_ag", None)
            continue
        accum = jnp.asarray(accum, dtype=ag.grad.dtype).reshape(ag.grad.shape)
        if ag.grad_req == "add":
            ag.grad._data = ag.grad._data + accum
        else:
            ag.grad._data = accum

    # retain_graph needs no action: tape nodes are plain Python objects
    # garbage-collected with the arrays that reference them, and backward is
    # re-runnable because nodes store their input values.


def _vjp_recorded(node: Node, outs_ct):
    """Backward of one node executed *through the dispatcher* so it is itself
    recorded (enables create_graph / higher-order grad)."""
    from .ndarray.ndarray import NDArray, _wrap, invoke_fn
    import jax.numpy as jnp

    n_in = len(node.in_values)
    present = [c is not None for c in outs_ct]  # static cotangent mask

    def vjp_op(*args):
        ins, cts = args[:n_in], args[n_in:]
        primals, vjp_fn = jax.vjp(node.fn, *ins)
        if isinstance(primals, (tuple, list)):
            full = [c if ok else jnp.zeros(p.shape, p.dtype)
                    for c, ok, p in zip(cts, present, primals)]
            grads = vjp_fn(tuple(full))
        else:
            grads = vjp_fn(cts[0])
        return tuple(grads)

    # Reconstruct NDArray views of the recorded inputs, preserving tape links.
    in_arrs = []
    for v, ag in zip(node.in_values, node.in_ag):
        a = _wrap(v)
        if ag is not None:
            a._ag = ag
        in_arrs.append(a)
    ct_arrs = []
    for c in outs_ct:
        if isinstance(c, NDArray):
            ct_arrs.append(c)  # keep tape link for grad-of-grad
        else:
            ct_arrs.append(_wrap(c if c is not None else jnp.zeros(1)))
    outs = invoke_fn(vjp_op, in_arrs + ct_arrs, name="_backward_%s" % node.name,
                     n_outputs=n_in)
    return outs


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """Return gradients of heads w.r.t. variables without touching ``.grad``
    buffers (reference autograd.py:273)."""
    from .ndarray.ndarray import NDArray, zeros

    if isinstance(heads, NDArray):
        heads = [heads]
    single = isinstance(variables, NDArray)
    if single:
        variables = [variables]
    # Temporarily redirect each variable's grad buffer inside its EXISTING
    # AGInfo (tape nodes hold references to that object, so swapping the
    # object would detach the variable from the recorded graph).
    saved = []
    bufs = []
    for v in variables:
        ag = getattr(v, "_ag", None)
        if ag is None or ag.node is not None:
            raise ValueError(
                "autograd.grad requires variables marked via attach_grad/"
                "mark_variables (reference semantics)")
        buf = zeros(v.shape, ctx=v.ctx, dtype=v.dtype)
        saved.append((ag, ag.grad, ag.grad_req))
        ag.grad, ag.grad_req = buf, "write"
        bufs.append(buf)
    try:
        backward(heads, head_grads, retain_graph=bool(retain_graph),
                 train_mode=train_mode, create_graph=create_graph)
    finally:
        for ag, g, req in saved:
            ag.grad, ag.grad_req = g, req
    return bufs[0] if single else bufs


def get_symbol(x):
    """Reference autograd.get_symbol: recover a symbolic graph from a recorded
    array. Provided via the Symbol tracing layer."""
    raise NotImplementedError(
        "get_symbol: use mxnet_tpu.symbol tracing (sym.var + block(sym)) instead")


class Function:
    """Custom differentiable function (reference autograd.py:368 Function,
    C++ ``c_api_function.cc``).

    Subclass and implement ``forward(self, *inputs)`` and
    ``backward(self, *output_grads)`` using NDArray ops.
    """

    def __init__(self):
        self._saved = None

    def save_for_backward(self, *arrays):
        self._saved = arrays

    @property
    def saved_tensors(self):
        return self._saved

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray, _wrap

        with pause():
            outputs = self.forward(*inputs)
        single = isinstance(outputs, NDArray)
        outs = [outputs] if single else list(outputs)

        if is_recording():
            self_ref = self

            def fn(*in_values):
                # pure wrapper: rerun forward on raw values
                ins = [_wrap(v) for v in in_values]
                with pause():
                    res = self_ref.forward(*ins)
                res = [res] if isinstance(res, NDArray) else list(res)
                vals = tuple(r._data for r in res)
                return vals if len(vals) > 1 else vals[0]

            # custom vjp: route through user backward
            import jax.numpy as jnp

            def fn_fwd(*in_values):
                return fn(*in_values), in_values

            def fn_bwd(res, cts):
                ins = res
                cts = cts if isinstance(cts, tuple) else (cts,)
                ct_arrs = [_wrap(c) for c in cts]
                with pause():
                    gs = self_ref.backward(*ct_arrs)
                gs = [gs] if isinstance(gs, NDArray) else list(gs)
                return tuple(g._data for g in gs)

            cfn = jax.custom_vjp(fn)
            cfn.defvjp(fn_fwd, fn_bwd)
            record_op(cfn, list(inputs), outs, name=type(self).__name__)
        return outputs
