"""Python custom operators: ``mx.operator.CustomOp`` / ``CustomOpProp``.

Capability parity with the reference's custom-op extension point
(``python/mxnet/operator.py`` CustomOp/CustomOpProp/register,
``src/operator/custom/custom-inl.h:52`` — a registry plus a dedicated
worker thread pushing async engine callbacks).

TPU-native mapping (SURVEY.md §7): the user's numpy ``forward``/``backward``
run on the host behind ``jax.pure_callback`` — XLA treats the callback as an
opaque host call with declared result shapes, so a Custom op composes with
jit/grad like any other op.  The gradient contract is a ``jax.custom_vjp``
whose backward is a second host callback into ``CustomOp.backward``.  The
op is registered into the operator registry as ``Custom``, making it
visible to every frontend the registry feeds: ``mx.nd.Custom(...)``,
``mx.sym.Custom(...)``, Gluon blocks, and Module graphs.

Contract notes vs the reference:

* ``in_data``/``out_data``/``in_grad``... are host buffer objects with the
  NDArray surface user code actually touches (``asnumpy``, ``shape``,
  ``dtype``, slicing, ``self.assign``-style writes).
* auxiliary states are materialized as zero buffers per call; persistent
  aux mutation (rare in reference custom ops) is not carried across calls.
* ``req`` is always ``'write'`` — the functional runtime has no in-place
  gradient accumulation; ``'add'`` is applied by the autodiff system.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Tuple

import numpy as onp

from .base import MXNetError
from .ops.registry import register as _register_op

__all__ = ["CustomOp", "CustomOpProp", "register", "get_all_registered",
           "custom"]


class _HostBuf:
    """Host-side stand-in for NDArray inside CustomOp callbacks."""

    __slots__ = ("_arr",)

    def __init__(self, arr):
        self._arr = onp.asarray(arr)

    # the NDArray surface custom-op bodies use
    def asnumpy(self):
        return self._arr

    @property
    def shape(self):
        return self._arr.shape

    @property
    def dtype(self):
        return self._arr.dtype

    def __getitem__(self, key):
        return self._arr[key]

    def __setitem__(self, key, value):
        self._arr[key] = _to_numpy(value)

    def __iadd__(self, value):
        self._arr += _to_numpy(value)
        return self

    def __repr__(self):
        return "_HostBuf(%r)" % (self._arr.shape,)


def _to_numpy(v):
    if isinstance(v, _HostBuf):
        return v._arr
    if hasattr(v, "asnumpy"):
        return v.asnumpy()
    return onp.asarray(v)


class CustomOp:
    """Base class for python operators (reference operator.py:428)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError()

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        raise NotImplementedError()

    def assign(self, dst, req, src):
        """Write ``src`` into ``dst`` honoring the request type."""
        if req == "null":
            return
        if req in ("write", "inplace"):
            dst[:] = src
        elif req == "add":
            dst[:] = _to_numpy(dst) + _to_numpy(src)
        else:
            raise MXNetError("unknown req %r" % (req,))


class CustomOpProp:
    """Shape/type/arity declaration for a custom op (reference
    operator.py:474)."""

    def __init__(self, need_top_grad=True):
        self.need_top_grad_ = need_top_grad

    def list_arguments(self) -> List[str]:
        return ["data"]

    def list_outputs(self) -> List[str]:
        return ["output"]

    def list_auxiliary_states(self) -> List[str]:
        return []

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]] * len(self.list_outputs()), \
            [()] * len(self.list_auxiliary_states())

    def infer_type(self, in_type):
        return in_type, [in_type[0]] * len(self.list_outputs()), \
            [in_type[0]] * len(self.list_auxiliary_states())

    def declare_backward_dependency(self, out_grad, in_data, out_data):
        deps = []
        if self.need_top_grad_:
            deps.extend(out_grad)
        deps.extend(in_data)
        deps.extend(out_data)
        return deps

    def create_operator(self, ctx, in_shapes, in_dtypes) -> CustomOp:
        raise NotImplementedError()


_PROP_REGISTRY: Dict[str, type] = {}
_CACHE_LOCK = threading.Lock()
_RUNNER_CACHE: Dict[Tuple, "_CustomRunner"] = {}


def register(op_type: str):
    """Decorator: register a CustomOpProp subclass under ``op_type``
    (reference operator.py register)."""

    def _do(prop_cls):
        if not issubclass(prop_cls, CustomOpProp):
            raise MXNetError(
                "register('%s') expects a CustomOpProp subclass" % op_type)
        _PROP_REGISTRY[op_type] = prop_cls
        return prop_cls

    return _do


def get_all_registered():
    return sorted(_PROP_REGISTRY)


class _CustomRunner:
    """One (op_type, attrs, shapes, dtypes, is_train) specialization:
    resolved shapes/types plus the custom_vjp-wrapped callback pair."""

    def __init__(self, op_type, attr_items, in_shapes, in_dtypes, is_train):
        import jax

        if op_type not in _PROP_REGISTRY:
            raise MXNetError(
                "Custom op type %r is not registered (known: %s)"
                % (op_type, get_all_registered()))
        attrs = dict(attr_items)
        self.prop = _PROP_REGISTRY[op_type](**attrs)
        names = self.prop.list_arguments()
        if len(in_shapes) != len(names):
            raise MXNetError(
                "Custom(%s) expects %d inputs %s, got %d"
                % (op_type, len(names), names, len(in_shapes)))
        shapes = self.prop.infer_shape([list(s) for s in in_shapes])
        in_s, out_s = shapes[0], shapes[1]
        aux_s = shapes[2] if len(shapes) > 2 else []
        types = self.prop.infer_type(list(in_dtypes))
        out_t = types[1]
        aux_t = types[2] if len(types) > 2 else []
        self.in_shapes = [tuple(s) for s in in_s]
        self.out_shapes = [tuple(s) for s in out_s]
        self.aux_shapes = [tuple(s) for s in (aux_s or [])]
        self.in_dtypes = list(in_dtypes)
        self.out_dtypes = [onp.dtype(t) for t in out_t]
        self.aux_dtypes = [onp.dtype(t) for t in (aux_t or [])]
        self.is_train = is_train
        self.n_in = len(self.in_shapes)
        self.n_out = len(self.out_shapes)
        self.op = self.prop.create_operator(
            None, self.in_shapes, self.in_dtypes)

        out_struct = tuple(jax.ShapeDtypeStruct(s, d) for s, d in
                           zip(self.out_shapes, self.out_dtypes))
        in_struct = tuple(jax.ShapeDtypeStruct(s, d) for s, d in
                          zip(self.in_shapes, self.in_dtypes))

        def _aux_bufs():
            return [_HostBuf(onp.zeros(s, d)) for s, d in
                    zip(self.aux_shapes, self.aux_dtypes)]

        def host_forward(*ins):
            in_bufs = [_HostBuf(a) for a in ins]
            out_bufs = [_HostBuf(onp.zeros(s, d)) for s, d in
                        zip(self.out_shapes, self.out_dtypes)]
            self.op.forward(self.is_train, ["write"] * self.n_out,
                            in_bufs, out_bufs, _aux_bufs())
            return tuple(b._arr.astype(d, copy=False) for b, d in
                         zip(out_bufs, self.out_dtypes))

        def host_backward(*flat):
            gouts = [_HostBuf(a) for a in flat[:self.n_out]]
            ins = [_HostBuf(a) for a in
                   flat[self.n_out:self.n_out + self.n_in]]
            outs = [_HostBuf(a) for a in flat[self.n_out + self.n_in:]]
            gin = [_HostBuf(onp.zeros(s, d)) for s, d in
                   zip(self.in_shapes, self.in_dtypes)]
            self.op.backward(["write"] * self.n_in, gouts, ins, outs,
                             gin, _aux_bufs())
            return tuple(b._arr.astype(d, copy=False) for b, d in
                         zip(gin, self.in_dtypes))

        def fwd_call(*ins):
            # graftlint: disable-next=trace-host-callback -- CustomOp IS
            # a host callback by design (the op body is user python)
            return jax.pure_callback(host_forward, out_struct, *ins,
                                     vmap_method="sequential")

        run = jax.custom_vjp(fwd_call)

        def _vjp_fwd(*ins):
            outs = fwd_call(*ins)
            return outs, (ins, outs)

        def _vjp_bwd(res, gouts):
            ins, outs = res
            # graftlint: disable-next=trace-host-callback -- CustomOp IS
            # a host callback by design
            return tuple(jax.pure_callback(
                host_backward, in_struct, *gouts, *ins, *outs,
                vmap_method="sequential"))

        run.defvjp(_vjp_fwd, _vjp_bwd)
        self.run = run

    def __call__(self, *ins):
        outs = self.run(*ins)
        return tuple(outs) if self.n_out > 1 else outs[0]


def _runner_for(op_type, attrs, arrays, is_train):
    in_shapes = tuple(tuple(a.shape) for a in arrays)
    in_dtypes = tuple(onp.dtype(str(a.dtype)) for a in arrays)
    is_train = bool(is_train)
    key = (op_type, tuple(sorted(attrs.items())), in_shapes, in_dtypes,
           is_train)
    with _CACHE_LOCK:
        runner = _RUNNER_CACHE.get(key)
        if runner is None:
            runner = _CustomRunner(op_type, tuple(sorted(attrs.items())),
                                   in_shapes, in_dtypes, is_train)
            _RUNNER_CACHE[key] = runner
    return runner


def _split_tensor_kwargs(op_type, attrs):
    """The reference's canonical call is keyword-form —
    ``Custom(data=x, op_type=...)`` — so array-valued kwargs are inputs,
    ordered by the prop's declared argument names; the rest are
    constructor attrs."""
    tensors = {k: v for k, v in attrs.items()
               if hasattr(v, "shape") and hasattr(v, "dtype")
               and not isinstance(v, (str, bytes))}
    static = {k: v for k, v in attrs.items() if k not in tensors}
    ordered = []
    if tensors:
        if op_type not in _PROP_REGISTRY:
            raise MXNetError(
                "Custom op type %r is not registered (known: %s)"
                % (op_type, get_all_registered()))
        names = _PROP_REGISTRY[op_type](**static).list_arguments()
        unknown = set(tensors) - set(names)
        if unknown:
            raise MXNetError(
                "Custom(%s): tensor kwargs %s are not in list_arguments %s"
                % (op_type, sorted(unknown), names))
        ordered = [tensors[n] for n in names if n in tensors]
    return ordered, static


@_register_op("Custom", aliases=("custom",), needs_training=True)
def custom(*inputs, op_type: str = "", training: bool = False, **attrs):
    """Invoke a registered python CustomOp (reference
    src/operator/custom/custom.cc).  ``op_type`` selects the registered
    CustomOpProp; tensor kwargs become inputs (keyword form), remaining
    attrs go to the prop constructor."""
    if not op_type:
        raise MXNetError("Custom requires op_type=<registered name>")
    kw_inputs, attrs = _split_tensor_kwargs(op_type, attrs)
    inputs = list(inputs) + kw_inputs
    runner = _runner_for(op_type, attrs, inputs, training)
    return runner(*inputs)
