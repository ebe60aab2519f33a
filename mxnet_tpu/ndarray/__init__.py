"""``mx.nd`` namespace: NDArray + every registered op as a function.

Mirrors the reference's import-time codegen of op wrappers from the C op
registry (``python/mxnet/ndarray/register.py:31-43``) — here via PEP 562
module ``__getattr__`` resolving names against the op registry lazily.
"""
from __future__ import annotations

from ..context import Context, current_context
from ..ops.registry import get_cast_policy, get_op, list_ops
from .ndarray import (  # noqa: F401
    NDArray, array, empty, zeros, ones, full, arange, linspace, eye,
    concat, stack, add_n, split, waitall, invoke_fn, from_numpy, from_jax,
    _wrap,
)
from .utils import save, load  # noqa: F401
from . import contrib  # noqa: F401
from . import sparse  # noqa: F401
from . import random  # noqa: F401
from . import linalg  # noqa: F401

_FUNC_CACHE = {}


def _make_op_func(op):
    """Build a python-callable wrapper for a registered op.

    NDArray-valued positional/keyword args become op inputs; everything else
    is a static attribute.  Handles ``out=`` (in-place rebind) and ``ctx=``
    (placement for source ops) — the generic signature contract the
    reference generates from dmlc::Parameter schemas.
    """
    cached = _FUNC_CACHE.get(op.name)
    if cached is not None:
        return cached

    def func(*args, **kwargs):
        out = kwargs.pop("out", None)
        kwargs.pop("name", None)  # symbol-compat no-op
        ctx = kwargs.pop("ctx", None)
        if isinstance(ctx, str):
            dt, _, di = ctx.partition("(")
            ctx = Context(dt, int(di.rstrip(")")) if di else 0)
        if op.needs_training and "training" not in kwargs:
            # wire autograd train/predict mode into mode-dependent ops
            # (reference: OpContext.is_train from Imperative train_mode flag)
            from .. import autograd as _ag
            kwargs["training"] = _ag.is_training()
        pos_idx = [i for i, a in enumerate(args) if isinstance(a, NDArray)]
        arrays = [args[i] for i in pos_idx]
        kw_keys = [k for k, v in kwargs.items() if isinstance(v, NDArray)]
        arrays += [kwargs[k] for k in kw_keys]
        policy = get_cast_policy()
        if policy is not None and arrays:
            static_attrs = {k: v for k, v in kwargs.items()
                            if not isinstance(v, NDArray)}
            tgt = policy(op.name, [a.dtype for a in arrays], static_attrs)
            if tgt is not None:
                import numpy as _onp
                arrays = [a.astype(tgt)
                          if (_onp.issubdtype(a.dtype, _onp.floating)
                              or str(a.dtype) == "bfloat16")
                          and str(a.dtype) != str(tgt) else a
                          for a in arrays]
        if op.needs_rng:
            from .. import random as _random
            key = _random.next_key()

        def fn(*vals):
            full_args = list(args)
            kw = dict(kwargs)
            j = 0
            for i in pos_idx:
                full_args[i] = vals[j]
                j += 1
            for k in kw_keys:
                kw[k] = vals[j]
                j += 1
            if op.needs_rng:
                kw.pop("ctx", None)
                return op.fn(key, *full_args, **kw)
            return op.fn(*full_args, **kw)

        sfactory = getattr(op.fn, "_sparse_vjp_factory", None)
        if sfactory is not None:
            static_kwargs = {k: v for k, v in kwargs.items()
                             if k not in kw_keys}
            shook = sfactory(static_kwargs)
            if shook is not None:  # only when sparse_grad requested
                fn._sparse_vjp = shook
        return invoke_fn(fn, arrays, name=op.name, out=out,
                         n_outputs=op.num_outputs, ctx=ctx,
                         record=op.differentiable)

    func.__name__ = op.name
    func.__doc__ = op.doc
    _FUNC_CACHE[op.name] = func
    return func


def __getattr__(name):
    op = get_op(name)
    if op is None:
        raise AttributeError("module 'ndarray' has no attribute %r" % name)
    return _make_op_func(op)


def __dir__():
    return sorted(set(list(globals().keys()) + list_ops()))
