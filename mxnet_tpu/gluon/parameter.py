"""Gluon Parameter / Constant / ParameterDict.

Reference: ``python/mxnet/gluon/parameter.py`` (1005 LoC) — Parameter with
deferred initialization, per-context copies, grad_req, and ParameterDict.

TPU-native redesign: a Parameter holds ONE logical NDArray.  The reference
keeps one copy per GPU and all-reduces gradients through KVStore; here
multi-device is expressed by *sharding/replicating the single array over a
``jax.sharding.Mesh``* (see ``mxnet_tpu.parallel``) — the jax.Array is the
multi-device object, so ``list_data()`` returns per-shard views only for API
parity.  Gradients live in a buffer attached via autograd.mark_variables,
so ``loss.backward()`` accumulates into ``param.grad()`` exactly like the
reference's ``kWriteTo``/``kAddTo`` req semantics.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from typing import Optional

import numpy as onp

from .. import autograd, initializer, telemetry
from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..ndarray import NDArray, zeros
from ..ndarray import ndarray as _nd_mod

__all__ = ["DeferredInitializationError", "Parameter", "Constant", "ParameterDict"]

# toggled by gluon.utils.materialize_params while tracing abstractly
_ABSTRACT_INIT = [False]


class DeferredInitializationError(MXNetError):
    """Raised when accessing a parameter whose shape is not yet known
    (reference parameter.py:45)."""


class Parameter:
    """A trainable array with lazy allocation (reference parameter.py:44).

    Parameters
    ----------
    name : str
    grad_req : {'write', 'add', 'null'}
    shape : tuple of int, 0 meaning unknown-until-first-forward
    dtype : numpy dtype
    init : Initializer or name
    """

    def __init__(self, name, grad_req="write", shape=None, dtype=onp.float32,
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = None
        if isinstance(shape, int):
            shape = (shape,)
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        self._data: Optional[NDArray] = None
        self._grad: Optional[NDArray] = None
        if not differentiable:
            grad_req = "null"
        self.grad_req = grad_req
        self._deferred_init = ()
        self._trainer = None
        self._stype = stype

    def __repr__(self):
        s = "Parameter {name} (shape={shape}, dtype={dtype})"
        return s.format(name=self.name, shape=self.shape, dtype=self.dtype)

    # ------------------------------------------------------------------
    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        assert req in ("write", "add", "null"), \
            "grad_req must be one of 'write', 'add', or 'null', but got '%s'" % req
        if not self._differentiable:
            req = "null"
        if self._grad_req == req:
            return
        self._grad_req = req
        if req == "null":
            self._grad = None
            if self._data is not None:
                self._data._ag = None
        elif self._data is not None:
            self._init_grad()

    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape) if new_shape is not None else None
            return
        unknown_ok = all(s1 in (0, s2) for s1, s2 in zip(self._shape, new_shape)) \
            and len(self._shape) == len(new_shape)
        if not unknown_ok:
            raise AssertionError(
                "Expected shape %s is incompatible with given shape %s for "
                "Parameter %s" % (str(new_shape), str(self._shape), self.name))
        self._shape = tuple(new_shape)

    # ------------------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=initializer.Uniform(),
                   force_reinit=False):
        """Allocate + fill (reference parameter.py initialize).  Unknown dims
        (0 in shape) defer until the first forward completes them."""
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = current_context()
        if isinstance(ctx, Context):
            ctx = [ctx]
        # init stays None when neither an explicit nor a param-own init is
        # set — then _init_impl uses default_init's name-suffix dispatch
        init = init if init is not None else self.init
        if self._shape is None or any(s <= 0 for s in self._shape):
            if self.allow_deferred_init:
                self._deferred_init = (init, ctx[0], default_init)
                return
            raise ValueError(
                "Cannot initialize Parameter '%s' because it has invalid shape: %s."
                % (self.name, str(self._shape)))
        self._init_impl(init, ctx[0], default_init)

    def _init_impl(self, init, ctx, default_init):
        self._deferred_init = ()
        import jax
        from ..context import Context, cpu as _cpu_ctx
        if _ABSTRACT_INIT[0]:
            # shape-inference trace (gluon.utils.materialize_params): give
            # the trace a placeholder; the real host-side initialization
            # runs after the trace completes
            import jax.numpy as jnp
            from ..ndarray.ndarray import _wrap
            self._data = _wrap(
                jnp.zeros(self._shape, onp.dtype(self.dtype)), _cpu_ctx())
            return
        # generate on the host (fast local kernel compiles — on an
        # accelerator backend every per-shape init op would compile over
        # the device link), then place with ONE transfer; jax RNG is
        # backend-independent so values are identical either way
        t0 = time.perf_counter()
        host = _cpu_ctx()
        from ..ndarray.ndarray import _wrap
        import jax.numpy as jnp
        with autograd.pause(), jax.default_device(host.jax_device):
            # host-numpy buffer → one transfer; avoids an XLA fill compile
            # per parameter shape
            data = _wrap(jnp.asarray(
                onp.zeros(self._shape, dtype=onp.dtype(self.dtype))), host)
            desc = initializer.InitDesc(self.name)
            if init is not None:
                # param-specific init bypasses the name-suffix dispatch
                # (reference: InitDesc attrs['__init__'] mechanism)
                fn = initializer.create(init)
                if isinstance(fn, initializer.Initializer):
                    fn._init_weight(desc, data)
                else:
                    fn(desc, data)
            else:
                initializer.create(default_init)(desc, data)
            if data._data.dtype != onp.dtype(self.dtype):
                data._data = jnp.asarray(
                    onp.asarray(data._data).astype(self.dtype))
        if ctx is not None and Context(ctx) != host:
            data = data.as_in_context(Context(ctx))
        self._data = data
        if self._grad_req != "null":
            self._init_grad()
        # the host draw and the CALL that places it (a transfer's end is
        # not waited for); aggregate only: a model has hundreds of these
        telemetry.observe("gluon.param.init", time.perf_counter() - t0)

    def _init_grad(self):
        """The eager gradient buffer is made when first READ (an eager
        ``backward``, ``grad()``) and not before: a compiled train step
        (``parallel.DataParallelStep``) computes its gradients inside its
        program and never reads it, so under it a model's worth of zeros —
        1.4 GB for 696M bfloat16 parameters — is never put on the chip."""
        self._grad = None
        autograd.mark_variables([self._data], [self._make_grad],
                                self._grad_req)

    def _make_grad(self):
        if self._grad is None:
            self._grad = zeros(self._shape, ctx=self._data.ctx,
                               dtype=self.dtype)
        return self._grad

    def _finish_deferred_init(self, shape):
        """Complete a deferred init once the full shape is known (layer calls
        this from its ``infer_shape``; reference _finish_deferred_init)."""
        self.shape = shape
        if self._deferred_init:
            init, ctx, default_init = self._deferred_init
            self._init_impl(init, ctx, default_init)

    # ------------------------------------------------------------------
    def data(self, ctx=None) -> NDArray:
        """The parameter value (reference parameter.py data)."""
        if self._data is None:
            if self._deferred_init:
                raise DeferredInitializationError(
                    "Parameter '%s' has not been initialized yet because "
                    "initialization was deferred. Actual initialization happens "
                    "during the first forward pass." % self.name)
            raise RuntimeError(
                "Parameter '%s' has not been initialized. You should initialize "
                "parameters with Block.initialize()." % self.name)
        return self._data

    def list_data(self):
        return [self.data()]

    def grad(self, ctx=None) -> NDArray:
        if self._grad_req == "null" or self._data is None:
            raise RuntimeError(
                "Cannot get gradient array for Parameter '%s' because grad_req='null'"
                % self.name)
        return self._make_grad()

    def list_grad(self):
        return [self.grad()]

    def list_ctx(self):
        if self._data is None:
            if self._deferred_init:
                return [self._deferred_init[1]]
            raise RuntimeError("Parameter '%s' has not been initialized" % self.name)
        return [self._data.ctx]

    def set_data(self, data):
        """Replace the value, preserving the autograd leaf marking (reference
        set_data — mutation must not detach the grad buffer)."""
        if self._data is None:
            if not self._deferred_init:
                raise RuntimeError(
                    "Parameter '%s' has not been initialized" % self.name)
            self.shape = data.shape
            init, ctx, default_init = self._deferred_init
            self._init_impl(initializer.Constant(data), ctx, default_init)
            return
        shape = tuple(data.shape) if hasattr(data, "shape") else None
        if shape is not None and shape != tuple(self._shape):
            raise AssertionError(
                "Failed to update param '%s': shape %s does not match existing "
                "shape %s." % (self.name, shape, self._shape))
        if isinstance(data, NDArray):
            self._data._data = data._data.astype(onp.dtype(self.dtype))
        else:
            import jax.numpy as jnp
            self._data._data = jnp.asarray(data, dtype=self.dtype)

    def zero_grad(self):
        if self._grad is not None:
            self._grad._data = zeros(self._grad.shape, dtype=self._grad.dtype)._data

    def reset_ctx(self, ctx):
        if self._data is not None:
            t0 = time.perf_counter()
            self._data = self._data.as_in_context(
                ctx[0] if isinstance(ctx, (list, tuple)) else ctx)
            if self._grad_req != "null":
                self._init_grad()
            telemetry.observe("gluon.param.place", time.perf_counter() - t0)

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is not None:
            t0 = time.perf_counter()
            with autograd.pause():
                self._data._data = self._data._data.astype(onp.dtype(dtype))
                if self._grad is not None:
                    self._grad._data = self._grad._data.astype(onp.dtype(dtype))
                    autograd.mark_variables([self._data], [self._grad], self._grad_req)
            telemetry.observe("gluon.param.place", time.perf_counter() - t0)

    def _load_init(self, data, ctx=None):
        """Initialize directly from a loaded array (reference _load_init)."""
        if self._shape is not None and len(self._shape) == len(data.shape):
            self.shape = tuple(
                d if s == 0 else s for s, d in zip(self._shape, data.shape))
        else:
            self._shape = data.shape
        if self._data is not None:
            self.set_data(data)
        else:
            self._init_impl(initializer.Constant(data),
                            ctx or current_context(), None)

    def _set_trainer(self, trainer):
        """Associate with a Trainer (reference parameter.py _set_trainer;
        sparse row_sparse params require exactly one trainer there — dense
        arrays have no such restriction, so we only keep the link)."""
        self._trainer = trainer

    def var(self):
        """Symbol view of this parameter (for Symbol/Module interop)."""
        from .. import symbol
        return symbol.var(self.name, shape=self.shape, dtype=self.dtype,
                          init=self.init)


class Constant(Parameter):
    """Non-trainable constant (reference parameter.py:626)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = _nd_mod.array(value)
        self.value = value

        class _Init(initializer.Initializer):
            def _init_weight(self2, _, arr):
                value.copyto(arr)

        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=_Init(), differentiable=False)


class ParameterDict:
    """Ordered name→Parameter mapping with prefix + shared fallback
    (reference parameter.py:681)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    def __repr__(self):
        s = "%s(\n  %s\n)" % (
            self._prefix + " " if self._prefix else "",
            "\n  ".join(repr(v) for v in self._params.values()))
        return s

    def __getitem__(self, key):
        return self._params[key]

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def __contains__(self, key):
        return key in self._params

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    @property
    def prefix(self):
        return self._prefix

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared._params:
            self._params[name] = self._shared._params[name]
            return self._params[name]
        return None

    def get(self, name, **kwargs):
        """Get-or-create (reference ParameterDict.get): prepends the prefix;
        checks attribute compatibility when the param exists."""
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            for k, v in kwargs.items():
                existing = getattr(param, k, None)
                if existing is None or v is None:
                    if v is not None:
                        setattr(param, k, v)
                    continue
                if k == "shape":
                    if len(v) == len(existing):
                        param.shape = tuple(
                            a if a != 0 else b for a, b in zip(v, existing))
                        continue
                if k == "dtype":
                    if onp.dtype(existing) != onp.dtype(v):
                        raise AssertionError(
                            "Parameter '%s' already exists with dtype=%s, "
                            "conflicting with requested dtype=%s." % (name, existing, v))
                    continue
                if k in ("init", "grad_req", "lr_mult", "wd_mult") \
                        and existing != v:
                    raise AssertionError(
                        "Parameter '%s' already exists with %s=%s, conflicting "
                        "with requested %s=%s (reference ParameterDict.get "
                        "asserts attribute consistency)."
                        % (name, k, existing, k, v))
        return param

    def get_constant(self, name, value=None):
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise KeyError("No constant named '%s'." % name)
            param = Constant(name, value)
            self._params[name] = param
        return param

    def update(self, other):
        for k, v in other.items():
            if k in self._params:
                assert self._params[k] is v, \
                    "Cannot update self with other because they have different " \
                    "Parameters with the same name '%s'" % k
            else:
                self._params[k] = v

    def initialize(self, init=initializer.Uniform(), ctx=None, verbose=False,
                   force_reinit=False):
        for _, v in self.items():
            v.initialize(None, ctx, init, force_reinit=force_reinit)

    def zero_grad(self):
        for v in self.values():
            v.zero_grad()

    def reset_ctx(self, ctx):
        for v in self.values():
            v.reset_ctx(ctx)

    def setattr(self, name, value):
        for v in self.values():
            setattr(v, name, value)

    def save(self, filename, strip_prefix=""):
        from ..ndarray.utils import save as nd_save
        arg_dict = {}
        for param in self.values():
            weight = param.data()
            if not param.name.startswith(strip_prefix):
                raise ValueError(
                    "Prefix '%s' is to be striped before saving, but Parameter's "
                    "name '%s' does not start with it." % (strip_prefix, param.name))
            arg_dict[param.name[len(strip_prefix):]] = weight
        nd_save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        from ..ndarray.utils import load as nd_load
        arg_dict = {restore_prefix + k: v for k, v in nd_load(filename).items()}
        if not allow_missing:
            for name in self.keys():
                assert name in arg_dict, \
                    "Parameter '%s' is missing in file '%s'" % (
                        name[len(restore_prefix):], filename)
        for name in arg_dict:
            if name not in self._params:
                assert ignore_extra, \
                    "Parameter '%s' loaded from file '%s' is not present in this " \
                    "ParameterDict" % (name[len(restore_prefix):], filename)
                continue
            self[name]._load_init(arg_dict[name], ctx)
