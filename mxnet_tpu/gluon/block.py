"""Gluon Block / HybridBlock: imperative modules with optional compilation.

Reference: ``python/mxnet/gluon/block.py`` (1186 LoC) — ``Block.__call__``
(:543), ``HybridBlock`` (:679) whose ``hybridize()`` (:840) traces
``hybrid_forward`` into a ``CachedOp`` (:793) for repeated graph execution.

TPU-native redesign: *hybridize = jax.jit*.  A hybridized block's forward is
traced once per (train-flag, input-shapes) into a single XLA program — the
exact role CachedOp's shape-keyed plan cache plays (``cached_op.cc:307``),
but the compiler also fuses/plans memory (MXPlanMemory's job).  The traced
function is pure: parameter values, inputs, and a PRNG key are arguments;
mutated auxiliary states (BatchNorm running stats) are *detected during
tracing* and become extra outputs written back after each call — MXNet's
mutable aux inputs, made functional.  Autograd composes: the whole jitted
program is recorded as ONE tape node, so ``loss.backward()`` runs XLA-grade
fused backward (vs the reference's per-op backward graph).
"""
from __future__ import annotations

import re
import threading
from collections import OrderedDict

import jax
import numpy as onp

from .. import autograd
from .. import random as _random
from ..base import MXNetError
from ..context import Context, current_context
from ..ndarray import NDArray
from ..ndarray.ndarray import _wrap, invoke_fn
from .parameter import DeferredInitializationError, Parameter, ParameterDict

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


class _NameCounter(threading.local):
    def __init__(self):
        self.counts = {}


_GLOBAL_NAMES = _NameCounter()


class _BlockScope:
    """Name-scope manager assigning unique prefixes (reference block.py:35)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                cnt = _GLOBAL_NAMES.counts.get(hint, 0)
                _GLOBAL_NAMES.counts[hint] = cnt + 1
                prefix = "%s%d_" % (hint, cnt)
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            cnt = current._counter.get(hint, 0)
            current._counter[hint] = cnt + 1
            prefix = "%s%d_" % (hint, cnt)
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, ptype, value, trace):
        if self._block._empty_prefix:
            return
        _BlockScope._current.value = self._old_scope


class Block:
    """Base building block (reference gluon/block.py:128)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()

    def _alias(self):
        return self.__class__.__name__.lower()

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join("  ({key}): {block}".format(
            key=key, block=_indent(repr(block), 2))
            for key, block in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        """Attribute assignment registers children and parameters
        (reference block.py __setattr__)."""
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and \
                    not isinstance(value, type(existing)) and \
                    not isinstance(existing, type(value)):
                raise TypeError(
                    "Changing attribute type for %s from %s to %s is not allowed."
                    % (name, type(existing), type(value)))
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params or self._reg_params[name] is value, \
                "Overriding Parameter attribute %s is not allowed." % name
            self._reg_params[name] = value
        super().__setattr__(name, value)

    # ------------------------------------------------------------------
    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        """Own parameters (reference: this block's ParameterDict, no
        descendants)."""
        return self._params

    def collect_params(self, select=None):
        """This block's params plus all descendants', optionally filtered by
        regex ``select`` (reference block.py collect_params)."""
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle.id] = hook
        return handle

    def register_forward_hook(self, hook):
        handle = _HookHandle(self._forward_hooks)
        self._forward_hooks[handle.id] = hook
        return handle

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        from .. import initializer as _init
        init = init if init is not None else _init.Uniform()
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        """Cascade to children (reference Block.hybridize; compilation only
        happens on HybridBlocks)."""
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def zero_grad(self):
        self.collect_params().zero_grad()

    # -- checkpoint (reference save_parameters/load_parameters) ---------
    def save_parameters(self, filename, deduplicate=False):
        """Save with structural names (reference block.py save_parameters)."""
        from ..ndarray.utils import save as nd_save
        params = self._collect_params_with_prefix()
        arg_dict = {key: val.data() for key, val in params.items()
                    if val._data is not None}
        nd_save(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False, dtype_source="current"):
        from ..ndarray.utils import load as nd_load
        loaded = nd_load(filename)
        if not isinstance(loaded, dict):
            raise ValueError(
                "load_parameters needs a name->NDArray dict file; %r "
                "contains an unnamed array list" % (filename,))
        # Module/export-style checkpoints tag names with arg:/aux:
        # (reference load_parameters strips them the same way)
        if loaded and any(k.startswith(("arg:", "aux:")) for k in loaded):
            loaded = {k.split(":", 1)[1] if k.startswith(("arg:", "aux:"))
                      else k: v for k, v in loaded.items()}
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        # accept both structural and prefixed formats (reference does the same)
        if loaded and not any("." in k for k in loaded.keys()) \
                and any("." in k for k in params.keys()):
            # prefixed format → match against the full parameter names,
            # keeping the arg:/aux: strip applied above
            full = self.collect_params()
            renamed = {self.prefix + k: v for k, v in loaded.items()}
            if not allow_missing:
                for name in full.keys():
                    assert name in renamed, \
                        "Parameter '%s' is missing in file '%s'" % (
                            name[len(self.prefix):], filename)
            for name, value in renamed.items():
                if name not in full.keys():
                    assert ignore_extra, \
                        "Parameter '%s' loaded from file '%s' is not " \
                        "present in this Block" % (name, filename)
                    continue
                full[name]._load_init(value, ctx)
            return
        if not allow_missing:
            for name in params.keys():
                assert name in loaded, \
                    "Parameter '%s' is missing in file '%s'" % (name, filename)
        for name in loaded:
            if name not in params:
                assert ignore_extra, \
                    "Parameter '%s' loaded from file '%s' is not present in " \
                    "this Block" % (name, filename)
                continue
            params[name]._load_init(loaded[name], ctx)

    # alias kept from older API (reference save_params deprecated names)
    save_params = save_parameters
    load_params = load_parameters

    # ------------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        # the block's name on every operation traced under it: a device
        # trace can then be reduced by block (metadata only; eagerly a
        # push and a pop of the name stack)
        with jax.named_scope(self._name or type(self).__name__):
            out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-layer summary table (reference block.py summary)."""
        summary = OrderedDict()
        hooks = []

        def _register(block, prefix):
            def hook(blk, inp, out):
                name = prefix or blk.__class__.__name__
                out0 = out[0] if isinstance(out, (list, tuple)) else out
                n_params = sum(
                    int(onp.prod(p.shape)) for p in blk._reg_params.values()
                    if p._data is not None)
                summary[name + " (" + blk.__class__.__name__ + ")"] = (
                    tuple(out0.shape), n_params)
            hooks.append(block.register_forward_hook(hook))
            for cname, child in block._children.items():
                _register(child, (prefix + "." if prefix else "") + cname)

        _register(self, "")
        try:
            self(*inputs)
        finally:
            for h in hooks:
                h.detach()
        print("-" * 70)
        print("%-40s %-20s %10s" % ("Layer (type)", "Output Shape", "Param #"))
        print("=" * 70)
        total = 0
        for name, (shape, n) in summary.items():
            print("%-40s %-20s %10d" % (name[:40], str(shape), n))
            total += n
        print("=" * 70)
        print("Total params: %d" % total)


class _HookHandle:
    _next_id = [0]

    def __init__(self, hooks_dict):
        self.id = _HookHandle._next_id[0]
        _HookHandle._next_id[0] += 1
        self._hooks = hooks_dict

    def detach(self):
        self._hooks.pop(self.id, None)


def _indent(s, num_spaces):
    lines = s.split("\n")
    if len(lines) == 1:
        return s
    return lines[0] + "\n" + "\n".join(" " * num_spaces + line for line in lines[1:])


# ---------------------------------------------------------------------------
# HybridBlock: trace-to-jit
# ---------------------------------------------------------------------------

def _flatten_args(args):
    """Flatten (possibly nested lists of) NDArrays into a list + template."""
    arrays = []

    def conv(a):
        if isinstance(a, NDArray):
            arrays.append(a)
            return ("__arr__", len(arrays) - 1)
        if isinstance(a, (list, tuple)):
            return ("__list__", [conv(x) for x in a], isinstance(a, tuple))
        return ("__static__", a)

    template = [conv(a) for a in args]
    return arrays, template


def _rebuild_args(template, arrays):
    def conv(t):
        tag = t[0]
        # graftlint: disable-next=trace-tracer-branch -- pytree tags
        # are Python strings from the flatten template, not traced
        if tag == "__arr__":
            return arrays[t[1]]
        # graftlint: disable-next=trace-tracer-branch -- pytree tags
        # are Python strings from the flatten template, not traced
        if tag == "__list__":
            items = [conv(x) for x in t[1]]
            # graftlint: disable-next=trace-tracer-branch -- t[2] is the
            # template's Python bool tuple-vs-list marker
            return tuple(items) if t[2] else items
        return t[1]

    return [conv(t) for t in template]


class _CachedGraph:
    """CachedOp analogue: shape-keyed cache of jitted traces of a block's
    forward (reference src/imperative/cached_op.cc:307 SetForwardGraph
    plan cache; here the "plan" is an XLA executable)."""

    def __init__(self, block, static_alloc=False, static_shape=False,
                 inline_limit=2, flags=()):
        import jax
        self._jax = jax
        self._block = block
        self._cache = {}
        # parameter list is fixed for the life of this cache (hybridize()/
        # cast() rebuild it), so compute it once — the reference CachedOp
        # likewise captures its param order at construction
        self._params = [p for _, p in sorted(block.collect_params().items())
                        if p._data is not None]

    def clear(self):
        self._cache.clear()

    def __call__(self, args):
        block = self._block
        arrays, template = _flatten_args(args)
        params = self._params
        training = autograd.is_training()
        key = (training, tuple((a.shape, str(a.dtype)) for a in arrays))
        entry = self._cache.get(key)
        if entry is None:
            entry = self._build(params, template, training)
            self._cache[key] = entry
        jfn, meta = entry
        key_arr = _wrap(_random.next_key())
        p_arrs = [p._data for p in params]
        outs = invoke_fn(jfn, [key_arr] + p_arrs + arrays,
                         name="CachedOp_%s" % block.name, n_outputs=2)
        n_out = meta["n_outputs"]
        out_arrs = outs[:n_out]
        # write back mutated aux states (running mean/var…), skipping the tape
        for p_idx, o in zip(meta["mutated"], outs[n_out:]):
            with autograd.pause():
                params[p_idx]._data._data = o._data
        if meta["out_is_seq"]:
            return out_arrs
        return out_arrs[0]

    def _build(self, params, template, training):
        """Create the jitted pure function.  Structure metadata (output
        arity, mutated-aux set) is captured during the first trace."""
        import jax
        block = self._block
        n_params = len(params)
        meta = {"n_outputs": None, "mutated": None, "out_is_seq": None}

        def raw_fn(key, *vals):
            pvals = vals[:n_params]
            ivals = vals[n_params:]
            saved = [(p._data._data, p._data._ag) for p in params]
            for p, v in zip(params, pvals):
                p._data._data = v
                p._data._ag = None
            try:
                in_arrays = [_wrap(v) for v in ivals]
                new_args = _rebuild_args(template, in_arrays)
                prev_rec = autograd.set_recording(False)
                prev_train = autograd.set_training(training)
                try:
                    with _random.key_supply(key):
                        out = block.forward(*new_args)
                finally:
                    autograd.set_recording(prev_rec)
                    autograd.set_training(prev_train)
                is_seq = isinstance(out, (list, tuple))
                out_list = list(out) if is_seq else [out]
                out_vals = [o._data for o in out_list]
                mutated = []
                mut_vals = []
                for i, (p, (old, _)) in enumerate(zip(params, saved)):
                    if p._data._data is not pvals[i]:
                        mutated.append(i)
                        mut_vals.append(p._data._data)
                # graftlint: disable-next=retrace-closure-array -- meta
                # is raw_fn's write-through channel reporting trace-time
                # output metadata; rebuilt once per cache miss
                meta["n_outputs"] = len(out_vals)
                meta["mutated"] = mutated
                meta["out_is_seq"] = is_seq
                return tuple(out_vals + mut_vals)
            finally:
                for p, (old, ag) in zip(params, saved):
                    p._data._data = old
                    p._data._ag = ag

        return jax.jit(raw_fn), meta


class HybridBlock(Block):
    """A Block that can be traced into a compiled XLA program
    (reference gluon/block.py:679)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_graph = None
        self._flags = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  inline_limit=2, **kwargs):
        """Activate compiled execution (reference block.py:840).  static_alloc
        and static_shape are accepted for API parity — XLA buffer assignment
        already provides static planning."""
        self._active = active
        self._flags = dict(static_alloc=static_alloc, static_shape=static_shape,
                           inline_limit=inline_limit, **kwargs)
        if self._cached_graph is not None:
            self._cached_graph.clear()
        self._cached_graph = None
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, inline_limit=inline_limit,
                          **kwargs)

    def _clear_cached_op(self):
        if self._cached_graph is not None:
            self._cached_graph.clear()
        self._cached_graph = None

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def infer_shape(self, *args):
        """Complete deferred parameter shapes from input shapes.  Layers with
        deferred params override this (the reference does it by symbolic
        shape inference; here each layer states its own shape rule, which is
        both simpler and jit-friendly).  Composite blocks need no override:
        their children infer as data flows through them."""

    def _deferred_params(self):
        return [p for p in self.collect_params().values()
                if p._data is None]

    def __call__(self, *args, **kwargs):
        if self._active:
            if kwargs:
                # kwargs are not part of the trace cache key; run eagerly so
                # hybridize never silently changes call semantics
                return super().__call__(*args, **kwargs)
            import jax
            arrays, _ = _flatten_args(args)
            if any(isinstance(a._data, jax.core.Tracer) for a in arrays):
                # already inside a parent's trace — execute through (the
                # reference inlines child CachedOps the same way)
                return super().__call__(*args, **kwargs)
            pending = self._deferred_params()
            if pending:
                # warm-up eager pass completes deferred shape inference
                return super().__call__(*args, **kwargs)
            if self._cached_graph is None:
                self._cached_graph = _CachedGraph(self, **self._flags)
            for hook in self._forward_pre_hooks.values():
                hook(self, args)
            out = self._cached_graph(args)
            for hook in self._forward_hooks.values():
                hook(self, args, out)
            return out
        return super().__call__(*args, **kwargs)

    def forward(self, x, *args):
        """Fetch own params and dispatch to hybrid_forward (reference
        block.py:910 switching on ndarray vs symbol inputs)."""
        from .. import ndarray as nd
        try:
            from .. import symbol as sym_mod
            from ..symbol import Symbol
        except ImportError:
            Symbol = None

        if Symbol is not None and isinstance(x, Symbol):
            params = {name: p.var() for name, p in self._reg_params.items()}
            return self.hybrid_forward(sym_mod, x, *args, **params)

        params = {}
        for name, p in self._reg_params.items():
            try:
                params[name] = p.data()
            except DeferredInitializationError:
                self.infer_shape(x, *args)
                params[name] = p.data()
        return self.hybrid_forward(nd, x, *args, **params)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def export(self, path, epoch=0):
        """Export params for deployment (reference HybridBlock.export saves
        symbol json + params; here: params + a jitted StableHLO text when
        available)."""
        fname = "%s-%04d.params" % (path, epoch)
        self.save_parameters(fname)
        return fname

    def optimize_for(self, x, *args, backend=None, **kwargs):
        """Reference block.py optimize_for: partition/compile for a backend.
        On TPU the backend is always XLA — equivalent to hybridize + warmup."""
        self.hybridize()
        self(x, *args)


class SymbolBlock(HybridBlock):
    """Construct a block from a symbolic graph (reference block.py:961).
    Implemented with the Symbol layer; see mxnet_tpu/symbol/."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=params)
        from ..symbol import Symbol
        if isinstance(outputs, (list, tuple)) and len(outputs) == 1:
            outputs = outputs[0]
        if isinstance(inputs, Symbol):
            inputs = [inputs]
        self._sym_outputs = outputs
        self._sym_inputs = inputs
        input_names = {i.name for i in inputs}
        for name in outputs.list_arguments():
            if name not in input_names:
                self.params.get(name, allow_deferred_init=True)
        for name in outputs.list_auxiliary_states():
            self.params.get(name, allow_deferred_init=True, grad_req="null")

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod
        sym = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_mod.var(n) for n in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            ret.load_parameters(param_file, ctx=ctx, cast_dtype=True)
        return ret

    def forward(self, *args):
        arg_dict = {}
        for sym_in, arr in zip(self._sym_inputs, args):
            arg_dict[sym_in.name] = arr
        for name, p in self.collect_params().items():
            arg_dict[name] = p.data()
        return self._sym_outputs.eval_imperative(arg_dict)
