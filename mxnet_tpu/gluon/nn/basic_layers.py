"""Basic Gluon layers.

Reference: ``python/mxnet/gluon/nn/basic_layers.py`` — Sequential,
Dense, Dropout, BatchNorm, LayerNorm, InstanceNorm, Embedding, Flatten,
Lambda, HybridLambda.  Kernels are the registered TPU ops (``ops/nn.py``);
each layer adds parameter management + deferred shape inference.
"""
from __future__ import annotations

import numpy as onp

from ... import autograd
from ...ndarray import NDArray
from ..block import Block, HybridBlock

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "Embedding",
           "BatchNorm", "BatchNormAddReLU", "InstanceNorm", "LayerNorm",
           "RMSNorm", "GroupNorm", "Flatten", "Lambda", "HybridLambda", "Activation"]


class Sequential(Block):
    """Stack of blocks executed sequentially (reference basic_layers.py:29)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x)
        return x

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                net.add(*layers)
            return net
        return layers

    def __len__(self):
        return len(self._children)

    def __iter__(self):
        return iter(self._children.values())


class HybridSequential(HybridBlock):
    """Hybridizable Sequential (reference basic_layers.py:96)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x, *args):
        # containers have no own params; just chain children
        for block in self._children.values():
            x = block(x)
        return x

    hybrid_forward = forward

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                net.add(*layers)
            return net
        return layers

    def __len__(self):
        return len(self._children)

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """Fully-connected layer y = act(x·Wᵀ + b) (reference
    basic_layers.py Dense; kernel = FullyConnected op lowering to one MXU
    matmul with fused bias/activation epilogue)."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None, bias_initializer="zeros",
                 in_units=0, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._in_units = in_units
        self._flatten = flatten
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(units, in_units), init=weight_initializer,
                dtype=dtype, allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get(
                    "bias", shape=(units,), init=bias_initializer, dtype=dtype,
                    allow_deferred_init=True)
            else:
                self.bias = None
            if activation is not None:
                self.act = Activation(activation, prefix=activation + "_")
            else:
                self.act = None

    def infer_shape(self, x, *args):
        in_units = int(onp.prod(x.shape[1:])) if self._flatten else x.shape[-1]
        self.weight._finish_deferred_init((self._units, in_units))
        if self.bias is not None:
            self.bias._finish_deferred_init((self._units,))

    def hybrid_forward(self, F, x, weight, bias=None):
        out = F.FullyConnected(x, weight, bias, no_bias=bias is None,
                               num_hidden=self._units, flatten=self._flatten)
        if self.act is not None:
            out = self.act(out)
        return out

    def __repr__(self):
        shape = self.weight.shape
        return "%s(%s -> %s, linear)" % (
            self.__class__.__name__, shape[1] if shape[1] else None, shape[0])


class Activation(HybridBlock):
    """Activation layer (reference basic_layers.py Activation)."""

    def __init__(self, activation, **kwargs):
        self._act_type = activation
        super().__init__(**kwargs)

    def _alias(self):
        return self._act_type

    def hybrid_forward(self, F, x):
        return F.Activation(x, act_type=self._act_type)

    def __repr__(self):
        return "Activation(%s)" % self._act_type


class Dropout(HybridBlock):
    """Inverted dropout (reference basic_layers.py Dropout; RNG = jax PRNG
    key threaded by the dispatcher, deterministic under mx.random.seed)."""

    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate
        self._axes = axes

    def hybrid_forward(self, F, x):
        if self._rate > 0:
            return F.Dropout(x, p=self._rate, axes=self._axes, cudnn_off=True)
        return F.identity(x) if hasattr(F, "identity") else x

    def __repr__(self):
        return "Dropout(p = %s, axes=%s)" % (self._rate, self._axes)


class BatchNorm(HybridBlock):
    """Batch normalization with moving stats (reference basic_layers.py
    BatchNorm over src/operator/nn/batch_norm).  Functional-style: the op
    returns batch stats; the moving-average update here becomes an extra
    jit output under hybridize (detected by the trace cache)."""

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones", running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._kwargs = {"axis": axis, "eps": epsilon, "momentum": momentum,
                        "fix_gamma": not scale,
                        "use_global_stats": use_global_stats}
        self._axis = axis
        self._momentum = momentum
        self.in_channels = in_channels
        self.gamma = self.params.get(
            "gamma", grad_req="write" if scale else "null",
            shape=(in_channels,), init=gamma_initializer,
            allow_deferred_init=True, differentiable=scale)
        self.beta = self.params.get(
            "beta", grad_req="write" if center else "null",
            shape=(in_channels,), init=beta_initializer,
            allow_deferred_init=True, differentiable=center)
        self.running_mean = self.params.get(
            "running_mean", grad_req="null", shape=(in_channels,),
            init=running_mean_initializer, allow_deferred_init=True,
            differentiable=False)
        self.running_var = self.params.get(
            "running_var", grad_req="null", shape=(in_channels,),
            init=running_variance_initializer, allow_deferred_init=True,
            differentiable=False)

    def infer_shape(self, x, *args):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            p._finish_deferred_init((c,))

    def cast(self, dtype):
        if onp.dtype(dtype).name == "float16":
            dtype = "float32"
        super().cast(dtype)

    def hybrid_forward(self, F, x, gamma, beta, running_mean, running_var):
        out, mean, var = F.BatchNorm(
            x, gamma, beta, running_mean, running_var,
            name="fwd", **self._kwargs)
        if autograd.is_training() and not self._kwargs["use_global_stats"]:
            m = self._momentum
            with autograd.pause():
                self.running_mean.set_data(running_mean * m + mean * (1 - m))
                self.running_var.set_data(running_var * m + var * (1 - m))
        return out

    def __repr__(self):
        in_channels = self.gamma.shape[0] if self.gamma.shape else None
        return "BatchNorm(axis=%d, eps=%s, momentum=%s, in_channels=%s)" % (
            self._axis, self._kwargs["eps"], self._momentum, in_channels)


class BatchNormAddReLU(BatchNorm):
    """BatchNorm whose output is fused with a residual add + ReLU:
    ``relu(BN(x) + residual)`` — the tail of every ResNet v1 residual
    unit (reference: cuDNN's BatchNormAddRelu fusion).  Same parameters,
    same moving-stats handling, and the same auto-naming alias as
    :class:`BatchNorm`, so substituting it for the last BatchNorm of a
    residual body keeps parameter names and checkpoints identical.  The
    elementwise tail is plain ``jax.numpy`` in fp32 (``ops/nn.py``), one
    XLA fusion in the convolutions' own layout."""

    def _alias(self):
        return "batchnorm"

    def hybrid_forward(self, F, x, residual, gamma, beta, running_mean,
                       running_var):
        out, mean, var = F.BatchNormAddRelu(
            x, residual, gamma, beta, running_mean, running_var,
            name="fwd", **self._kwargs)
        if autograd.is_training() and not self._kwargs["use_global_stats"]:
            m = self._momentum
            with autograd.pause():
                self.running_mean.set_data(running_mean * m + mean * (1 - m))
                self.running_var.set_data(running_var * m + var * (1 - m))
        return out


class InstanceNorm(HybridBlock):
    """Reference basic_layers.py InstanceNorm."""

    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._epsilon = epsilon
        self.gamma = self.params.get(
            "gamma", grad_req="write" if scale else "null",
            shape=(in_channels,), init=gamma_initializer, allow_deferred_init=True)
        self.beta = self.params.get(
            "beta", grad_req="write" if center else "null",
            shape=(in_channels,), init=beta_initializer, allow_deferred_init=True)

    def infer_shape(self, x, *args):
        c = x.shape[self._axis]
        self.gamma._finish_deferred_init((c,))
        self.beta._finish_deferred_init((c,))

    def hybrid_forward(self, F, x, gamma, beta):
        if self._axis == 1:
            return F.InstanceNorm(x, gamma, beta, eps=self._epsilon)
        x = x.swapaxes(1, self._axis)
        return F.InstanceNorm(x, gamma, beta, eps=self._epsilon).swapaxes(1, self._axis)


class LayerNorm(HybridBlock):
    """Reference basic_layers.py LayerNorm (src/operator/nn/layer_norm)."""

    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._axis = axis
        self._epsilon = epsilon
        self.gamma = self.params.get(
            "gamma", grad_req="write" if scale else "null",
            shape=(in_channels,), init=gamma_initializer, allow_deferred_init=True)
        self.beta = self.params.get(
            "beta", grad_req="write" if center else "null",
            shape=(in_channels,), init=beta_initializer, allow_deferred_init=True)

    def infer_shape(self, x, *args):
        c = x.shape[self._axis]
        self.gamma._finish_deferred_init((c,))
        self.beta._finish_deferred_init((c,))

    def hybrid_forward(self, F, x, gamma, beta):
        return F.LayerNorm(x, gamma, beta, axis=self._axis, eps=self._epsilon)


class RMSNorm(HybridBlock):
    """``x / sqrt(mean(x^2) + epsilon) * gamma`` over ``axis``: the
    normalisation of pre-norm decoder blocks (no mean, no shift).  With
    ``groups`` > 1 each of that many equal parts of the last axis has its
    own mean; ``forward(x, gate)`` norms ``x * silu(gate)`` (the gated
    group norm at the end of a Mamba-2 mixer)."""

    def __init__(self, axis=-1, epsilon=1e-5, gamma_initializer="ones",
                 in_channels=0, groups=1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if groups > 1 and axis != -1:
            raise ValueError("groups divide the last axis, got axis=%d"
                             % axis)
        self._axis = axis
        self._epsilon = epsilon
        self._groups = groups
        self.gamma = self.params.get(
            "gamma", shape=(in_channels,), init=gamma_initializer,
            allow_deferred_init=True)

    def infer_shape(self, x, *args):
        self.gamma._finish_deferred_init((x.shape[self._axis],))

    def hybrid_forward(self, F, x, gate=None, gamma=None):
        return F.RMSNorm(x, gamma, gate, axis=self._axis,
                         eps=self._epsilon, groups=self._groups)


class GroupNorm(HybridBlock):
    """Reference nn/group_norm (root-level op in src/operator/nn)."""

    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._num_groups = num_groups
        self._epsilon = epsilon
        self.gamma = self.params.get(
            "gamma", grad_req="write" if scale else "null",
            shape=(in_channels,), init=gamma_initializer, allow_deferred_init=True)
        self.beta = self.params.get(
            "beta", grad_req="write" if center else "null",
            shape=(in_channels,), init=beta_initializer, allow_deferred_init=True)

    def infer_shape(self, x, *args):
        c = x.shape[1]
        self.gamma._finish_deferred_init((c,))
        self.beta._finish_deferred_init((c,))

    def hybrid_forward(self, F, x, gamma, beta):
        return F.GroupNorm(x, gamma, beta, num_groups=self._num_groups,
                           eps=self._epsilon)


class Embedding(HybridBlock):
    """Index → vector lookup (reference basic_layers.py Embedding;
    kernel = XLA gather on the MXU-adjacent VPU)."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        self._input_dim = input_dim
        self._output_dim = output_dim
        self._kwargs = {"input_dim": input_dim, "output_dim": output_dim,
                        "dtype": dtype, "sparse_grad": sparse_grad}
        self.weight = self.params.get(
            "weight", shape=(input_dim, output_dim), init=weight_initializer,
            dtype=dtype, allow_deferred_init=True)

    def hybrid_forward(self, F, x, weight):
        return F.Embedding(x, weight, **self._kwargs)

    def __repr__(self):
        return "Embedding(%d -> %d, %s)" % (
            self._input_dim, self._output_dim, self._kwargs["dtype"])


class Flatten(HybridBlock):
    """Flatten to (batch, -1) (reference basic_layers.py Flatten)."""

    def hybrid_forward(self, F, x):
        return x.reshape((0, -1))

    def __repr__(self):
        return self.__class__.__name__


class Lambda(Block):
    """Wrap a function as a Block (reference basic_layers.py Lambda)."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            from ... import ndarray as nd
            assert hasattr(nd, function), \
                "Function name %s is not found in ndarray." % function
            self._func_impl = getattr(nd, function)
            self._func_name = function
        elif callable(function):
            self._func_impl = function
            self._func_name = function.__name__
        else:
            raise ValueError("Unrecognized function in lambda: %s of type %s"
                             % (function, type(function)))

    def forward(self, *args):
        return self._func_impl(*args)

    def __repr__(self):
        return "Lambda(%s)" % self._func_name


class HybridLambda(HybridBlock):
    """Wrap a function as a HybridBlock (reference HybridLambda)."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            from ... import ndarray as nd
            self._func = lambda F, *args: getattr(F, function)(*args)
            self._func_name = function
        elif callable(function):
            self._func = function
            self._func_name = function.__name__
        else:
            raise ValueError("Unrecognized function in lambda: %s of type %s"
                             % (function, type(function)))

    def hybrid_forward(self, F, x, *args):
        return self._func(F, x, *args)

    def __repr__(self):
        return "HybridLambda(%s)" % self._func_name
