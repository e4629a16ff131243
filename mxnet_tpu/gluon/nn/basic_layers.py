"""Basic layers (reference: python/mxnet/gluon/nn/basic_layers.py).

TPU-native notes: every layer lowers to registry ops that are jnp/lax
one-liners, so a hybridized net is a single fused XLA program; BatchNorm
running stats are Parameters with grad_req='null' updated functionally.
"""
from __future__ import annotations

import numpy as onp

from ... import ndarray as nd
from ...base import MXNetError
from ..block import Block, HybridBlock
from ..parameter import DeferredInitializationError

__all__ = [
    "Sequential",
    "HybridSequential",
    "Dense",
    "Dropout",
    "BatchNorm",
    "InstanceNorm",
    "LayerNorm",
    "GroupNorm",
    "Embedding",
    "Flatten",
    "Lambda",
    "HybridLambda",
]


class Sequential(Block):
    """Stack of Blocks run in order."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                net.add(*layers)
            return net
        return layers

    def __iter__(self):
        return iter(self._children.values())

    def hybridize(self, active=True, **kwargs):
        if self._children and all(
            isinstance(c, HybridBlock) for c in self._children.values()
        ):
            import warnings

            warnings.warn(
                "All children of this Sequential layer are HybridBlocks. "
                "Consider using HybridSequential for the best performance."
            )
        super().hybridize(active, **kwargs)


class HybridSequential(HybridBlock):
    """Stack of HybridBlocks; hybridize() compiles the whole stack into one
    XLA program."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def hybrid_forward(self, F, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                net.add(*layers)
            return net
        return layers

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """Fully-connected layer: data @ W.T + b (reference Dense; op parity
    src/operator/nn/fully_connected.cc)."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None,
                 bias_initializer="zeros", in_units=0, **kwargs):
        super().__init__(**kwargs)
        self._flatten = flatten
        self._units = units
        self._in_units = in_units
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(units, in_units), init=weight_initializer,
                dtype=dtype, allow_deferred_init=True,
            )
            if use_bias:
                self.bias = self.params.get(
                    "bias", shape=(units,), init=bias_initializer,
                    dtype=dtype, allow_deferred_init=True,
                )
            else:
                self.bias = None
            if activation is not None:
                self.act = Activation(activation, prefix=activation + "_")
            else:
                self.act = None

    def _infer_param_shapes(self, x, *args):
        if self._flatten:
            in_units = int(onp.prod(x.shape[1:]))
        else:
            in_units = x.shape[-1]
        self.weight.shape = (self._units, in_units)

    def hybrid_forward(self, F, x, weight, bias=None):
        out = F.FullyConnected(
            *((x, weight) if bias is None else (x, weight, bias)),
            no_bias=bias is None, num_hidden=self._units,
            flatten=self._flatten,
        )
        if self.act is not None:
            out = self.act(out)
        return out

    def __repr__(self):
        shape = self.weight.shape
        return (
            f"Dense({shape[1] if shape[1] else None} -> {shape[0]}, "
            f"{'linear' if self.act is None else self.act._act_type})"
        )


class Dropout(HybridBlock):
    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate
        self._axes = axes

    def hybrid_forward(self, F, x):
        if self._rate <= 0:
            return x
        return F.Dropout(x, p=self._rate, axes=self._axes)

    def __repr__(self):
        return f"Dropout(p = {self._rate}, axes={self._axes})"


class BatchNorm(HybridBlock):
    """Batch normalization with running stats (reference BatchNorm; op
    src/operator/nn/batch_norm.cc).  Running stats are grad_req='null'
    Parameters; the op returns the updated stats which we write back —
    functional state update instead of the reference's in-place aux-state
    mutation."""

    def __init__(self, axis=None, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones",
                 running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0, **kwargs):
        super().__init__(**kwargs)
        if axis is None:  # default follows the nn.default_layout scope
            from .layout import channel_axis
            axis = channel_axis()
        self._kwargs = {
            "axis": axis, "eps": epsilon, "momentum": momentum,
            "fix_gamma": not scale, "use_global_stats": use_global_stats,
        }
        self._axis = axis
        self.in_channels = in_channels
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True, differentiable=scale,
            )
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True, differentiable=center,
            )
            self.running_mean = self.params.get(
                "running_mean", grad_req="null", shape=(in_channels,),
                init=running_mean_initializer, allow_deferred_init=True,
                differentiable=False,
            )
            self.running_var = self.params.get(
                "running_var", grad_req="null", shape=(in_channels,),
                init=running_variance_initializer, allow_deferred_init=True,
                differentiable=False,
            )

    def _infer_param_shapes(self, x, *args):
        channels = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean,
                  self.running_var):
            p.shape = (channels,)

    def cast(self, dtype):
        if onp.dtype(dtype).name in ("float16", "bfloat16"):
            dtype = "float32"  # stats stay fp32 (reference semantics)
        super().cast(dtype)

    def hybrid_forward(self, F, x, gamma, beta, running_mean, running_var):
        from ... import autograd

        training = (
            autograd.is_training()
            and not self._kwargs["use_global_stats"]
        )
        if training:
            out, batch_mean, batch_var = F.BatchNorm(
                x, gamma, beta, running_mean, running_var,
                output_mean_var=True, **self._kwargs
            )
            m = self._kwargs["momentum"]
            with autograd.pause():
                new_mean = m * running_mean + (1.0 - m) * batch_mean
                new_var = m * running_var + (1.0 - m) * batch_var
                # functional state write-back; under jit tracing the
                # HybridBlock harvests this as an extra program output
                running_mean._adopt(new_mean._data)
                running_var._adopt(new_var._data)
            return out
        return F.BatchNorm(
            x, gamma, beta, running_mean, running_var, **self._kwargs
        )

    def __repr__(self):
        in_channels = self.gamma.shape[0]
        return (
            f"BatchNorm(axis={self._axis}, eps={self._kwargs['eps']}, "
            f"momentum={self._kwargs['momentum']}, "
            f"in_channels={in_channels if in_channels else None})"
        )


class InstanceNorm(HybridBlock):
    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._epsilon = epsilon
        self.in_channels = in_channels
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True,
            )
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True,
            )

    def _infer_param_shapes(self, x, *args):
        channels = x.shape[self._axis]
        self.gamma.shape = (channels,)
        self.beta.shape = (channels,)

    def hybrid_forward(self, F, x, gamma, beta):
        if self._axis == 1:
            return F.InstanceNorm(x, gamma, beta, eps=self._epsilon)
        x = x.swapaxes(1, self._axis)
        return F.InstanceNorm(x, gamma, beta, eps=self._epsilon).swapaxes(
            1, self._axis
        )


class LayerNorm(HybridBlock):
    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._axis = axis
        self._epsilon = epsilon
        self.in_channels = in_channels
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True,
            )
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True,
            )

    def _infer_param_shapes(self, x, *args):
        channels = x.shape[self._axis]
        self.gamma.shape = (channels,)
        self.beta.shape = (channels,)

    def hybrid_forward(self, F, x, gamma, beta):
        return F.LayerNorm(x, gamma, beta, axis=self._axis, eps=self._epsilon)


class GroupNorm(HybridBlock):
    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._num_groups = num_groups
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(num_groups,), init=gamma_initializer,
                allow_deferred_init=True,
            )
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(num_groups,), init=beta_initializer,
                allow_deferred_init=True,
            )

    def hybrid_forward(self, F, x, gamma, beta):
        return F.GroupNorm(
            x, gamma, beta, num_groups=self._num_groups, eps=self._epsilon
        )


class Embedding(HybridBlock):
    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        self._input_dim = input_dim
        self._output_dim = output_dim
        self._dtype = dtype
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(input_dim, output_dim),
                init=weight_initializer, dtype=dtype,
            )

    def hybrid_forward(self, F, x, weight):
        return F.Embedding(
            x, weight, input_dim=self._input_dim,
            output_dim=self._output_dim, dtype=self._dtype,
        )

    def __repr__(self):
        return f"Embedding({self._input_dim} -> {self._output_dim}, {self._dtype})"


class Flatten(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.Flatten(x)

    def __repr__(self):
        return "Flatten"


class Lambda(Block):
    """Wrap a function (or nd op name) as a Block."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            if not hasattr(nd, function):
                raise MXNetError(f"Function name {function} is not found in nd.")
            self._func_impl = getattr(nd, function)
            self._func_name = function
        elif callable(function):
            self._func_impl = function
            self._func_name = function.__name__
        else:
            raise MXNetError(
                "Unrecognized function in lambda: {} of type {}".format(
                    function, type(function)
                )
            )

    def forward(self, *args):
        return self._func_impl(*args)

    def __repr__(self):
        return f"Lambda({self._func_name})"


class HybridLambda(HybridBlock):
    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            if not hasattr(nd, function):
                raise MXNetError(f"Function name {function} is not found in nd.")
            self._func_name = function

            def _fn(F, *args):
                return getattr(F, function)(*args)

            self._func = _fn
        elif callable(function):
            self._func = function
            self._func_name = function.__name__
        else:
            raise MXNetError(
                "Unrecognized function in lambda: {} of type {}".format(
                    function, type(function)
                )
            )

    def hybrid_forward(self, F, x, *args):
        return self._func(F, x, *args)

    def __repr__(self):
        return f"HybridLambda({self._func_name})"


from .activations import Activation  # noqa: E402  (cycle: Dense uses it)
