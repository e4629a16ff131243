"""Optimizers.

Reference parity: python/mxnet/optimizer/optimizer.py (Optimizer registry,
per-param lr/wd multipliers, mixed-precision master weights, Updater) and
the fused optimizer *ops* in src/operator/optimizer_op.cc.

TPU-native redesign: each update rule is a pure jitted function
``(weight, grad, *state, lr, wd, ...) -> (new_weight, *new_state)``.
XLA fuses the whole rule into one kernel — the analog of the reference's
hand-fused SGD/Adam CUDA kernels — and jit caching per shape plays the
role of the reference's multi-tensor batching.  State lives in device
buffers between steps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as onp

from .. import ndarray as nd
from ..base import MXNetError

__all__ = [
    "Optimizer", "SGD", "Signum", "NAG", "Adam", "AdamW", "AdaGrad",
    "RMSProp", "AdaDelta", "Adamax", "Nadam", "Ftrl", "FTML", "LARS",
    "SGLD", "DCASGD", "LBSGD", "Updater", "create", "register",
    "get_updater", "Test",
]

_REGISTRY: dict[str, type] = {}


def register(klass):
    name = klass.__name__.lower()
    _REGISTRY[name] = klass
    return klass


def create(name, **kwargs):
    if isinstance(name, Optimizer):
        return name
    if name.lower() not in _REGISTRY:
        raise MXNetError(f"Cannot find optimizer {name}")
    return _REGISTRY[name.lower()](**kwargs)


class Optimizer:
    """Base optimizer (reference optimizer.py:Optimizer).

    State handling: ``create_state(index, weight)`` returns a tuple of
    NDArrays; ``update(index, weight, grad, state)`` applies one step
    functionally (weight/state buffers are rebound, not mutated).
    """

    opt_registry = _REGISTRY  # reference-compat alias

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        if param_idx2name is None:
            param_idx2name = {}
        self.idx2name = param_idx2name.copy()
        self.param_dict = param_dict if param_dict else {}
        self.set_lr_mult({})
        self.set_wd_mult({})

    create_optimizer = staticmethod(create)

    # ------------------------------------------------------------ lr / wd
    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise MXNetError(
                "LRScheduler of the optimizer has already been defined. "
                "Note that set_learning_rate can mutate the value of the "
                "learning rate of the optimizer only when the LRScheduler "
                "of the optimizer is undefined.")
        self.lr = lr

    @property
    def learning_rate(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = {}
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        self.wd_mult.update(args_wd_mult)

    def _get_lr(self, index):
        lr = self.learning_rate
        if index in self.param_dict:
            lr *= self.param_dict[index].lr_mult
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index):
        wd = self.wd
        if index in self.param_dict:
            wd *= self.param_dict[index].wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index],
                              self.num_update)

    # ------------------------------------------------------------- state
    def create_state(self, index, weight):
        return ()

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and weight.dtype in (onp.float16,
                                                     jnp.bfloat16):
            master = weight.astype("float32")
            return (master, self.create_state(index, master))
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        raise NotImplementedError

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and weight.dtype in (onp.float16,
                                                     jnp.bfloat16):
            master, base_state = state
            g32 = grad.astype("float32")
            self.update(index, master, g32, base_state)
            weight._adopt(master._data.astype(weight._data.dtype))
        else:
            self.update(index, weight, grad, state)

    # -------------------------------------------------- shared grad prep
    def _prep(self, grad_v):
        g = grad_v * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        return g

    # ------------------------------------------------ fused SPMD interface
    # make_train_step compiles fwd+bwd+update into ONE XLA program (the
    # analog of the reference's fused optimizer ops,
    # src/operator/optimizer_op.cc + contrib/multi_lars.cc); the optimizer
    # contributes a pure per-parameter rule.  Hyper-parameters are read
    # from self at trace time; lr schedulers are evaluated at self.lr's
    # trace-time value (step-dependent schedules re-trace on lr change).
    #: True for stochastic rules (SGLD) whose fused_update consumes the
    #: PRNG key; deterministic rules leave it False so make_train_step
    #: skips the per-parameter key fold-in (hundreds of dead scalar ops
    #: in the compiled step otherwise).
    needs_key = False

    def fused_state(self, w):
        """Initial per-parameter state as a tuple of jax arrays; mirrors
        create_state so eager and fused paths keep identical layouts."""
        return tuple(s._data for s in self.create_state(0, nd.NDArray(w)))

    def fused_update(self, w, g, state, t, key=None):
        """Pure update: (w, g, state, t[, key]) -> (new_w, new_state).

        w/g/state are jax arrays (or tracers inside pjit); t is the
        traced step count (1-based) for bias-corrected rules; key is a
        PRNG key for stochastic rules (SGLD).
        """
        raise MXNetError(
            f"{type(self).__name__} does not provide a fused SPMD rule")

    #: True when fused_update applies the same math to every element
    #: independently of its neighbors, so running it on an arbitrary
    #: slice of a flat dtype-homogeneous bucket of MANY parameters is
    #: identical to running it per-parameter — the contract the ZeRO-1
    #: sharded-server exchange (parallel.zero) relies on.  Norm-based
    #: rules (LARS, GroupAdaGrad) set False; LARS provides the
    #: bucket-aware form below instead.
    fused_elementwise = True

    def fused_bucket_update(self, w, g, state, t, key=None, seg_ids=None,
                            num_segments=None, axis_name=None):
        """Update one flat bucket SHARD (the server-side-optimizer
        analog, kvstore_dist_server.h:346).  ``w``/``g``/``state`` are
        this device's slice of the flat bucket; ``seg_ids`` maps each
        element to its parameter within the bucket and ``axis_name``
        names the shard axis, for rules needing cross-shard
        per-parameter reductions.  Default: delegate to the
        elementwise ``fused_update``."""
        if not self.fused_elementwise:
            raise MXNetError(
                f"{type(self).__name__} is not elementwise and provides "
                "no bucket-aware fused rule")
        return self.fused_update(w, g, state, t, key=key)


def _jit(fn):
    """jit with scalar hyper-params as traced args (no recompile per lr)."""
    return jax.jit(fn)


# ================================================================= rules
@_jit
def _sgd_step(w, g, lr, wd):
    return w - lr * (g + wd * w)


@_jit
def _sgd_mom_step(w, mom, g, lr, wd, momentum):
    mom = momentum * mom - lr * (g + wd * w)
    return w + mom, mom


@register
class SGD(Optimizer):
    """SGD with momentum (reference optimizer.py SGD; op
    src/operator/optimizer_op.cc sgd_update/sgd_mom_update).

    update: mom = momentum*mom - lr*(grad + wd*w); w += mom
    """

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return ()
        return (nd.zeros(weight.shape, ctx=weight.context,
                         dtype=weight.dtype),)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._prep(grad._data)
        if self.momentum == 0.0:
            weight._adopt(_sgd_step(weight._data, g, lr, wd))
        else:
            (mom,) = state
            new_w, new_m = _sgd_mom_step(
                weight._data, mom._data, g, lr, wd, self.momentum)
            weight._adopt(new_w)
            mom._adopt(new_m)

    def fused_update(self, w, g, state, t, key=None):
        g = self._prep(g)
        if self.momentum == 0.0:
            # momentum may have been zeroed LIVE: pass any existing
            # slot through untouched (the eager rule leaves it stale
            # too) so the traced state structure never changes
            return _sgd_step(w, g, self.learning_rate, self.wd), state
        (mom,) = state
        new_w, new_m = _sgd_mom_step(w, mom, g, self.learning_rate,
                                     self.wd, self.momentum)
        return new_w, (new_m,)


@register
class Test(Optimizer):
    """Reference test optimizer: w += grad * rescale."""

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, ctx=weight.context),)

    def update(self, index, weight, grad, state):
        weight._adopt(weight._data + grad._data * self.rescale_grad)

    def fused_update(self, w, g, state, t, key=None):
        return w + g * self.rescale_grad, state


@_jit
def _nag_step(w, mom, g, lr, wd, momentum):
    g = g + wd * w
    mom = momentum * mom + g
    return w - lr * (g + momentum * mom), mom


@register
class NAG(Optimizer):
    """Nesterov accelerated SGD (reference NAG)."""

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return ()
        return (nd.zeros(weight.shape, ctx=weight.context,
                         dtype=weight.dtype),)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._prep(grad._data)
        if self.momentum == 0.0:
            weight._adopt(_sgd_step(weight._data, g, lr, wd))
        else:
            (mom,) = state
            new_w, new_m = _nag_step(weight._data, mom._data, g, lr, wd,
                                     self.momentum)
            weight._adopt(new_w)
            mom._adopt(new_m)

    def fused_update(self, w, g, state, t, key=None):
        g = self._prep(g)
        if self.momentum == 0.0:
            # see SGD: live-zeroed momentum keeps the slot structure
            return _sgd_step(w, g, self.learning_rate, self.wd), state
        (mom,) = state
        new_w, new_m = _nag_step(w, mom, g, self.learning_rate, self.wd,
                                 self.momentum)
        return new_w, (new_m,)


@_jit
def _signum_step(w, mom, g, lr, wd, momentum, wd_lh):
    mom = momentum * mom - (1 - momentum) * (g + wd * w)
    return (1 - lr * wd_lh) * w + lr * jnp.sign(mom), mom


@register
class Signum(Optimizer):
    """signSGD / Signum (reference Signum)."""

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0,
                 **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return ()
        return (nd.zeros(weight.shape, ctx=weight.context,
                         dtype=weight.dtype),)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._prep(grad._data)
        if self.momentum == 0.0:
            weight._adopt(
                (1 - lr * self.wd_lh) * weight._data
                - lr * jnp.sign(g + wd * weight._data))
        else:
            (mom,) = state
            new_w, new_m = _signum_step(
                weight._data, mom._data, g, lr, wd, self.momentum,
                self.wd_lh)
            weight._adopt(new_w)
            mom._adopt(new_m)

    def fused_update(self, w, g, state, t, key=None):
        g = self._prep(g)
        lr, wd = self.learning_rate, self.wd
        if self.momentum == 0.0:
            # see SGD: live-zeroed momentum keeps the slot structure
            return ((1 - lr * self.wd_lh) * w
                    - lr * jnp.sign(g + wd * w)), state
        (mom,) = state
        new_w, new_m = _signum_step(w, mom, g, lr, wd, self.momentum,
                                    self.wd_lh)
        return new_w, (new_m,)


@_jit
def _adam_step(w, m, v, g, lr, wd, beta1, beta2, eps, t):
    g = g + wd * w
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * g * g
    coef1 = 1.0 - beta1 ** t
    coef2 = 1.0 - beta2 ** t
    lr_t = lr * jnp.sqrt(coef2) / coef1
    return w - lr_t * m / (jnp.sqrt(v) + eps), m, v


@register
class Adam(Optimizer):
    """Adam (reference Adam; op adam_update)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (
            nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
            nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
        )

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr, wd = self._get_lr(index), self._get_wd(index)
        m, v = state
        g = self._prep(grad._data)
        new_w, new_m, new_v = _adam_step(
            weight._data, m._data, v._data, g, lr, wd, self.beta1,
            self.beta2, self.epsilon, float(t))
        weight._adopt(new_w)
        m._adopt(new_m)
        v._adopt(new_v)

    def fused_update(self, w, g, state, t, key=None):
        m, v = state
        new_w, new_m, new_v = _adam_step(
            w, m, v, self._prep(g), self.learning_rate, self.wd,
            self.beta1, self.beta2, self.epsilon, t)
        return new_w, (new_m, new_v)


@_jit
def _adamw_step(w, m, v, g, lr, eta, wd, beta1, beta2, eps, t):
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * g * g
    coef1 = 1.0 - beta1 ** t
    coef2 = 1.0 - beta2 ** t
    lr_t = lr * jnp.sqrt(coef2) / coef1
    return w - eta * (lr_t * m / (jnp.sqrt(v) + eps) + wd * w), m, v


@register
class AdamW(Adam):
    """Decoupled weight decay Adam (reference
    src/operator/contrib/adamw.cc)."""

    def __init__(self, eta=1.0, **kwargs):
        super().__init__(**kwargs)
        self.eta = eta

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr, wd = self._get_lr(index), self._get_wd(index)
        m, v = state
        g = self._prep(grad._data)
        new_w, new_m, new_v = _adamw_step(
            weight._data, m._data, v._data, g, lr, self.eta, wd,
            self.beta1, self.beta2, self.epsilon, float(t))
        weight._adopt(new_w)
        m._adopt(new_m)
        v._adopt(new_v)

    def fused_update(self, w, g, state, t, key=None):
        m, v = state
        new_w, new_m, new_v = _adamw_step(
            w, m, v, self._prep(g), self.learning_rate, self.eta,
            self.wd, self.beta1, self.beta2, self.epsilon, t)
        return new_w, (new_m, new_v)


@_jit
def _adagrad_step(w, hist, g, lr, wd, eps):
    # reference adagrad op: history accumulates the raw grad^2, eps sits
    # inside the sqrt, and wd applies as a decoupled term
    hist = hist + g * g
    return w - lr * (g / jnp.sqrt(hist + eps) + wd * w), hist


@register
class AdaGrad(Optimizer):
    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, ctx=weight.context,
                         dtype=weight.dtype),)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        (hist,) = state
        g = self._prep(grad._data)
        new_w, new_h = _adagrad_step(weight._data, hist._data, g, lr, wd,
                                     self.float_stable_eps)
        weight._adopt(new_w)
        hist._adopt(new_h)

    def fused_update(self, w, g, state, t, key=None):
        (hist,) = state
        new_w, new_h = _adagrad_step(w, hist, self._prep(g),
                                     self.learning_rate, self.wd,
                                     self.float_stable_eps)
        return new_w, (new_h,)


@_jit
def _group_adagrad_step(w, hist, g, lr, eps):
    hist = hist + jnp.mean(jnp.square(g), axis=tuple(range(1, g.ndim)),
                           keepdims=True)
    return w - lr * g / jnp.sqrt(hist + eps), hist


@register
class GroupAdaGrad(Optimizer):
    """Per-row (group) AdaGrad (reference
    python/mxnet/optimizer/contrib.py GroupAdaGrad + fused op
    src/operator/contrib/optimizer_op.cc group_adagrad_update):

        history += mean(square(grad), axis=1, keepdims=True)
        weight  -= lr * grad / sqrt(history + eps)

    One adaptive rate per output row — the embedding-table optimizer.
    Weight decay is not supported (reference contract).  Not
    bucket-shardable: the per-row history couples elements and no
    flat-bucket form exists, so ``optimizer_sharding="ps"`` rejects
    it."""

    fused_elementwise = False

    def __init__(self, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        assert len(weight.shape) >= 2, \
            "GroupAdaGrad needs >=2-dim weights (one group per row)"
        return (nd.zeros((weight.shape[0],) + (1,) *
                         (len(weight.shape) - 1),
                         ctx=weight.context, dtype=weight.dtype),)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        assert self._get_wd(index) == 0.0, \
            "GroupAdaGrad does not support weight decay"
        lr = self._get_lr(index)
        (hist,) = state
        new_w, new_h = _group_adagrad_step(
            weight._data, hist._data, self._prep(grad._data), lr,
            self.float_stable_eps)
        weight._adopt(new_w)
        hist._adopt(new_h)

    def fused_update(self, w, g, state, t, key=None):
        assert self.wd == 0.0, \
            "GroupAdaGrad does not support weight decay"
        (hist,) = state
        new_w, new_h = _group_adagrad_step(
            w, hist, self._prep(g), self.learning_rate,
            self.float_stable_eps)
        return new_w, (new_h,)


@_jit
def _rmsprop_step(w, n, g, lr, wd, rho, eps):
    g = g + wd * w
    n = rho * n + (1 - rho) * g * g
    return w - lr * g / jnp.sqrt(n + eps), n


@_jit
def _rmsprop_alex_step(w, n, gavg, delta, g, lr, wd, rho, momentum, eps):
    g = g + wd * w
    n = rho * n + (1 - rho) * g * g
    gavg = rho * gavg + (1 - rho) * g
    delta = momentum * delta - lr * g / jnp.sqrt(n - gavg * gavg + eps)
    return w + delta, n, gavg, delta


@register
class RMSProp(Optimizer):
    """RMSProp (reference RMSProp; centered=True uses Alex Graves' variant)."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        z = lambda: nd.zeros(weight.shape, ctx=weight.context,
                             dtype=weight.dtype)
        if self.centered:
            return (z(), z(), z())
        return (z(),)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._prep(grad._data)
        if self.centered:
            n, gavg, delta = state
            new_w, new_n, new_g, new_d = _rmsprop_alex_step(
                weight._data, n._data, gavg._data, delta._data, g, lr, wd,
                self.gamma1, self.gamma2, self.epsilon)
            weight._adopt(new_w)
            n._adopt(new_n)
            gavg._adopt(new_g)
            delta._adopt(new_d)
        else:
            (n,) = state
            new_w, new_n = _rmsprop_step(
                weight._data, n._data, g, lr, wd, self.gamma1, self.epsilon)
            weight._adopt(new_w)
            n._adopt(new_n)
        if self.clip_weights:
            weight._adopt(jnp.clip(weight._data, -self.clip_weights,
                                   self.clip_weights))

    def fused_update(self, w, g, state, t, key=None):
        g = self._prep(g)
        lr, wd = self.learning_rate, self.wd
        if self.centered:
            n, gavg, delta = state
            new_w, new_n, new_g, new_d = _rmsprop_alex_step(
                w, n, gavg, delta, g, lr, wd, self.gamma1, self.gamma2,
                self.epsilon)
            new_state = (new_n, new_g, new_d)
        else:
            (n,) = state
            new_w, new_n = _rmsprop_step(w, n, g, lr, wd, self.gamma1,
                                         self.epsilon)
            new_state = (new_n,)
        if self.clip_weights:
            new_w = jnp.clip(new_w, -self.clip_weights, self.clip_weights)
        return new_w, new_state


@_jit
def _adadelta_step(w, acc_g, acc_delta, g, wd, rho, eps):
    g = g + wd * w
    acc_g = rho * acc_g + (1 - rho) * g * g
    delta = jnp.sqrt(acc_delta + eps) / jnp.sqrt(acc_g + eps) * g
    acc_delta = rho * acc_delta + (1 - rho) * delta * delta
    return w - delta, acc_g, acc_delta


@register
class AdaDelta(Optimizer):
    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        z = lambda: nd.zeros(weight.shape, ctx=weight.context,
                             dtype=weight.dtype)
        return (z(), z())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        acc_g, acc_delta = state
        g = self._prep(grad._data)
        new_w, new_ag, new_ad = _adadelta_step(
            weight._data, acc_g._data, acc_delta._data, g, wd, self.rho,
            self.epsilon)
        weight._adopt(new_w)
        acc_g._adopt(new_ag)
        acc_delta._adopt(new_ad)

    def fused_update(self, w, g, state, t, key=None):
        acc_g, acc_delta = state
        new_w, new_ag, new_ad = _adadelta_step(
            w, acc_g, acc_delta, self._prep(g), self.wd, self.rho,
            self.epsilon)
        return new_w, (new_ag, new_ad)


@_jit
def _adamax_step(w, m, u, g, lr, wd, beta1, beta2, t):
    g = g + wd * w
    m = beta1 * m + (1 - beta1) * g
    u = jnp.maximum(beta2 * u, jnp.abs(g))
    lr_t = lr / (1.0 - beta1 ** t)
    return w - lr_t * m / (u + 1e-8), m, u


@register
class Adamax(Optimizer):
    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999,
                 **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2

    def create_state(self, index, weight):
        z = lambda: nd.zeros(weight.shape, ctx=weight.context,
                             dtype=weight.dtype)
        return (z(), z())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr, wd = self._get_lr(index), self._get_wd(index)
        m, u = state
        g = self._prep(grad._data)
        new_w, new_m, new_u = _adamax_step(
            weight._data, m._data, u._data, g, lr, wd, self.beta1,
            self.beta2, float(t))
        weight._adopt(new_w)
        m._adopt(new_m)
        u._adopt(new_u)

    def fused_update(self, w, g, state, t, key=None):
        m, u = state
        new_w, new_m, new_u = _adamax_step(
            w, m, u, self._prep(g), self.learning_rate, self.wd,
            self.beta1, self.beta2, t)
        return new_w, (new_m, new_u)


@_jit
def _nadam_step(w, m, v, g, lr, wd, beta1, beta2, eps, t, m_schedule,
                schedule_decay):
    g = g + wd * w
    momentum_t = beta1 * (1.0 - 0.5 * 0.96 ** (t * schedule_decay))
    momentum_t_1 = beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * schedule_decay))
    new_m_schedule = m_schedule * momentum_t
    m_schedule_next = new_m_schedule * momentum_t_1
    g_prime = g / (1.0 - new_m_schedule)
    m = beta1 * m + (1.0 - beta1) * g
    m_prime = m / (1.0 - m_schedule_next)
    v = beta2 * v + (1.0 - beta2) * g * g
    v_prime = v / (1.0 - beta2 ** t)
    m_bar = (1.0 - momentum_t) * g_prime + momentum_t_1 * m_prime
    return w - lr * m_bar / (jnp.sqrt(v_prime) + eps), m, v, new_m_schedule


@register
class Nadam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        z = lambda: nd.zeros(weight.shape, ctx=weight.context,
                             dtype=weight.dtype)
        return (z(), z())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr, wd = self._get_lr(index), self._get_wd(index)
        m, v = state
        g = self._prep(grad._data)
        new_w, new_m, new_v, ms = _nadam_step(
            weight._data, m._data, v._data, g, lr, wd, self.beta1,
            self.beta2, self.epsilon, float(t), self.m_schedule,
            self.schedule_decay)
        self.m_schedule = float(ms)
        weight._adopt(new_w)
        m._adopt(new_m)
        v._adopt(new_v)

    def fused_state(self, w):
        # m_schedule is per-parameter carried state in the fused path
        # (the eager path keeps it as a python attribute)
        return (jnp.zeros_like(w), jnp.zeros_like(w),
                jnp.ones((), dtype=jnp.float32))

    def fused_update(self, w, g, state, t, key=None):
        m, v, m_schedule = state
        new_w, new_m, new_v, new_ms = _nadam_step(
            w, m, v, self._prep(g), self.learning_rate, self.wd,
            self.beta1, self.beta2, self.epsilon, t, m_schedule,
            self.schedule_decay)
        return new_w, (new_m, new_v, new_ms)


@_jit
def _ftrl_step(w, z, n, g, lr, wd, lamda1, beta):
    sigma = (jnp.sqrt(n + g * g) - jnp.sqrt(n)) / lr
    z = z + g - sigma * w
    n = n + g * g
    denom = wd + (beta + jnp.sqrt(n)) / lr
    new_w = jnp.where(
        jnp.abs(z) > lamda1,
        -(z - jnp.sign(z) * lamda1) / denom,
        jnp.zeros_like(w))
    return new_w, z, n


@register
class Ftrl(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        z = lambda: nd.zeros(weight.shape, ctx=weight.context,
                             dtype=weight.dtype)
        return (z(), z())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        zst, n = state
        g = self._prep(grad._data)
        new_w, new_z, new_n = _ftrl_step(
            weight._data, zst._data, n._data, g, lr, wd, self.lamda1,
            self.beta)
        weight._adopt(new_w)
        zst._adopt(new_z)
        n._adopt(new_n)

    def fused_update(self, w, g, state, t, key=None):
        z, n = state
        new_w, new_z, new_n = _ftrl_step(
            w, z, n, self._prep(g), self.learning_rate, self.wd,
            self.lamda1, self.beta)
        return new_w, (new_z, new_n)


@_jit
def _ftml_step(w, d, s, z, g, lr, wd, beta1, beta2, eps, t):
    g = g + wd * w
    v = beta2 * s + (1 - beta2) * g * g
    d_t = (1.0 - beta1 ** t) / lr * (
        jnp.sqrt(v / (1.0 - beta2 ** t)) + eps)
    sigma_t = d_t - beta1 * d
    z = beta1 * z + (1.0 - beta1) * g - sigma_t * w
    return -z / d_t, d_t, v, z


@register
class FTML(Optimizer):
    def __init__(self, beta1=0.6, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(**kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        z = lambda: nd.zeros(weight.shape, ctx=weight.context,
                             dtype=weight.dtype)
        return (z(), z(), z())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr, wd = self._get_lr(index), self._get_wd(index)
        d, s, zz = state
        g = self._prep(grad._data)
        new_w, new_d, new_s, new_z = _ftml_step(
            weight._data, d._data, s._data, zz._data, g, lr, wd,
            self.beta1, self.beta2, self.epsilon, float(t))
        weight._adopt(new_w)
        d._adopt(new_d)
        s._adopt(new_s)
        zz._adopt(new_z)

    def fused_update(self, w, g, state, t, key=None):
        d, s, z = state
        new_w, new_d, new_s, new_z = _ftml_step(
            w, d, s, z, self._prep(g), self.learning_rate, self.wd,
            self.beta1, self.beta2, self.epsilon, t)
        return new_w, (new_d, new_s, new_z)


@_jit
def _lars_step(w, mom, g, lr, wd, momentum, eta, eps):
    w_norm = jnp.linalg.norm(w)
    g_norm = jnp.linalg.norm(g)
    trust = jnp.where(
        (w_norm > 0) & (g_norm > 0),
        eta * w_norm / (g_norm + wd * w_norm + eps),
        jnp.ones_like(w_norm))
    scaled_lr = lr * trust
    mom = momentum * mom + scaled_lr * (g + wd * w)
    return w - mom, mom


def _lars_bucket_step(w, mom, g, seg_ids, lr, wd, momentum, eta, eps,
                      num_segments, axis_name=None):
    """LARS over one bucket shard: per-PARAMETER trust ratios from
    segment-summed squared norms, psum'd over the shard axis when a
    parameter spans shards (the multi_lars/multi_sum_sq pipeline,
    src/operator/contrib/multi_lars.cc, applied to the ZeRO layout).
    The shard is 1-D, or rows of a leaf-shaped bucket's one leaf
    (``seg_ids`` of the same shape; summed in row-major order, which
    is the flat shard's)."""
    flat_ids = seg_ids.reshape(-1)
    w_ss = jax.ops.segment_sum((w * w).reshape(-1), flat_ids,
                               num_segments=num_segments)
    g_ss = jax.ops.segment_sum((g * g).reshape(-1), flat_ids,
                               num_segments=num_segments)
    if axis_name is not None:
        with jax.named_scope("mx_exchange"):
            w_ss = jax.lax.psum(w_ss, axis_name)
            g_ss = jax.lax.psum(g_ss, axis_name)
    w_norm = jnp.sqrt(w_ss)
    g_norm = jnp.sqrt(g_ss)
    trust = jnp.where((w_norm > 0) & (g_norm > 0),
                      eta * w_norm / (g_norm + wd * w_norm + eps),
                      jnp.ones_like(w_norm))
    scaled_lr = (lr * trust)[seg_ids]
    mom = momentum * mom + scaled_lr * (g + wd * w)
    return w - mom, mom


@register
class LARS(Optimizer):
    """Layer-wise Adaptive Rate Scaling (reference optimizer.py:796 and
    the multi_lars fused ops, src/operator/contrib/multi_lars.cc)."""

    #: the trust ratio is a per-TENSOR norm, so the generic
    #: slice-the-bucket delegation is wrong; fused_bucket_update below
    #: recovers exact layer norms from segment sums + psum instead
    fused_elementwise = False

    def __init__(self, momentum=0.0, lars_eta=0.001, lars_epsilon=0,
                 momentum_correction=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.eta = lars_eta
        self.epsilon = lars_epsilon

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, ctx=weight.context,
                         dtype=weight.dtype),)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        (mom,) = state
        g = self._prep(grad._data)
        new_w, new_m = _lars_step(
            weight._data, mom._data, g, lr, wd, self.momentum, self.eta,
            self.epsilon)
        weight._adopt(new_w)
        mom._adopt(new_m)

    def fused_update(self, w, g, state, t, key=None):
        (mom,) = state
        new_w, new_m = _lars_step(
            w, mom, self._prep(g), self.learning_rate, self.wd,
            self.momentum, self.eta, self.epsilon)
        return new_w, (new_m,)

    def fused_bucket_update(self, w, g, state, t, key=None, seg_ids=None,
                            num_segments=None, axis_name=None):
        if seg_ids is None:
            # whole-tensor bucket: degenerate to the per-param rule
            return self.fused_update(w, g, state, t, key=key)
        (mom,) = state
        new_w, new_m = _lars_bucket_step(
            w, mom, self._prep(g), seg_ids, self.learning_rate, self.wd,
            self.momentum, self.eta, self.epsilon, num_segments,
            axis_name)
        return new_w, (new_m,)


@register
class LBSGD(SGD):
    """Large-batch SGD with warmup (reference LBSGD; here LARS-style
    adaptive rate atop SGD semantics)."""

    def __init__(self, momentum=0.0, multi_precision=False,
                 warmup_strategy="linear", warmup_epochs=5, batch_scale=1,
                 updates_per_epoch=32, begin_epoch=0, num_epochs=60,
                 **kwargs):
        super().__init__(momentum=momentum,
                         multi_precision=multi_precision, **kwargs)
        self.warmup_strategy = warmup_strategy
        self.warmup_epochs = warmup_epochs
        self.batch_scale = batch_scale
        self.updates_per_epoch = updates_per_epoch
        self.init_updates = begin_epoch * updates_per_epoch
        self.num_epochs = num_epochs


@register
class SGLD(Optimizer):
    """Stochastic Gradient Langevin Dynamics (reference SGLD)."""

    needs_key = True

    def create_state(self, index, weight):
        return ()

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._prep(grad._data)
        noise = nd.random_normal(
            0, math.sqrt(lr), shape=weight.shape,
            dtype=str(weight.dtype) if weight.dtype != jnp.bfloat16
            else "float32")
        weight._adopt(
            weight._data - lr / 2 * (g + wd * weight._data)
            + noise._data.astype(weight._data.dtype))

    def fused_update(self, w, g, state, t, key=None):
        if key is None:
            raise MXNetError("SGLD fused rule needs a PRNG key")
        lr, wd = self.learning_rate, self.wd
        g = self._prep(g)
        noise = math.sqrt(lr) * jax.random.normal(
            key, w.shape, dtype=jnp.float32).astype(w.dtype)
        return w - lr / 2 * (g + wd * w) + noise, state


@_jit
def _dcasgd_step(w, mom, prev_w, g, lr, wd, momentum, lamda):
    g = g + wd * w
    mom = momentum * mom - lr * (g + lamda * g * g * (w - prev_w))
    return w + mom, mom, w


@register
class DCASGD(Optimizer):
    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        z = lambda: nd.zeros(weight.shape, ctx=weight.context,
                             dtype=weight.dtype)
        return (z(), weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        mom, prev_w = state
        g = self._prep(grad._data)
        new_w, new_m, new_prev = _dcasgd_step(
            weight._data, mom._data, prev_w._data, g, lr, wd,
            self.momentum, self.lamda)
        weight._adopt(new_w)
        mom._adopt(new_m)
        prev_w._adopt(new_prev)

    def fused_update(self, w, g, state, t, key=None):
        mom, prev_w = state
        new_w, new_m, new_prev = _dcasgd_step(
            w, mom, prev_w, self._prep(g), self.learning_rate, self.wd,
            self.momentum, self.lamda)
        return new_w, (new_m, new_prev)


# ================================================================ Updater
class Updater:
    """Applies an optimizer locally (reference optimizer.py:1943
    get_updater); used by KVStore local mode and Module."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}
        self.states_synced = {}

    def __call__(self, index, grad, weight):
        if index not in self.states:
            state = self.optimizer.create_state_multi_precision(
                index, weight)
            self.states[index] = self._match_sharding(state, weight)
            self.states_synced[index] = True
        self.optimizer.update_multi_precision(
            index, weight, grad, self.states[index])

    @staticmethod
    def _match_sharding(state, weight):
        """Place freshly-created state like its weight: under a Module
        data mesh the weight is replicated over N devices, and a state
        array committed to a single device would make the fused update
        a cross-committed-device error."""
        w = weight._data
        sharding = getattr(w, "sharding", None)
        if sharding is None or not hasattr(w, "devices") \
                or len(w.devices()) <= 1:
            return state

        from jax.sharding import NamedSharding, PartitionSpec

        repl = NamedSharding(sharding.mesh, PartitionSpec()) \
            if isinstance(sharding, NamedSharding) else None

        def place(s):
            if isinstance(s, (tuple, list)):
                return type(s)(place(x) for x in s)
            if isinstance(s, nd.NDArray):
                if s.shape == weight.shape:
                    s._data = jax.device_put(s._data, sharding)
                elif repl is not None:
                    # state with its own shape (GroupAdaGrad's per-row
                    # history): replicate over the same mesh so the
                    # fused update sees one consistent device set
                    s._data = jax.device_put(s._data, repl)
            return s

        return place(state)

    def get_states(self, dump_optimizer=False):
        import copy
        import pickle

        if dump_optimizer:
            # runtime handles (live Parameter objects) must not be
            # serialized: the reference excludes them, and pickling them
            # would both duplicate every weight tensor into the .states
            # file and detach lr_mult/wd_mult lookups from the live
            # parameters after load
            opt = copy.copy(self.optimizer)
            opt.param_dict = {}
            return pickle.dumps((self.states, opt))
        return pickle.dumps(self.states)

    def set_states(self, states):
        import pickle

        states = pickle.loads(states)
        if isinstance(states, tuple) and len(states) == 2:
            self.states, new_opt = states
            # reattach the live param_dict (stripped at save time)
            new_opt.param_dict = getattr(self.optimizer, "param_dict", {})
            self.optimizer = new_opt
        else:
            self.states = states
        self.states_synced = dict.fromkeys(self.states.keys(), False)


def get_updater(optimizer):
    return Updater(optimizer)
