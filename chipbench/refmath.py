"""Plain float32 ``jax.numpy`` building blocks shared by the references
under ``chipbench/reference/``: convolution, dense, training-mode batch
normalisation, pooling, the loss, the optimizers' rules (SGD with
momentum, AdamW), and the fake quantisation the lower-precision control
uses.

Nothing here imports the program (``mxnet_tpu``).  Layouts are fixed:
activations NHWC, convolution weights OHWI (out, kh, kw, in / groups),
dense weights (out, in).  Every product runs at ``HIGHEST`` precision
(on a TPU a float32 matmul is otherwise one bf16 pass).

``precision`` is ``"float32"`` (the reference) or the control below a
bf16 configuration: ``"float8"`` snaps the operands of every product to
the e4m3 grid at a per-tensor scale and the cotangent that enters each
product's backward to e5m2, the usual fp8 training recipe.  The products
themselves stay float32, so the control differs from the reference by its
operands' rounding and nothing else.
"""
import jax
import jax.numpy as jnp
from jax import lax

_HIGHEST = lax.Precision.HIGHEST
_E4M3_MAX = 448.0
_E5M2_MAX = 57344.0


def _snap(x, dtype, top):
    """``x`` rounded to ``dtype``'s grid at the scale that maps its
    largest magnitude to ``top``."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _q_operand(x):
    return _snap(x, jnp.float8_e4m3fn, _E4M3_MAX)


_q_operand.defvjp(lambda x: (_q_operand(x), None), lambda _, ct: (ct,))


@jax.custom_vjp
def _q_cotangent(y):
    return y


_q_cotangent.defvjp(lambda y: (y, None),
                    lambda _, ct: (_snap(ct, jnp.float8_e5m2, _E5M2_MAX),))


def _operands(precision, *arrays):
    if precision == "float32":
        return arrays
    if precision == "float8":
        return tuple(_q_operand(a) for a in arrays)
    raise ValueError(f"unknown precision {precision!r}")


def _result(precision, y):
    return _q_cotangent(y) if precision == "float8" else y


def conv(x, w, stride=1, pad=0, groups=1, precision="float32"):
    x, w = _operands(precision, x, w)
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "OHWI", "NHWC"),
        feature_group_count=groups, precision=_HIGHEST)
    return _result(precision, y)


def dense(x, w, b=None, precision="float32"):
    x, w = _operands(precision, x, w)
    y = _result(precision, jnp.matmul(x, w.T, precision=_HIGHEST))
    return y if b is None else y + b


def batchnorm(x, gamma, beta, eps):
    """Training mode: ``x`` normalised by this batch's mean and biased
    variance over N,H,W; returns ``(y, mean, var)``."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * gamma + beta, mean, var


def moving_average(running, batch, momentum):
    """The upstream BatchNorm operator's moving statistic (batch_norm.cc):
    ``running * momentum + batch * (1 - momentum)``, the variance biased."""
    return running * momentum + lax.stop_gradient(batch) * (1.0 - momentum)


def maxpool(x, kernel, stride, pad):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, kernel, kernel, 1),
        (1, stride, stride, 1), [(0, 0), (pad, pad), (pad, pad), (0, 0)])


def softmax_cross_entropy(logits, labels):
    """Mean of -log softmax(logits)[label] over the rows and, where the
    logits have them, over all positions of every row."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.mean(picked)


def sgd_momentum(w, mom, g, lr, momentum, wd):
    """MXNet's rule (optimizer_op.cc sgd_mom_update):
    mom = momentum * mom - lr * (g + wd * w);  w += mom."""
    mom = momentum * mom - lr * (g + wd * w)
    return w + mom, mom


def adamw(w, m, v, g, t, lr, beta1, beta2, epsilon, wd):
    """Adam with decoupled weight decay (Loshchilov & Hutter,
    arXiv:1711.05101, Algorithm 2, schedule multiplier 1) at step ``t``
    (from 1): ``m = beta1 m + (1 - beta1) g``, ``v = beta2 v + (1 - beta2)
    g^2``, ``w -= lr_t m / (sqrt(v) + epsilon) + wd w`` with ``lr_t = lr
    sqrt(1 - beta2^t) / (1 - beta1^t)``.  The bias correction is written
    as the program's ``_adamw_step`` and Kingma & Ba's section 2 have it,
    folded into the rate; Algorithm 2 divides ``m`` and ``v`` themselves,
    which is the same but for ``epsilon``, which there is added to
    ``sqrt(v / (1 - beta2^t))`` and here to ``sqrt(v)``: a factor of
    ``sqrt(1 - beta2^t)`` on ``epsilon``, 0.03 at the first step, that
    shows only where a gradient is as small as ``epsilon``.  The decay
    ``wd w`` is not multiplied by the rate, in the algorithm and in the
    program alike.  Returns ``(w, m, v)``."""
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * g * g
    lr_t = lr * jnp.sqrt(1.0 - beta2 ** t) / (1.0 - beta1 ** t)
    return w - (lr_t * m / (jnp.sqrt(v) + epsilon) + wd * w), m, v


def optimizer_rule(opt):
    """A configuration's ``optimizer`` group as ``(slots, update,
    first_gradient)``: ``update(w, state, g, t) -> (w, state)`` over a
    state of ``slots`` arrays that start at nought, and
    ``first_gradient(state after step 1)``, the gradient of step 1 as the
    rule got it, or None where the state does not hold it (under SGD it
    follows from the weights: ``chipbench/compare.py``)."""
    if opt["name"] == "sgd":
        def update(w, state, g, t):
            w, mom = sgd_momentum(w, state[0], g, opt["learning_rate"],
                                  opt["momentum"], opt["wd"])
            return w, (mom,)
        return 1, update, None
    if opt["name"] == "adamw":
        def update(w, state, g, t):
            w, m, v = adamw(w, state[0], state[1], g, t,
                            opt["learning_rate"], opt["beta1"],
                            opt["beta2"], opt["epsilon"], opt["wd"])
            return w, (m, v)
        return 2, update, lambda state: state[0] / (1.0 - opt["beta1"])
    raise ValueError(f"no plain rule for optimizer {opt['name']!r}")


def loss_and_grad(forward, arch, precision):
    """The jitted ``(params, x, y) -> ((loss, moved), grads)`` of one
    reference: ``forward(params, x, arch, precision)`` gives the logits
    and ``moved``, the new value of every leaf that the forward pass
    itself moves (batch normalisation's running statistics) by its index
    in ``params``; ``arch`` is the configuration file's ``arch`` group,
    ``x`` may come in bf16 (it is widened, which is exact) or hold token
    ids (left as they are) and ``y`` holds class ids: one a row, or one
    a position."""
    def loss(params, x, y):
        if jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(jnp.float32)
        logits, moved = forward(params, x, arch, precision)
        return softmax_cross_entropy(logits, y), moved

    return jax.jit(jax.value_and_grad(loss, has_aux=True))
