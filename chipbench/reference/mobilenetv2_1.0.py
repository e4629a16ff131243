"""MobileNetV2 (Sandler et al., arXiv:1801.04381, Table 2), training mode,
plain float32 ``jax.numpy``.

Written from the paper: a 3x3/2 convolution of 32 channels, seventeen
inverted-residual blocks (1x1 expansion by ``t``, 3x3 depthwise with the
block's stride, linear 1x1 projection; batch normalisation after each,
ReLU6 after the first two; a shortcut where stride is 1 and the channels
match), a 1x1 convolution to 1280 channels, global average pooling and a
1x1 convolution to the classes.  No convolution has a bias.

One departure from the paper, stated in the configuration
(``expand_when_t_is_1``): upstream MXNet's model zoo keeps the 1x1
"expansion" also in the first block, where ``t`` is 1, and so does this.

The state is a flat list in the order the layers are applied, each batch
normalisation's running mean and variance after its scale and shift.  The
running statistics move as the upstream operator moves them
(``bn_momentum`` 0.9, biased variance); no gradient reaches them.
"""
import jax
import jax.numpy as jnp

from chipbench import refmath as rm


def _blocks(arch):
    """(cin, expanded, cout, stride) of every inverted-residual block."""
    cin = arch["stem"]["channels"]
    for t, c, n, s in arch["blocks_t_c_n_s"]:
        for i in range(n):
            yield cin, cin * t, c, s if i == 0 else 1
            cin = c


def param_specs(arch, in_channels, classes):
    """[(kind, shape)] of every array of the net's state, the running
    statistics among them.  ``gamma_last`` is the scale of the batch
    normalisation that closes a residual branch."""
    def conv_bn(cout, k, cin, last=False):
        return [("conv", (cout, k, k, cin)),
                ("gamma_last" if last else "gamma", (cout,)),
                ("beta", (cout,)),
                ("running_mean", (cout,)), ("running_var", (cout,))]

    stem = arch["stem"]
    specs = conv_bn(stem["channels"], stem["kernel"], in_channels)
    for cin, mid, cout, stride in _blocks(arch):
        if mid != cin or arch["expand_when_t_is_1"]:
            specs += conv_bn(mid, 1, cin)
        specs += conv_bn(mid, 3, 1) \
            + conv_bn(cout, 1, mid, last=(stride == 1 and cin == cout))
    specs += conv_bn(arch["last_channels"], 1, cout)
    return specs + [("conv", (classes, 1, 1, arch["last_channels"]))]


def forward(params, x, arch, precision="float32"):
    """``(logits, moved)`` of a batch ``x`` (N, H, W, C) normalised by its
    own statistics; ``moved`` holds the new running mean and variance of
    every batch normalisation by their index in ``params``."""
    eps = arch["bn_eps"]
    moved = {}
    at = [0]

    def conv_bn(t, stride, pad, p, groups=1):
        w, gamma, beta = p
        y, mean, var = rm.batchnorm(
            rm.conv(t, w, stride, pad, groups, precision=precision),
            gamma, beta, eps)
        return y, (mean, var)

    def take(n):
        """The next ``n`` convolutions with their scale and shift, and
        where their running statistics lie."""
        first = range(at[0], at[0] + 5 * n, 5)
        at[0] += 5 * n
        return [tuple(params[i:i + 3]) for i in first], list(first)

    def move(first, stats):
        for i, (mean, var) in zip(first, stats):
            moved[i + 3] = rm.moving_average(params[i + 3], mean,
                                             arch["bn_momentum"])
            moved[i + 4] = rm.moving_average(params[i + 4], var,
                                             arch["bn_momentum"])

    def relu6(t):
        return jnp.clip(t, 0.0, 6.0)

    stem = arch["stem"]
    ps, first = take(1)
    x, stats = conv_bn(x, stem["stride"], stem["pad"], ps[0])
    move(first, [stats])
    x = relu6(x)
    for cin, mid, cout, stride in _blocks(arch):
        expand = mid != cin or arch["expand_when_t_is_1"]

        def block(t, ps, stride=stride, mid=mid, expand=expand,
                  shortcut=(stride == 1 and cin == cout)):
            u, stats = t, []
            if expand:
                u, s = conv_bn(u, 1, 0, ps[0])
                u = relu6(u)
                stats.append(s)
            u, s = conv_bn(u, stride, 1, ps[-2], groups=mid)
            stats.append(s)
            u, s = conv_bn(relu6(u), 1, 0, ps[-1])
            stats.append(s)
            return (u + t if shortcut else u), stats

        # recompute each block in the backward pass: the float32
        # reference of a whole batch then fits beside nothing else
        ps, first = take(3 if expand else 2)
        x, stats = jax.checkpoint(block)(x, ps)
        move(first, stats)
    ps, first = take(1)
    x, stats = conv_bn(x, 1, 0, ps[0])
    move(first, [stats])
    x = relu6(x).mean(axis=(1, 2), keepdims=True)
    logits = rm.conv(x, params[at[0]], precision=precision)
    return logits.reshape(x.shape[0], -1), moved
