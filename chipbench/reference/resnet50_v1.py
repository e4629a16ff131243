"""ResNet v1 with bottleneck blocks (He et al., arXiv:1512.03385, Table 1
and Figure 5 right), training mode, plain float32 ``jax.numpy``.

Written from the paper: a 7x7/2 convolution of 64 channels, 3x3/2 max
pooling, four stages of bottleneck blocks (1x1 reduce, 3x3, 1x1 expand,
each followed by batch normalisation, ReLU after the first two and after
the addition), a projection shortcut (1x1 convolution + batch
normalisation, option B) on the first block of each stage, the stride of
a stage on the first 1x1 convolution of its first block and on its
projection, global average pooling and a 1000-way dense layer.  No
convolution has a bias.

The state is a flat list in the order the layers are applied, the
projection after the block's body, each batch normalisation's running
mean and variance after its scale and shift: see :func:`param_specs`.
The running statistics move as the upstream operator moves them
(``bn_momentum`` 0.9, biased variance); no gradient reaches them.
"""
import jax

from chipbench import refmath as rm


def _blocks(arch):
    """(cin, mid, cout, stride, has_projection) of every block."""
    cin = arch["stem"]["channels"]
    for stage in arch["stages"]:
        cout = stage["channels"]
        for b in range(stage["blocks"]):
            yield (cin, cout // arch["bottleneck_ratio"], cout,
                   stage["stride"] if b == 0 else 1, b == 0)
            cin = cout


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
    for cin, mid, cout, _, proj in _blocks(arch):
        specs += conv_bn(mid, 1, cin) + conv_bn(mid, 3, mid) \
            + conv_bn(cout, 1, mid, last=True)
        if proj:
            specs += conv_bn(cout, 1, cin)
    return specs + [("dense", (classes, cout)), ("bias", (classes,))]


def forward(params, x, arch, precision="float32"):
    """``(logits, moved)`` of a batch ``x`` (N, H, W, C) normalised by its
    own statistics; ``moved`` holds the new running mean and variance of
    every batch normalisation by their index in ``params``."""
    eps = arch["bn_eps"]
    moved = {}
    at = [0]

    def conv_bn(t, stride, pad, p):
        w, gamma, beta = p
        y, mean, var = rm.batchnorm(
            rm.conv(t, w, stride, pad, precision=precision), gamma, beta,
            eps)
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

    stem = arch["stem"]
    ps, first = take(1)
    x, stats = conv_bn(x, stem["stride"], stem["pad"], ps[0])
    move(first, [stats])
    x = jax.nn.relu(x)
    pool = stem["pool"]
    x = rm.maxpool(x, pool["kernel"], pool["stride"], pool["pad"])
    for _, _, _, stride, proj in _blocks(arch):
        def block(t, ps, stride=stride):
            u, s0 = conv_bn(t, stride, 0, ps[0])
            u, s1 = conv_bn(jax.nn.relu(u), 1, 1, ps[1])
            u, s2 = conv_bn(jax.nn.relu(u), 1, 0, ps[2])
            stats = [s0, s1, s2]
            short = t
            if len(ps) == 4:
                short, s3 = conv_bn(t, stride, 0, ps[3])
                stats.append(s3)
            return jax.nn.relu(u + short), stats

        # recompute each block in the backward pass: the float32
        # reference of a whole batch then fits beside nothing else
        ps, first = take(4 if proj else 3)
        x, stats = jax.checkpoint(block)(x, ps)
        move(first, stats)
    x = x.mean(axis=(1, 2))
    w, b = params[at[0]:at[0] + 2]
    return rm.dense(x, w, b, precision=precision), moved
