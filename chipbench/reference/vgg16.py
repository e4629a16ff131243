"""VGG, configuration D (Simonyan & Zisserman, arXiv:1409.1556, Table 1),
training mode, plain float32 ``jax.numpy``.

Written from the paper: five stages of 3x3 convolutions of stride 1 and
padding 1 (2, 2, 3, 3, 3 of them, of 64, 128, 256, 512, 512 channels),
each followed by a ReLU, a 2x2 max pooling of stride 2 after each stage,
then three dense layers (4096, 4096, classes) with a ReLU after the first
two.  Every convolution and dense layer has a bias.

One departure from the paper, stated in the configuration (``dropout``
0): the paper drops half of the first two dense layers' outputs while
training; the masks are the program's own random draws, which a
reference that takes nothing from the program cannot repeat.

The state is a flat list in the order the layers are applied, each
layer's weights and then its bias: see :func:`param_specs`.  The forward
pass moves none of it.
"""
import jax

from chipbench import refmath as rm


def _convs(arch, in_channels):
    """(cin, cout, closes_stage) of every convolution."""
    cin = in_channels
    for stage in arch["stages"]:
        for k in range(stage["convs"]):
            yield cin, stage["channels"], k == stage["convs"] - 1
            cin = stage["channels"]


def param_specs(arch, in_channels, classes):
    """[(kind, shape)] of every array of the net's state."""
    specs = []
    for cin, cout, _ in _convs(arch, in_channels):
        specs += [("conv", (cout, arch["kernel"], arch["kernel"], cin)),
                  ("bias", (cout,))]
    side = arch["input_side"] // 2 ** len(arch["stages"])
    fan_in = side * side * cout
    for width in arch["dense"] + [classes]:
        specs += [("dense", (width, fan_in)), ("bias", (width,))]
        fan_in = width
    return specs


def forward(params, x, arch, precision="float32"):
    """``(logits, moved)`` of a batch ``x`` (N, H, W, C); ``moved`` is
    empty: no layer of this net keeps statistics."""
    it = iter(params)
    pool = arch["pool"]

    def conv_relu(t, w, b):
        return jax.nn.relu(
            rm.conv(t, w, 1, arch["pad"], precision=precision) + b)

    for _, _, closes in _convs(arch, x.shape[-1]):
        # recompute each convolution's output in the backward pass: the
        # float32 reference of a whole batch then fits
        x = jax.checkpoint(conv_relu)(x, next(it), next(it))
        if closes:
            x = rm.maxpool(x, pool["kernel"], pool["stride"], 0)
    x = x.reshape(x.shape[0], -1)
    for _ in arch["dense"]:
        x = jax.nn.relu(rm.dense(x, next(it), next(it), precision))
    return rm.dense(x, next(it), next(it), precision), {}
