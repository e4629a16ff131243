"""The W-paired arms of Convolution and Pooling (ISSUE 26).

On a TPU a channel-last 3x3 stride-1 convolution and a 2x2/2
max-pooling of at most 64 channels run on ``[N, H, W/2, 2C]``: two
W-neighbours side by side on the 128 lanes.  These tests run on the
CPU, where ``kernel_target.on_tpu()`` is false and the registered ops
bypass, so (a) they call the two arms directly and hold them to
``conv_general_dilated`` / ``reduce_window`` and their gradients, (b)
they hold every bypass to the parent's lowered text, and (c) they
answer the probe themselves to count which call sites take an arm.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mxnet_tpu.ops import conv as C
from mxnet_tpu.ops import kernel_target
from mxnet_tpu.ops.registry import get_op

convolution = get_op("Convolution").fn
pooling = get_op("Pooling").fn


@pytest.fixture
def tpu_target(monkeypatch):
    """This process runs on the CPU; the test answers the probe."""
    monkeypatch.setattr(kernel_target, "on_tpu", lambda: True)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# ------------------------------------------------------- the convolution arm
@pytest.mark.parametrize("w", [8, 12])
@pytest.mark.parametrize("ci,co", [(3, 64), (64, 64), (48, 32)])
def test_paired_convolution_matches_conv_general_dilated(ci, co, w):
    kx, kw, kc = jax.random.split(jax.random.key(ci * 100 + w), 3)
    x = jax.random.normal(kx, (2, 6, w, ci))
    wt = jax.random.normal(kw, (co, 3, 3, ci))
    ct = jax.random.normal(kc, (2, 6, w, co))

    def plain(x, wt):
        return lax.conv_general_dilated(
            x, wt, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=C._dimnums(2, True))

    with jax.default_matmul_precision("highest"):
        y0, vjp0 = jax.vjp(plain, x, wt)
        y1, vjp1 = jax.vjp(lambda x, wt: C._wpack_conv3x3(x, wt, 1), x, wt)
        (dx0, dw0), (dx1, dw1) = vjp0(ct), vjp1(ct)
    assert y1.shape == y0.shape
    assert _rel(y1, y0) < 5e-6
    assert _rel(dx1, dx0) < 5e-6
    assert _rel(dw1, dw0) < 5e-6


def test_paired_convolution_keeps_the_height_padding():
    """Only the padding in W is part of the rewrite."""
    x = jax.random.normal(jax.random.key(0), (1, 5, 4, 3))
    wt = jax.random.normal(jax.random.key(1), (4, 3, 3, 3))
    for pad_h in (0, 2):
        want = lax.conv_general_dilated(
            x, wt, (1, 1), [(pad_h, pad_h), (1, 1)],
            dimension_numbers=C._dimnums(2, True), precision="highest")
        with jax.default_matmul_precision("highest"):
            got = C._wpack_conv3x3(x, wt, pad_h)
        assert got.shape == want.shape == (1, 3 + 2 * pad_h, 4, 4)
        assert _rel(got, want) < 5e-6


# ----------------------------------------------------------- the pooling arm
def _reduce_window_max(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                             (1, 2, 2, 1), "VALID")


def _tied_input(dtype, shape=(3, 8, 12, 5)):
    """Post-ReLU zeros and values rounded to halves: most windows hold
    a tie, many of them above zero."""
    x = jax.nn.relu(jax.random.normal(jax.random.key(7), shape))
    return (jnp.round(x * 2) / 2).astype(dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paired_pooling_is_select_and_scatter_at_ties(dtype):
    x = _tied_input(dtype)
    windows = x.reshape(3, 4, 2, 6, 2, 5)
    top = windows.max(axis=(2, 4), keepdims=True)
    tied = ((windows == top).sum(axis=(2, 4)) > 1) & (top[:, :, 0, :, 0] > 0)
    assert int(tied.sum()) > 20  # the input does hold ties above zero
    y0, vjp0 = jax.vjp(_reduce_window_max, x)
    y1, vjp1 = jax.vjp(C._wpack_maxpool2x2, x)
    ct = jax.random.normal(jax.random.key(8), y0.shape).astype(dtype)
    (g0,), (g1,) = vjp0(ct), vjp1(ct)
    assert y1.dtype == y0.dtype and g1.dtype == g0.dtype
    assert y1.shape == y0.shape and g1.shape == g0.shape
    assert float(jnp.abs(y1 - y0).max()) == 0.0
    assert float(jnp.abs(g1 - g0).max()) == 0.0
    # the whole cotangent goes to one member: nothing is halved
    assert float(jnp.abs(g1.astype("float32").reshape(3, 4, 2, 6, 2, 5)
                         .sum(axis=(2, 4)) - ct.astype("float32")).max()) == 0


def test_paired_pooling_keeps_a_nan():
    x = _tied_input("float32").at[0, 2, 3, 1].set(jnp.nan)
    got, want = C._wpack_maxpool2x2(x), _reduce_window_max(x)
    assert bool(jnp.isnan(got[0, 1, 1, 1]))
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want))


# --------------------------------------------------------------- the bypasses
_BIG = (2, 128, 128, 8)  # the smallest map that an arm takes


def _conv_case(shape=_BIG, co=8, layout="NHWC", **kw):
    ci = shape[1] if layout == "NCHW" else shape[3]
    group = kw.get("num_group", 1)
    kernel = kw.pop("kernel", (3, 3))
    wshape = (co, ci // group) + kernel if layout == "NCHW" \
        else (co,) + kernel + (ci // group,)
    kw = dict(dict(kernel=kernel, num_filter=co, pad=(1, 1), layout=layout,
                   no_bias=True), **kw)
    return (lambda x, w: convolution(x, w, **kw),
            [jax.ShapeDtypeStruct(shape, jnp.float32),
             jax.ShapeDtypeStruct(wshape, jnp.float32)])


def _pool_case(shape=_BIG, layout="NHWC", **kw):
    kw = dict(dict(kernel=(2, 2), stride=(2, 2), pool_type="max",
                   layout=layout), **kw)
    return (lambda x: pooling(x, **kw),
            [jax.ShapeDtypeStruct(shape, jnp.float32)])


_BYPASS = {
    "conv_odd_w": lambda: _conv_case((2, 128, 129, 8)),
    "conv_stride_2": lambda: _conv_case(stride=(2, 2)),
    "conv_groups": lambda: _conv_case(num_group=2),
    "conv_dilation": lambda: _conv_case(dilate=(2, 2)),
    "conv_128_channels_in": lambda: _conv_case((2, 128, 128, 128)),
    "conv_128_channels_out": lambda: _conv_case(co=128),
    "conv_nchw": lambda: _conv_case((2, 8, 128, 128), layout="NCHW"),
    "conv_1x1": lambda: _conv_case(kernel=(1, 1), pad=(0, 0)),
    "conv_pad_w_0": lambda: _conv_case(pad=(1, 0)),
    "conv_batch_128": lambda: _conv_case((128, 128, 128, 8)),
    "conv_small_map": lambda: _conv_case((2, 56, 56, 8)),
    "pool_3x3": lambda: _pool_case(kernel=(3, 3)),
    "pool_padded": lambda: _pool_case(pad=(1, 1)),
    "pool_avg": lambda: _pool_case(pool_type="avg"),
    "pool_odd_h": lambda: _pool_case((2, 129, 128, 8)),
    "pool_128_channels": lambda: _pool_case((2, 128, 128, 128)),
    "pool_nchw": lambda: _pool_case((2, 8, 128, 128), layout="NCHW"),
    "pool_overlapping": lambda: _pool_case(stride=(1, 1)),
    "pool_batch_128": lambda: _pool_case((128, 128, 128, 8)),
    "pool_small_map": lambda: _pool_case((2, 112, 112, 8)),
}


def _lowered(fn, specs):
    return jax.jit(fn).lower(*specs).as_text()


@pytest.mark.parametrize("case", sorted(_BYPASS))
def test_bypass_lowers_to_the_parents_form(case, monkeypatch):
    """What the rule leaves out lowers, on a TPU, to exactly the text
    it lowers to where no arm exists, and counts nothing."""
    fn, specs = _BYPASS[case]()
    monkeypatch.setattr(kernel_target, "on_tpu", lambda: False)
    parent = _lowered(fn, specs)
    monkeypatch.setattr(kernel_target, "on_tpu", lambda: True)
    jax.clear_caches()
    before = kernel_target.packed_counts()
    assert _lowered(fn, specs) == parent
    assert kernel_target.packed_counts() == before


@pytest.mark.parametrize("op", ["Convolution", "Pooling"])
def test_off_the_tpu_the_arm_is_not_taken(op):
    """``on_tpu()`` false (this process): the shapes the arms exist for
    lower to the plain primitive."""
    fn, specs = _conv_case() if op == "Convolution" else _pool_case()
    before = kernel_target.packed_counts()
    text = _lowered(fn, specs)
    assert kernel_target.packed_counts() == before
    assert ("stablehlo.reduce_window" in text) == (op == "Pooling")
    assert "x64x16xf32>" not in text  # no paired map


@pytest.mark.parametrize("op", ["Convolution", "Pooling"])
def test_on_the_tpu_the_arm_is_taken_and_counted(op, tpu_target):
    fn, specs = _conv_case() if op == "Convolution" else _pool_case()
    before = kernel_target.packed_counts().get(op, 0)
    text = _lowered(fn, specs)
    assert kernel_target.packed_counts()[op] == before + 1
    paired = {"Convolution": "tensor<2x128x64x16xf32>",
              "Pooling": "tensor<2x64x2x64x16xf32>"}[op]
    assert paired in text
    assert "reduce_window" not in text


def test_registered_ops_agree_with_the_plain_form(tpu_target, monkeypatch):
    """Through the registered ops, arm against bypass, bias included."""
    kx, kw, kb = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(kx, _BIG)
    wt = jax.random.normal(kw, (8, 3, 3, 8)) * 0.1
    b = jax.random.normal(kb, (8,))

    def net(x, wt, b):
        h = jax.nn.relu(convolution(x, wt, b, kernel=(3, 3), num_filter=8,
                                    pad=(1, 1), layout="NHWC"))
        return pooling(h, kernel=(2, 2), stride=(2, 2), pool_type="max",
                       layout="NHWC").sum()

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(net, argnums=(0, 1, 2))(x, wt, b)
        monkeypatch.setattr(kernel_target, "on_tpu", lambda: False)
        want = jax.value_and_grad(net, argnums=(0, 1, 2))(x, wt, b)
    assert abs(float(got[0]) - float(want[0])) < 1e-5 * abs(float(want[0]))
    for g, w_ in zip(got[1], want[1]):
        assert _rel(g, w_) < 1e-5


def test_vgg16_takes_two_convolutions_and_one_pooling(tpu_target):
    """The cell's net at the cell's shapes: the decision is made while
    the program is traced, so the count is of call sites."""
    import mxnet_tpu as mx
    from chipbench import nets
    from mxnet_tpu.gluon import nn

    with nn.default_layout("NHWC"):
        net = nets.vgg_without_dropout(16)
    net.initialize(init=mx.init.Zero())
    before = kernel_target.packed_counts()
    jax.eval_shape(lambda x: net(mx.nd.NDArray(x))._data,
                   jax.ShapeDtypeStruct((64, 224, 224, 3), jnp.float32))
    after = kernel_target.packed_counts()
    assert {k: after[k] - before.get(k, 0) for k in after} \
        == {"Convolution": 2, "Pooling": 1}
