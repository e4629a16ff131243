"""Convolution / Deconvolution / Pooling / UpSampling.

Reference parity: src/operator/nn/convolution.cc, deconvolution.cc,
pooling.cc, upsampling.cc (+ their cuDNN wrappers nn/cudnn/ with the
autotuned algo registry cudnn_algoreg-inl.h).  TPU-native: one
``lax.conv_general_dilated`` call — XLA picks MXU tilings, so the whole
cuDNN algorithm-selection machinery disappears.

Layouts: the reference's channel-first NCW/NCHW/NCDHW family (weights
OIHW: num_filter, C/group, *k) and the channel-last NWC/NHWC/NDHWC
family (weights O*kI: num_filter, *k, C/group — the reference's NHWC
weight convention, convolution.cc layout param).  Channel-last is the
TPU-native layout: the channel dim lands on the 128-lane minor axis, so
XLA feeds the MXU without inserting transposes.  The lanes are full
from 128 channels up.  Below that the chip's compiler pads every map to
128 lanes (a 64-channel map takes twice its bytes and the MXU sees half
its width both ways) unless the map is paired: on a TPU a 3x3 stride-1
convolution and a 2x2/2 max-pooling of at most 64 channels, at most 96
images and at least 128x128 pixels (``_wpack_fits``) run on
``[N, H, W/2, 2C]``, two W-neighbours side by side on the lanes
(``_wpack_conv3x3``, ``_wpack_maxpool2x2``).  Both enter and leave the
paired form by a row-major reshape inside the op, and XLA cancels the
reshapes between neighbouring paired ops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import kernel_target
from .registry import register_op

_CHANNEL_LAST = frozenset(("NWC", "NHWC", "NDHWC"))
_CHANNEL_FIRST = frozenset(("NCW", "NCHW", "NCDHW"))


def _tup(v, n, default=1):
    if v is None or v == ():
        return (default,) * n
    if isinstance(v, int):
        return (v,) * n
    return tuple(int(x) for x in v)


def _channel_last(layout, nd):
    if layout is None or layout in _CHANNEL_FIRST:
        return False
    if layout in _CHANNEL_LAST:
        return True
    raise ValueError(f"unsupported layout {layout!r} for {nd}d conv/pool")


def _dimnums(nd, channel_last=False):
    spatial = ["W", "HW", "DHW"][nd - 1]
    if channel_last:
        specs = (f"N{spatial}C", f"O{spatial}I", f"N{spatial}C")
    else:
        specs = (f"NC{spatial}", f"OI{spatial}", f"NC{spatial}")
    return jax.lax.conv_dimension_numbers(
        (1, 1) + (1,) * nd, (1, 1) + (1,) * nd, specs)


# NOTE on 1x1 conv gradients (r04 measurement): a custom matmul-form VJP
# (lax.dot_general for dw/dx) was tried and REVERTED — isolated, every
# formulation (builtin conv transpose rule, explicit dots) runs at the
# same ~48 TF/s on v5e because these grads are BANDWIDTH-bound at
# ResNet shapes, and inside the full train step the dot form was a net
# loss (it breaks the BN-reduce/relu fusions XLA builds around the
# backward convs).
#
# r05 revisits this for CHANNEL-LAST only: in NHWC a 1x1 conv is a
# native [N*H*W, Ci] @ [Ci, Co] matmul with no layout change, and XLA's
# matmul emitters fuse elementwise epilogues at least as well as the
# conv emitters.  Gated off by default pending the step-level A/B
# (MXNET_CONV_1X1_DOT=1 to enable).


def _conv1x1_dot(data, weight, stride, cl):
    """Channel-last 1x1 conv as a dot_general over the channel dim.
    data [N, *sp, Ci], weight [Co, *(1,)*nd, Ci] -> [N, *sp', Co].

    The lowering choice is an autotune variant ("conv1x1_dot"): the
    in-step tuner forces it while racing, a cached winner applies via
    the jit entry points' program_scope, and an explicitly-set
    MXNET_CONV_1X1_DOT overrides both (autotune.variant_choice)."""
    from ..autotune import variant_choice

    if not cl or not variant_choice("conv1x1_dot", default=False):
        return None
    nd = data.ndim - 2
    if any(s != 1 for s in stride):
        idx = (slice(None),) + tuple(
            slice(None, None, s) for s in stride) + (slice(None),)
        data = data[idx]
    co = weight.shape[0]
    w2 = weight.reshape(co, data.shape[-1])
    return jax.lax.dot_general(
        data, w2, dimension_numbers=(((data.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=data.dtype)


def _stem_space_to_depth(data, weight, jnp_pad=jnp.pad):
    """The 7x7/stride-2/pad-3 RGB stem conv as a 4x4/stride-1 conv on a
    space-to-depth input (channel-first only).

    A 3-channel 7x7 kernel occupies 3 of the MXU's 128 input lanes; the
    2x2 space-to-depth rearrangement quadruples the channel count and
    halves the spatial extent, which is the standard TPU ResNet stem
    transform (MLPerf reference models use the same trick).  Exactly
    equivalent: with xp = pad(x, 3) and k = 2a+b (b the parity),
    y[p] = sum_k w[k] xp[2p+k] = sum_b sum_a w[2a+b] xp_b[p+a].
    Autodiff flows through the rearrangement, so backward convs also run
    on the 12-channel tensors.
    """
    n, c, h, w_ = data.shape
    o = weight.shape[0]
    xp = jnp_pad(data, ((0, 0), (0, 0), (3, 3), (3, 3)))
    hq, wq = (h + 6) // 2, (w_ + 6) // 2
    xs = xp.reshape(n, c, hq, 2, wq, 2)
    xs = xs.transpose(0, 1, 3, 5, 2, 4).reshape(n, c * 4, hq, wq)
    w8 = jnp_pad(weight, ((0, 0), (0, 0), (0, 1), (0, 1)))
    ws = w8.reshape(o, c, 4, 2, 4, 2)
    ws = ws.transpose(0, 1, 3, 5, 2, 4).reshape(o, c * 4, 4, 4)
    return jax.lax.conv_general_dilated(
        xs, ws, window_strides=(1, 1), padding=[(0, 0), (0, 0)],
        dimension_numbers=_dimnums(2, False), feature_group_count=1)


#: widest map the W-paired arms take: two of them fill the 128 lanes
_WPACK_MAX_CHANNELS = 64
#: largest batch: the chip's layout has the batch on the sublanes, and
#: from 128 images up the plain convolution fills the MXU from the
#: batch (paired form measured 1.2-2.1x faster at 1..96 images, 0.8x
#: at 128 and 256: PERF.md, PR 26)
_WPACK_MAX_BATCH = 96
#: smallest map.  The paired form pays a relayout wherever its
#: neighbour is not paired, and an op cannot see its neighbours: alone
#: between 1x1 convolutions it loses a quarter of the block's time at
#: any size (PERF.md, PR 26).  Maps this large with this few channels
#: are first stages built of 3x3 convolutions (VGG, U-Net); the
#: 64-channel stages behind a strided stem (ResNet's 56x56) bypass.
_WPACK_MIN_PIXELS = 128 * 128


def _wpack_fits(shape):
    """Is an NHWC map one that the W-paired arms take on a TPU?"""
    n, h, w_, c = shape
    return (c <= _WPACK_MAX_CHANNELS and n <= _WPACK_MAX_BATCH
            and w_ % 2 == 0 and h * w_ >= _WPACK_MIN_PIXELS
            and kernel_target.on_tpu())


def _wpack_conv3x3(data, weight, pad_h):
    """NHWC 3x3/stride-1/pad-W-1 convolution on W-pairs: data
    [N, H, W, Ci] (W even), weight [Co, 3, 3, Ci] -> [N, H', W, Co].

    ``xp = data.reshape(N, H, W/2, 2*Ci)`` holds columns 2j and 2j+1 of
    the map in one row of lanes (halves r = 0, 1).  Output column 2j+q
    reads input columns 2j+q+k-1, k the kernel's tap in W; that column
    is pair j+t-1, half r, where ``k = 2*(t-1) + r - q + 1``.  So the
    paired kernel ``wp[q*Co+o, kh, t, r*Ci+i] = w[o, kh, k, i]``, zero
    where k falls outside the kernel (6 of its 12 blocks are filled),
    gives exactly the products of the plain convolution plus exact
    zeros: twice the multiply-adds on four times the MXU.  Autodiff
    goes through the rewrite: both backward convolutions run on the
    paired maps, and dw comes back through the transpose of the block
    placement.
    """
    n, h, w_, ci = data.shape
    co = weight.shape[0]
    xp = data.reshape(n, h, w_ // 2, 2 * ci)
    k0, k1, k2 = (weight[:, :, k:k + 1] for k in range(3))
    z = jnp.zeros_like(k0)
    # block (q, r) over its three paired taps t
    taps = {(0, 0): (z, k1, z), (0, 1): (k0, k2, z),
            (1, 0): (z, k0, k2), (1, 1): (z, k1, z)}
    wp = jnp.concatenate([
        jnp.concatenate([jnp.concatenate(taps[q, r], axis=2)
                         for r in (0, 1)], axis=3)
        for q in (0, 1)], axis=0)
    out = jax.lax.conv_general_dilated(
        xp, wp, window_strides=(1, 1), padding=[(pad_h, pad_h), (1, 1)],
        dimension_numbers=_dimnums(2, True))
    return out.reshape(n, out.shape[1], w_, co)


@register_op("Convolution", aliases=("Convolution_v1",))
def convolution(data, weight, bias=None, *, kernel, num_filter, stride=None,
                dilate=None, pad=None, num_group=1, no_bias=False,
                workspace=1024, cudnn_tune=None, cudnn_off=False,
                layout=None):
    """Reference: src/operator/nn/convolution.cc."""
    nd = len(kernel)
    stride = _tup(stride, nd)
    dilate = _tup(dilate, nd)
    pad = _tup(pad, nd, 0)
    cl = _channel_last(layout, nd)
    if (nd == 2 and not cl and kernel == (7, 7) and stride == (2, 2)
            and pad == (3, 3) and dilate == (1, 1) and num_group == 1
            and data.shape[1] <= 4 and data.shape[2] % 2 == 0
            and data.shape[3] % 2 == 0):
        out = _stem_space_to_depth(data, weight)
    elif (kernel == (1,) * nd and pad == (0,) * nd
          and dilate == (1,) * nd and num_group == 1
          and (out := _conv1x1_dot(data, weight, stride, cl)) is not None):
        pass  # NHWC 1x1 fast path (see _conv1x1_dot)
    elif (nd == 2 and cl and kernel == (3, 3) and stride == (1, 1)
          and dilate == (1, 1) and num_group == 1 and pad[1] == 1
          and weight.shape[0] <= _WPACK_MAX_CHANNELS
          and _wpack_fits(data.shape)):
        kernel_target.packed("Convolution")
        out = _wpack_conv3x3(data, weight, pad[0])
    else:
        dn = _dimnums(nd, cl)
        out = jax.lax.conv_general_dilated(
            data, weight,
            window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate,
            dimension_numbers=dn,
            feature_group_count=num_group,
        )
    if not no_bias and bias is not None:
        out = out + (bias if cl else bias.reshape((1, -1) + (1,) * nd))
    return out


@register_op("Deconvolution")
def deconvolution(data, weight, bias=None, *, kernel, num_filter,
                  stride=None, dilate=None, pad=None, adj=None,
                  target_shape=None, num_group=1, no_bias=True,
                  workspace=512, cudnn_tune=None, cudnn_off=False,
                  layout=None):
    """Reference: src/operator/nn/deconvolution.cc — the transposed conv:
    implemented as input-dilated convolution (lhs_dilation=stride)."""
    nd = len(kernel)
    stride = _tup(stride, nd)
    dilate = _tup(dilate, nd)
    pad = _tup(pad, nd, 0)
    adj = _tup(adj, nd, 0)
    cl = _channel_last(layout, nd)
    # effective padding for transposed conv: k_eff - 1 - p
    padding = []
    for i in range(nd):
        k_eff = (kernel[i] - 1) * dilate[i] + 1
        lo = k_eff - 1 - pad[i]
        hi = k_eff - 1 - pad[i] + adj[i]
        padding.append((lo, hi))
    if cl:
        # weight (C_in, *k, C_out/group) -> flip spatial; kernel IO roles
        # are expressed via the I<spatial>O rhs spec, no physical swap
        spatial = ["W", "HW", "DHW"][nd - 1]
        dn = jax.lax.conv_dimension_numbers(
            (1, 1) + (1,) * nd, (1, 1) + (1,) * nd,
            (f"N{spatial}C", f"I{spatial}O", f"N{spatial}C"))
        w = jnp.flip(weight, axis=tuple(range(1, 1 + nd)))
        if num_group > 1:
            ci, co_g = w.shape[0], w.shape[-1]
            w = w.reshape(num_group, ci // num_group, *w.shape[1:])
            w = jnp.moveaxis(w, 0, -2)  # (ci/g, *k, g, co_g)
            w = w.reshape(ci // num_group, *w.shape[1:-2], num_group * co_g)
    else:
        dn = _dimnums(nd)
        # weight layout (C_in, C_out/group, *k) -> flip spatial, swap IO
        w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
        if num_group > 1:
            ci, co_g = w.shape[0], w.shape[1]
            w = w.reshape(num_group, ci // num_group, co_g, *w.shape[2:])
            w = jnp.swapaxes(w, 1, 2)
            w = w.reshape(num_group * co_g, ci // num_group, *w.shape[3:])
        else:
            w = jnp.swapaxes(w, 0, 1)
    out = jax.lax.conv_general_dilated(
        data, w,
        window_strides=(1,) * nd,
        padding=padding,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
    )
    if not no_bias and bias is not None:
        out = out + (bias if cl else bias.reshape((1, -1) + (1,) * nd))
    return out


@jax.custom_vjp
def _wpack_maxpool2x2(data):
    """NHWC 2x2/stride-2 max-pooling on W-pairs: [N, H, W, C] (H, W
    even, floating) -> [N, H/2, W/2, C].  On ``data.reshape(N, H/2, 2,
    W/2, 2C)`` a window's four members are the two lane halves of its
    two rows, so no ``reduce_window`` is left: one reduction over the
    pair of rows, then the two halves elementwise.  The gradient
    (``_wpack_maxpool2x2_bwd``) is ``select_and_scatter``'s: the whole
    cotangent to the first member equal to the maximum."""
    return _wpack_maxpool2x2_fwd(data)[0]


def _first_max(a, b):
    """Of two (value, place) pairs the greater value, and of equal
    values the earlier place (what ``select_and_scatter``'s ``ge``
    keeps).  The value is ``maximum``'s, so a NaN stays a NaN."""
    (va, ia), (vb, ib) = a, b
    later = (vb > va) | ((vb == va) & (ib < ia))
    return jnp.maximum(va, vb), jax.lax.select(later, ib, ia)


def _wpack_maxpool2x2_fwd(data):
    n, h, w_, c = data.shape
    rows = data.reshape(n, h // 2, 2, w_ // 2, 2 * c)
    # each column's maximum over its two rows, and the row it sits in
    top, row = jax.lax.reduce(
        (rows, jax.lax.broadcasted_iota(jnp.int8, rows.shape, 2)),
        (jnp.array(-jnp.inf, data.dtype), jnp.array(2, jnp.int8)),
        _first_max, (2,))
    tl, tr, rl, rr = top[..., :c], top[..., c:], row[..., :c], row[..., c:]
    # reduce_window's order: row 0 left, row 0 right, row 1 left, ...
    left = (tl > tr) | ((tl == tr) & (rl <= rr))
    member = jax.lax.select(left, 2 * rl, 2 * rr + 1)
    # the backward pass reads it beside the paired cotangent
    return jnp.maximum(tl, tr), jnp.concatenate([member, member], axis=-1)


def _wpack_maxpool2x2_bwd(member, ct):
    """``member`` [N, H/2, W/2, 2C] int8: which of its window's four
    members (0..3, in ``reduce_window``'s order) was the first equal to
    the maximum, once per lane half.  The cotangent goes there whole,
    elementwise, assembled in the paired form.  (A ``jnp.maximum`` chain
    under autodiff would halve it at ties.)"""
    n, h2, w2, c2 = member.shape
    shape = (n, h2, 2, w2, c2)
    here = 2 * jax.lax.broadcasted_iota(jnp.int32, shape, 2) \
        + (jax.lax.broadcasted_iota(jnp.int32, shape, 4) >= c2 // 2)
    over_rows = lambda a: jnp.broadcast_to(a[:, :, None], shape)
    ct2 = jnp.concatenate([ct, ct], axis=-1)
    grad = jax.lax.select(over_rows(member) == here.astype(jnp.int8),
                          over_rows(ct2), jnp.zeros(shape, ct.dtype))
    # Without the barrier XLA:TPU moves the reshape below up to the two
    # broadcasts over the pair of rows and writes both out at full size
    # (822 + 411 MB at VGG's first stage) before the select reads them.
    grad = jax.lax.optimization_barrier(grad)
    return (grad.reshape(n, 2 * h2, 2 * w2, c2 // 2),)


_wpack_maxpool2x2.defvjp(_wpack_maxpool2x2_fwd, _wpack_maxpool2x2_bwd)


@register_op("Pooling", aliases=("Pooling_v1",))
def pooling(data, *, kernel=(), pool_type="max", global_pool=False,
            stride=None, pad=None, pooling_convention="valid",
            count_include_pad=True, cudnn_off=False, p_value=2,
            layout=None):
    """Reference: src/operator/nn/pooling.cc via lax.reduce_window."""
    nd = data.ndim - 2
    cl = _channel_last(layout, nd)
    sp0 = 1 if cl else 2  # first spatial axis
    if global_pool:
        kernel = data.shape[sp0:sp0 + nd]
        stride = (1,) * nd
        pad = (0,) * nd
    stride = _tup(stride, nd)
    pad = _tup(pad, nd, 0)
    kernel = _tup(kernel, nd)
    # even H and W: the "valid" and "full" conventions agree
    if (pool_type == "max" and nd == 2 and cl and kernel == (2, 2)
            and stride == (2, 2) and pad == (0, 0)
            and data.shape[1] % 2 == 0
            and jnp.issubdtype(data.dtype, jnp.floating)
            and _wpack_fits(data.shape)):
        kernel_target.packed("Pooling")
        return _wpack_maxpool2x2(data)
    if cl:
        dims = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
    else:
        dims = (1, 1) + kernel
        strides = (1, 1) + stride
    sp_pad = [(p, p) for p in pad]
    if pooling_convention == "full":
        # ceil mode: add extra right-pad so last window fits
        sp_pad = []
        for i in range(nd):
            size = data.shape[sp0 + i] + 2 * pad[i]
            rem = (size - kernel[i]) % stride[i]
            extra = (stride[i] - rem) % stride[i] if rem else 0
            sp_pad.append((pad[i], pad[i] + extra))
    if cl:
        base_pad = [(0, 0)] + sp_pad + [(0, 0)]
    else:
        base_pad = [(0, 0), (0, 0)] + sp_pad

    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else jnp.iinfo(data.dtype).min
        return jax.lax.reduce_window(data, init, jax.lax.max, dims, strides,
                                     base_pad)
    if pool_type in ("avg", "sum"):
        s = jax.lax.reduce_window(data, 0.0, jax.lax.add, dims, strides,
                                  base_pad)
        if pool_type == "sum":
            return s
        if count_include_pad:
            denom = 1
            for k in kernel:
                denom *= k
            return s / denom
        ones = jnp.ones_like(data)
        cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, dims, strides,
                                    base_pad)
        return s / cnt
    if pool_type == "lp":
        s = jax.lax.reduce_window(jnp.abs(data) ** p_value, 0.0, jax.lax.add,
                                  dims, strides, base_pad)
        return s ** (1.0 / p_value)
    raise ValueError(f"unknown pool_type {pool_type}")


@register_op("UpSampling")
def upsampling(*inputs, scale, sample_type="nearest", num_args=1,
               num_filter=0, multi_input_mode="concat", workspace=512):
    """Reference: src/operator/nn/upsampling.cc."""
    data = inputs[0]
    n, c, h, w = data.shape
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
    else:  # bilinear: reference uses a Deconvolution with bilinear kernel
        out = jax.image.resize(data, (n, c, h * scale, w * scale),
                               method="bilinear")
    return out


@register_op("BilinearSampler")
def bilinear_sampler(data, grid, *, cudnn_off=False):
    """Reference: src/operator/bilinear_sampler.cc — grid in [-1, 1]."""
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1) * (w - 1) / 2
    gy = (grid[:, 1] + 1) * (h - 1) / 2
    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    wx = gx - x0
    wy = gy - y0

    def gather(y, x):
        yc = jnp.clip(y, 0, h - 1).astype(jnp.int32)
        xc = jnp.clip(x, 0, w - 1).astype(jnp.int32)
        valid = ((y >= 0) & (y <= h - 1) & (x >= 0) & (x <= w - 1))
        idx = yc * w + xc
        flat = data.reshape(n, c, h * w)
        g = jnp.take_along_axis(
            flat, idx.reshape(n, 1, -1).repeat(c, axis=1), axis=2
        ).reshape(n, c, *gx.shape[1:])
        return g * valid[:, None].astype(data.dtype)

    out = (gather(y0, x0) * ((1 - wx) * (1 - wy))[:, None]
           + gather(y0, x0 + 1) * (wx * (1 - wy))[:, None]
           + gather(y0 + 1, x0) * ((1 - wx) * wy)[:, None]
           + gather(y0 + 1, x0 + 1) * (wx * wy)[:, None])
    return out


@register_op("GridGenerator")
def grid_generator(data, *, transform_type="affine", target_shape=(0, 0)):
    """Reference: src/operator/grid_generator.cc."""
    h, w = target_shape
    if transform_type == "affine":
        n = data.shape[0]
        ys = jnp.linspace(-1, 1, h)
        xs = jnp.linspace(-1, 1, w)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        base = jnp.stack([gx.reshape(-1), gy.reshape(-1),
                          jnp.ones(h * w)], axis=0)
        theta = data.reshape(n, 2, 3)
        out = jnp.einsum("nij,jk->nik", theta, base)
        return out.reshape(n, 2, h, w)
    # warp
    n = data.shape[0]
    ys = jnp.arange(h, dtype=data.dtype)
    xs = jnp.arange(w, dtype=data.dtype)
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    flow_x = (data[:, 0] + gx) * 2 / max(w - 1, 1) - 1
    flow_y = (data[:, 1] + gy) * 2 / max(h - 1, 1) - 1
    return jnp.stack([flow_x, flow_y], axis=1)


@register_op("SpatialTransformer")
def spatial_transformer(data, loc, *, target_shape=(0, 0),
                        transform_type="affine", sampler_type="bilinear",
                        cudnn_off=False):
    """Reference: src/operator/spatial_transformer.cc."""
    from .registry import get_op

    g = get_op("GridGenerator").fn(loc, transform_type=transform_type,
                                   target_shape=target_shape)
    return get_op("BilinearSampler").fn(data, g)
