#!/usr/bin/env python
"""Per-operator benchmark harness (reference: benchmark/opperf/ —
opperf.py runs every registered op with timing via the profiler).

Times eager dispatch+execution of registered ops on representative
shapes, emitting one JSON line per op:

    python benchmark/opperf.py [--ops dot,Convolution] [--runs 25]
        [--large]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as onp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.ops.registry import get_op, list_ops  # noqa: E402


def _standard_inputs(large=False):
    n = 1024 if large else 128
    a = onp.random.rand(n, n).astype("float32")
    return {
        # (inputs, params) per op family; unary/binary auto-probe below
        "dot": ([a, a], {}),
        "batch_dot": ([onp.random.rand(8, n, 64).astype("float32"),
                       onp.random.rand(8, 64, n).astype("float32")], {}),
        "FullyConnected": ([a, a, onp.zeros(n, "float32")],
                           dict(num_hidden=n)),
        "Convolution": ([onp.random.rand(8, 32, 64, 64).astype("float32"),
                         onp.random.rand(64, 32, 3, 3).astype("float32"),
                         onp.zeros(64, "float32")],
                        dict(kernel=(3, 3), num_filter=64, pad=(1, 1))),
        "Pooling": ([onp.random.rand(8, 32, 64, 64).astype("float32")],
                    dict(kernel=(2, 2), stride=(2, 2))),
        "BatchNorm": ([onp.random.rand(8, 32, 32, 32).astype("float32"),
                       onp.ones(32, "float32"), onp.zeros(32, "float32"),
                       onp.zeros(32, "float32"), onp.ones(32, "float32")],
                      {}),
        # fused bn->relu->1x1conv (ops/pallas_conv.py): NHWC input,
        # channel-last O11I weight
        "_contrib_BNReluConv": (
            [onp.random.rand(4, 8, 8, 16).astype("float32") + 0.1,
             onp.random.rand(16).astype("float32") + 0.5,
             onp.random.rand(16).astype("float32") * 0.2,
             onp.random.rand(24, 1, 1, 16).astype("float32") * 0.3],
            {}),
        "softmax": ([a], {}),
        "sum": ([a], {}),
        "transpose": ([a], {}),
        "sort": ([a], {}),
        "_npi_einsum": ([a, a], dict(subscripts="ij,jk->ik")),
        **_family_inputs(),
    }


def _family_inputs():
    """Specs for ops whose required hyper-params defeat the auto-probe
    (the reference opperf's per-op rule tables)."""
    img = onp.random.rand(8, 16, 32, 32).astype("float32")
    vec16 = onp.ones(16, "float32")
    z16 = onp.zeros(16, "float32")
    seq = onp.random.rand(16, 8, 32).astype("float32")
    rois = onp.array([[0, 2, 2, 20, 20], [4, 1, 1, 16, 16]], "float32")
    anchors = onp.random.rand(1, 64, 4).astype("float32")
    cls_prob = onp.random.rand(2, 3, 64).astype("float32")
    loc_pred = onp.random.rand(2, 256).astype("float32")
    det_label = onp.array([[[0, .1, .1, .4, .4]], [[1, .5, .5, .9, .9]]],
                          "float32")
    from mxnet_tpu.ops.rnn import rnn_param_size

    psz = rnn_param_size("lstm", 1, 32, 64)
    qkv = onp.random.rand(16, 4, 96).astype("float32")
    return {
        "Activation": ([img], dict(act_type="relu")),
        "LeakyReLU": ([img], dict(act_type="leaky")),
        "Cast": ([img], dict(dtype="float16")),
        "Pad": ([img], dict(mode="constant",
                            pad_width=(0, 0, 0, 0, 1, 1, 1, 1))),
        "UpSampling": ([img], dict(scale=2, sample_type="nearest")),
        "SliceChannel": ([img], dict(num_outputs=2)),
        "LayerNorm": ([img, vec16, z16], dict(axis=1)),
        "GroupNorm": ([img, onp.ones(4, "float32"),
                       onp.zeros(4, "float32")], dict(num_groups=4)),
        "InstanceNorm": ([img, vec16, z16], {}),
        "SyncBatchNorm": ([img, vec16, z16, z16.copy(), vec16.copy()],
                          {}),
        "Deconvolution": ([img, onp.random.rand(16, 8, 3, 3)
                           .astype("float32")],
                          dict(kernel=(3, 3), num_filter=8,
                               stride=(2, 2), pad=(1, 1))),
        "DeformableConvolution": (
            [img, onp.zeros((8, 18, 32, 32), "float32"),
             onp.random.rand(16, 16, 3, 3).astype("float32")],
            dict(kernel=(3, 3), num_filter=16, pad=(1, 1),
                 no_bias=True)),
        "BilinearResize2D": ([img], dict(height=64, width=64)),
        "AdaptiveAvgPooling2D": ([img], dict(output_size=(4, 4))),
        "Correlation": ([img, img.copy()],
                        dict(kernel_size=1, max_displacement=2,
                             pad_size=2)),
        "GridGenerator": ([onp.random.rand(8, 6).astype("float32")],
                          dict(transform_type="affine",
                               target_shape=(16, 16))),
        "ROIPooling": ([img, rois],
                       dict(pooled_size=(4, 4), spatial_scale=1.0)),
        "_contrib_ROIAlign": ([img, rois],
                              dict(pooled_size=(4, 4),
                                   spatial_scale=1.0)),
        "RNN": ([seq, onp.random.uniform(-0.1, 0.1, psz)
                 .astype("float32"),
                 onp.zeros((1, 8, 64), "float32"),
                 onp.zeros((1, 8, 64), "float32")],
                dict(state_size=64, num_layers=1, mode="lstm")),
        "_contrib_MultiBoxPrior": ([img], dict(sizes=(0.5,),
                                               ratios=(1.0,))),
        "_contrib_MultiBoxDetection": ([cls_prob, loc_pred, anchors],
                                       {}),
        "_contrib_MultiBoxTarget": ([anchors, det_label,
                                     cls_prob], {}),
        "_contrib_box_iou": ([onp.random.rand(8, 4).astype("float32"),
                              onp.random.rand(8, 4).astype("float32")],
                             {}),
        "_contrib_interleaved_matmul_selfatt_qk": ([qkv],
                                                   dict(heads=8)),
        "_contrib_interleaved_matmul_selfatt_valatt": (
            [qkv, onp.random.rand(32, 16, 16).astype("float32")],
            dict(heads=8)),
        "_contrib_quantize_v2": ([img], {}),
        "_contrib_dequantize": (
            [onp.random.randint(-127, 127, (16, 16)).astype("int8"),
             onp.array([-1.0], "float32"), onp.array([1.0], "float32")],
            {}),
        "one_hot": ([onp.arange(16, dtype="float32")], dict(depth=32)),
        "Embedding": ([onp.arange(16, dtype="float32"),
                       onp.random.rand(100, 32).astype("float32")],
                      dict(input_dim=100, output_dim=32)),
        "SequenceMask": ([seq], {}),
        "topk": ([onp.random.rand(16, 64).astype("float32")],
                 dict(k=4)),
        "pick": ([onp.random.rand(16, 8).astype("float32"),
                  onp.zeros(16, "float32")], {}),
        # ---- kwarg-required tail (r04: the grad sweep and opperf share
        # this table; every differentiable op needs a probeable spec)
        "_plus_scalar": ([img], dict(scalar=2.0)),
        "_minus_scalar": ([img], dict(scalar=2.0)),
        "_rminus_scalar": ([img], dict(scalar=2.0)),
        "_mul_scalar": ([img], dict(scalar=2.0)),
        "_div_scalar": ([img], dict(scalar=2.0)),
        "_power_scalar": ([img], dict(scalar=2.0)),
        # (_mod/_rmod/_rdiv/_rpower scalar variants live in the
        # FD-conditioned block below)
        "_maximum_scalar": ([img], dict(scalar=0.5)),
        "_minimum_scalar": ([img], dict(scalar=0.5)),
        "clip": ([img], dict(a_min=0.2, a_max=0.8)),
        "tile": ([onp.random.rand(8, 8).astype("float32")],
                 dict(reps=(2, 3))),
        "repeat": ([onp.random.rand(8, 8).astype("float32")],
                   dict(repeats=3)),
        "flip": ([onp.random.rand(8, 8).astype("float32")],
                 dict(axis=0)),
        "expand_dims": ([onp.random.rand(8, 8).astype("float32")],
                        dict(axis=1)),
        "slice": ([onp.random.rand(16, 16).astype("float32")],
                  dict(begin=(2, 2), end=(10, 12))),
        "slice_axis": ([onp.random.rand(16, 16).astype("float32")],
                       dict(axis=0, begin=2, end=10)),
        "broadcast_to": ([onp.random.rand(1, 16).astype("float32")],
                         dict(shape=(8, 16))),
        "broadcast_axes": ([onp.random.rand(1, 16).astype("float32")],
                           dict(axis=0, size=8)),
        "depth_to_space": ([onp.random.rand(2, 8, 4, 4)
                            .astype("float32")], dict(block_size=2)),
        "space_to_depth": ([onp.random.rand(2, 2, 8, 8)
                            .astype("float32")], dict(block_size=2)),
        "split_v2": ([onp.random.rand(8, 16).astype("float32")],
                     dict(indices=(2, 5), _num=3)),
        "gather_nd": ([onp.random.rand(8, 8).astype("float32"),
                       onp.array([[0, 2, 4], [1, 3, 5]], "int64")], {}),
        "scatter_nd": ([onp.random.rand(3).astype("float32"),
                        onp.array([[0, 2, 4]], "int64")],
                       dict(shape=(8,))),
        "batch_take": ([onp.random.rand(16, 16).astype("float32"),
                        onp.arange(16, dtype="int64")], {}),
        "take": ([onp.random.rand(32, 8).astype("float32"),
                  onp.arange(16, dtype="int64")], {}),
        "amp_cast": ([img], dict(dtype="float32")),
        "amp_multicast": ([img, img.copy()], dict(num_outputs=2)),
        "_contrib_dot_product_attention": (
            [onp.random.rand(2, 16, 32).astype("float32"),
             onp.random.rand(2, 16, 32).astype("float32"),
             onp.random.rand(2, 16, 32).astype("float32")],
            dict(num_heads=4, interpret=True)),
        "_random_pdf_uniform": (
            [onp.random.uniform(0.4, 0.6, (8, 16)).astype("float32"),
             onp.full((8,), 0.05, "float32"),
             onp.full((8,), 0.95, "float32")], {}),
        "_random_pdf_dirichlet": (
            [_simplex(8, 4), onp.random.uniform(1.5, 2.5, (8, 4))
             .astype("float32")], {}),
        # conditioned linalg inputs: random 128x128 determinants/
        # inverses are numerically meaningless for FD checks
        "_linalg_det": ([_spd(6)], {}),
        "_npi_det": ([_spd(6)], {}),
        "_linalg_potrf": ([_spd(6)], {}),
        "_npi_cholesky": ([_spd(6)], {}),
        "_linalg_potri": ([_spd(6)], {}),
        "_linalg_trsm": ([_tril(6), onp.random.rand(6, 6)
                          .astype("float32")], {}),
        "_npi_tensorinv": ([_spd(6).reshape(2, 3, 2, 3)], dict(ind=2)),
        "_npi_matrix_power": ([_spd(6)], dict(n=2)),
        "_npi_cross": ([onp.random.rand(8, 3).astype("float32"),
                        onp.random.rand(8, 3).astype("float32")], {}),
        "_npi_moveaxis": ([onp.random.rand(4, 6, 8).astype("float32")],
                          dict(source=0, destination=2)),
        "_npi_roll": ([onp.random.rand(8, 8).astype("float32")],
                      dict(shift=3, axis=1)),
        "_npi_rollaxis": ([onp.random.rand(4, 6, 8).astype("float32")],
                          dict(axis=2, start=0)),
        "_npi_take_along_axis": (
            [onp.random.rand(8, 8).astype("float32"),
             onp.random.randint(0, 8, (8, 4)).astype("int64")],
            dict(axis=1)),
        "_np_arccosh": ([onp.random.uniform(1.5, 3.0, (8, 16))
                         .astype("float32")], {}),
        "_hypot_scalar": ([onp.random.uniform(0.3, 0.9, (8, 16))
                           .astype("float32")], dict(scalar=2.0)),
        # denominators bounded away from numerator range: keeps the
        # fmod/floor family off its kink lattice for FD
        "_mod": ([onp.random.uniform(0.1, 0.4, (8, 16))
                  .astype("float32"),
                  onp.random.uniform(0.6, 0.9, (8, 16))
                  .astype("float32")], {}),
        "_npi_fmod": ([onp.random.uniform(0.1, 0.4, (8, 16))
                       .astype("float32"),
                       onp.random.uniform(0.6, 0.9, (8, 16))
                       .astype("float32")], {}),
        "_npi_floor_divide": ([onp.random.uniform(0.1, 0.4, (8, 16))
                               .astype("float32"),
                               onp.random.uniform(0.6, 0.9, (8, 16))
                               .astype("float32")], {}),
        "_mod_scalar": ([onp.random.uniform(0.1, 0.9, (8, 16))
                         .astype("float32")], dict(scalar=2.0)),
        "_rmod_scalar": ([onp.random.uniform(1.1, 1.9, (8, 16))
                          .astype("float32")], dict(scalar=1.0)),
        "_rdiv_scalar": ([onp.random.uniform(0.3, 0.9, (8, 16))
                          .astype("float32")], dict(scalar=2.0)),
        "_rpower_scalar": ([onp.random.uniform(0.3, 0.9, (8, 16))
                            .astype("float32")], dict(scalar=2.0)),
        "CTCLoss": ([onp.random.rand(10, 2, 6).astype("float32"),
                     onp.array([[1, 2, 3, 0], [2, 4, 0, 0]],
                               "float32")], {}),
        "BilinearSampler": (
            [onp.random.rand(2, 3, 8, 8).astype("float32"),
             onp.random.uniform(-0.9, 0.9, (2, 2, 8, 8))
             .astype("float32")], {}),
        "SpatialTransformer": (
            [onp.random.rand(2, 3, 8, 8).astype("float32"),
             onp.array([[1.0, 0.1, 0.0, -0.1, 1.0, 0.0]] * 2,
                       "float32")],
            dict(target_shape=(8, 8), transform_type="affine",
                 sampler_type="bilinear")),
        "_contrib_interleaved_matmul_encdec_qk": (
            [onp.random.rand(12, 2, 32).astype("float32"),
             onp.random.rand(10, 2, 64).astype("float32")],
            dict(heads=4)),
        "_contrib_interleaved_matmul_encdec_valatt": (
            [onp.random.rand(10, 2, 64).astype("float32"),
             onp.random.rand(8, 12, 10).astype("float32")],
            dict(heads=4)),
    }


def _spd(n):
    a = onp.random.RandomState(3).rand(n, n).astype("float32")
    m = a @ a.T + n * onp.eye(n, dtype="float32")
    # normalize so det ~ O(1): determinant-family FD otherwise sweeps
    # the loss's cos() through multiple periods per epsilon step
    return (m / n).astype("float32")


def _tril(n):
    a = onp.tril(onp.random.RandomState(4).rand(n, n)).astype("float32")
    return a + n * onp.eye(n, dtype="float32")


def _simplex(b, k):
    a = onp.random.RandomState(5).rand(b, k).astype("float32") + 0.2
    return a / a.sum(-1, keepdims=True)


def bench_op(opname, inputs, params, ctx, runs):
    """Marginal per-call device time via the chained fori_loop timer
    (benchmark/devtime.py).  A host-loop sweep can read negative
    marginal times when dispatch/readback jitter exceeds a sub-ms op;
    the device-side chain makes that impossible by construction (one
    program, one scalar readback, data-dependent iterations)."""
    import jax

    from devtime import device_chain_time

    op = get_op(opname)
    vals = [mx.nd.array(x, ctx=ctx)._data for x in inputs]
    kwargs = dict(params)

    if not vals and op.key_param:
        # zero-input sampler: the chained timer needs a data
        # dependence or XLA hoists the draw out of the loop (measuring
        # an empty body).  Fold the chain's perturbed dummy counter
        # into the PRNG key so every iteration draws fresh.
        base_key = jax.random.key(0)
        dummy = mx.nd.array(onp.zeros((1,), "int32"), ctx=ctx)._data

        def fn(d):
            kw = dict(kwargs)
            kw[op.key_param] = jax.random.fold_in(base_key, d[0])
            return op.fn(**kw)

        vals = [dummy]
    else:
        if op.key_param and op.key_param not in kwargs:
            kwargs[op.key_param] = jax.random.key(0)

        def fn(*args):
            return op.fn(*args, **kwargs)

    dt, _, samples = device_chain_time(
        fn, vals, target_spread=0.4,
        trials=max(3, min(runs // 8, 5)),
        subtract_overhead=True, return_samples=True)
    return dt, samples


# ops whose signatures genuinely need bespoke shapes/params beyond the
# curated table and the auto-probe (IO-coupled, subgraph-attr, or
# index-typed inputs); everything else in the registry gets timed
SKIP_OPS = frozenset((
    "_foreach", "_while_loop", "_cond",  # subgraph-JSON attrs
    "custom",  # user-provided op body
    # complex-valued iFFT: runs on a v5e and matches numpy to 1e-6
    # (PR 21's chip run); out of the sweep until opperf is next run
    # on the chip
    "_contrib_ifft",
))

#: ops the chained timer CANNOT measure honestly, each with the reason
#: (the grad sweep's SKIP_JUSTIFICATIONS discipline applied here —
#: every registered non-alias op is timed or justified)
JUSTIFIED_SKIPS = {
    "_npi_hanning": "zero-input deterministic generator: loop-"
                    "invariant, XLA hoists it out of the chained loop "
                    "so only a per-iteration copy would be timed",
    "_npi_hamming": "zero-input deterministic generator (see hanning)",
    "_npi_blackman": "zero-input deterministic generator (see hanning)",
    "_npi_bartlett": "zero-input deterministic generator (see hanning)",
    "_npi_indices": "zero-input deterministic generator (see hanning)",
    "_npi_tri": "zero-input deterministic generator (see hanning)",
    "_contrib_count_sketch": "integer hash-index inputs: the chain's "
                             "float perturbation corrupts them",
    "_getitem": "python-object `key` parameter (slices/ellipsis): not "
                "a tensor program knob; covered by crop/slice timings",
}


def _bench_extra_inputs():
    """Curated specs for ops the auto-probe cannot type out: optimizer
    update rules, scalar-compare family, quantized conv/fc, MultiBox*,
    numpy tail ops, random samplers (reference
    benchmark/opperf/utils/op_registry_utils.py keeps the same
    per-family registries)."""
    n = 1024
    a = onp.random.rand(n, n).astype("float32")
    v = onp.random.rand(n).astype("float32")
    ints = onp.random.randint(0, 255, (n, n)).astype("int32")
    q8 = onp.random.randint(-127, 127, (8, 32, 32, 32)).astype("int8")
    w8 = onp.random.randint(-127, 127, (64, 32, 3, 3)).astype("int8")
    mm = onp.float32
    opt = {
        "sgd_update": ([a, a], dict(lr=0.1)),
        "sgd_mom_update": ([a, a, a], dict(lr=0.1, momentum=0.9)),
        "nag_mom_update": ([a, a, a], dict(lr=0.1, momentum=0.9)),
        "adam_update": ([a, a, a, a], dict(lr=0.1)),
        "rmsprop_update": ([a, a, a], dict(lr=0.1)),
        "rmspropalex_update": ([a, a, a, a, a], dict(lr=0.1)),
        "ftrl_update": ([a, a, a, a], dict(lr=0.1)),
        "signsgd_update": ([a, a], dict(lr=0.1)),
        "signum_update": ([a, a, a], dict(lr=0.1, momentum=0.9)),
        "multi_sgd_update": ([a, a], dict(lrs=(0.1,), wds=(0.0,),
                                          num_weights=1)),
        "multi_sgd_mom_update": ([a, a, a],
                                 dict(lrs=(0.1,), wds=(0.0,),
                                      num_weights=1)),
        "multi_lars": ([v, v, v, v], dict(eta=0.001, eps=1e-8)),
        # _sparse_adagrad_update is an alias of adagrad_update (timed)
    }
    # bucketed flat-tensor rows (round 9): one launch over a 1M-element
    # flat bucket — the sharded-server exchange's inner update as
    # benchmarked ops (the multi_mp_sgd/multi_lars analog); seg_ids
    # partitions the bucket into 16 "parameters" for the LARS trust
    # ratios (int input: the chain perturbation adds an integer 0)
    flat = onp.random.rand(n * n).astype("float32")
    seg = onp.repeat(onp.arange(16, dtype="int32"), (n * n) // 16)
    opt.update({
        "_fused_bucket_sgd_mom_update": (
            [flat, flat.copy(), flat.copy()],
            dict(lr=0.1, momentum=0.9)),
        "_fused_bucket_adam_update": (
            [flat, flat.copy(), flat.copy(), flat.copy()],
            dict(lr=0.1)),
        "_fused_bucket_lars_update": (
            [flat, flat.copy(), flat.copy(), seg],
            dict(lr=0.1, momentum=0.9, num_segments=16)),
        # round 14: the Pallas fused-bucket kernel arms of the same
        # three updates (ops/pallas_opt.py — prep + rule + loss-scale
        # check in one VMEM pass; interpret mode off-TPU) so benchdiff
        # trends kernel-vs-jnp per round
        "_pallas_bucket_sgd_mom_update": (
            [flat, flat.copy(), flat.copy()],
            dict(lr=0.1, momentum=0.9)),
        "_pallas_bucket_adam_update": (
            [flat, flat.copy(), flat.copy(), flat.copy()],
            dict(lr=0.1)),
        "_pallas_bucket_lars_update": (
            [flat, flat.copy(), flat.copy(), seg],
            dict(lr=0.1, momentum=0.9, num_segments=16)),
        # round 16: the bucket WIRE beside the bucket update — the
        # stage-2/3 backward reduce-scatter and stage-3 forward
        # all-gather (ops/collective_ops.py) at the same 1M-element
        # flat-bucket shape, so one jsonl round shows exchange and
        # update cost on the same x-axis; on the 1-device smoke both
        # degenerate to the copy floor (zero-communication baseline)
        "reduce_scatter": ([flat], {}),
        "all_gather": ([flat], {}),
    })
    scalar_cmp = {
        name: ([a], dict(scalar=0.5))
        for name in ("_equal_scalar", "_not_equal_scalar",
                     "_greater_scalar", "_greater_equal_scalar",
                     "_lesser_scalar", "_lesser_equal_scalar")
    }
    rand = {
        # zero-input samplers: bench_op folds the chain's perturbed
        # dummy into the PRNG key, so every iteration draws fresh
        name: ([], dict(shape=(n, n)))
        for name in ("_random_uniform", "_random_normal",
                     "_random_exponential", "_random_poisson",
                     "_random_gamma", "_random_negative_binomial",
                     "_random_generalized_negative_binomial")
    }
    rand["_random_randint"] = ([], dict(low=0, high=100, shape=(n, n)))
    npi = {
        "_npi_bincount": ([onp.random.randint(0, 512, n * 16)
                           .astype(mm)], {}),
        "_npi_bitwise_and": ([ints, ints], {}),
        "_npi_bitwise_or": ([ints, ints], {}),
        "_npi_bitwise_xor": ([ints, ints], {}),
        "_npi_bitwise_not": ([ints], {}),
        "_npi_left_shift": ([ints, onp.full((n, n), 2, "int32")], {}),
        "_npi_right_shift": ([ints, onp.full((n, n), 2, "int32")], {}),
        "_npi_full_like": ([a], dict(fill_value=3.0)),
        "_npi_delete": ([v], dict(obj=5, axis=0)),
        "_npi_insert": ([v, onp.float32([1.5])], dict(obj=5, axis=0)),
        "_npi_interp": ([onp.sort(v), onp.sort(v),
                         onp.random.rand(n).astype(mm)], {}),
        "_npi_percentile": ([a], dict(q=50.0)),
        "_npi_quantile": ([a], dict(q=0.5)),
        "_npi_resize": ([a], dict(new_shape=(n // 2, 2 * n))),
        # bucketized static-size variants (the jit contract for
        # value-dependent output shapes)
        "_npi_unique": ([onp.random.randint(0, 256, (n * 64,))
                         .astype(mm)], dict(size=256)),
        "_npi_nonzero": ([(onp.random.rand(n, n) > 0.5)
                          .astype(mm)], dict(size=n * n)),
        "crop": ([a], dict(begin=(8, 8), end=(n - 8, n - 8))),
    }
    quant = {
        "_contrib_quantize": ([a, onp.float32([0.0]),
                               onp.float32([1.0])], {}),
        # round 18: the calibrated-range entry point the quantized
        # rewrite stitches in front of every int8 layer — timed beside
        # dot/Convolution/FullyConnected so the int8-vs-fp32 per-op
        # ratio is visible in the benchdiff table
        "_contrib_quantize_v2": (
            [a], dict(min_calib_range=-1.0, max_calib_range=1.0)),
        "_contrib_requantize": (
            [onp.random.randint(-2**20, 2**20, (n, n)).astype("int32"),
             onp.float32([-1.0]), onp.float32([1.0])], {}),
        "_contrib_quantized_conv": (
            [q8, w8, onp.zeros(64, "int8"),
             onp.float32([-1]), onp.float32([1]), onp.float32([-1]),
             onp.float32([1]), onp.float32([-1]), onp.float32([1])],
            dict(kernel=(3, 3), num_filter=64, pad=(1, 1))),
        "_contrib_quantized_fully_connected": (
            [onp.random.randint(-127, 127, (128, 256)).astype("int8"),
             onp.random.randint(-127, 127, (512, 256)).astype("int8"),
             onp.zeros(512, "int8"),
             onp.float32([-1]), onp.float32([1]), onp.float32([-1]),
             onp.float32([1]), onp.float32([-1]), onp.float32([1])],
            dict(num_hidden=512)),
    }
    nb = 256  # boxes per image for the detection family
    anchors = onp.random.rand(1, nb, 4).astype(mm)
    det = {
        "MultiBoxPrior": ([onp.random.rand(8, 3, 64, 64).astype(mm)],
                          dict(sizes=(0.5, 0.25), ratios=(1.0, 2.0))),
        "MultiBoxTarget": ([anchors,
                            onp.random.rand(8, 4, 5).astype(mm),
                            onp.random.rand(8, 4, nb).astype(mm)], {}),
        "MultiBoxDetection": ([
            onp.random.rand(8, 4, nb).astype(mm),
            onp.random.rand(8, nb * 4).astype(mm), anchors], {}),
        "_contrib_Proposal": ([
            onp.random.rand(2, 2 * 9, 16, 16).astype(mm),
            onp.random.rand(2, 4 * 9, 16, 16).astype(mm),
            onp.tile(onp.float32([256, 256, 1.0]), (2, 1))],
            dict(scales=(2, 4, 8), ratios=(0.5, 1, 2),
                 rpn_pre_nms_top_n=512, rpn_post_nms_top_n=128,
                 rpn_min_size=1)),
        "_contrib_hawkesll": ([
            onp.random.rand(4).astype(mm) + 0.5,
            onp.random.rand(4).astype(mm) * 0.5,
            onp.random.rand(4).astype(mm) + 0.5,
            onp.zeros((8, 4), mm),
            onp.random.rand(8, 100).astype(mm),
            onp.random.randint(0, 4, (8, 100)).astype(mm),
            onp.full((8,), 100.0, mm),
            onp.full((8,), 120.0, mm)], {}),
    }
    return {**opt, **scalar_cmp, **rand, **npi, **quant, **det}


def auto_inputs(opname):
    """Probe an input signature: square activations at several arities,
    with a per-family shape heuristic for common tensor+vector ops."""
    op = get_op(opname)
    x = onp.random.uniform(0.3, 0.9, (128, 128)).astype("float32")
    v = onp.random.uniform(0.3, 0.9, (128,)).astype("float32")
    candidates = [[x], [x, x], [x, x, x], [v], [v, v], [x, v]]
    for args in candidates:
        try:
            vals = [mx.nd.array(a)._data for a in args]
            kwargs = {}
            if op.key_param:
                import jax

                kwargs[op.key_param] = jax.random.key(0)
            out = op.fn(*vals, **kwargs)
            if isinstance(out, (tuple, list)) and len(out) == 0:
                return None
            return args, {}
        except Exception:
            continue
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default=None,
                    help="comma list; default = curated + all probe-able")
    ap.add_argument("--runs", type=int, default=25)
    ap.add_argument("--large", action="store_true")
    ap.add_argument("--baseline", default=None,
                    help="prior OPPERF jsonl; adds per-op regression "
                         "columns (prev_ms, speedup)")
    args = ap.parse_args()

    prev = {}
    if args.baseline:
        with open(args.baseline) as f:
            for line in f:
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                if "op" in row and "avg_time_ms" in row:
                    prev[row["op"]] = row["avg_time_ms"]

    ctx = mx.gpu(0)
    curated = {**_standard_inputs(args.large), **_bench_extra_inputs()}
    if args.ops:
        names = args.ops.split(",")
    else:
        # registry-wide (reference opperf runs every registered op):
        # curated shapes win, auto-probe covers the rest, SKIP_OPS
        # documents the ops needing bespoke harnesses
        seen_defs = {}
        for o in sorted(list_ops()):
            if o in SKIP_OPS:
                continue
            seen_defs.setdefault(id(get_op(o)), o)  # dedupe aliases
        names = sorted(set(list(curated) + list(seen_defs.values())))
    skipped = []
    justified = {}
    for name in names:
        if name in JUSTIFIED_SKIPS:
            justified[name] = JUSTIFIED_SKIPS[name]
            continue
        if name in curated:
            spec = curated[name]
        else:
            spec = auto_inputs(name)
            if spec is None:
                skipped.append(name)
                continue
        try:
            dt, samples = bench_op(name, spec[0], spec[1], ctx,
                                   args.runs)
        except Exception as e:
            # a curated or explicitly requested op failing must be
            # visible; only blind auto-probe misses go to the skip list
            if args.ops or name in curated:
                print(json.dumps({"op": name, "error": repr(e)}),
                      flush=True)
            if not args.ops:
                skipped.append(name)
            continue
        # avg is now a TRUE mean over the per-trial marginal times
        # (it used to alias device_chain_time's median, which made the
        # p50 column a duplicate); p50/p99 are nearest-rank (the
        # shared telemetry.opstats convention), so tools/benchdiff.py
        # trends tail latency alongside the mean
        from mxnet_tpu.telemetry.opstats import percentile

        samples = sorted(samples) or [dt]
        mean = sum(samples) / len(samples)
        p50 = percentile(samples, 0.50)
        p99 = percentile(samples, 0.99)
        row = {"op": name, "avg_time_ms": round(mean * 1e3, 4),
               "p50_time_ms": round(p50 * 1e3, 4),
               "p99_time_ms": round(p99 * 1e3, 4),
               "trials": len(samples),
               "method": "device-chain"}
        if name in prev:
            row["prev_ms"] = prev[name]
            if prev[name] > 0 and dt > 0:
                row["speedup_vs_prev"] = round(prev[name] / (dt * 1e3), 2)
        print(json.dumps(row), flush=True)
    # coverage gate (the grad sweep's discipline): every registered
    # non-alias op is timed, justified, or listed as a visible failure
    print(json.dumps({"skipped_unprobeable": len(skipped),
                      "ops": sorted(skipped),
                      "justified_skips": justified}), flush=True)


if __name__ == "__main__":
    main()
