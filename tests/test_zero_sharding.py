"""Sharded-server step (round 9): flat-bucketed reduce-scatter
gradients + shard-owned optimizer — ZeRO-1 as the TPU-native parameter
server (parallel.zero, make_train_step optimizer_sharding="ps", the
Module kvstore='dist_sync' mapping).

The acceptance invariants from the issue:

* parity: the sharded step's params/opt state match the replicated
  step bit-exactly for fp32 SGD over >= 10 steps (adam/lars allclose),
  including under dynamic loss scaling;
* collectives: dp(16) emits <= 8 reduce-scatters + <= 8 all-gathers
  (vs one all-reduce per tensor replicated), read from the compiled
  HLO via the same ``collective_bytes`` counter the dryrun/CI gate
  uses;
* memory: per-chip optimizer-state bytes under sharding ~ total/N;
* checkpoints: sharded optimizer state gathers to the LEGACY .states
  layout and re-shards on load (files interchangeable with replicated
  runs).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, sym
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import get_mesh, make_train_step
from mxnet_tpu.parallel import zero

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ bucket plan
def test_plan_buckets_dtype_homogeneous_and_bounded():
    params = {
        "a": jnp.zeros((300,), jnp.float32),
        "b": jnp.zeros((300,), jnp.float32),
        "c": jnp.zeros((10, 10), jnp.bfloat16),
        "d": jnp.zeros((700,), jnp.float32),
        "e": jnp.zeros((50,), jnp.bfloat16),
    }
    plan = zero.plan_buckets(params, n_shards=8, capacity=512)
    # capacity 512: [a,b] close before d (300+300 <= 512? no: 600 > 512
    # -> a alone? greedy closes when ADDING would exceed: a(300) then
    # b would make 600 > 512 -> close [a]; b(300) + d(700) > 512 ->
    # close [b]; [d] alone; bf16 group: [c(100), e(50)] fits
    assert [b.names for b in plan] == [("a",), ("b",), ("d",),
                                       ("c", "e")]
    for b in plan:
        assert b.padded % 8 == 0
        assert b.padded >= b.size
    assert plan[-1].dtype == "bfloat16"
    # env knob is the default capacity (the authentic reference bound)
    os.environ["MXNET_KVSTORE_BIGARRAY_BOUND"] = "512"
    try:
        assert [b.names for b in zero.plan_buckets(params, 8)] == \
            [b.names for b in plan]
    finally:
        del os.environ["MXNET_KVSTORE_BIGARRAY_BOUND"]
    # a single param larger than the bound still gets a whole bucket
    assert ("d",) in [b.names for b in plan]


def test_flatten_unflatten_roundtrip_and_segments():
    params = {"w": jnp.arange(12.0).reshape(3, 4),
              "b": jnp.arange(5.0) + 100}
    (bucket,) = zero.plan_buckets(params, n_shards=8, capacity=1 << 20)
    flat = zero.flatten_bucket(bucket, params)
    assert flat.shape == (bucket.padded,) and bucket.padded % 8 == 0
    back = zero.unflatten_bucket(bucket, flat)
    for k in params:
        onp.testing.assert_array_equal(onp.asarray(back[k]),
                                       onp.asarray(params[k]))
    ids, nseg = zero.bucket_segments(bucket)
    assert nseg == 3  # 2 params + the inert padding segment
    assert (ids[:12] == 0).all() and (ids[12:17] == 1).all()
    assert (ids[17:] == 2).all()


class _Spec:
    def __init__(self, shape, dtype="float32"):
        self.shape, self.dtype = shape, onp.dtype(dtype)


def _vgg16_shaped():
    """VGG-16's parameter dict by shape, in the order the step's dict
    has it (sorted by name)."""
    convs = [(64, 3), (64, 64), (128, 64), (128, 128), (256, 128),
             (256, 256), (256, 256), (512, 256), (512, 512), (512, 512),
             (512, 512), (512, 512), (512, 512)]
    params = {}
    for i, (cout, cin) in enumerate(convs):
        params[f"vgg0_conv2d{i}_weight"] = _Spec((cout, 3, 3, cin))
        params[f"vgg0_conv2d{i}_bias"] = _Spec((cout,))
    for i, (out, fan_in) in enumerate([(4096, 25088), (4096, 4096),
                                       (1000, 4096)]):
        params[f"vgg0_dense{i}_weight"] = _Spec((out, fan_in))
        params[f"vgg0_dense{i}_bias"] = _Spec((out,))
    return dict(sorted(params.items()))


def test_plan_marks_leaf_shaped_buckets_of_vgg16():
    """At 4 shards and the default bound: a bucket of ONE leaf whose
    rows divide into shards of whole tile rows keeps the leaf's shape
    (dense0, dense1, the six convolutions over the bound); dense2 (250
    rows a shard), every 1-D leaf and every bucket that packs stay
    flat."""
    plan = zero.plan_buckets(_vgg16_shaped(), 4)
    by_name = {n: b for b in plan for n in b.names}
    for n in ("vgg0_dense0_weight", "vgg0_dense1_weight"):
        b = by_name[n]
        assert b.leaf and b.names == (n,) and b.pad == 0
        assert b.shape == b.shapes[0] and b.layout == "leaf"
    d2 = by_name["vgg0_dense2_weight"]
    assert not d2.leaf and d2.shape == (d2.padded,)
    for b in plan:
        if len(b.names) > 1 or len(b.shapes[0]) < 2:
            assert not b.leaf and b.layout == "flat", b.names
    # the convolutions that sit alone in a bucket (512 rows: 128 a
    # shard) go leaf-shaped; those that pack with a bias stay flat
    for i in (7, 8, 9, 10, 11, 12):
        assert by_name[f"vgg0_conv2d{i}_weight"].leaf, i
    n_leaf, n_buckets, share = zero.leaf_share(plan)
    assert (n_leaf, n_buckets) == (8, len(plan))
    assert share >= 0.86
    dense = sum(by_name[f"vgg0_dense{i}_weight"].padded for i in (0, 1))
    assert dense / sum(b.padded for b in plan) >= 0.86
    layout = zero.bucket_layout(plan, stage=2)
    assert [k for k, *_ in layout] == zero.stage3_param_keys(plan)
    assert [(lay, n) for _, lay, n, _ in layout] == \
        [(b.layout, b.padded) for b in plan]
    # the train step exchanges every leaf-shaped bucket round the ring
    # at stages 1 and 2, none at stage 3 (its gather is the forward's)
    assert [how for *_, how in layout] == \
        ["ring" if b.leaf else "native" for b in plan]
    for stage in (1, 2):
        assert zero.ring_share(plan, stage) == zero.leaf_share(plan)
    assert zero.ring_share(plan, 3) == (0, len(plan), 0.0)
    assert {how for *_, how in zero.bucket_layout(plan, 3)} == {"native"}


@pytest.mark.parametrize("shape,n_shards,dtype,leaf", [
    ((4096, 4096), 4, "float32", True),
    ((1000, 4096), 8, "float32", False),   # 125 rows a shard
    ((1000, 4096), 4, "float32", False),   # 250 rows: cuts a tile
    ((1024, 4096), 8, "float32", True),
    ((64, 8), 8, "float32", True),
    ((64, 8), 8, "bfloat16", False),       # 16 rows a bf16 tile
    ((128, 8), 8, "bfloat16", True),
    ((4096 * 4096,), 4, "float32", False),  # nothing to keep 1-D
    ((512, 3, 3, 512), 4, "float32", True),
])
def test_leaf_rule_reads_rows_shards_and_dtype(shape, n_shards, dtype,
                                               leaf):
    (b,) = zero.plan_buckets({"w": _Spec(shape, dtype)}, n_shards,
                             capacity=1)
    assert b.leaf == leaf
    assert b.shape == (shape if leaf else (b.padded,))
    # two leaves in one bucket pack, whatever their shapes
    (b2,) = zero.plan_buckets(
        {"w": _Spec(shape, dtype), "v": _Spec(shape, dtype)}, n_shards,
        capacity=1 << 40)
    assert not b2.leaf


def test_leaf_bucket_roundtrip_slices_and_segments():
    w = jnp.arange(64.0 * 8).reshape(64, 8)
    (b,) = zero.plan_buckets({"w": w}, n_shards=8, capacity=1)
    assert b.leaf
    arr = zero.flatten_bucket(b, {"w": w})
    assert arr is w                       # nothing is packed
    assert zero.unflatten_bucket(b, arr)["w"] is w
    # a flat copy of the same content (a tree saved before leaf-shaped
    # buckets) unflattens to the leaf too
    onp.testing.assert_array_equal(
        onp.asarray(zero.unflatten_bucket(b, w.reshape(-1))["w"]),
        onp.asarray(w))
    # shard k is rows [8k, 8k+8): the flat layout's k-th stretch
    for k in (0, 5):
        onp.testing.assert_array_equal(
            onp.asarray(zero.shard_slice(arr, 8, k)).reshape(-1),
            onp.asarray(zero.shard_slice(w.reshape(-1), 8, k)))
    ids, nseg = zero.bucket_segments(b)
    assert nseg == 2 and ids.shape == b.shape and not ids.any()
    assert ids.strides == (0, 0)          # a view: no memory


def test_layout_tells_variant_keys_and_fingerprints_apart(monkeypatch):
    params = {"w": _Spec((64, 8)), "b": _Spec((64,))}
    leafy = zero.plan_buckets(params, 8, capacity=300)
    assert [b.leaf for b in leafy] == [True, False]
    monkeypatch.setattr(zero, "_leaf_shaped", lambda *a: False)
    flat = zero.plan_buckets(params, 8, capacity=300)
    assert not any(b.leaf for b in flat)
    assert [b.padded for b in flat] == [b.padded for b in leafy]
    assert zero.flat_variant_key(flat) == ((576,), "float32")  # legacy
    assert zero.flat_variant_key(leafy) != zero.flat_variant_key(flat)
    for stage in (None, 3):
        assert zero.plan_fingerprint(leafy, 8, stage) != \
            zero.plan_fingerprint(flat, 8, stage)


# ------------------------------------------- the ring over the data axis
def _ring_vs_native(n, shape, dtype, ring, leaf):
    """(ring's rows, native rows, ring's gathered leaf on every device,
    native gathered leaf) of ``leaf``: one gradient a device, stacked."""
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.parallel import compat_shard_map

    mesh = get_mesh((n,), ("data",))

    def local(g):
        g = g[0]
        idx = jax.lax.axis_index("data")
        mine = zero.ring_reduce_scatter(g, "data", ring, idx)
        ref = jax.lax.psum_scatter(g, "data", scatter_dimension=0,
                                   tiled=True)
        # the gather writes over an array of the leaf's shape (the old
        # weights in the step): every row of it has to be replaced
        full = zero.ring_gather(jnp.full_like(g, 7), mine, "data", ring,
                                idx)
        return mine, ref, full[None], jax.lax.all_gather(
            ref, "data", tiled=True)[None]

    fn = jax.jit(compat_shard_map(local, mesh, in_specs=P("data"),
                                  out_specs=(P("data"),) * 4))
    return [onp.asarray(a) for a in fn(leaf)], fn


#: a leaf of 2 dimensions and a convolution's of 4; rows a shard that
#: travel both ways round in halves (16 a shard at 8 shards) or one
#: way whole (8 a shard: a half would cut a float32 tile)
_RING_SHAPES = {"dense": lambda n: (n * 16, 24),
                "conv": lambda n: (n * 32, 3, 3, 5),
                "one_way": lambda n: (n * 8, 40)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", sorted(_RING_SHAPES))
@pytest.mark.parametrize("n", [2, 4, 8])
def test_ring_exchange_against_the_native_collectives(n, kind, dtype):
    shape = _RING_SHAPES[kind](n)
    # a ring that is not the axis' own order where there is one to take
    ring = (0, 1, 3, 2) + tuple(range(4, n)) if n >= 4 else (1, 0)
    rng = onp.random.RandomState(n)
    # whole numbers: any order of addition is exact, so the ring's sum
    # IS the native collective's, to the bit
    whole = jnp.asarray(rng.randint(-8, 9, (n,) + shape), dtype)
    (mine, ref, full, gathered), fn = _ring_vs_native(
        n, shape, dtype, ring, whole)
    assert mine.dtype == ref.dtype and mine.shape == ref.shape == shape
    onp.testing.assert_array_equal(mine, ref)
    onp.testing.assert_array_equal(
        mine.astype("float32"),
        onp.asarray(whole).astype("float32").sum(0))
    # the gather's result has the leaf's shape, every device holds the
    # same array, and it is what the native gather returns
    assert full.shape == (n,) + shape
    for d in range(n):
        onp.testing.assert_array_equal(full[d], gathered[0])
        onp.testing.assert_array_equal(full[d], mine)
    # random data: the same n terms in the ring's order, so within
    # n roundings of the dtype of the sum of the terms' sizes ...
    noisy = jnp.asarray(rng.randn(*((n,) + shape)), dtype)
    (mine, ref, full, _), _ = _ring_vs_native(n, shape, dtype, ring,
                                              noisy)
    eps = float(jnp.finfo(dtype).eps)
    size = onp.abs(onp.asarray(noisy).astype("float64")).sum(0)
    gap = onp.abs(mine.astype("float64") - ref.astype("float64"))
    assert (gap <= n * eps * size).all(), float((gap / size).max())
    exact = onp.asarray(noisy).astype("float64").sum(0)
    assert (onp.abs(mine.astype("float64") - exact)
            <= n * eps * size).all()
    # ... and a second run repeats the first to the bit
    again = [onp.asarray(a) for a in fn(noisy)]
    onp.testing.assert_array_equal(again[0], mine)
    onp.testing.assert_array_equal(again[2], full)


def test_ring_order_follows_physical_neighbours():
    """The hops go round physical neighbours: read from the devices'
    coordinates where they have any (a 2x2 listed row by row has a
    diagonal between its second and third), the axis' order else."""
    import collections

    from jax.sharding import Mesh

    Chip = collections.namedtuple("Chip", "id coords")

    def order(coords, axes=("data",), shape=None):
        devs = onp.empty(len(coords), object)
        devs[:] = [Chip(i, c) for i, c in enumerate(coords)]
        return zero.ring_order(
            Mesh(devs.reshape(shape or (len(coords),)), axes), "data")

    square = [(0, 0, 0), (1, 0, 0), (0, 1, 0), (1, 1, 0)]
    assert order(square) == (0, 1, 3, 2)
    # already a ring: kept
    assert order([square[i] for i in (0, 1, 3, 2)]) == (0, 1, 2, 3)
    # 2x4, listed row by row: out along one row, back along the other
    two_by_four = [(x, y, 0) for y in range(2) for x in range(4)]
    ring = order(two_by_four)
    assert sorted(ring) == list(range(8)) and ring[0] == 0
    for a, b in zip(ring, ring[1:] + ring[:1]):
        assert sum(abs(p - q) for p, q in zip(two_by_four[a],
                                              two_by_four[b])) == 1
    # a line of chips has no way round: the axis' own order
    assert order([(x, 0, 0) for x in range(4)]) == (0, 1, 2, 3)
    # the data axis of a (data, model) mesh: read along its first column
    assert order(square, ("data", "model"), (4, 1)) == (0, 1, 3, 2)
    # the CPU's devices have no coordinates
    assert zero.ring_order(get_mesh((8,), ("data",)), "data") == \
        tuple(range(8))


# ----------------------------------------------------------------- parity
#: widths of the seeded MLP: the historic one packs or stays flat at
#: 8 shards (32, 16 and 4 rows); the leafy one has leaves of 64 and 128
#: rows, which sit alone over bucket_bound=300 and go leaf-shaped
_MLP, _LEAFY = (32, 16, 4), (64, 128, 4)
_WIDTHS = pytest.mark.parametrize("widths", [_MLP, _LEAFY],
                                  ids=["mlp", "leafy"])


#: The train step sums a leaf-shaped bucket's gradient hop by hop in the
#: ring's order; the replicated step and a flat bucket are summed by the
#: native collective in its own.  The same eight float32 terms in another
#: order round differently, so after ten steps a weight of the leafy net
#: stands within this bound, not on the bits: 64 roundings (2**-23 each)
#: of the weight's own size, and as many of 0.01 for weights near nought.
#: Adam divides a gradient by its own size, which magnifies the rounding
#: of one near nought: a thousandth of a step of lr = 0.1 there.
_ROUNDINGS = 64 * 2.0 ** -23
_SUM_ORDER = {"adam": dict(rtol=_ROUNDINGS, atol=0.1 * 1e-3)}
_SUM_ORDER_ELSE = dict(rtol=_ROUNDINGS, atol=_ROUNDINGS * 1e-2)


def _assert_same(got, want, exact, err_msg, optimizer=None):
    if exact:
        onp.testing.assert_array_equal(got, want, err_msg=err_msg)
    else:
        onp.testing.assert_allclose(
            got, want, err_msg=err_msg,
            **_SUM_ORDER.get(optimizer, _SUM_ORDER_ELSE))


def _mlp_net(widths=_MLP):
    mx.random.seed(0)
    onp.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(widths[0], activation="relu"),
                nn.Dense(widths[1], activation="relu"),
                nn.Dense(widths[2]))
    net.initialize(init=mx.init.Xavier())
    net(mx.nd.zeros((1, 8)))
    return net


def _layouts(state):
    """{bucket key: "leaf" | "flat"} read off a by-bucket opt_state."""
    return {k: "leaf" if max(getattr(a, "ndim", 0) for a in v) > 1
            else "flat" for k, v in state.items()
            if k.startswith("_bucket") and v}


def _run_steps(optimizer, n_steps=10, widths=_MLP, **kw):
    mesh = get_mesh((8,), ("data",))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step, p, s = make_train_step(
        _mlp_net(widths), loss_fn, optimizer=optimizer, learning_rate=0.1,
        momentum=0.9, mesh=mesh, donate=False, **kw)
    rng = onp.random.RandomState(0)
    X = jnp.asarray(rng.rand(32, 8).astype("float32"))
    y = jnp.asarray(rng.randint(0, 4, (32,)).astype("float32"))
    key = jax.random.key(0)
    loss = None
    for i in range(n_steps):
        loss, p, s = step(p, s, X, y, key, float(i + 1))
    # block auto-prefix differs between builds; align by suffix
    p = {k.split("_", 1)[-1]: onp.asarray(v) for k, v in p.items()}
    return float(loss), p, s


@_WIDTHS
@pytest.mark.parametrize("optimizer,exact", [
    ("sgd", True),      # acceptance: bit-exact fp32
    ("adam", False),    # allclose (carries 2 slots)
    ("lars", False),    # allclose (trust ratios via segment psum)
])
def test_sharded_step_parity_with_replicated(optimizer, exact, widths,
                                             monkeypatch):
    l_r, p_r, _ = _run_steps(optimizer, widths=widths)
    l_s, p_s, s_s = _run_steps(optimizer, widths=widths,
                               optimizer_sharding="ps", bucket_bound=300)
    assert set(p_r) == set(p_s)
    layouts = _layouts(s_s)
    if widths == _LEAFY:
        # two leaves are exchanged, updated and kept as their own rows
        # ... and the same step over flat buckets (the layout every
        # bucket had before) ends where this one does, the order of the
        # ring's sum apart
        assert sorted(layouts.values()).count("leaf") == 2
        monkeypatch.setattr(zero, "_leaf_shaped", lambda *a: False)
        l_f, p_f, s_f = _run_steps(optimizer, widths=widths,
                                   optimizer_sharding="ps",
                                   bucket_bound=300)
        assert set(_layouts(s_f).values()) == {"flat"}
        _assert_same(l_f, l_s, False, "loss", optimizer)
        for k in p_f:
            _assert_same(p_f[k], p_s[k], False, k, optimizer)
        if exact:
            # the flat step IS the replicated one, bit for bit
            assert l_r == l_f
            for k in p_r:
                onp.testing.assert_array_equal(p_r[k], p_f[k], err_msg=k)
    else:
        assert set(layouts.values()) == {"flat"}
    if exact:
        ring = widths == _LEAFY
        _assert_same(l_r, l_s, not ring, "loss")
        for k in p_r:
            _assert_same(p_r[k], p_s[k], not ring, k)
    elif widths == _LEAFY:
        assert onp.isclose(l_r, l_s, rtol=1e-6)
        for k in p_r:
            _assert_same(p_r[k], p_s[k], False, k, optimizer)
    else:
        assert onp.isclose(l_r, l_s, rtol=1e-6)
        for k in p_r:
            onp.testing.assert_allclose(p_r[k], p_s[k], rtol=1e-5,
                                        atol=1e-7, err_msg=k)
    # the optimizer state really lives in buckets, sharded over 'data'
    bkeys = [k for k in s_s if k.startswith("_bucket")]
    assert bkeys
    for bk in bkeys:
        for leaf in s_s[bk]:
            if getattr(leaf, "ndim", 0):
                assert leaf.sharding.spec == jax.sharding.PartitionSpec(
                    "data")


@_WIDTHS
def test_sharded_step_parity_under_dynamic_loss_scaling(widths):
    l_r, p_r, s_r = _run_steps("sgd", loss_scale="dynamic", widths=widths)
    l_s, p_s, s_s = _run_steps("sgd", loss_scale="dynamic", widths=widths,
                               optimizer_sharding="ps", bucket_bound=300)
    ring = widths == _LEAFY
    assert ("leaf" in _layouts(s_s).values()) == ring
    _assert_same(l_r, l_s, not ring, "loss")
    for k in p_r:
        _assert_same(p_r[k], p_s[k], not ring, k)
    # the scale/finite-counter bookkeeping matches too
    for a, b in zip(s_r["_loss_scale"], s_s["_loss_scale"]):
        assert float(onp.asarray(a)) == float(onp.asarray(b))


def _trajectory(poison, widths, **kw):
    """Four steps of the seeded MLP on dp(8) from the seeded weights,
    the third batch carrying one ``inf`` where ``poison``: the loss,
    params (by name suffix) and opt_state after every step, the
    start included as entry 0."""
    mesh = get_mesh((8,), ("data",))
    step, p, s = make_train_step(
        _mlp_net(widths), gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer="sgd", learning_rate=0.1, momentum=0.9, mesh=mesh,
        donate=False, **kw)
    rng = onp.random.RandomState(0)
    X = rng.rand(32, 8).astype("float32")
    y = jnp.asarray(rng.randint(0, 4, (32,)).astype("float32"))
    X_bad = X.copy()
    X_bad[3, 2] = onp.inf

    def snap(loss, p, s):
        return (None if loss is None else onp.asarray(loss),
                {k.split("_", 1)[-1]: onp.asarray(v) for k, v in p.items()},
                jax.tree_util.tree_map(onp.asarray, s))

    traj = [snap(None, p, s)]
    for i in range(4):
        xb = X_bad if poison and i == 2 else X
        loss, p, s = step(p, s, jnp.asarray(xb), y, jax.random.key(0),
                          float(i + 1))
        traj.append(snap(loss, p, s))
    return traj


@pytest.mark.parametrize("loss_scale,nan_guard", [
    (None, False), (None, True), (128.0, False), (128.0, True),
    ("dynamic", False),  # dynamic scaling turns the guard off
])
@_WIDTHS
def test_both_arms_take_the_same_steps(loss_scale, nan_guard, widths):
    """The replicated step and the ``ps`` step are one sequence (scale,
    gradient, verdict, update, keep, bookkeeping): from the same
    weights they take the same four steps under every loss-scale mode,
    with and without the guard (to the bit over flat buckets; within
    the order of the ring's sum, ``_SUM_ORDER``, where a bucket is
    leaf-shaped), and a poisoned batch leaves params and state as they
    came in both."""
    poison = nan_guard or loss_scale == "dynamic"
    ring = widths == _LEAFY
    rep = _trajectory(poison, widths, loss_scale=loss_scale,
                      nan_guard=nan_guard)
    ps = _trajectory(poison, widths, loss_scale=loss_scale,
                     nan_guard=nan_guard, optimizer_sharding="ps",
                     bucket_bound=300)
    assert ("leaf" in _layouts(ps[0][2]).values()) == ring
    for i in range(1, 5):
        (l_r, p_r, s_r), (l_s, p_s, s_s) = rep[i], ps[i]
        if onp.isfinite(l_r):
            _assert_same(l_r, l_s, not ring, f"loss {i}")
        else:
            onp.testing.assert_array_equal(l_r, l_s, err_msg=f"loss {i}")
        assert set(p_r) == set(p_s)
        for k in p_r:
            _assert_same(p_r[k], p_s[k], not ring,
                         f"{k} after step {i}")
        for key in ("_loss_scale", "_bad_steps"):
            assert (key in s_r) == (key in s_s)
            if key in s_r:
                for a, b in zip(jax.tree_util.tree_leaves(s_r[key]),
                                jax.tree_util.tree_leaves(s_s[key])):
                    onp.testing.assert_array_equal(
                        a, b, err_msg=f"{key} after step {i}")
    assert ("_bad_steps" in rep[0][2]) == nan_guard
    assert ("_loss_scale" in rep[0][2]) == (loss_scale == "dynamic")
    if not poison:
        assert all(onp.isfinite(t[0]) for t in rep[1:])
        return
    for traj in (rep, ps):
        (_, p2, s2), (l3, p3, s3), (l4, p4, _) = traj[2], traj[3], traj[4]
        assert not onp.isfinite(l3) and onp.isfinite(l4)
        for k in p2:
            onp.testing.assert_array_equal(p3[k], p2[k], err_msg=k)
            assert not onp.array_equal(p4[k], p3[k]), k
        for k in s2:
            if k.startswith("_") and not k.startswith("_bucket"):
                continue  # the bookkeeping is what moves
            for a, b in zip(jax.tree_util.tree_leaves(s3[k]),
                            jax.tree_util.tree_leaves(s2[k])):
                onp.testing.assert_array_equal(a, b, err_msg=k)
        if nan_guard:
            assert [int(t[2]["_bad_steps"]) for t in traj] == \
                [0, 0, 0, 1, 0]
        else:
            scales = [float(t[2]["_loss_scale"][0]) for t in traj]
            assert scales == [65536.0] * 3 + [32768.0] * 2
            assert [int(t[2]["_loss_scale"][1]) for t in traj] == \
                [0, 1, 2, 0, 1]


def test_zero_layout_and_runlog_name_the_leaf_shaped_share(tmp_path):
    """What the plan decided is readable off the step and off the
    RunLog's compile record, with no program text."""
    from mxnet_tpu import telemetry

    path = str(tmp_path / "run.jsonl")
    telemetry.reset(path)
    try:
        mesh = get_mesh((8,), ("data",))
        step, p, s = make_train_step(
            _mlp_net(_LEAFY), gluon.loss.SoftmaxCrossEntropyLoss(),
            optimizer="sgd", learning_rate=0.1, momentum=0.9, mesh=mesh,
            donate=False, optimizer_sharding="ps", bucket_bound=300)
        rng = onp.random.RandomState(0)
        X = jnp.asarray(rng.rand(32, 8).astype("float32"))
        y = jnp.asarray(rng.randint(0, 4, (32,)).astype("float32"))
        step(p, s, X, y, jax.random.key(0), 1.0)
    finally:
        telemetry.close()
    plan = step.zero_plan
    assert step.zero_layout == [
        (f"_bucket{i}", "leaf", b.padded, "ring") if b.leaf else
        (f"_bucket{i}", "flat", b.padded, "native")
        for i, b in enumerate(plan)]
    leaf = {b.names[0].split("_", 1)[-1]: b.shape for b in plan if b.leaf}
    assert leaf == {"dense0_weight": (64, 8), "dense1_weight": (128, 64)}
    n_leaf, n, share = zero.leaf_share(plan)
    assert (n_leaf, n) == (2, 6)
    assert share == (512 + 8192) / sum(b.padded for b in plan)
    with open(path) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    (comp,) = [r for r in recs if r.get("type") == "compile"
               and r.get("program") == "train_step"]
    assert comp["fingerprint"]["sharding"] == (
        f"ps (2 of 6 buckets leaf-shaped, {100 * share:.1f}% of the "
        f"elements; 2 by the ring, {100 * share:.1f}%)")
    assert zero.ring_share(plan, step.zero_stage) == (2, 6, share)
    # the state of a leaf-shaped bucket is the leaf's rows
    for (bk, lay, *_), b in zip(step.zero_layout, plan):
        assert s[bk][0].shape == b.shape
        assert (s[bk][0].ndim > 1) == (lay == "leaf")


def test_sharded_step_env_knob_and_guards():
    mesh = get_mesh((8,), ("data",))
    loss_fn = gluon.loss.L2Loss()
    net = nn.Dense(4, in_units=8)
    net.initialize()
    # env force-ON: MXNET_ZERO_STAGE=1/2/3 opts a meshed step in
    os.environ["MXNET_ZERO_STAGE"] = "2"
    try:
        step, _, s = make_train_step(net, loss_fn, mesh=mesh, donate=False)
        assert any(k.startswith("_bucket") for k in s)
        assert step.zero_stage == 2
    finally:
        del os.environ["MXNET_ZERO_STAGE"]
    # env force-OFF (0) beats the explicit opt-in, by either keyword
    os.environ["MXNET_ZERO_STAGE"] = "0"
    try:
        for kw in (dict(optimizer_sharding="ps"), dict(zero_stage=3)):
            step, _, s = make_train_step(net, loss_fn, mesh=mesh,
                                         donate=False, **kw)
            assert not any(k.startswith("_bucket") for k in s)
            assert getattr(step, "zero_stage", None) is None
    finally:
        del os.environ["MXNET_ZERO_STAGE"]
    # tp param_spec does not compose
    from mxnet_tpu.parallel import P

    with pytest.raises(MXNetError, match="param_spec"):
        make_train_step(net, loss_fn, mesh=mesh, donate=False,
                        optimizer_sharding="ps",
                        param_spec={"dense0_weight": P("data", None)})
    # compression demands the sharded wire
    with pytest.raises(MXNetError, match="optimizer_sharding"):
        make_train_step(net, loss_fn, mesh=mesh, donate=False,
                        gradient_compression={"type": "2bit"})
    # non-elementwise rule without a bucket form is rejected loudly
    with pytest.raises(MXNetError, match="bucket"):
        make_train_step(net, loss_fn, optimizer="groupadagrad",
                        mesh=mesh, donate=False,
                        optimizer_sharding="ps")
    # meshless: warns and stays replicated rather than failing
    with pytest.warns(UserWarning, match="mesh"):
        _, _, s = make_train_step(net, loss_fn, donate=False,
                                  optimizer_sharding="ps")
    assert not any(k.startswith("_bucket") for k in s)


# ------------------------------------------------------------ collectives
def _collective_counts(**kw):
    mesh = get_mesh((8,), ("data",))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step, p, s = make_train_step(
        _mlp_net(), loss_fn, optimizer="sgd", learning_rate=0.1,
        momentum=0.9, mesh=mesh, donate=False, **kw)
    rng = onp.random.RandomState(0)
    X = jnp.asarray(rng.rand(32, 8).astype("float32"))
    y = jnp.asarray(rng.randint(0, 4, (32,)).astype("float32"))
    hlo = step.lower(p, s, X, y, jax.random.key(0), 1.0) \
        .compile().as_text()
    return zero.collective_bytes(hlo)


def test_collective_structure_on_8dev_mesh():
    rep = _collective_counts()
    shd = _collective_counts(optimizer_sharding="ps", bucket_bound=300)
    # replicated: every gradient tensor is all-reduced (6 params +
    # loss).  XLA combines them into one tuple-shaped launch, so the
    # tensors are counted, and the launches only bounded
    assert rep["tensors"]["all-reduce"] >= 6
    assert 1 <= rep["counts"]["all-reduce"] <= rep["tensors"]["all-reduce"]
    assert rep["counts"]["reduce-scatter"] == 0
    # sharded: exactly one reduce-scatter + one all-gather per bucket
    # (3 at bound=300 for this MLP) and only the loss pmean all-reduce
    c = shd["counts"]
    assert c["reduce-scatter"] == 3
    assert c["all-gather"] == 3
    assert c["all-reduce"] <= 2
    # same total gradient bytes, just batched (RS+AG ~ 2x params; the
    # replicated AR carries params once but per-tensor)
    assert shd["bytes"]["reduce-scatter"] > 0
    one = _collective_counts(optimizer_sharding="ps")  # default bound:
    assert one["counts"]["reduce-scatter"] == 1        # one flat bucket


# the HLO text today's XLA prints: neighbouring collectives combined into
# one tuple-shaped op whose shape carries /*index=N*/ comments (CPU and
# TPU), TPU tiling annotations, async -start/-done pairs, and references
# to a collective from other instructions
_HLO_LINES = {
    "cpu_combined_all_reduce": (
        "  %all-reduce.1 = (f32[], f32[32]{0}, f32[4,8]{1,0}, f32[16]{0}, "
        "f32[16,8]{1,0}, /*index=5*/f32[4]{0}, f32[4,16]{1,0}) "
        "all-reduce(%a, %b, %c, %d, %e, %f, %g), channel_id=1, "
        "replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%region_0.1",
        ("all-reduce", 1, 7, 4 * (1 + 32 + 32 + 16 + 128 + 4 + 64))),
    "tpu_tiled_tuple_all_reduce": (
        "  %all-reduce.34 = (f32[]{:T(128)}, f32[957888]{0:T(1024)S(1)}, "
        "f32[1000]{0:T(1024)}, f32[2048000]{0:T(1024)}, "
        "f32[629248]{0:T(1024)}, /*index=5*/f32[529408]{0:T(1024)}) "
        "all-reduce(%x0, %x1, %x2, %x3, %x4, %x5), channel_id=2, "
        "replica_groups={{0,1,2,3}}, use_global_device_ids=true",
        ("all-reduce", 1, 6,
         4 * (1 + 957888 + 1000 + 2048000 + 629248 + 529408))),
    "tpu_all_gather": (
        "  %all-gather.141 = f32[8192]{0:T(1024)S(1)} all-gather("
        "%get-tuple-element.1767), channel_id=1, replica_groups="
        "{{0,1,2,3}}, dimensions={0}, frontend_attributes={"
        "async_collective_name=\"all-gather-start.3\"}",
        ("all-gather", 1, 1, 4 * 8192)),
    "async_all_gather_start": (
        "  %all-gather-start.2 = (bf16[128]{0}, bf16[1024]{0}) "
        "all-gather-start(%p), channel_id=3, dimensions={0}",
        ("all-gather", 1, 1, 2 * 1024)),
    "async_done_is_not_a_launch": (
        "  %all-gather-done.2 = bf16[1024]{0} all-gather-done("
        "%all-gather-start.2)", None),
    "reference_is_not_a_launch": (
        "  %get-tuple-element.9 = f32[2048]{0:T(1024)S(1)} "
        "get-tuple-element(%all-reduce.34), index=21", None),
}


@pytest.mark.parametrize("case", sorted(_HLO_LINES))
def test_collective_bytes_reads_todays_hlo_text(case):
    line, want = _HLO_LINES[case]
    got = zero.collective_bytes("HloModule m\n" + line + "\n")
    if want is None:
        assert sum(got["counts"].values()) == 0
        assert got["total_bytes"] == 0
        return
    kind, launches, tensors, nbytes = want
    assert got["counts"][kind] == launches
    assert got["tensors"][kind] == tensors
    assert got["bytes"][kind] == nbytes
    assert got["total_bytes"] == nbytes


def test_dp16_resnet_collective_budget_acceptance():
    """THE acceptance bar: ResNet-18 dp(16) under
    optimizer_sharding="ps" compiles to <= 8 reduce-scatters + <= 8
    all-gathers (vs one all-reduce per tensor replicated), counted
    from the compiled HLO in a 16-device CPU-mesh subprocess — the
    same program/bound the ci ``collectives_budget`` cell gates."""
    body = textwrap.dedent("""\
        import os, json, sys
        sys.path.insert(0, %r)
        os.environ["MXNET_KVSTORE_BIGARRAY_BOUND"] = "4000000"
        import jax
        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp
        import numpy as onp
        import mxnet_tpu as mx
        from mxnet_tpu import gluon
        from mxnet_tpu.parallel import get_mesh, make_train_step
        from mxnet_tpu.parallel.zero import collective_bytes

        def build():
            net = gluon.model_zoo.vision.get_resnet(1, 18, classes=10)
            net.initialize(init=mx.init.Xavier())
            net(mx.nd.zeros((1, 3, 32, 32)))
            return net

        mesh = get_mesh((16,), ("data",))
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        x = jnp.asarray(onp.random.rand(32, 3, 32, 32).astype("float32"))
        y = jnp.asarray(onp.random.randint(0, 10, (32,)).astype("float32"))
        key = jax.random.key(0)
        out = {}
        for label, kw in (("replicated", {}),
                          ("sharded", {"optimizer_sharding": "ps"})):
            step, p, s = make_train_step(
                build(), loss_fn, optimizer="sgd", learning_rate=0.1,
                mesh=mesh, donate=False, autotune=False, **kw)
            out[label] = collective_bytes(
                step.lower(p, s, x, y, key, 1.0).compile().as_text())
        print(json.dumps(out))
        """) % (_REPO,)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                     if "host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=16").strip()
    r = subprocess.run([sys.executable, "-c", body], env=env,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.splitlines()[-1])
    rep, shd = out["replicated"]["counts"], out["sharded"]["counts"]
    # replicated: one all-reduce per gradient tensor (the r05 artifact
    # counted 54; jax versions shift the exact figure, the per-tensor
    # structure doesn't)
    assert rep["all-reduce"] >= 20, rep
    # sharded: the budget the CI gate enforces
    assert shd["reduce-scatter"] <= 8, shd
    assert 1 <= shd["all-gather"] <= 8, shd
    assert shd["all-reduce"] <= 2, shd


# ----------------------------------------------------------------- memory
def test_optimizer_state_bytes_shard_as_params_over_n():
    """Adam carries 2 slots: per-chip opt-state bytes under sharding
    must be ~ 2*params/8 on the 8-device mesh (vs 2*params replicated
    on every chip)."""
    mesh = get_mesh((8,), ("data",))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    net = _mlp_net()
    step, p, s = make_train_step(
        net, loss_fn, optimizer="adam", mesh=mesh, donate=False,
        optimizer_sharding="ps")
    param_bytes = sum(v.nbytes for v in p.values())
    local = 0
    for k, st in s.items():
        if not k.startswith("_bucket"):
            continue
        for leaf in st:
            if getattr(leaf, "ndim", 0):
                local += leaf.addressable_shards[0].data.nbytes
    expect = 2 * param_bytes / 8
    # padding rounds each bucket up to a multiple of 8 elements
    assert expect <= local <= expect * 1.1 + 2 * 8 * 4, (local, expect)


# ----------------------------------------------- Module dist_sync mapping
def _mlp_symbol(hidden=16):
    d = sym.Variable("data")
    fc1 = sym.FullyConnected(d, num_hidden=hidden, name="fc1")
    act = sym.Activation(fc1, act_type="relu", name="relu1")
    fc2 = sym.FullyConnected(act, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(fc2, sym.Variable("softmax_label"),
                             name="softmax")


def _fit_module(kvstore, optimizer="sgd", epochs=2, extra_params=(),
                hidden=16):
    rng = onp.random.RandomState(7)
    X = rng.randn(64, 10).astype("float32")
    y = (X @ rng.randn(10, 4)).argmax(axis=1).astype("float32")
    it = mx.io.NDArrayIter(X, y, batch_size=8, shuffle=False)
    mod = mx.mod.Module(_mlp_symbol(hidden),
                        context=[mx.gpu(i) for i in range(8)])
    mod.bind(data_shapes=it.provide_data,
             label_shapes=it.provide_label)
    mod.init_params(initializer=mx.init.Xavier())
    arg, aux = mod.get_params()
    r = onp.random.RandomState(3)
    det = {n: mx.nd.array((r.randn(*v.shape) * 0.3).astype("float32"))
           for n, v in arg.items()}
    mod.set_params(det, aux)
    opt_params = [("learning_rate", 0.1)]
    if optimizer in ("sgd", "lars"):
        opt_params.append(("momentum", 0.9))
    opt_params.extend(extra_params)
    mod.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                       optimizer_params=tuple(opt_params))
    for _ in range(epochs):
        it.reset()
        for batch in it:
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
    arg, _ = mod.get_params()
    return mod, {n: v.asnumpy() for n, v in arg.items()}


def test_module_dist_sync_maps_to_sharded_updater():
    """Module.fit(kvstore='dist_sync') on a data mesh runs the
    server-side-optimizer analog: state sharded in flat buckets,
    updates on the owned shard only — and trains the same model as
    the replicated updater."""
    mod_s, p_s = _fit_module("dist_sync")
    assert isinstance(mod_s._updater, zero.ShardedBucketUpdater)
    mod_l, p_l = _fit_module("local")
    assert not isinstance(mod_l._updater, zero.ShardedBucketUpdater)
    for n in p_l:
        onp.testing.assert_allclose(p_s[n], p_l[n], rtol=1e-5,
                                    atol=1e-6, err_msg=n)
    # .states files are interchangeable: sharded gathers to the legacy
    # layout, the eager updater loads it, and re-sharding round-trips
    # bit-exactly
    blob = mod_s._get_optimizer_states()
    import pickle

    # fit checkpoints ride dump_optimizer=True: (states, optimizer)
    # with the optimizer's counters seeded so cross-mode resumes keep
    # their bias-correction step
    legacy, opt_copy = pickle.loads(blob)
    assert opt_copy.num_update == mod_s._updater._t == 16
    # per-param legacy layout + the reserved "__step" counter (the
    # fused rules take t explicitly; eager carries it through inert)
    assert set(legacy) == {"fc1_weight", "fc1_bias", "fc2_weight",
                           "fc2_bias", "__step"}
    mod_l._set_optimizer_states(blob)
    mod_s._set_optimizer_states(mod_l._get_optimizer_states())
    a = legacy
    b, _ = pickle.loads(mod_s._get_optimizer_states())
    for k in a:
        for x, yv in zip(a[k], b[k]):
            onp.testing.assert_array_equal(x.asnumpy(), yv.asnumpy())


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_module_leaf_shaped_states_interchange(optimizer, monkeypatch):
    """A leaf-shaped bucket's state lives as rows of the leaf; on save
    it gathers to the same legacy per-param pickle as ever, so it loads
    into a replicated run and into a flat-bucket run and back, bit for
    bit."""
    import pickle

    monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "300")
    mod_s, p_s = _fit_module("dist_sync", optimizer, hidden=64)
    upd = mod_s._updater
    assert isinstance(upd, zero.ShardedBucketUpdater)
    leafy = [b for b in upd.plan if b.leaf]
    assert [b.names for b in leafy] == [("fc1_weight",)]
    for b, st in zip(upd.plan, upd._states):
        for leaf in st:
            if getattr(leaf, "ndim", 0):
                assert leaf.shape == b.shape
                assert leaf.sharding.spec == \
                    jax.sharding.PartitionSpec("data")
    mod_l, p_l = _fit_module("local", optimizer, hidden=64)
    with monkeypatch.context() as m:
        m.setattr(zero, "_leaf_shaped", lambda *a: False)
        mod_f, p_f = _fit_module("dist_sync", optimizer, hidden=64)
    assert not any(b.leaf for b in mod_f._updater.plan)
    for n in p_l:
        onp.testing.assert_allclose(p_s[n], p_l[n], rtol=1e-5,
                                    atol=1e-6, err_msg=n)
        onp.testing.assert_array_equal(p_s[n], p_f[n], err_msg=n)

    def legacy(mod):
        states, _ = pickle.loads(mod._get_optimizer_states())
        return {k: [x.asnumpy() for x in v] for k, v in states.items()
                if k != "__step"}

    want = legacy(mod_s)
    assert want["fc1_weight"][0].shape == (64, 10)
    blob = mod_s._get_optimizer_states()
    for other in (mod_l, mod_f):
        # leaf-shaped -> the other layout -> and back
        other._set_optimizer_states(blob)
        got = legacy(other)
        assert set(got) == set(want)
        for k in want:
            for a, b in zip(want[k], got[k]):
                onp.testing.assert_array_equal(a, b, err_msg=k)
        mod_s._set_optimizer_states(other._get_optimizer_states())
        back = legacy(mod_s)
        for k in want:
            for a, b in zip(want[k], back[k]):
                onp.testing.assert_array_equal(a, b, err_msg=k)
    # the plans tell themselves apart where a checkpoint is stamped
    assert upd.topology()["plan_fingerprint"] != \
        mod_f._updater.topology()["plan_fingerprint"]


def test_module_sharded_engages_with_weight_decay():
    """wd>0 auto-seeds wd_mult=0 on every bias (set_wd_mult): the
    sharded updater must still ENGAGE — buckets partition by effective
    (lr, wd) so per-param multipliers stay exact — and match the eager
    updater's math."""
    mod_s, p_s = _fit_module("dist_sync",
                             extra_params=(("wd", 1e-2),))
    assert isinstance(mod_s._updater, zero.ShardedBucketUpdater)
    # biases (wd_mult 0) and weights (wd) landed in separate buckets
    groups = {b.group for b in mod_s._updater.plan}
    assert len(groups) == 2, groups
    mod_l, p_l = _fit_module("local", extra_params=(("wd", 1e-2),))
    for n in p_l:
        onp.testing.assert_allclose(p_s[n], p_l[n], rtol=1e-5,
                                    atol=1e-6, err_msg=n)


def test_sharded_live_num_update_clock_matches_eager():
    """Callbacks reading module._optimizer.num_update (the classic
    decay-every-K-updates recipe) must see the same live clock under
    kvstore='dist_sync' as under 'local' — including a nonzero
    begin_num_update, which also seeds adam's bias-correction t so the
    two updaters stay in parity on resumed-style counters."""
    seed = (("begin_num_update", 5),)
    mod_s, p_s = _fit_module("dist_sync", optimizer="adam",
                             extra_params=seed)
    mod_l, p_l = _fit_module("local", optimizer="adam",
                             extra_params=seed)
    assert isinstance(mod_s._updater, zero.ShardedBucketUpdater)
    # 2 epochs x 8 batches on top of begin_num_update=5
    assert mod_l._optimizer.num_update == 21
    assert mod_s._optimizer.num_update == 21
    assert mod_s._updater._t == 21
    for n in p_l:
        onp.testing.assert_allclose(p_s[n], p_l[n], rtol=1e-5,
                                    atol=1e-6, err_msg=n)


def test_sharding_env_rejects_unknown_values(monkeypatch):
    monkeypatch.setenv("MXNET_ZERO_STAGE", "sharded")
    with pytest.raises(MXNetError, match="not a recognized"):
        zero.resolve_stage("ps", None, get_mesh((8,), ("data",)))


def test_stale_step_entry_loses_to_fresh_optimizer_counters():
    """sharded -> eager -> sharded resume chain: the eager leg carries
    the original "__step" inert while its own counters advance, so a
    later sharded set_states must trust the dump's num_update, not the
    stale states entry."""
    import pickle

    mod, _ = _fit_module("dist_sync", optimizer="adam")
    upd = mod._updater
    blob = upd.get_states(dump_optimizer=True)
    states, opt_copy = pickle.loads(blob)
    # simulate the eager leg: 8 more updates advanced the optimizer's
    # counters but left the inherited "__step" untouched
    opt_copy.num_update = upd._t + 8
    upd.set_states(pickle.dumps((states, opt_copy)))
    assert upd._t == 24  # num_update won; stale __step=16 ignored


def test_sharded_updater_step_counter_survives_states_roundtrip():
    """Bias-corrected rules (adam) need the step count across a
    save/load: the reserved "__step" entry restores _t, so a resumed
    adam run does not restart its bias correction at t=1."""
    mod, _ = _fit_module("dist_sync", optimizer="adam")
    upd = mod._updater
    assert isinstance(upd, zero.ShardedBucketUpdater)
    t_before = upd._t
    assert t_before == 16  # 8 batches/epoch x 2 epochs
    blob = upd.get_states()
    upd._t = 0
    upd.set_states(blob)
    assert upd._t == t_before


def test_sharded_updater_tracks_live_lr_and_wd_mutation():
    """The eager updater reads lr/wd on every update; the sharded one
    bakes them at trace — so it must RE-SYNC when the caller mutates
    them mid-training (the epoch-decay recipe).  The wd 0 -> 1e-2 flip
    also re-partitions the params (bias wd_mult=0), exercising the
    gather -> replan -> re-shard path."""
    rng = onp.random.RandomState(7)
    X = rng.randn(64, 10).astype("float32")
    y = (X @ rng.randn(10, 4)).argmax(axis=1).astype("float32")

    def run(kvstore):
        it = mx.io.NDArrayIter(X, y, batch_size=8, shuffle=False)
        mod = mx.mod.Module(_mlp_symbol(),
                            context=[mx.gpu(i) for i in range(8)])
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label)
        mod.init_params(initializer=mx.init.Xavier())
        arg, aux = mod.get_params()
        r = onp.random.RandomState(3)
        mod.set_params(
            {n: mx.nd.array((r.randn(*v.shape) * 0.3).astype("float32"))
             for n, v in arg.items()}, aux)
        mod.init_optimizer(kvstore=kvstore, optimizer="sgd",
                           optimizer_params=(("learning_rate", 0.1),
                                             ("momentum", 0.9)))
        for epoch in range(4):
            if epoch == 2:
                # mid-training decay + late weight decay
                mod._optimizer.set_learning_rate(0.01)
                mod._optimizer.wd = 1e-2
            it.reset()
            for batch in it:
                mod.forward(batch, is_train=True)
                mod.backward()
                mod.update()
        arg, _ = mod.get_params()
        return mod, {n: v.asnumpy() for n, v in arg.items()}

    mod_s, p_s = run("dist_sync")
    assert isinstance(mod_s._updater, zero.ShardedBucketUpdater)
    # the wd flip split biases (wd_mult 0) from weights: re-bucketed
    assert len({b.group for b in mod_s._updater.plan}) == 2
    mod_l, p_l = run("local")
    for n in p_l:
        onp.testing.assert_allclose(p_s[n], p_l[n], rtol=1e-5,
                                    atol=1e-6, err_msg=n)


def test_sharded_updater_tracks_live_momentum_mutation():
    """lr/wd are not the only live hyper-params: the eager updater
    reads momentum/clip_gradient/... on every update too, so mutating
    them mid-training must re-bake + re-trace the sharded step — not
    silently keep the values traced at init."""
    rng = onp.random.RandomState(7)
    X = rng.randn(64, 10).astype("float32")
    y = (X @ rng.randn(10, 4)).argmax(axis=1).astype("float32")

    def run(kvstore):
        it = mx.io.NDArrayIter(X, y, batch_size=8, shuffle=False)
        mod = mx.mod.Module(_mlp_symbol(),
                            context=[mx.gpu(i) for i in range(8)])
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label)
        mod.init_params(initializer=mx.init.Xavier())
        arg, aux = mod.get_params()
        r = onp.random.RandomState(3)
        mod.set_params(
            {n: mx.nd.array((r.randn(*v.shape) * 0.3).astype("float32"))
             for n, v in arg.items()}, aux)
        mod.init_optimizer(kvstore=kvstore, optimizer="sgd",
                           optimizer_params=(("learning_rate", 0.1),
                                             ("momentum", 0.9)))
        for epoch in range(4):
            if epoch == 2:
                # kill momentum + start clipping mid-run
                mod._optimizer.momentum = 0.0
                mod._optimizer.clip_gradient = 0.05
            it.reset()
            for batch in it:
                mod.forward(batch, is_train=True)
                mod.backward()
                mod.update()
        arg, _ = mod.get_params()
        return mod, {n: v.asnumpy() for n, v in arg.items()}

    mod_s, p_s = run("dist_sync")
    assert isinstance(mod_s._updater, zero.ShardedBucketUpdater)
    mod_l, p_l = run("local")
    for n in p_l:
        onp.testing.assert_allclose(p_s[n], p_l[n], rtol=1e-5,
                                    atol=1e-6, err_msg=n)


@pytest.mark.parametrize("kvstore", ["local", "dist_sync"])
def test_resume_repoints_module_optimizer_at_live_one(kvstore):
    """set_states installs the UNPICKLED optimizer as the updater's
    live one; the module must re-point at it, or post-resume
    mutations (module._optimizer.lr = ...) hit a dead object and are
    silently ignored for the rest of training."""
    mod, _ = _fit_module(kvstore)
    blob = mod._get_optimizer_states()
    pre_resume_opt = mod._optimizer
    mod._set_optimizer_states(blob)
    assert mod._optimizer is mod._updater.optimizer
    assert mod._optimizer is not pre_resume_opt
    # and the mutation actually lands in the running update
    mod._optimizer.lr = 0.123
    if kvstore == "dist_sync":
        mod._updater._sync_hyper_params()
        assert all(b.group[0] == pytest.approx(0.123)
                   for b in mod._updater.plan)
    else:
        assert mod._updater.optimizer._get_lr("fc1_weight") \
            == pytest.approx(0.123)


def test_sharded_set_states_refuses_ineligible_optimizer():
    """init_optimizer's eligibility gate runs against the init-time
    optimizer only; a cross-mode resume pickle can smuggle in
    semantics the flat buckets cannot reproduce (an eager dump's
    lr_scheduler, multi-precision masters).  set_states must refuse
    loudly — silently pinning the lr at the resume-point value is the
    silent-math-change failure mode."""
    import pickle

    mod, _ = _fit_module("dist_sync")
    upd = mod._updater
    blob = upd.get_states(dump_optimizer=True)
    states, opt_copy = pickle.loads(blob)
    opt_copy.multi_precision = True
    with pytest.raises(MXNetError, match="multi_precision"):
        upd.set_states(pickle.dumps((states, opt_copy)))
    # the updater kept its own optimizer and stays usable
    assert upd.optimizer is not opt_copy
    assert not upd.optimizer.multi_precision
    # a layout-mismatched rule (Nadam's fused state carries an extra
    # schedule scalar) is refused by the same shared predicate Module's
    # init gate uses — not crashed on later inside the jitted update
    from mxnet_tpu import optimizer as opt_mod

    with pytest.raises(MXNetError, match="layouts differ"):
        upd.set_states(pickle.dumps((states, opt_mod.create("nadam"))))
    upd.set_states(blob)


def test_sharded_updater_state_lost_raises_clear_error():
    """A step failing mid-execution consumes the DONATED state
    buffers; get_states (the preemption drain's final checkpoint) must
    raise a clear restore-from-checkpoint error, not crash on deleted
    arrays — and a set_states restore recovers."""
    mod, _ = _fit_module("dist_sync")
    upd = mod._updater
    blob = upd.get_states()
    upd._states = None  # what the failed-step handler records
    with pytest.raises(MXNetError, match="last checkpoint"):
        upd.get_states()
    with pytest.raises(MXNetError, match="last checkpoint"):
        upd.update_all([])
    upd.set_states(blob)
    assert upd._states is not None


def test_sharded_dump_optimizer_seeds_eager_counters():
    """Sharded -> EAGER resume of a bias-corrected rule: the
    dump_optimizer pickle's counters are seeded with the sharded step
    count, so the eager Updater continues adam's bias correction
    instead of restarting at t=1."""
    from mxnet_tpu import optimizer as opt_mod

    mod, _ = _fit_module("dist_sync", optimizer="adam")
    upd = mod._updater
    assert isinstance(upd, zero.ShardedBucketUpdater)
    blob = upd.get_states(dump_optimizer=True)
    eager = opt_mod.get_updater(opt_mod.create("adam"))
    eager.set_states(blob)
    assert eager.optimizer.begin_num_update == upd._t
    w, g = mx.nd.ones((16,)), mx.nd.ones((16,))
    eager("fc1_bias", g, w)
    assert eager.optimizer._index_update_count["fc1_bias"] == upd._t + 1


def test_module_sharding_env_force_off_and_fallbacks(monkeypatch):
    # 0 beats the kvstore='dist_sync' mapping; any stage forces the
    # sharded updater (ZeRO-1 whatever the stage) on a local kvstore
    monkeypatch.setenv("MXNET_ZERO_STAGE", "0")
    mod, _ = _fit_module("dist_sync", epochs=1)
    assert not isinstance(mod._updater, zero.ShardedBucketUpdater)
    monkeypatch.setenv("MXNET_ZERO_STAGE", "3")
    mod, _ = _fit_module("local", epochs=1)
    assert isinstance(mod._updater, zero.ShardedBucketUpdater)
    monkeypatch.delenv("MXNET_ZERO_STAGE")
    # semantics the flat buckets cannot reproduce fall back LOUDLY to
    # the eager updater instead of silently changing the math
    mod, _ = _fit_module("dist_sync", optimizer="nadam", epochs=1)
    assert not isinstance(mod._updater, zero.ShardedBucketUpdater)


# --------------------------------------------- kvstore satellite fixes
def test_compression_residuals_keyed_per_bucket_shard():
    from mxnet_tpu.kvstore import GradientCompression

    gc = GradientCompression(threshold=0.5)
    g = jnp.asarray([0.3, -0.3])
    # same key, different shards: residuals must NOT cross-feed
    q0 = gc.compress("k", g, shard=0)
    q1 = gc.compress("k", g, shard=1)
    assert (onp.asarray(q0) == 0).all() and (onp.asarray(q1) == 0).all()
    # second round: each shard's own residual pushes it over threshold
    q0b = gc.compress("k", g, shard=0)
    onp.testing.assert_allclose(onp.asarray(q0b), [0.5, -0.5])
    assert ("k", 0) in gc._residual and ("k", 1) in gc._residual
    # a shared-residual implementation would have fired on q1 already
    q1b = gc.compress("k", g, shard=1)
    onp.testing.assert_allclose(onp.asarray(q1b), [0.5, -0.5])


def test_dist_push_slices_bigarrays_per_shard_residual(monkeypatch):
    """The production caller of the (key, shard) keying: a compressed
    dist push of an array above MXNET_KVSTORE_BIGARRAY_BOUND slices
    it into bound-sized bucket-shards, each with its own residual —
    and the concatenated wire payload is byte-identical to whole-array
    packing (4-aligned slice edges)."""
    monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "8")
    kv = mx.kv.create("dist_sync")  # 1-worker group
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    g = onp.linspace(-1.0, 1.0, 20).astype("float32")
    kv.init("big", mx.nd.zeros((20,)))
    kv.push("big", mx.nd.array(g))
    # 20 elements / 8-bound -> 3 slices, each with its own residual
    keys = sorted(k for k in kv._compression._residual
                  if isinstance(k, tuple) and k[0] == "big")
    assert keys == [("big", 0), ("big", 1), ("big", 2)]
    # wire payload identical to whole-array packing (fresh compressor)
    from mxnet_tpu.kvstore import GradientCompression

    ref = GradientCompression(0.5)
    expect = onp.asarray(ref.compress_packed("big", g))
    got = kv._compress_packed_bigarray("big2", jnp.asarray(g))
    onp.testing.assert_array_equal(got, expect)
    # and the pulled value decodes the sliced payload correctly
    out = mx.nd.zeros((20,))
    kv.pull("big", out=out)
    q, _ = __import__("mxnet_tpu").kvstore.quantize_2bit(
        jnp.asarray(g), 0.5)
    onp.testing.assert_allclose(out.asnumpy(), onp.asarray(q))
    # the slice step is PINNED per key: a mid-run bound change must
    # not re-slice a key whose residual layout already exists
    monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "1000000")
    kv.push("big", mx.nd.array(g))  # would crash on shape mismatch
    assert kv._comp_slice_step["big"] == 8
    # re-configuring compression discards every residual, so the pins
    # protect nothing: keys re-pin at the CURRENT bound
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    assert kv._comp_slice_step == {}
    kv.push("big", mx.nd.array(g))
    assert kv._comp_slice_step["big"] == 1000000


@pytest.mark.parametrize("dtype", ["float16", "bfloat16"])
def test_narrow_accumulate_survives_bucket_roundtrip(dtype):
    """kvstore narrow-dtype path: fp16/bf16 gradients quantize through
    an fp32 accumulator (kvstore.py _reduce/_widen), so K sub-threshold
    pushes accumulate to EXACTLY K * fp32(narrow(g)) — a narrow-dtype
    accumulator would have rounded the running sum."""
    kv = mx.kv.create("dist_sync")  # 1-worker group: local quantize
    kv.set_gradient_compression({"type": "2bit", "threshold": 1.0})
    g = onp.full((8,), 0.1, dtype)
    kv.init("w", mx.nd.zeros((8,), dtype=dtype))
    for k in range(3):
        kv.push("w", mx.nd.array(g, dtype=dtype))
    (resid,) = [v for v in kv._compression._residual.values()]
    assert resid.dtype == jnp.float32
    expect = 3 * onp.float32(onp.asarray(g.astype(dtype))[0])
    onp.testing.assert_allclose(onp.asarray(resid),
                                onp.full((8,), expect), rtol=1e-7)
    # store value keeps the narrow dtype (the widen round-trip)
    out = mx.nd.zeros((8,), dtype=dtype)
    kv.pull("w", out=out)
    assert str(out.dtype) in (dtype, f"<class 'jax.numpy.{dtype}'>") or \
        onp.dtype(out.asnumpy().dtype).itemsize <= 4


@_WIDTHS
def test_sharded_step_compression_residual_shard_local(widths):
    """In-step 2-bit compression: residual carried as fp32 shard-local
    state in its bucket's layout, error feedback converges training."""
    mesh = get_mesh((8,), ("data",))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step, p, s = make_train_step(
        _mlp_net(widths), loss_fn, optimizer="sgd", learning_rate=0.05,
        momentum=0.9, mesh=mesh, donate=False, optimizer_sharding="ps",
        bucket_bound=300,
        gradient_compression={"type": "2bit", "threshold": 0.05})
    rkeys = [k for k in s if k.startswith("_residual")]
    assert len(rkeys) == len(step.zero_plan)  # one per bucket
    assert len(rkeys) == (3 if widths == _MLP else 6)
    for rk, b in zip(rkeys, step.zero_plan):
        assert s[rk].dtype == jnp.float32
        assert s[rk].shape == b.shape
        assert s[rk].sharding.spec == jax.sharding.PartitionSpec("data")
    rng = onp.random.RandomState(0)
    X = jnp.asarray(rng.rand(32, 8).astype("float32"))
    y = jnp.asarray(rng.randint(0, 4, (32,)).astype("float32"))
    key = jax.random.key(0)
    losses = []
    for i in range(12):
        loss, p, s = step(p, s, X, y, key, float(i + 1))
        losses.append(float(loss))
    assert all(onp.isfinite(v) for v in losses)
    assert losses[-1] < losses[0]  # error feedback actually trains
    assert float(onp.abs(onp.asarray(s[rkeys[0]])).max()) > 0
