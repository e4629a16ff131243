"""ZeRO stage ladder (stages 1/2/3 over one bucket plan): the
bit-identity drill and the structural acceptance gates.

``MXNET_ZERO_STAGE`` / ``make_train_step(zero_stage=...)`` select how
much of the sharded-server exchange shards:

* stage 1 — per-bucket all-reduce, grads replicated, optimizer state
  sharded (classic ZeRO-1);
* stage 2 — per-bucket reduce-scatter (the historic ``ps`` default
  program, bit-for-bit);
* stage 3 — parameters live as flat bucket shards; the forward
  all-gathers each bucket (prefetch, no inter-bucket dependency), the
  backward's reduce-scatters fall out of differentiating through the
  tiled gathers, and nothing gathers back.

Acceptance invariants from the issue:

* the three stages are BIT-IDENTICAL over >= 6 steps for sgd,
  sgd-momentum, adam and lars where every bucket is flat (stage 3's
  AD-transposed reduce-scatter is the same psum_scatter stage 2 emits
  explicitly); where a leaf-shaped bucket rides the ring, stages 1 and
  2 are, and stage 3 stands within the order of the sum (``_SUM_ORDER``);
* stage-3 per-chip param bytes ~ total/N, and its RS+AG exchange
  bytes stay within 1.05x the analytic plan minimum;
* the compiled stage-3 forward shows one all-gather per bucket with
  compute interleaved between gathers (``overlap_report``);
* stage-3 checkpoints stamp ``sharding="zero3"`` + a stage-salted
  plan fingerprint, so a stage-2 world refuses them (reshard), and
  the named round-trip through ``stage3_save_params`` /
  ``stage3_load_params`` is bit-exact.
"""

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import get_mesh, make_train_step, zero
from mxnet_tpu.resilience.elastic import reshard_verdict, topology_block


#: widths of the seeded MLP: the historic one stays flat at 8 shards
#: (32, 16 and 4 rows); the leafy one has leaves of 64 and 128 rows,
#: which sit alone over bucket_bound=300 and are exchanged leaf-shaped
_MLP, _LEAFY = (32, 16, 4), (64, 128, 4)


#: A leaf-shaped bucket's gradient is summed hop by hop in the ring's
#: order at stages 1 and 2; stage 3 (its gather's transpose) and a flat
#: bucket are summed by the native collective in its own.  The same
#: eight float32 terms in another order round differently, so after six
#: steps a weight of the leafy net stands within this bound, not on the
#: bits: 64 roundings (2**-23 each) of the weight's own size, and as
#: many of 0.01 for the weights near nought.  Adam divides a gradient by
#: its own size, which magnifies the rounding of one near nought: a
#: thousandth of a step of lr = 0.1 there.
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


def _run_stage(optimizer, stage, n_steps=6, momentum=0.9, widths=_MLP,
               **kw):
    """Train the seeded MLP for ``n_steps`` under the given ZeRO stage
    (None = the caller's kw decide); returns (loss, step_fn, params,
    opt_state) with params still in the stage's live layout."""
    mesh = get_mesh((8,), ("data",))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    if stage is not None:
        kw.update(optimizer_sharding="ps", zero_stage=stage)
    step, p, s = make_train_step(
        _mlp_net(widths), loss_fn, optimizer=optimizer, learning_rate=0.1,
        momentum=momentum, mesh=mesh, donate=False, autotune=False,
        bucket_bound=300, **kw)
    rng = onp.random.RandomState(0)
    X = jnp.asarray(rng.rand(32, 8).astype("float32"))
    y = jnp.asarray(rng.randint(0, 4, (32,)).astype("float32"))
    key = jax.random.key(0)
    loss = None
    for i in range(n_steps):
        loss, p, s = step(p, s, X, y, key, float(i + 1))
    return float(loss), step, p, s


def _named(step, p):
    """Named host params regardless of live layout (stage 3 gathers
    its buckets back first); block auto-prefix differs between
    builds, align by suffix."""
    if getattr(step, "zero_stage", None) == 3:
        p = zero.gather_stage3_params(
            step.zero_plan, {k: onp.asarray(v) for k, v in p.items()})
    return {k.split("_", 1)[-1]: onp.asarray(v) for k, v in p.items()}


# ------------------------------------------------------ bit-identity
@pytest.mark.parametrize("widths", [_MLP, _LEAFY], ids=["mlp", "leafy"])
@pytest.mark.parametrize("optimizer,momentum", [
    ("sgd", 0.0),   # plain sgd
    ("sgd", 0.9),   # sgd + momentum slot
    ("adam", 0.9),  # two slots + bias correction
    ("lars", 0.9),  # segment-wise trust ratios over the bucket
])
def test_stages_bit_identical(optimizer, momentum, widths, monkeypatch):
    finals = {}
    losses = {}
    for stage in (1, 2, 3):
        loss, step, p, _ = _run_stage(optimizer, stage,
                                      momentum=momentum, widths=widths)
        losses[stage] = loss
        finals[stage] = _named(step, p)
        layouts = [lay for _, lay, *_ in step.zero_layout]
        assert layouts.count("leaf") == (2 if widths == _LEAFY else 0)
        if stage == 3:
            # the parameters themselves live by bucket, each in its
            # bucket's shape, rows over the data axis
            for (bk, *_), b in zip(step.zero_layout, step.zero_plan):
                assert p[bk].shape == b.shape
                assert p[bk].sharding.spec == \
                    jax.sharding.PartitionSpec("data")
        assert [how for *_, how in step.zero_layout] == [
            "ring" if lay == "leaf" and stage < 3 else "native"
            for lay in layouts]
    # stages 1 and 2 take the same hops round the ring for a leaf-shaped
    # bucket and the same native collective for a flat one: the bits
    assert losses[1] == losses[2]
    # stage 3 sums by its gather's transpose, the native collective:
    # the bits where every bucket is flat, the sum's order apart else
    ring = widths == _LEAFY
    _assert_same(losses[3], losses[2], not ring, "loss, stage 3 vs 2",
                 optimizer)
    for stage in (1, 3):
        assert set(finals[stage]) == set(finals[2])
        for k in finals[2]:
            _assert_same(finals[stage][k], finals[2][k],
                         stage == 1 or not ring,
                         f"stage {stage} vs 2 at {k}", optimizer)
    if ring:
        # the same ladder over flat buckets (every bucket's layout
        # before): one algorithm; only the order of the sum differs
        monkeypatch.setattr(zero, "_leaf_shaped", lambda *a: False)
        loss, step, p, _ = _run_stage(optimizer, 2, momentum=momentum,
                                      widths=widths)
        assert {lay for _, lay, *_ in step.zero_layout} == {"flat"}
        _assert_same(loss, losses[2], False, "loss, flat vs leaf",
                     optimizer)
        flat = _named(step, p)
        for k in finals[2]:
            _assert_same(flat[k], finals[2][k], False,
                         f"flat vs leaf {k}", optimizer)
        # ... and flat buckets at stage 2 ARE stage 3's sum: the bits
        _assert_same(loss, losses[3], True, "loss, flat vs stage 3")
        for k in finals[3]:
            _assert_same(flat[k], finals[3][k], True,
                         f"flat vs stage 3 {k}")


def test_stage2_is_the_unset_default_program():
    # zero_stage unset under ps_mode must BE stage 2 (the historic
    # program): same variant key, same fingerprint, same collectives
    _, step_d, p_d, _ = _run_stage("sgd", None, n_steps=1,
                                   optimizer_sharding="ps")
    _, step_2, p_2, _ = _run_stage("sgd", 2, n_steps=1)
    assert step_d.zero_stage == 2
    plan = step_d.zero_plan
    assert zero.flat_variant_key(plan) == \
        zero.flat_variant_key(plan, stage=2)
    assert zero.plan_fingerprint(plan, 8) == \
        zero.plan_fingerprint(plan, 8, stage=2)
    n_d, n_2 = _named(step_d, p_d), _named(step_2, p_2)
    for k in n_d:
        onp.testing.assert_array_equal(n_d[k], n_2[k], err_msg=k)


# ------------------------------------------- structure: wire + memory
def _stage3_compiled():
    mesh = get_mesh((8,), ("data",))
    step, p, s = make_train_step(
        _mlp_net(), gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer="sgd", learning_rate=0.1, momentum=0.9, mesh=mesh,
        donate=False, autotune=False, bucket_bound=300,
        optimizer_sharding="ps", zero_stage=3)
    rng = onp.random.RandomState(0)
    X = jnp.asarray(rng.rand(32, 8).astype("float32"))
    y = jnp.asarray(rng.randint(0, 4, (32,)).astype("float32"))
    hlo = step.lower(p, s, X, y, jax.random.key(0),
                     1.0).compile().as_text()
    return step, p, s, hlo


def test_stage3_exchange_bytes_within_analytic_budget():
    step, _, _, hlo = _stage3_compiled()
    plan = step.zero_plan
    assert len(plan) >= 2  # bucket_bound=300 splits the MLP
    acc = zero.collective_bytes(hlo)
    floor = zero.analytic_exchange_bytes(plan, 8, 3)
    measured = acc["bytes"]["reduce-scatter"] + \
        acc["bytes"]["all-gather"]
    analytic = floor["reduce-scatter"] + floor["all-gather"]
    assert analytic > 0
    # the issue's collectives-bytes budget: within 5% of the analytic
    # minimum (and never below it — that would mean a bucket is not
    # being exchanged at all)
    assert analytic <= measured <= 1.05 * analytic
    # one RS and one AG per bucket, no replicated-param gather-back
    assert acc["counts"]["reduce-scatter"] == len(plan)
    assert acc["counts"]["all-gather"] == len(plan)


def test_stage3_per_chip_param_bytes_one_nth():
    step, p, _, _ = _stage3_compiled()
    plan = step.zero_plan
    total_padded = sum(
        b.padded * onp.dtype(b.dtype).itemsize for b in plan)
    per_chip = sum(v.addressable_shards[0].data.nbytes
                   for v in p.values())
    assert per_chip * 8 == total_padded
    for v in p.values():
        assert v.sharding.spec == jax.sharding.PartitionSpec("data")


@pytest.mark.xfail(
    strict=True,
    reason="jax 0.9.0's XLA:CPU schedules every stage-3 bucket gather "
           "back to back at the program head (synchronous all-gathers, no "
           "latency-hiding scheduler), so the compiled schedule shows no "
           "compute between bucket k's gather and bucket k+1's.  The "
           "prefetch contract below is NOT met on this mesh; whether the "
           "chip's scheduler hides the gathers is a device-trace question "
           "(ROADMAP S6).  strict: the day the schedule interleaves again "
           "this mark must go.")
def test_stage3_overlap_report_and_trace():
    step, _, _, hlo = _stage3_compiled()
    plan = step.zero_plan
    rep = zero.overlap_report(hlo, plan, 8)
    assert len(rep["gathers"]) == len(plan)
    # the prefetch contract: compute interleaves between bucket
    # gathers instead of all gathers stacking at the program head
    assert rep["overlapped"]


def test_stage3_overlap_reader():
    """What the reader itself owes, whatever the schedule says: every
    bucket's gather found, in issue order, the consumers' compute after
    the last of them."""
    step, _, _, hlo = _stage3_compiled()
    plan = step.zero_plan
    rep = zero.overlap_report(hlo, plan, 8)
    assert [g["bucket"] for g in rep["gathers"]] == list(range(len(plan)))
    pos = [g["pos"] for g in rep["gathers"]]
    assert pos == sorted(pos)
    assert rep["gathers"][-1]["compute_between"] > 0


# ---------------------------------------- fingerprints + checkpoints
def test_stage3_fingerprint_and_topology_refuse_stage2():
    _, step, _, _ = _run_stage("sgd", 3, n_steps=1)
    plan = step.zero_plan
    mesh = get_mesh((8,), ("data",))
    # the stage salt: a stage-3 plan never fingerprints like stage 2
    assert zero.plan_fingerprint(plan, 8, 3) != \
        zero.plan_fingerprint(plan, 8, 2)
    topo2 = topology_block(mesh=mesh, sharding="ps", plan=plan)
    topo3 = topology_block(mesh=mesh, sharding="zero3", plan=plan,
                           zero_stage=3)
    assert topo3["zero_stage"] == 3
    verdict = reshard_verdict(topo3, topo2)
    assert verdict["reshard"]
    # same stage-3 world on both sides: provably no reshard
    assert not reshard_verdict(topo3, dict(topo3))["reshard"]


def test_stage3_param_checkpoint_roundtrip_bit_exact():
    from mxnet_tpu.resilience.checkpoint import (stage3_load_params,
                                                 stage3_save_params)

    _, step, p, _ = _run_stage("adam", 3, n_steps=3)
    plan = step.zero_plan
    mesh = get_mesh((8,), ("data",))
    named = stage3_save_params(plan, p)  # host-gathered legacy layout
    assert set(named) == {n for b in plan for n in b.names}
    back = stage3_load_params(plan, named, mesh=mesh)
    assert set(back) == set(p)
    for bk in p:
        onp.testing.assert_array_equal(onp.asarray(back[bk]),
                                       onp.asarray(p[bk]), err_msg=bk)
        assert back[bk].sharding.spec == \
            jax.sharding.PartitionSpec("data")


@pytest.mark.parametrize("stage", [2, 3])
def test_state_saved_flat_is_taken_by_the_leaf_shaped_step(stage,
                                                           monkeypatch):
    """An ``opt_state`` (and, at stage 3, the params) saved by bucket
    before leaf-shaped buckets holds every ``_bucket<i>`` 1-D.  Its
    content is the leaf's, row-major, so the new step reshapes it and
    goes on (bit for bit at stage 3; at stage 2 the flat run summed its
    gradients in the native collective's order and this one in the
    ring's: ``_SUM_ORDER``); anything else is refused with the reason."""
    exact = stage == 3
    kw = dict(momentum=0.9, widths=_LEAFY)
    _, step, p3, s3 = _run_stage("adam", stage, n_steps=3, **kw)
    _, _, p6, s6 = _run_stage("adam", stage, n_steps=6, **kw)
    with monkeypatch.context() as m:  # the flat run: same 3 steps
        m.setattr(zero, "_leaf_shaped", lambda *a: False)
        _, fstep, fp3, fs3 = _run_stage("adam", stage, n_steps=3, **kw)
    assert {lay for _, lay, *_ in fstep.zero_layout} == {"flat"}
    leafy = [bk for bk, lay, *_ in step.zero_layout if lay == "leaf"]
    assert len(leafy) == 2
    for bk in leafy:
        assert fs3[bk][0].ndim == 1 and s3[bk][0].ndim == 2
        _assert_same(onp.asarray(fs3[bk][0]).reshape(s3[bk][0].shape),
                     onp.asarray(s3[bk][0]), exact, bk, "adam")
    # ... three more steps of the NEW step from the flat-saved trees
    rng = onp.random.RandomState(0)
    X = jnp.asarray(rng.rand(32, 8).astype("float32"))
    y = jnp.asarray(rng.randint(0, 4, (32,)).astype("float32"))
    p, s = fp3, fs3
    if stage == 2:
        # named params: the block's auto-prefix differs between builds
        mine = {k.split("_", 1)[-1]: k for k in p3}
        p = {mine[k.split("_", 1)[-1]]: v for k, v in fp3.items()}
    for i in range(3, 6):
        _, p, s = step(p, s, X, y, jax.random.key(0), float(i + 1))
    for bk in s6:
        for a, b in zip(jax.tree_util.tree_leaves(s[bk]),
                        jax.tree_util.tree_leaves(s6[bk])):
            _assert_same(onp.asarray(a), onp.asarray(b), exact, bk,
                         "adam")
    want = _named(step, p6)
    for k, v in _named(step, p).items():
        _assert_same(v, want[k], exact, k, "adam")
    # a bucket laid out under another plan is refused, not mis-laid
    bad = dict(s3)
    bad[leafy[0]] = tuple(a[:-8] if a.ndim else a for a in s3[leafy[0]])
    with pytest.raises(MXNetError, match="another bucket plan"):
        step(p3, bad, X, y, jax.random.key(0), 4.0)
    # and what already is in the plan's layout is handed through as is
    assert zero.adopt_layout(step.zero_plan, s3) is s3


# ------------------------------------------------------- env plumbing
def test_env_knob_selects_stage_and_rejects_unknown(monkeypatch):
    monkeypatch.setenv("MXNET_ZERO_STAGE", "3")
    _, step, p, _ = _run_stage("sgd", None, n_steps=1)
    assert step.zero_stage == 3
    assert set(p) == set(zero.stage3_param_keys(step.zero_plan))
    monkeypatch.setenv("MXNET_ZERO_STAGE", "7")
    with pytest.raises(MXNetError):
        _run_stage("sgd", None, n_steps=1)


def test_env_knob_overrides_caller_stage(monkeypatch):
    monkeypatch.setenv("MXNET_ZERO_STAGE", "1")
    _, step, p, _ = _run_stage("sgd", 3, n_steps=1)
    assert step.zero_stage == 1
    # stage 1 keeps the named replicated layout
    assert not any(k.startswith("_bucket") for k in p)
