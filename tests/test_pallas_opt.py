"""Pallas fused-bucket optimizer kernels (ops/pallas_opt.py): parity
vs the jnp ``fused_bucket_update`` baseline in interpret mode on CPU,
the fused dynamic-loss-scale verdict, the ``fused_bucket_opt`` variant
plumbing through ``zero.bucket_shard_update`` (ZeRO step AND the
Module-side ShardedBucketUpdater), and winner persistence across
processes for every round-14 variant op.

Parity contract: sgd/sgd_mom are BIT-exact in fp32 (same expressions,
same order).  Adam is ulp-tight, not bit-exact, by construction of the
comparison: XLA fuses the jitted jnp baseline with FMA contraction
(jit-vs-eager of the SAME jnp adam expression already differs by 1-2
ulp on CPU), while interpret-mode Pallas executes op-by-op.  LARS is
allclose (segment-sum reduction order differs).
"""
import os
import subprocess
import sys

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import autotune as at
from mxnet_tpu.ops import pallas_opt as po
from mxnet_tpu.optimizer.optimizer import LARS, SGD, Adam, Signum
from mxnet_tpu.parallel import zero

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    d = str(tmp_path / "atcache")
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE_DIR", d)
    at.cache_clear()
    yield d
    at.cache_clear()


def _flat(n, seed=0, scale=1.0):
    return jnp.asarray(
        onp.random.RandomState(seed).randn(n).astype("float32") * scale)


def test_sgd_mom_bit_exact_fp32_and_finite_verdict():
    n = 1000  # NOT a lane multiple: exercises the (1, L) view + tail
    w, g, m = _flat(n, 0), _flat(n, 1), _flat(n, 2)
    opt = SGD(momentum=0.9, learning_rate=0.1, wd=1e-4)
    ref_w, (ref_m,) = opt.fused_bucket_update(w, g, (m,), 1.0)
    new_w, (new_m,), fin = po.bucket_update(
        opt, w, g, (m,), 1.0, with_finite=True, interpret=True)
    assert bool((ref_w == new_w).all())
    assert bool((ref_m == new_m).all())
    assert bool(fin) is True
    # one poisoned element flips the fused loss-scale verdict, exactly
    # like the jnp isfinite(g).all() check it replaces
    _, _, fin2 = po.bucket_update(
        opt, w, g.at[7].set(jnp.nan), (m,), 1.0, with_finite=True,
        interpret=True)
    assert bool(fin2) is False
    _, _, fin3 = po.bucket_update(
        opt, w, g.at[n - 1].set(jnp.inf), (m,), 1.0, with_finite=True,
        interpret=True)
    assert bool(fin3) is False


def test_sgd_momentum_zero_passes_state_through():
    n = 256  # lane multiple: exercises the (rows, 128) view
    w, g = _flat(n, 0), _flat(n, 1)
    opt = SGD(momentum=0.0, learning_rate=0.05, wd=0.0)
    ref_w, ref_state = opt.fused_bucket_update(w, g, (), 1.0)
    new_w, new_state, _ = po.bucket_update(opt, w, g, (), 1.0,
                                           interpret=True)
    assert bool((ref_w == new_w).all())
    assert new_state == ()


def test_sgd_prep_rescale_and_clip_parity():
    n = 640
    w, g, m = _flat(n, 0), _flat(n, 1, scale=4.0), _flat(n, 2)
    opt = SGD(momentum=0.9, learning_rate=0.1, wd=1e-3,
              rescale_grad=0.5, clip_gradient=1.0)
    ref_w, (ref_m,) = opt.fused_bucket_update(w, g, (m,), 1.0)
    new_w, (new_m,), _ = po.bucket_update(opt, w, g, (m,), 1.0,
                                          interpret=True)
    assert bool((ref_w == new_w).all())
    assert bool((ref_m == new_m).all())


def test_adam_ulp_tight_fp32():
    n = 1000
    w, g = _flat(n, 0), _flat(n, 1)
    m, v = _flat(n, 2), jnp.abs(_flat(n, 3))
    opt = Adam(learning_rate=0.01, wd=1e-4)
    ref_w, (rm, rv) = opt.fused_bucket_update(w, g, (m, v), 3.0)
    new_w, (nm, nv), fin = po.bucket_update(
        opt, w, g, (m, v), jnp.float32(3.0), with_finite=True,
        interpret=True)
    # XLA FMA-contracts the jitted baseline; interpret mode cannot —
    # the gap is 1-2 ulp, never more (see module docstring)
    assert float(jnp.abs(ref_w - new_w).max()) < 3e-6
    assert float(jnp.abs(rm - nm).max()) < 1e-6
    assert float(jnp.abs(rv - nv).max()) < 1e-6
    assert bool(fin) is True


def test_lars_allclose_with_segments():
    n = 1152
    w, g, m = _flat(n, 0), _flat(n, 1), _flat(n, 2)
    ids = onp.repeat(onp.arange(4, dtype="int32"), n // 4)
    opt = LARS(momentum=0.9, learning_rate=0.1, wd=1e-4)
    ref_w, (ref_m,) = opt.fused_bucket_update(
        w, g, (m,), 1.0, seg_ids=jnp.asarray(ids), num_segments=5)
    new_w, (new_m,), _ = po.bucket_update(
        opt, w, g, (m,), 1.0, seg=(ids, 5), with_finite=True,
        interpret=True)
    onp.testing.assert_allclose(onp.asarray(ref_w), onp.asarray(new_w),
                                rtol=1e-6, atol=1e-6)
    onp.testing.assert_allclose(onp.asarray(ref_m), onp.asarray(new_m),
                                rtol=1e-6, atol=1e-6)


def test_bf16_sgd_bucket_parity():
    n = 512
    rng = onp.random.RandomState(5)
    w = jnp.asarray(rng.randn(n), jnp.bfloat16)
    g = jnp.asarray(rng.randn(n), jnp.bfloat16)
    m = jnp.asarray(rng.randn(n), jnp.bfloat16)
    opt = SGD(momentum=0.9, learning_rate=0.1, wd=0.0)
    ref_w, (ref_m,) = opt.fused_bucket_update(w, g, (m,), 1.0)
    new_w, (new_m,), _ = po.bucket_update(opt, w, g, (m,), 1.0,
                                          interpret=True)
    assert new_w.dtype == jnp.bfloat16
    assert bool((ref_w == new_w).all())
    assert bool((ref_m == new_m).all())


def test_unsupported_rules_report_reasons():
    assert po.supported(SGD(momentum=0.9), "float32") is None
    assert po.supported(Adam(), "float32") is None
    assert "bf16" not in (po.supported(Adam(), "bfloat16") or "")
    assert po.supported(Adam(), "bfloat16") is not None
    assert po.supported(Signum(momentum=0.9), "float32") is not None
    assert po.supported(LARS(), "float32", nseg=500) is not None
    # bucket_update mirrors supported(): unsupported -> None, caller
    # keeps the jnp arm
    n = 256
    w, g, m = _flat(n, 0), _flat(n, 1), _flat(n, 2)
    assert po.bucket_update(Signum(momentum=0.9), w, g, (m,), 1.0,
                            interpret=True) is None


def test_bucket_shard_update_variant_plumbing(cache_dir):
    """pallas=True runs the kernel, pallas=False the jnp rule,
    pallas=None consults the fused_bucket_opt variant; want_finite
    returns the fused verdict on the kernel arm and None on jnp (the
    caller keeps its own bit-identical check)."""
    params = {"a": _flat(96, 0).reshape(12, 8), "b": _flat(40, 1)}
    plan = zero.plan_buckets(params, 1)
    (b,) = plan
    opt = SGD(momentum=0.9, learning_rate=0.1, wd=0.0)
    g = _flat(b.padded, 2)
    state = (jnp.zeros((b.padded,), jnp.float32),)

    w_sh, uw_j, us_j, fin_j = zero.bucket_shard_update(
        b, opt, params, g, state, 1.0, n_shards=1, idx=0, axis=None,
        pallas=False, want_finite=True)
    assert fin_j is None  # jnp arm: caller's own check stands
    _, uw_p, us_p, fin_p = zero.bucket_shard_update(
        b, opt, params, g, state, 1.0, n_shards=1, idx=0, axis=None,
        pallas=True, want_finite=True)
    assert bool(fin_p) == bool(jnp.isfinite(g).all())
    assert bool((uw_j == uw_p).all())
    assert bool((us_j[0] == us_p[0]).all())
    # pallas=None consults the registry: a force scope picks the arm
    with at.force(fused_bucket_opt=True):
        _, uw_c, _, fin_c = zero.bucket_shard_update(
            b, opt, params, g, state, 1.0, n_shards=1, idx=0,
            axis=None, want_finite=True)
    assert fin_c is not None
    assert bool((uw_c == uw_p).all())
    # an unsupported rule under pallas=True silently keeps jnp
    sgn = Signum(momentum=0.9, learning_rate=0.1)
    st = (jnp.zeros((b.padded,), jnp.float32),)
    _, uw_f, _, fin_f = zero.bucket_shard_update(
        b, sgn, params, g, st, 1.0, n_shards=1, idx=0, axis=None,
        pallas=True, want_finite=True)
    assert fin_f is None  # fell back: jnp arm, no fused verdict


@pytest.mark.parametrize("opt,n_state", [
    (SGD(momentum=0.9, learning_rate=0.1, wd=1e-4), 1),
    (Adam(learning_rate=0.01), 2),
    (LARS(momentum=0.9, learning_rate=0.1), 1),
])
def test_leaf_shaped_shard_declines_the_kernels(opt, n_state):
    """The kernels stream 1-D shards.  Rows of a leaf-shaped bucket
    are not flattened for them (that would lay the shard out anew each
    way): the arm reports itself infeasible there, counted with the
    reason, and the jnp rule runs — same numbers as pallas=False."""
    from mxnet_tpu.ops import kernel_target

    w = _flat(64 * 8, 0).reshape(64, 8)
    (b,) = zero.plan_buckets({"w": w}, 2, capacity=1)
    assert b.leaf
    g = _flat(32 * 8, 1).reshape(32, 8)     # shard 1 of 2: 32 rows
    state = tuple(jnp.zeros((32, 8), jnp.float32)
                  for _ in range(n_state))
    seg = zero.bucket_segments(b) if isinstance(opt, LARS) else None
    kw = dict(n_shards=2, idx=1, axis=None, seg=seg, want_finite=True)
    before = kernel_target.declined_counts().get("fused_bucket_opt", 0)
    w_sh, uw_p, us_p, fin_p = zero.bucket_shard_update(
        b, opt, {"w": w}, g, state, 1.0, pallas=True, **kw)
    assert kernel_target.declined_counts()["fused_bucket_opt"] == \
        before + 1
    assert fin_p is None                    # the caller's check stands
    assert w_sh.shape == uw_p.shape == (32, 8)
    onp.testing.assert_array_equal(onp.asarray(w_sh),
                                   onp.asarray(w[32:]))
    _, uw_j, us_j, _ = zero.bucket_shard_update(
        b, opt, {"w": w}, g, state, 1.0, pallas=False, **kw)
    assert bool((uw_p == uw_j).all())
    for a, c in zip(us_p, us_j):
        assert a.shape == (32, 8) and bool((a == c).all())
    assert po.bucket_update(opt, w_sh, g, state, 1.0,
                            interpret=True) is None


def test_sharded_updater_pallas_parity_and_key():
    """ShardedBucketUpdater with the kernel arm forced matches the jnp
    arm on a dp(4) CPU mesh (adam, two steps), and its variant cache
    key reflects the flat layout."""
    from jax.sharding import Mesh

    from mxnet_tpu import nd

    mesh = Mesh(onp.array(jax.devices()[:4]).reshape(4,), ("data",))
    rng = onp.random.RandomState(0)
    base_p = {f"p{i}": rng.randn(40 + i, 7).astype("float32")
              for i in range(3)}
    base_g = {n: rng.randn(*v.shape).astype("float32")
              for n, v in base_p.items()}
    results = {}
    for arm in ("0", "1"):
        os.environ["MXNET_PALLAS_OPT"] = arm
        try:
            p = {n: nd.array(v) for n, v in base_p.items()}
            g = {n: nd.array(v) for n, v in base_g.items()}
            upd = zero.ShardedBucketUpdater(
                Adam(learning_rate=0.01, wd=1e-4), mesh,
                {n: v._data for n, v in p.items()})
            assert upd._variant_key()[0] == (
                sum(b.padded for b in upd.plan),)
            for _ in range(2):
                upd.update_all([(n, g[n], p[n]) for n in p])
            assert upd._pallas is (arm == "1")
            results[arm] = {n: v.asnumpy() for n, v in p.items()}
        finally:
            os.environ.pop("MXNET_PALLAS_OPT", None)
    for n in results["0"]:
        onp.testing.assert_allclose(results["0"][n], results["1"][n],
                                    rtol=1e-6, atol=3e-6)


def test_ps_step_pallas_parity_with_dynamic_scaling(cache_dir):
    """make_train_step(optimizer_sharding='ps') with the kernel arm
    forced: 3 steps of adam + dynamic loss scaling on a dp(4) mesh
    match the jnp arm — incl. the loss-scale bookkeeping, whose
    finiteness verdict is the kernel-fused one on the pallas arm."""
    from jax.sharding import Mesh

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import make_train_step

    mesh = Mesh(onp.array(jax.devices()[:4]).reshape(4,), ("data",))
    x = jnp.asarray(onp.random.RandomState(0).rand(8, 8)
                    .astype("float32"))
    y = jnp.asarray(onp.random.RandomState(1).randint(0, 4, (8,))
                    .astype("float32"))
    key = jax.random.key(0)
    # ONE net for both arms (a rebuild would re-draw initializers
    # under fresh layer names); make_train_step snapshots its params
    mx.random.seed(11)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(init=mx.init.Xavier(), ctx=mx.cpu())
    net(mx.nd.zeros((2, 8)))
    outs = {}
    for arm in ("0", "1"):
        os.environ["MXNET_PALLAS_OPT"] = arm
        try:
            step, params, opt_state = make_train_step(
                net, gluon.loss.SoftmaxCrossEntropyLoss(),
                optimizer="adam", learning_rate=0.01, mesh=mesh,
                optimizer_sharding="ps", loss_scale="dynamic",
                donate=False)
            loss = None
            for t in range(3):
                loss, params, opt_state = step(params, opt_state, x, y,
                                               key, float(t + 1))
            outs[arm] = (float(loss),
                         {n: onp.asarray(v) for n, v in params.items()},
                         float(opt_state["_loss_scale"][0]),
                         int(opt_state["_loss_scale"][1]))
        finally:
            os.environ.pop("MXNET_PALLAS_OPT", None)
    assert outs["0"][2] == outs["1"][2]  # scale bookkeeping identical
    assert outs["0"][3] == outs["1"][3]
    assert abs(outs["0"][0] - outs["1"][0]) < 1e-5
    for n in outs["0"][1]:
        onp.testing.assert_allclose(outs["0"][1][n], outs["1"][1][n],
                                    rtol=1e-5, atol=3e-6)


def test_registry_ops_registered():
    from mxnet_tpu.ops.registry import get_op

    n = 512
    w, g, m = _flat(n, 0), _flat(n, 1), _flat(n, 2)
    op = get_op("_pallas_bucket_sgd_mom_update")
    new_w, new_m = op.fn(w, g, m, lr=0.1, momentum=0.9)
    ref_w, (ref_m,) = SGD(momentum=0.9, learning_rate=0.1,
                          wd=0.0).fused_bucket_update(w, g, (m,), 1.0)
    assert bool((ref_w == new_w).all())
    assert get_op("_pallas_bucket_adam_update") is not None
    assert get_op("_pallas_bucket_lars_update") is not None


@pytest.mark.parametrize("op,winner", [
    ("fused_bucket_opt", "pallas"),
    ("flash_attention", "pallas_pad"),
    ("dtype_ladder", "bf16"),
    ("pallas_bnreluconv", "stock"),
])
def test_round14_winners_persist_across_processes(cache_dir, op,
                                                  winner):
    """Every round-14 variant op's winner reloads from autotune.json
    in a DIFFERENT process without re-timing (the shared algo-registry
    contract the acceptance gate names)."""
    assert winner in at.VARIANT_OPS[op]
    at.record(op, (3, 9, 9, 3), "float32", winner=winner,
              timings={k: 1.0 for k in at.VARIANT_OPS[op]},
              platform="cpu", mesh="none")
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from mxnet_tpu import autotune as at\n"
        "w = at.lookup(%r, (3, 9, 9, 3), 'float32',\n"
        "              platform='cpu', mesh='none')\n"
        "assert w == %r, w\n"
        "with at.program_scope((3, 9, 9, 3), 'float32',\n"
        "                      platform='cpu', mesh='none'):\n"
        "    c = at.variant_choice(%r)\n"
        "assert c == at.VARIANT_OPS[%r][%r], c\n"
        "print('child-ok')\n" % (_REPO, op, winner, op, op, winner)
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "child-ok" in out.stdout


def test_env_override_parsers(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS_OPT", "1")
    assert at.variant_choice("fused_bucket_opt") is True
    monkeypatch.setenv("MXNET_PALLAS_OPT", "0")
    assert at.variant_choice("fused_bucket_opt") is False
    monkeypatch.setenv("MXNET_FLASH_ATTENTION", "pallas_pad")
    assert at.variant_choice("flash_attention") == "pallas_pad"
    monkeypatch.setenv("MXNET_FLASH_ATTENTION", "0")
    assert at.variant_choice("flash_attention") == "naive"
    monkeypatch.setenv("MXNET_DTYPE_LADDER", "bf16")
    assert at.variant_choice("dtype_ladder") == "bf16"
    assert at.dtype_ladder_armed() is True
    monkeypatch.setenv("MXNET_DTYPE_LADDER", "1")
    # armed, but no hand override: the cached winner decides
    assert at.variant_choice("dtype_ladder") is None
    assert at.dtype_ladder_armed() is True
    monkeypatch.setenv("MXNET_DTYPE_LADDER", "0")
    assert at.dtype_ladder_armed() is False
    monkeypatch.setenv("MXNET_BNRELUCONV_VARIANT", "stock")
    assert at.variant_choice("pallas_bnreluconv") == "stock"
