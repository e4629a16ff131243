"""fp8 end-to-end (round 19): the e4m3/e5m2 training rung in the dtype
ladder and the fp8 arm in the quantized-inference race.

Training side: delayed-scaling recurrence units, the qdq
straight-through pair, amax histories updated in-graph, unarmed builds
HLO bit-identical to round 18, e4m3 overflow triggering scale backoff
without corrupting opt_state, and fp8-vs-bf16 loss parity on a smoke
MLP.  Inference side: fp8-pinned forward agreement vs fp32, the fp8
``.mxje`` artifact identified by ``param_dtypes`` without
deserialization, and the amp-lists/ladder eligibility agreement.
Collected by tier-1 and by ``ci fp8_smoke``.
"""
import os
import subprocess
import sys

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autotune as at
from mxnet_tpu import gluon, nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import pallas_opt as po
from mxnet_tpu.parallel import make_train_step

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    d = str(tmp_path / "atcache")
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE_DIR", d)
    at.cache_clear()
    yield d
    at.cache_clear()


# ------------------------------------------------ delayed-scaling units
def test_delayed_scale_recurrence():
    """scale = fmax / (2 * max(history)); the history is a rolling
    window; a non-finite amax writes 2*max(prev, 1), halving the next
    scale (the loss-scale backoff shape)."""
    hist = jnp.zeros((4,), jnp.float32)
    hist, scale = po.fp8_delayed_scale(hist, jnp.float32(2.0))
    assert float(hist[-1]) == 2.0
    assert float(scale) == pytest.approx(448.0 / (2.0 * 2.0))
    # a smaller amax does NOT raise the scale while 2.0 is in-window
    hist, scale = po.fp8_delayed_scale(hist, jnp.float32(0.5))
    assert float(scale) == pytest.approx(448.0 / (2.0 * 2.0))
    # once 2.0 rolls out of the window the scale re-expands
    for _ in range(3):
        hist, scale = po.fp8_delayed_scale(hist, jnp.float32(0.5))
    assert float(scale) == pytest.approx(448.0 / (2.0 * 0.5))
    # overflow: the non-finite amax is replaced by 2*max(prev, 1)
    hist, scale = po.fp8_delayed_scale(hist, jnp.float32(onp.inf))
    assert bool(jnp.isfinite(hist).all())
    assert float(hist[-1]) == pytest.approx(2.0 * 1.0)
    assert float(scale) == pytest.approx(448.0 / (2.0 * 2.0))
    # e5m2 (gradients) uses its own fmax
    h2, s2 = po.fp8_delayed_scale(jnp.zeros((2,), jnp.float32),
                                  jnp.float32(1.0), fmax=po.E5M2_MAX)
    assert float(s2) == pytest.approx(po.E5M2_MAX / 2.0)


def test_fp8_qdq_snaps_and_straight_through():
    """The fwd snaps onto the e4m3 grid at the given scale (clipping
    at ±448 BEFORE the cast — e4m3fn has no inf), the bwd passes the
    gradient through snapped to the e5m2 grid, and the scales get
    zero gradient."""
    v = jnp.asarray([1.0, 2.5, 300.0, 500.0, -500.0], jnp.float32)
    out = po.fp8_qdq(v, jnp.float32(1.0), jnp.float32(1.0))
    assert bool(jnp.isfinite(out).all())  # 500 clipped, not NaN
    onp.testing.assert_allclose(
        onp.asarray(out), [1.0, 2.5, 288.0, 448.0, -448.0])

    def f(v, s, g):
        return jnp.sum(po.fp8_qdq(v, s, g) * 2.0)

    gv, gs, gg = jax.grad(f, argnums=(0, 1, 2))(
        v, jnp.float32(1.0), jnp.float32(1.0))
    # straight-through: the incoming grad (all 2.0) snapped to e5m2
    onp.testing.assert_allclose(onp.asarray(gv), 2.0)
    assert float(gs) == 0.0 and float(gg) == 0.0


def test_scale_bookkeeping_shared_with_loss_scaler():
    """The loss-scale verdict helper lives in pallas_opt beside
    fp8_delayed_scale (one module, so the two backoff rules cannot
    drift) and the train step calls it, at its one bookkeeping site."""
    import inspect

    from mxnet_tpu.parallel import train_step

    # the step body binds the dynamic-loss-scale verdict to the
    # pallas_opt helper rather than an inline copy
    assert inspect.getsource(train_step).count(
        "_po.scale_bookkeeping(") == 1
    s, g = po.scale_bookkeeping(jnp.bool_(False), jnp.float32(8.0),
                                jnp.int32(5))
    assert float(s) == 4.0 and int(g) == 0  # overflow halves, resets
    s, g = po.scale_bookkeeping(jnp.bool_(True), jnp.float32(8.0),
                                jnp.int32(1999))
    assert float(s) == 16.0 and int(g) == 0  # interval up: doubles


# ------------------------------------------------- the training rung
def _mlp_step(monkeypatch, ladder, **kw):
    monkeypatch.setenv("MXNET_DTYPE_LADDER", ladder)
    net = nn.HybridSequential(prefix="fp8t_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=6,
                         prefix="d0_"),
                nn.Dense(3, in_units=16, prefix="d1_"))
    net.initialize(init=mx.init.Xavier(rnd_type="gaussian"))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    return make_train_step(net, loss_fn, optimizer="sgd",
                           learning_rate=0.1, donate=False, **kw)


def _data(seed=7):
    rng = onp.random.RandomState(seed)
    x = jnp.asarray(rng.rand(8, 6).astype("float32"))
    y = jnp.asarray(rng.randint(0, 3, (8,)).astype("float32"))
    return x, y


def test_unarmed_build_is_bit_identical_and_carries_no_state(
        monkeypatch, cache_dir):
    """The acceptance contract: a build that did not arm the ladder
    lowers to EXACTLY the round-18 HLO — no fp8 state, no qdq, not
    one instruction different — and arming changes both."""
    mx.random.seed(3)

    def build(ladder):
        if ladder is None:
            monkeypatch.delenv("MXNET_DTYPE_LADDER", raising=False)
        else:
            monkeypatch.setenv("MXNET_DTYPE_LADDER", ladder)
        # fixed prefix: the global gluon name counter must not leak
        # layer counts into the HLO text this test compares
        net = nn.Dense(8, in_units=6, prefix="dense0_")
        net.initialize()
        step, p, o = make_train_step(net, gluon.loss.L2Loss(),
                                     optimizer="sgd",
                                     learning_rate=0.1, donate=False)
        x = jnp.ones((4, 6), "float32")
        y = jnp.ones((4, 8), "float32")
        hlo = jax.jit(step).lower(p, o, x, y, jax.random.key(0),
                                  1.0).as_text()
        return hlo, o

    hlo_off, o_off = build(None)
    hlo_fp8, o_fp8 = build("fp8")
    hlo_off2, o_off2 = build(None)
    assert hlo_off == hlo_off2
    assert "_fp8" not in o_off and "_fp8" not in o_off2
    assert hlo_fp8 != hlo_off
    assert "_fp8" in o_fp8
    assert set(o_fp8["_fp8"]) == {"x", "g", "w"}
    assert list(o_fp8["_fp8"]["w"]) == ["dense0_weight"]


def test_fp8_pin_trains_with_in_graph_amax(monkeypatch, cache_dir):
    """MXNET_DTYPE_LADDER=fp8 pins the rung: the loss decreases, the
    amax histories update inside the jitted step (no host sync), and
    the scales follow the delayed recipe."""
    mx.random.seed(11)
    step, p, o = _mlp_step(monkeypatch, "fp8")
    assert "_fp8" in o
    assert set(o["_fp8"]["w"]) == {"fp8t_d0_weight", "fp8t_d1_weight"}
    x, y = _data()
    losses = []
    key = jax.random.key(0)
    for _ in range(8):
        loss, p, o = step(p, o, x, y, key, 1.0)
        losses.append(float(loss))
    assert all(onp.isfinite(losses))
    assert losses[-1] < losses[0]
    xs, xh = o["_fp8"]["x"]
    # the history carries the real input amax and the scale is
    # fmax / (2 * max(hist)) — computed in-graph across 8 steps
    assert float(jnp.max(xh)) == pytest.approx(float(jnp.abs(x).max()))
    assert float(xs) == pytest.approx(
        448.0 / (2.0 * float(jnp.max(xh))), rel=1e-5)
    gs, gh = o["_fp8"]["g"]
    assert float(jnp.max(gh)) > 0 and float(gs) > 0


def test_overflow_backoff_without_corrupting_opt_state(monkeypatch,
                                                       cache_dir):
    """An e4m3-overflowing input (and then a non-finite one) drives
    the x scale down via the history WITHOUT poisoning params or the
    histories themselves — the overflow observation IS the backoff."""
    mx.random.seed(11)
    step, p, o = _mlp_step(monkeypatch, "fp8")
    x, y = _data()
    key = jax.random.key(0)
    loss, p, o = step(p, o, x, y, key, 1.0)
    scale_before = float(o["_fp8"]["x"][0])
    # amax 1e9 >> 448: the next scale collapses to fmax/(2e9)
    xb = x.at[0, 0].set(1e9)
    loss, p, o = step(p, o, xb, y, key, 1.0)
    assert float(o["_fp8"]["x"][0]) == pytest.approx(448.0 / 2e9,
                                                     rel=1e-5)
    assert float(o["_fp8"]["x"][0]) < scale_before
    # a non-finite amax halves again and the history stays finite
    xinf = x.at[0, 0].set(onp.inf)
    loss, p, o = step(p, o, xinf, y, key, 1.0)
    assert bool(jnp.isfinite(o["_fp8"]["x"][1]).all())
    assert float(o["_fp8"]["x"][0]) == pytest.approx(448.0 / 4e9,
                                                     rel=1e-5)
    for n in ("fp8t_d0_weight", "fp8t_d1_weight"):
        assert bool(jnp.isfinite(p[n]).all())
    # recovery: the spike rolls out of the (default 16) window
    for _ in range(20):
        loss, p, o = step(p, o, x, y, key, 1.0)
    assert float(o["_fp8"]["x"][0]) == pytest.approx(
        448.0 / (2.0 * float(jnp.abs(x).max())), rel=1e-5)


def test_amax_history_length_knob(monkeypatch, cache_dir):
    monkeypatch.setenv("MXNET_FP8_AMAX_HISTORY", "4")
    step, p, o = _mlp_step(monkeypatch, "fp8")
    assert o["_fp8"]["x"][1].shape == (4,)
    assert o["_fp8"]["g"][1].shape == (4,)


def test_loss_parity_fp8_vs_bf16(monkeypatch, cache_dir):
    """The documented tolerance: over >= 6 steps on the smoke MLP the
    pinned-fp8 loss tracks the pinned-bf16 loss within 10% relative
    at every step (e4m3 holds ~2 significant digits, so the first
    step's forward carries the largest quantization offset — measured
    ~6% here — and the descent path is the same)."""

    mx.random.seed(23)
    net = nn.HybridSequential(prefix="fp8p_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=6,
                         prefix="d0_"),
                nn.Dense(3, in_units=16, prefix="d1_"))
    net.initialize(init=mx.init.Xavier(rnd_type="gaussian"))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def run(ladder):
        # ONE net: both rungs descend from the identical initial
        # params (training is functional — the block is not mutated)
        monkeypatch.setenv("MXNET_DTYPE_LADDER", ladder)
        step, p, o = make_train_step(net, loss_fn, optimizer="sgd",
                                     learning_rate=0.1, donate=False)
        x, y = _data(seed=23)
        key = jax.random.key(1)
        out = []
        for _ in range(6):
            loss, p, o = step(p, o, x, y, key, 1.0)
            out.append(float(loss))
        return onp.asarray(out)

    l_bf16 = run("bf16")
    l_fp8 = run("fp8")
    assert onp.isfinite(l_fp8).all()
    assert l_fp8[-1] < l_fp8[0]
    onp.testing.assert_allclose(l_fp8, l_bf16, rtol=0.10)


def test_three_rung_race_and_cross_process_reload(monkeypatch,
                                                  cache_dir):
    """MXNET_DTYPE_LADDER=fp32,bf16,fp8 races all three rungs in-step;
    the winner persists in autotune.json and a DIFFERENT process with
    the same roster reloads it without re-timing (the subprocess
    pattern of test_autotune)."""
    mx.random.seed(5)
    step, p, o = _mlp_step(monkeypatch, "fp32,bf16,fp8",
                           sample_data=_data())
    rep = at.last_report()
    assert set(rep["dtype_ladder"]["timings"]) == {"fp32", "bf16",
                                                   "fp8"}
    winner = rep["dtype_ladder"]["winner"]
    assert winner in ("fp32", "bf16", "fp8")
    x, y = _data()
    loss, p, o = step(p, o, x, y, jax.random.key(0), 1.0)
    assert onp.isfinite(float(loss))

    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import numpy as onp\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import autotune as at, gluon\n"
        "from mxnet_tpu.gluon import nn\n"
        "from mxnet_tpu.parallel import make_train_step\n"
        "import jax.numpy as jnp\n"
        "mx.random.seed(5)\n"
        "net = nn.HybridSequential(prefix='fp8t_')\n"
        "with net.name_scope():\n"
        "    net.add(nn.Dense(16, activation='relu', in_units=6,\n"
        "                     prefix='d0_'),\n"
        "            nn.Dense(3, in_units=16, prefix='d1_'))\n"
        "net.initialize(init=mx.init.Xavier(rnd_type='gaussian'))\n"
        "rng = onp.random.RandomState(7)\n"
        "x = jnp.asarray(rng.rand(8, 6).astype('float32'))\n"
        "y = jnp.asarray(rng.randint(0, 3, (8,)).astype('float32'))\n"
        "make_train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(),\n"
        "                optimizer='sgd', learning_rate=0.1,\n"
        "                donate=False, sample_data=(x, y))\n"
        "rep = at.last_report()['dtype_ladder']\n"
        "assert rep['cached'] is True, rep\n"
        "assert rep['winner'] == %r, rep\n"
        "print('child-ok')\n" % (_REPO, winner)
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_DTYPE_LADDER="fp32,bf16,fp8",
               MXNET_AUTOTUNE_CACHE_DIR=os.environ[
                   "MXNET_AUTOTUNE_CACHE_DIR"])
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "child-ok" in out.stdout


def test_cached_fp8_winner_needs_roster_opt_in(monkeypatch, cache_dir):
    """A cached fp8 ladder winner never applies to a build whose
    roster did not name fp8 (its opt_state carries no fp8 state to
    run on) — op_variants narrows the roster, and the entry simply
    re-races."""
    assert set(at.op_variants("dtype_ladder")) == {"fp32", "bf16",
                                                   "fp8"}
    monkeypatch.setenv("MXNET_DTYPE_LADDER", "fp32,bf16")
    assert set(at.op_variants("dtype_ladder")) == {"fp32", "bf16"}
    monkeypatch.setenv("MXNET_DTYPE_LADDER", "fp8")
    assert set(at.op_variants("dtype_ladder")) == {"fp8"}
    # "1"/"auto" keeps the round-14 pair: fp8 NEVER joins implicitly
    monkeypatch.setenv("MXNET_DTYPE_LADDER", "1")
    assert set(at.op_variants("dtype_ladder")) == {"fp32", "bf16"}
    assert at.ladder_rungs() == ("fp32", "bf16")
    monkeypatch.delenv("MXNET_DTYPE_LADDER")
    assert at.ladder_rungs() == ()

    # the narrowing applied to a cached winner: record fp8 as the
    # winner, then look through program_scope with a bf16-only roster
    mx.random.seed(5)
    monkeypatch.setenv("MXNET_DTYPE_LADDER", "fp32,bf16,fp8")
    x, y = _data()
    at.record("dtype_ladder", x.shape, x.dtype, winner="fp8",
              platform="cpu", mesh="none")
    with at.program_scope(x.shape, x.dtype, platform="cpu",
                          mesh="none"):
        assert at.variant_choice("dtype_ladder") == "fp8"
    monkeypatch.setenv("MXNET_DTYPE_LADDER", "fp32,bf16")
    with at.program_scope(x.shape, x.dtype, platform="cpu",
                          mesh="none"):
        assert at.variant_choice("dtype_ladder") is None


# ------------------------------------------------- the inference arm
def _quantized_net():
    mx.random.seed(42)
    onp.random.seed(42)
    from mxnet_tpu.quantization import calibrate, quantize_net

    net = nn.HybridSequential(prefix="fp8q_")
    with net.name_scope():
        net.add(nn.Conv2D(8, kernel_size=3, padding=1, in_channels=3),
                nn.Flatten(),
                nn.Dense(16, activation="relu"),
                nn.Dense(4))
    net.initialize()
    x = nd.array(onp.random.randn(4, 3, 8, 8).astype("float32"))
    ref = net(x).asnumpy()
    calib = calibrate(net, [x], mode="naive")
    quantize_net(net, calib)
    return net, x, ref, calib


def test_fp8_arm_agreement_and_env_pin(cache_dir, monkeypatch):
    net, x, ref, calib = _quantized_net()
    with at.force(quantized_conv="fp8", quantized_fc="fp8"):
        out = net(x).asnumpy()
    # the adoption floor the benchdiff gate holds the arm to
    agree = float((out.argmax(1) == ref.argmax(1)).mean())
    assert agree >= 0.99
    assert float(onp.abs(out - ref).max()) < 0.15 * float(
        onp.abs(ref).max())
    # MXNET_QUANTIZE=fp8 pins the same program
    monkeypatch.setenv("MXNET_QUANTIZE", "fp8")
    onp.testing.assert_allclose(net(x).asnumpy(), out)


def test_fp8_calibrated_amax_is_the_consumed_statistic():
    net, x, ref, calib = _quantized_net()
    name = [n for n in calib.layers() if "conv" in n][0]
    mn, mx_ = calib.range(name, "in")
    assert calib.amax(name, "in") == pytest.approx(
        max(abs(mn), abs(mx_)))
    assert calib.amax("never_observed") is None


def test_fp8_artifact_param_dtypes_roundtrip(cache_dir, tmp_path):
    """export_model -> artifact_info names the float8 dtypes in the
    v2 header WITHOUT deserialization, and the artifact serves AOT
    with the exact fp8 program output."""
    from mxnet_tpu import deploy

    net, x, ref, calib = _quantized_net()
    path = str(tmp_path / "fp8.mxje")
    with at.force(quantized_conv="fp8", quantized_fc="fp8"):
        deploy.export_model(net, x, path, platforms=("cpu",))
        expect = net(x).asnumpy()
    info = deploy.artifact_info(path)
    assert info["quantized"] is True
    # conv + 2 dense bake e4m3 weights; their biases stay f32
    assert info["param_dtypes"].get("float8_e4m3fn") == 3
    assert info["param_dtypes"].get("float32") == 3
    f = deploy.load_model(path)
    onp.testing.assert_allclose(f(x).asnumpy(), expect, rtol=1e-6)
    # int8-pinned export of the SAME net is still identified as int8
    p2 = str(tmp_path / "int8.mxje")
    with at.force(quantized_conv=True, quantized_fc=True):
        deploy.export_model(net, x, p2, platforms=("cpu",))
    assert "float8_e4m3fn" not in deploy.artifact_info(
        p2)["param_dtypes"]


def test_tune_quantized_races_three_arms(cache_dir):
    from mxnet_tpu.quantization import tune_quantized

    net, x, ref, calib = _quantized_net()
    report = tune_quantized(net, x, iters=3)
    for op in ("quantized_conv", "quantized_fc"):
        assert set(report[op]["timings"]) == {"fp32", "int8", "fp8"}


# ---------------------------------------------- registration + policy
def test_float8_dtypes_registered_and_saved_as_fp32(tmp_path):
    from mxnet_tpu.dtype import dtype_name, normalize_dtype

    assert normalize_dtype("fp8") is jnp.float8_e4m3fn
    assert normalize_dtype("e4m3") is jnp.float8_e4m3fn
    assert normalize_dtype("e5m2") is jnp.float8_e5m2
    assert dtype_name("float8_e4m3fn") == "float8_e4m3fn"
    a = nd.array([1.0, 2.5, 300.0]).astype("fp8")
    assert a.dtype == jnp.float8_e4m3fn
    onp.testing.assert_allclose(a.asnumpy().astype("float32"),
                                [1.0, 2.5, 288.0])  # e4m3 grid
    # the bfloat16 on-disk rule: saved as float32, loads as float32
    path = str(tmp_path / "w.params")
    nd.save(path, {"w": a})
    back = nd.load(path)["w"]
    assert back.dtype == onp.dtype("float32")
    onp.testing.assert_allclose(back.asnumpy(), [1.0, 2.5, 288.0])


def test_missing_float8_support_is_loud(monkeypatch):
    """No silent fp32 fallback: a build without ml_dtypes float8
    raises MXNetError from dtype normalization AND from an fp8-pinned
    quantized trace."""
    from mxnet_tpu import dtype as dt

    monkeypatch.setattr(dt, "float8_supported", lambda: False)
    with pytest.raises(MXNetError, match="float8"):
        dt.normalize_dtype("fp8")
    from mxnet_tpu.quantization.rewrite import QuantizedDense

    dense = nn.Dense(4, in_units=6, prefix="loud0_")
    dense.initialize()
    wrapper = QuantizedDense(dense, in_range=(-1.0, 1.0))
    with at.force(quantized_fc="fp8"):
        with pytest.raises(MXNetError, match="float8"):
            wrapper._arm()


def test_amp_lists_agree_with_ladder_eligibility():
    """FP8_OPS is the matmul/conv family only — a strict subset of the
    bf16 target list, disjoint from the fp32-forced list: norms,
    softmax and reductions never drop below bf16, exactly the
    eligibility rule the ladder's fp8 rung applies."""
    from mxnet_tpu.contrib.amp import lists

    fp8 = set(lists.FP8_OPS)
    assert fp8 and fp8 < set(lists.TARGET_DTYPE_OPS)
    assert not fp8 & set(lists.FP32_OPS)
    assert {"FullyConnected", "Convolution", "dot"} <= fp8
    for never in ("BatchNorm", "LayerNorm", "softmax", "sum", "mean",
                  "norm"):
        assert never not in fp8
    assert lists.FP8_FUNCS is lists.FP8_OPS
