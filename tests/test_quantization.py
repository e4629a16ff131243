"""Quantized inference subsystem (round 18).

The surface under test is the calibrate -> rewrite -> race -> export ->
serve chain (mxnet_tpu.quantization + the deploy/serving integration):

* quantize/dequantize roundtrip error bounds (uint8 affine + int8
  symmetric), quantized FC/conv vs fp32 inside calibrated-range
  tolerance, calibrated vs on-the-fly range parity;
* entropy vs naive calibration on a skewed-activation distribution
  (KL clips the outliers, min/max does not);
* the int8 avg-pool round-to-nearest regression (round-18 satellite:
  the cast back from the float average must not truncate toward 0);
* the net rewrite: eligible layers swap to quantized wrappers with
  int8-triple stitching inside Sequentials, excluded_names and
  MXNET_QUANTIZE=0 both restore bit-exact fp32, Module calibration
  taps symbol internals;
* adoption by measurement: tune_quantized persists winners in
  autotune.json and a FRESH PROCESS answers from the cache without
  re-timing;
* THE drill: calibrate a TRAINED net on a synthetic corpus, rewrite
  to int8, export the CRC+metadata-framed .mxje, relaunch-serve it
  AOT in a subprocess (run-log retrace counter 0) and require top-1
  agreement >= 99% vs the fp32 arm;
* the artifact identity: export_model's v2 header answers
  artifact_info's quantized/param_dtypes without deserializing, and
  the fleet's ModelHost surfaces it through residency() across an
  fp32 -> int8 swap.
"""
import json
import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autotune, deploy, gluon, nd
from mxnet_tpu import quantization as quant
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _qv2(x, **kw):
    return nd.invoke("_contrib_quantize_v2", [nd.array(x)], **kw)


# ------------------------------------------------- op-level error bounds
def test_quantize_dequantize_roundtrip_bounds():
    """uint8 affine and int8 symmetric roundtrips stay within half a
    quantization step of the input — the analytic error bound, not a
    loose atol."""
    x = (onp.random.rand(64, 64) * 3 - 1.2).astype("float32")
    mn, mx_ = float(x.min()), float(x.max())

    # uint8 affine: [min, max] -> [0, 255]
    q, qmn, qmx = nd.invoke(
        "_contrib_quantize",
        [nd.array(x), nd.array([mn]), nd.array([mx_])],
        out_type="uint8")
    assert q.asnumpy().dtype == onp.uint8
    back = nd.invoke("_contrib_dequantize", [q, qmn, qmx]).asnumpy()
    step = (mx_ - mn) / 255.0
    assert onp.abs(back - x).max() <= step / 2 + 1e-6

    # int8 symmetric: +-amax -> +-127
    q8, q8mn, q8mx = _qv2(x)
    assert q8.asnumpy().dtype == onp.int8
    back8 = nd.invoke("_contrib_dequantize",
                      [q8, q8mn, q8mx]).asnumpy()
    step8 = max(abs(mn), abs(mx_)) / 127.0
    assert onp.abs(back8 - x).max() <= step8 / 2 + 1e-6


def test_quantized_fc_conv_within_calibrated_tolerance():
    """int8 FC and conv against the fp32 references, with ranges
    calibrated to the true min/max: the error budget is the sum of
    the input/weight grid steps propagated through the contraction."""
    x = (onp.random.rand(4, 32) * 2 - 1).astype("float32")
    w = (onp.random.rand(8, 32) * 0.4 - 0.2).astype("float32")
    b = (onp.random.rand(8) * 0.2 - 0.1).astype("float32")
    xq, xmn, xmx = _qv2(x)
    wq, wmn, wmx = _qv2(w)
    bq, bmn, bmx = _qv2(b)
    acc, omn, omx = nd.invoke(
        "_contrib_quantized_fully_connected",
        [xq, wq, bq, xmn, xmx, wmn, wmx, bmn, bmx], num_hidden=8)
    out = nd.invoke("_contrib_dequantize", [acc, omn, omx]).asnumpy()
    expect = x @ w.T + b
    # per-term grid error ~ (sx*|w| + sw*|x|)/127 summed over K terms
    budget = 32 * (onp.abs(x).max() * 0.2 / 127
                   + onp.abs(w).max() * 1.0 / 127) + 0.01
    assert onp.abs(out - expect).max() <= budget

    xc = (onp.random.rand(2, 3, 8, 8) - 0.5).astype("float32")
    wc = (onp.random.rand(4, 3, 3, 3) * 0.4 - 0.2).astype("float32")
    xq, xmn, xmx = _qv2(xc)
    wq, wmn, wmx = _qv2(wc)
    bq = nd.zeros((4,)).astype("int8")
    one = nd.array([1.0])
    acc, omn, omx = nd.invoke(
        "_contrib_quantized_conv",
        [xq, wq, bq, xmn, xmx, wmn, wmx, -one, one],
        kernel=(3, 3), num_filter=4, pad=(1, 1))
    out = nd.invoke("_contrib_dequantize", [acc, omn, omx]).asnumpy()
    expect = nd.invoke(
        "Convolution",
        [nd.array(xc), nd.array(wc), nd.zeros((4,))],
        kernel=(3, 3), num_filter=4, pad=(1, 1)).asnumpy()
    budget = 27 * (0.5 * 0.2 / 127 + 0.2 * 0.5 / 127) + 0.01
    assert onp.abs(out - expect).max() <= budget


def test_calibrated_vs_onthefly_range_parity():
    """quantize_v2 / requantize with calibrated ranges equal to the
    data's ACTUAL extrema must reproduce the on-the-fly path bit for
    bit — the calibrated fast path changes where the range comes
    from, never the math."""
    x = (onp.random.rand(32, 32) * 4 - 2).astype("float32")
    amax = float(onp.abs(x).max())
    q_fly, mn_fly, mx_fly = _qv2(x)
    q_cal, mn_cal, mx_cal = _qv2(x, min_calib_range=-amax,
                                 max_calib_range=amax)
    onp.testing.assert_array_equal(q_fly.asnumpy(), q_cal.asnumpy())
    onp.testing.assert_allclose(mx_fly.asnumpy(), mx_cal.asnumpy())

    acc = onp.random.randint(-2**24, 2**24, (32, 32)).astype("int32")
    rmin, rmax = nd.array([-3.0]), nd.array([3.0])
    real = acc.astype("float64") * (3.0 / (2**31 - 1))
    real_amax = float(onp.abs(real).max())
    r_fly = nd.invoke("_contrib_requantize",
                      [nd.array(acc), rmin, rmax])
    r_cal = nd.invoke("_contrib_requantize",
                      [nd.array(acc), rmin, rmax],
                      min_calib_range=-real_amax,
                      max_calib_range=real_amax)
    onp.testing.assert_array_equal(r_fly[0].asnumpy(),
                                   r_cal[0].asnumpy())
    onp.testing.assert_allclose(r_fly[2].asnumpy(),
                                r_cal[2].asnumpy(), rtol=1e-6)


def test_entropy_vs_naive_on_skewed_activations():
    """A gaussian bulk with rare huge outliers: naive min/max
    stretches the int8 grid over empty space, the KL threshold clips
    the outliers — entropy must pick a MUCH tighter range and
    reconstruct the bulk strictly better."""
    rng = onp.random.RandomState(7)
    stats = quant.TensorStats(collect_hist=True)
    batches = []
    for _ in range(4):
        a = rng.randn(50000).astype("float32")
        a[:4] *= 100.0  # the rare outliers
        stats.update(a)
        batches.append(a)
    n_mn, n_mx = stats.range("naive")
    e_mn, e_mx = stats.range("entropy")
    assert e_mx < n_mx / 3, (e_mx, n_mx)
    assert e_mn == -e_mx  # symmetric by construction

    bulk = onp.concatenate(batches)
    bulk = bulk[onp.abs(bulk) < 5.0]

    def bulk_err(mn, mx_):
        q, qmn, qmx = _qv2(bulk, min_calib_range=mn,
                           max_calib_range=mx_)
        back = nd.invoke("_contrib_dequantize",
                         [q, qmn, qmx]).asnumpy()
        return float(onp.abs(back - bulk).mean())

    assert bulk_err(e_mn, e_mx) < bulk_err(n_mn, n_mx) / 3


def test_entropy_uniform_keeps_full_range():
    """No outliers (uniform bulk): the KL sweep must NOT clip — the
    threshold stays at (about) the true max.  Regression for the
    quantize-q-from-clipped-p bug where every sweep won at the
    smallest threshold (KL(p||p) = 0)."""
    rng = onp.random.RandomState(3)
    stats = quant.TensorStats(collect_hist=True)
    for _ in range(4):
        stats.update(rng.rand(20000).astype("float32") * 1.25)
    _, e_mx = stats.range("entropy")
    assert e_mx > 1.1, e_mx


def test_entropy_histogram_widening_is_bounded():
    """A near-zero first batch (dead activation on batch 0) must not
    make a later normal-magnitude batch allocate a range/width-sized
    histogram: past the widening cap the collector REBINS into the
    standard resolution and stays usable."""
    from mxnet_tpu.quantization.calibrate import _MAX_BINS

    stats = quant.TensorStats(collect_hist=True)
    stats.update(onp.zeros(100, dtype="float32"))       # amax 0
    stats.update(onp.full(100, 1e-7, dtype="float32"))  # tiny seed
    rng = onp.random.RandomState(0)
    stats.update(rng.rand(20000).astype("float32") * 2.0)
    assert len(stats._hist) <= _MAX_BINS
    _, e_mx = stats.range("entropy")
    assert 1.5 < e_mx <= 2.1, e_mx


# --------------------------------------------------- avg-pool satellite
def test_quantized_avg_pool_rounds_to_nearest():
    """Round-18 satellite: the int8 avg-pool must ROUND the float
    average back to the int8 grid, not truncate toward zero — parity
    against the dequantized-fp32 reference."""
    # codes whose 2x2 window averages have fractional parts that
    # expose truncation: e.g. (1+2+2+2)/4 = 1.75 -> 2, trunc gives 1
    codes = onp.array([[[[1, 2, 5, -1],
                         [2, 2, -2, -3],
                         [7, 0, 3, 3],
                         [0, 0, 3, 4]]]], dtype="int8")
    mn, mx_ = nd.array([-127.0]), nd.array([127.0])
    q, _, _ = nd.invoke("_contrib_quantized_pooling",
                        [nd.array(codes), mn, mx_],
                        kernel=(2, 2), stride=(2, 2), pool_type="avg")
    got = q.asnumpy().astype("int32")
    # explicit 2x2/stride-2 window means
    ref = onp.zeros((1, 1, 2, 2))
    for i in range(2):
        for j in range(2):
            ref[0, 0, i, j] = codes[0, 0, 2*i:2*i+2,
                                    2*j:2*j+2].astype("float64").mean()
    expect = onp.rint(ref).astype("int32")
    onp.testing.assert_array_equal(got, expect)
    # the fractional window (1.75) is the truncation tripwire
    assert ref[0, 0, 0, 0] == 1.75 and got[0, 0, 0, 0] == 2

    # random parity vs the dequantized-fp32 reference: dequantize,
    # fp32 avg-pool, re-quantize on the same grid == int8 avg-pool
    rnd = onp.random.randint(-127, 128, (2, 3, 8, 8)).astype("int8")
    q2, _, _ = nd.invoke("_contrib_quantized_pooling",
                         [nd.array(rnd), mn, mx_],
                         kernel=(2, 2), stride=(2, 2),
                         pool_type="avg")
    fp = nd.invoke("Pooling",
                   [nd.array(rnd.astype("float32"))],
                   kernel=(2, 2), stride=(2, 2),
                   pool_type="avg").asnumpy()
    onp.testing.assert_array_equal(q2.asnumpy().astype("int32"),
                                   onp.rint(fp).astype("int32"))


# ------------------------------------------------------- the rewrite
def _small_net(with_act=False):
    net = nn.HybridSequential()
    with net.name_scope():
        if with_act:
            net.add(nn.Conv2D(8, 3, padding=1),
                    nn.Activation("relu"),
                    nn.MaxPool2D(), nn.Flatten(), nn.Dense(10))
        else:
            net.add(nn.Conv2D(8, 3, padding=1), nn.MaxPool2D(),
                    nn.AvgPool2D(), nn.Flatten(), nn.Dense(10))
    net.initialize(init=mx.init.Xavier())
    net(nd.zeros((1, 3, 16, 16)))
    return net


def _corpus(n=3, batch=8, seed=0):
    rng = onp.random.RandomState(seed)
    return [rng.rand(batch, 3, 16, 16).astype("float32")
            for _ in range(n)]


def test_rewrite_stitched_chain_and_fallback(monkeypatch):
    """A Sequential without activations stitches the whole chain —
    conv emits the int8 triple, pooling/flatten pass it through,
    dense consumes it — and the int8 program tracks fp32 within the
    calibrated tolerance; MXNET_QUANTIZE=0 pins every wrapper to its
    fp32 arm BIT-EXACTLY."""
    net = _small_net()
    x = nd.array(_corpus(1)[0])
    ref = net(x).asnumpy()
    calib = quant.calibrate(net, _corpus(), mode="naive")
    qnet = quant.quantize_net(net, calib)
    wrappers = quant.quantized_layers(qnet)
    kinds = sorted(type(w).__name__ for w in wrappers)
    assert kinds == ["QuantizedConv", "QuantizedDense",
                     "QuantizedFlatten", "QuantizedPooling",
                     "QuantizedPooling"]
    conv = next(w for w in wrappers
                if type(w).__name__ == "QuantizedConv")
    dense = next(w for w in wrappers
                 if type(w).__name__ == "QuantizedDense")
    assert conv.emit_q and not conv.accept_q
    assert dense.accept_q and not dense.emit_q
    out = qnet(x).asnumpy()
    rel = onp.abs(out - ref).max() / (onp.abs(ref).max() + 1e-9)
    assert rel < 0.12, rel

    monkeypatch.setenv("MXNET_QUANTIZE", "0")
    onp.testing.assert_array_equal(qnet(x).asnumpy(), ref)
    monkeypatch.setenv("MXNET_QUANTIZE", "1")
    onp.testing.assert_array_equal(qnet(x).asnumpy(), out)


def test_rewrite_excluded_names_escape_hatch():
    """A layer named in excluded_names is neither calibrated nor
    rewritten — it keeps running the original fp32 block."""
    net = _small_net()
    dense_name = net[4].name
    calib = quant.calibrate(net, _corpus(), mode="naive",
                            excluded_names=(dense_name,))
    assert dense_name not in calib
    qnet = quant.quantize_net(net, calib)
    assert isinstance(qnet[4], nn.Dense)  # untouched
    assert any(type(w).__name__ == "QuantizedConv"
               for w in quant.quantized_layers(qnet))


def test_rewrite_needs_calibration():
    net = _small_net()
    empty = quant.CalibrationResult({}, "naive", 1)
    with pytest.raises(MXNetError, match="calibrated"):
        quant.quantize_net(net, empty)


def test_rewrite_hybridized_and_activation_boundary():
    """With activations between layers the chain breaks (each wrapper
    is self-contained, fp32 at its boundary) and the hybridized (jit)
    forward matches the eager int8 forward bit for bit."""
    net = _small_net(with_act=True)
    x = nd.array(_corpus(1)[0])
    calib = quant.calibrate(net, _corpus(), mode="naive")
    qnet = quant.quantize_net(net, calib)
    conv = next(w for w in quant.quantized_layers(qnet)
                if type(w).__name__ == "QuantizedConv")
    assert not conv.emit_q  # the relu sits between conv and pool
    eager = qnet(x).asnumpy()
    qnet.hybridize()
    onp.testing.assert_array_equal(qnet(x).asnumpy(), eager)


def test_rewrite_attribute_style_block():
    """Attribute-resolved children (self.fc = Dense) swap in both the
    child registry and the attribute, and self-contained wrappers
    (no Sequential seam) still quantize conv/fc — pooling stays fp32
    outside a chain."""
    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.conv = nn.Conv2D(4, 3, padding=1)
                self.pool = nn.MaxPool2D()
                self.fc = nn.Dense(6)

        def hybrid_forward(self, F, x):
            return self.fc(self.pool(self.conv(x)))

    net = Net()
    net.initialize(init=mx.init.Xavier())
    x = nd.array(_corpus(1)[0])
    ref = net(x).asnumpy()
    calib = quant.calibrate(net, _corpus(), mode="naive")
    qnet = quant.quantize_net(net, calib)
    assert type(qnet.conv).__name__ == "QuantizedConv"
    assert type(qnet.fc).__name__ == "QuantizedDense"
    assert isinstance(qnet.pool, nn.MaxPool2D)  # chain-only layer
    wrappers = quant.quantized_layers(qnet)
    assert not any(w.emit_q for w in wrappers)  # no Sequential seam
    out = qnet(x).asnumpy()
    rel = onp.abs(out - ref).max() / (onp.abs(ref).max() + 1e-9)
    assert rel < 0.12, rel


def test_calibrate_module_symbol_taps():
    """The Module front door: quantizable symbol nodes are tapped via
    get_internals and the collected input range matches the data."""
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, name="fc1", num_hidden=8)
    out = mx.sym.softmax(fc)
    mod = mx.mod.Module(out, data_names=("data",), label_names=())
    mod.bind(data_shapes=[("data", (4, 16))], for_training=False)
    mod.init_params(initializer=mx.init.Xavier())
    batches = [onp.random.rand(4, 16).astype("float32") * 2 - 1
               for _ in range(3)]
    calib = quant.calibrate(mod, batches, mode="naive")
    assert "fc1" in calib
    mn, mx_ = calib.range("fc1", "in")
    lo = min(float(b.min()) for b in batches)
    hi = max(float(b.max()) for b in batches)
    assert abs(mn - lo) < 1e-6 and abs(mx_ - hi) < 1e-6
    assert calib.range("fc1", "out") is not None


def test_quantize_telemetry_records(tmp_path):
    """Armed run log: calibrate + rewrite + export emit schema-valid
    ``quantize`` records naming action/mode/layer counts."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import schema

    runlog = str(tmp_path / "quant.jsonl")
    os.environ["MXNET_RUNLOG"] = runlog
    telemetry.reset()
    try:
        net = _small_net()
        calib = quant.calibrate(net, _corpus(), mode="entropy")
        qnet = quant.quantize_net(net, calib)
        path = str(tmp_path / "q.mxje")
        deploy.export_model(qnet, _corpus(1)[0], path,
                            platforms=("cpu",))
    finally:
        telemetry.close()
        os.environ.pop("MXNET_RUNLOG", None)
        telemetry.reset()
    recs = [json.loads(ln) for ln in open(runlog) if ln.strip()]
    qrecs = [r for r in recs if r["type"] == "quantize"]
    actions = [r["action"] for r in qrecs]
    assert "calibrate" in actions and "rewrite" in actions \
        and "export" in actions
    for r in qrecs:
        assert schema.validate_record(r) == [], r
    cal = next(r for r in qrecs if r["action"] == "calibrate")
    assert cal["mode"] == "entropy" and cal["layers"] >= 2


def test_env_knobs_registered():
    from mxnet_tpu import config

    assert config.get_env("MXNET_QUANTIZE") == ""
    assert config.get_env("MXNET_QUANT_CALIB_MODE") == "naive"
    assert config.get_env("MXNET_QUANT_CALIB_BATCHES") == 10


# ------------------------------------------------ adoption by measurement
def test_winner_persistence_across_processes(tmp_path):
    """The round-9 contract for the int8 arms: tune_quantized records
    winners in autotune.json; a FRESH PROCESS consults the cache and
    answers without re-timing (cached=True for every raced op)."""
    cache_dir = str(tmp_path / "atcache")
    os.environ["MXNET_AUTOTUNE_CACHE_DIR"] = cache_dir
    autotune.cache_clear()
    try:
        net = _small_net()
        calib = quant.calibrate(net, _corpus(), mode="naive")
        qnet = quant.quantize_net(net, calib)
        report = quant.tune_quantized(qnet, _corpus(1)[0], iters=4)
        assert set(report) == {"quantized_conv", "quantized_fc"}
        for op, r in report.items():
            # fp8 joined the race in round 19 — any arm may win on CPU
            assert r["winner"] in ("fp32", "int8", "fp8")
            assert not r.get("cached")
        entries = json.load(open(
            os.path.join(cache_dir, "autotune.json")))["entries"]
        assert any(k.startswith("quantized_conv|") for k in entries)
        assert any(k.startswith("quantized_fc|") for k in entries)

        child = textwrap.dedent("""
            import json, os, sys
            import numpy as onp
            sys.path.insert(0, %r)
            import mxnet_tpu as mx
            from mxnet_tpu import nd
            from mxnet_tpu import quantization as quant
            from mxnet_tpu.gluon import nn
            net = nn.HybridSequential()
            with net.name_scope():
                net.add(nn.Conv2D(8, 3, padding=1), nn.MaxPool2D(),
                        nn.AvgPool2D(), nn.Flatten(), nn.Dense(10))
            net.initialize(init=mx.init.Xavier())
            net(nd.zeros((1, 3, 16, 16)))
            rng = onp.random.RandomState(0)
            corpus = [rng.rand(8, 3, 16, 16).astype("float32")
                      for _ in range(3)]
            calib = quant.calibrate(net, corpus, mode="naive")
            qnet = quant.quantize_net(net, calib)
            rep = quant.tune_quantized(qnet, corpus[0], iters=4)
            print(json.dumps({op: {"winner": r["winner"],
                                   "cached": bool(r.get("cached"))}
                              for op, r in rep.items()}))
        """) % _REPO
        env = dict(os.environ)
        env["MXNET_AUTOTUNE_CACHE_DIR"] = cache_dir
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run([sys.executable, "-c", child],
                           capture_output=True, text=True,
                           timeout=300, env=env)
        assert r.returncode == 0, r.stderr[-2000:]
        child_rep = json.loads(r.stdout.strip().splitlines()[-1])
        for op in ("quantized_conv", "quantized_fc"):
            assert child_rep[op]["cached"] is True
            assert child_rep[op]["winner"] == report[op]["winner"]
    finally:
        os.environ.pop("MXNET_AUTOTUNE_CACHE_DIR", None)
        autotune.cache_clear()


# ---------------------------------------------------- artifact identity
def test_artifact_info_quantized_roundtrip(tmp_path):
    """export_model's v2 header round-trips quantized/param_dtypes
    through artifact_info; fp32 nets say so; legacy v1 artifacts
    report None (unknown), never a guess."""
    fp32_net = nn.Dense(4, in_units=3)
    fp32_net.initialize()
    p32 = str(tmp_path / "f.mxje")
    deploy.export_model(fp32_net, nd.zeros((2, 3)), p32,
                        platforms=("cpu",))
    info = deploy.artifact_info(p32)
    assert info["quantized"] is False
    assert info["param_dtypes"] == {"float32": 2}
    assert info["batch"] == 2 and info["item_shape"] == (3,)

    net = _small_net()
    calib = quant.calibrate(net, _corpus(), mode="naive")
    qnet = quant.quantize_net(net, calib)
    p8 = str(tmp_path / "q.mxje")
    with autotune.force(quantized_conv=True, quantized_fc=True):
        deploy.export_model(qnet, _corpus(1)[0], p8,
                            platforms=("cpu",))
    info8 = deploy.artifact_info(p8)
    assert info8["quantized"] is True
    assert info8["param_dtypes"].get("int8", 0) >= 2

    # the identity describes the PROGRAM: every arm forced fp32 means
    # the export baked the fp32 originals, and the header says so
    pf = str(tmp_path / "qf.mxje")
    with autotune.force(quantized_conv=False, quantized_fc=False):
        deploy.export_model(qnet, _corpus(1)[0], pf,
                            platforms=("cpu",))
    assert deploy.artifact_info(pf)["quantized"] is False

    # legacy v1 frame: quantized/param_dtypes unknown -> None
    from mxnet_tpu.deploy import _HEADER, _MAGIC, _read_payload

    import zlib as _zlib

    blob = _read_payload(p32)
    v1 = str(tmp_path / "v1.mxje")
    with open(v1, "wb") as f:
        f.write(_MAGIC + _HEADER.pack(
            _zlib.crc32(blob) & 0xFFFFFFFF, len(blob)) + blob)
    legacy = deploy.artifact_info(v1)
    assert legacy["quantized"] is None
    assert legacy["param_dtypes"] is None
    assert legacy["batch"] == 2


def test_export_winner_scope_is_single_platform_only(tmp_path):
    """A cached adoption winner is keyed per platform, and ONE
    multi-platform artifact cannot honor two verdicts: a
    single-platform export bakes the cached winner for THAT platform,
    a multi-platform export ignores cached winners (only force/env
    decide) — the exporting CPU host's verdict must not pin the TPU
    lowering forever."""
    cache_dir = str(tmp_path / "atcache")
    os.environ["MXNET_AUTOTUNE_CACHE_DIR"] = cache_dir
    autotune.cache_clear()
    try:
        net = _small_net()
        calib = quant.calibrate(net, _corpus(), mode="naive")
        qnet = quant.quantize_net(net, calib)
        x = _corpus(1)[0]
        # hand-record fp32 winners for this signature on THIS platform
        for op in ("quantized_conv", "quantized_fc"):
            autotune.record(op, x.shape, x.dtype, "fp32",
                            platform="cpu")
        p_single = str(tmp_path / "single.mxje")
        deploy.export_model(qnet, x, p_single, platforms=("cpu",))
        # the cpu-keyed fp32 verdict baked in
        assert deploy.artifact_info(p_single)["quantized"] is False
        p_multi = str(tmp_path / "multi.mxje")
        deploy.export_model(qnet, x, p_multi,
                            platforms=("cpu", "tpu"))
        # multi-platform: cached winners do NOT apply — the wrappers'
        # int8 default stands
        assert deploy.artifact_info(p_multi)["quantized"] is True
    finally:
        os.environ.pop("MXNET_AUTOTUNE_CACHE_DIR", None)
        autotune.cache_clear()


def test_fleet_residency_surfaces_quantized(tmp_path):
    """ModelHost admission keeps the artifact identity: residency()
    tells the int8 artifact from fp32 across an fp32 -> int8 swap —
    the operator reads it without deserializing any program."""
    from mxnet_tpu.serving.fleet import ModelHost

    net = _small_net()
    x = _corpus(1, batch=4)[0]
    p32 = str(tmp_path / "f.mxje")
    deploy.export_model(net, x, p32, platforms=("cpu",))
    calib = quant.calibrate(net, _corpus(), mode="naive")
    qnet = quant.quantize_net(net, calib)
    p8 = str(tmp_path / "q.mxje")
    with autotune.force(quantized_conv=True, quantized_fc=True):
        deploy.export_model(qnet, x, p8, platforms=("cpu",))

    host = ModelHost(hbm_budget_mb=0)
    try:
        host.load("m", p32, coalesce_ms=1.0)
        res = host.residency()["models"]["m"]
        assert res["quantized"] is False
        # the zero-downtime upgrade: fp32 -> int8 under the same name
        host.swap("m", p8)
        res = host.residency()["models"]["m"]
        assert res["quantized"] is True
        assert res["param_dtypes"].get("int8", 0) >= 2
        # the request waits as long as its result is waited for: the
        # default 100 ms SLO sheds it on a machine busy with six workers
        out = host.submit(x[0], deadline_ms=30000).result(timeout=30)
        assert onp.isfinite(onp.asarray(out)).all()
    finally:
        host.close_all()


# ----------------------------------------------------------- THE drill
def test_drill_calibrate_rewrite_export_serve_aot(tmp_path):
    """Acceptance drill: calibrate a TRAINED net on a synthetic
    corpus, rewrite to int8, export .mxje, relaunch-serve it AOT in a
    fresh process (run-log retrace counter 0 — load, not retrace) and
    require >= 99% top-1 agreement with the fp32 net on the
    calibration corpus."""
    from mxnet_tpu.parallel import DataParallelTrainer

    rng = onp.random.RandomState(42)
    nclass, item = 4, (3, 16, 16)
    protos = rng.rand(nclass, *item).astype("float32")

    def make_batch(n):
        # noise well inside the prototype separation: the agreement
        # verdict must measure quantization error, not boundary
        # samples (the bench phase uses the same recipe)
        y = rng.randint(0, nclass, n)
        return ((protos[y] + 0.15 * rng.rand(n, *item))
                .astype("float32"), y.astype("float32"))

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(8, 3, padding=1), nn.Activation("relu"),
                nn.MaxPool2D(), nn.Flatten(), nn.Dense(nclass))
    net.initialize(init=mx.init.Xavier())
    net(nd.zeros((1,) + item))
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer="sgd",
        learning_rate=0.2)
    for _ in range(60):
        xb, yb = make_batch(32)
        trainer.fit_batch(xb, yb)
    trainer.sync_to_block()

    corpus = [make_batch(32)[0] for _ in range(4)]
    fp32_logits = onp.concatenate(
        [net(nd.array(b)).asnumpy() for b in corpus])

    calib = quant.calibrate(net, corpus, mode="entropy",
                            num_batches=len(corpus))
    qnet = quant.quantize_net(net, calib)
    artifact = str(tmp_path / "int8.mxje")
    with autotune.force(quantized_conv=True, quantized_fc=True):
        deploy.export_model(qnet, corpus[0], artifact,
                            platforms=("cpu",))
    assert deploy.artifact_info(artifact)["quantized"] is True

    # relaunch-serve in a FRESH process: AOT warm start, submit every
    # corpus sample through the server, dump outputs + close telemetry
    corpus_npy = str(tmp_path / "corpus.npy")
    onp.save(corpus_npy, onp.concatenate(corpus))
    out_npy = str(tmp_path / "served.npy")
    runlog = str(tmp_path / "serve.jsonl")
    child = textwrap.dedent("""
        import sys
        import numpy as onp
        sys.path.insert(0, %r)
        import mxnet_tpu as mx
        from mxnet_tpu import telemetry
        from mxnet_tpu.serving import ModelServer
        artifact, corpus_npy, out_npy = sys.argv[1:4]
        xs = onp.load(corpus_npy)
        srv = ModelServer.from_artifact(artifact, coalesce_ms=1.0,
                                        slo_ms=30000.0)
        srv.start(warm=True)
        try:
            handles = [srv.submit(x) for x in xs]
            outs = onp.stack([onp.asarray(h.result(timeout=120))
                              for h in handles])
        finally:
            srv.drain(timeout=10.0)
            srv.close()
            telemetry.close()
        onp.save(out_npy, outs)
        print("served", len(outs))
    """) % _REPO
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_RUNLOG"] = runlog
    r = subprocess.run(
        [sys.executable, "-c", child, artifact, corpus_npy, out_npy],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-3000:]

    served = onp.load(out_npy)
    assert served.shape[0] == fp32_logits.shape[0]
    agreement = (served.argmax(1)
                 == fp32_logits.argmax(1)).mean()
    assert agreement >= 0.99, agreement

    # load-not-retrace: the AOT server emitted ZERO compile events
    recs = [json.loads(ln) for ln in open(runlog) if ln.strip()]
    end = next(rc for rc in recs if rc["type"] == "run_end")
    assert end["counters"]["compiles"] == 0, \
        "AOT relaunch must be load-not-retrace"
