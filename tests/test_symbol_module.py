"""Symbol front-end + Module API tests.

Reference models: tests/python/unittest/test_symbol.py, test_module.py,
tests/python/train/test_mlp.py (Module.fit convergence),
test_bucketing.py.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sym


def _mlp_symbol(num_hidden=16, classes=4):
    data = sym.Variable("data")
    fc1 = sym.FullyConnected(data, num_hidden=num_hidden, name="fc1")
    act = sym.Activation(fc1, act_type="relu", name="relu1")
    fc2 = sym.FullyConnected(act, num_hidden=classes, name="fc2")
    return sym.SoftmaxOutput(fc2, sym.Variable("softmax_label"),
                             name="softmax")


def test_symbol_compose_and_listing():
    out = _mlp_symbol()
    assert out.list_arguments() == [
        "data", "fc1_weight", "fc1_bias", "fc2_weight", "fc2_bias",
        "softmax_label"]
    assert out.list_outputs() == ["softmax_output"]
    internals = out.get_internals()
    assert "relu1" in [s.split("_output")[0] for s in
                       internals.list_outputs()]


def test_symbol_infer_shape():
    out = _mlp_symbol()
    arg_shapes, out_shapes, aux_shapes = out.infer_shape(
        data=(8, 10), softmax_label=(8,))
    args = out.list_arguments()
    d = dict(zip(args, arg_shapes))
    assert d["fc1_weight"] == (16, 10)
    assert d["fc1_bias"] == (16,)
    assert d["fc2_weight"] == (4, 16)
    assert out_shapes == [(8, 4)]


def test_symbol_arithmetic():
    a = sym.Variable("a")
    b = sym.Variable("b")
    c = 2 * a + b / 4 - 3
    ex = c.bind(mx.cpu(), {"a": mx.nd.ones((2, 2)),
                           "b": mx.nd.ones((2, 2)) * 4})
    out = ex.forward()[0].asnumpy()
    onp.testing.assert_allclose(out, onp.full((2, 2), 0.0))


def test_executor_forward_backward():
    out = _mlp_symbol()
    ex = out.simple_bind(mx.cpu(), data=(8, 10), softmax_label=(8,))
    for n in ("fc1_weight", "fc2_weight"):
        ex.arg_dict[n]._adopt(
            mx.nd.random_normal(0, 0.1, shape=ex.arg_dict[n].shape)._data)
    ex.forward(is_train=True,
               data=mx.nd.random_uniform(shape=(8, 10)),
               softmax_label=mx.nd.array([0, 1, 2, 3] * 2))
    assert ex.outputs[0].shape == (8, 4)
    probs = ex.outputs[0].asnumpy()
    onp.testing.assert_allclose(probs.sum(-1), onp.ones(8), rtol=1e-5)
    ex.backward()
    assert float(ex.grad_dict["fc2_weight"].asnumpy().std()) > 0


def test_symbol_json_roundtrip(tmp_path):
    out = _mlp_symbol()
    f = str(tmp_path / "net-symbol.json")
    out.save(f)
    back = mx.sym.load(f)
    assert back.list_arguments() == out.list_arguments()
    assert back.tojson() == out.tojson()


def test_legacy_json_upgrade():
    """Load a v0.8-era JSON graph (param-style schema with
    ``backward_source_id``, two-element inputs and a BatchNorm that
    lists no moving-stat inputs: the legacy_json_util.cc upgrade path)
    from the fixture committed beside the tests."""
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "legacy_v0_8_mlp.json")
    with open(path) as f:
        legacy = mx.sym.load_json(f.read())
    args = legacy.list_arguments()
    assert args[0] == "data"
    assert "fc1_weight" in args
    # the upgrade grew the aux inputs the old schema left out
    assert legacy.list_auxiliary_states() == ["bn1_moving_mean",
                                              "bn1_moving_var"]
    a, o, _ = legacy.infer_shape(data=(4, 100))
    assert o == [(4, 10)]


def test_batchnorm_symbol_aux():
    data = sym.Variable("data")
    bn = sym.BatchNorm(data, name="bn0")
    assert bn.list_auxiliary_states() == ["bn0_moving_mean",
                                          "bn0_moving_var"]
    assert "bn0_gamma" in bn.list_arguments()
    ex = bn.simple_bind(mx.cpu(), data=(2, 3, 4, 4))
    ex.aux_dict["bn0_moving_var"]._adopt(mx.nd.ones((3,))._data)
    ex.arg_dict["bn0_gamma"]._adopt(mx.nd.ones((3,))._data)
    out = ex.forward(is_train=False,
                     data=mx.nd.random_uniform(shape=(2, 3, 4, 4)))
    assert out[0].shape == (2, 3, 4, 4)


def test_module_fit_convergence():
    rng = onp.random.RandomState(7)
    w = rng.randn(10, 4).astype("float32")
    X = rng.randn(256, 10).astype("float32")
    y = (X @ w).argmax(axis=1).astype("float32")
    train = mx.io.NDArrayIter(X, y, batch_size=32, shuffle=True)
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    mod.fit(train, num_epoch=10, optimizer="sgd",
            optimizer_params=(("learning_rate", 0.2),
                              ("momentum", 0.9)),
            initializer=mx.init.Xavier())
    m = mx.metric.Accuracy()
    score = mod.score(train, m)
    assert score[0][1] > 0.85, score


def test_module_predict_and_checkpoint(tmp_path):
    rng = onp.random.RandomState(0)
    X = rng.rand(20, 10).astype("float32")
    y = onp.zeros(20, dtype="float32")
    it = mx.io.NDArrayIter(X, y, batch_size=5)
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=mx.init.Xavier())
    pred = mod.predict(it)
    assert pred.shape == (20, 4)

    prefix = str(tmp_path / "model")
    mod.init_optimizer()
    mod.save_checkpoint(prefix, 3)
    symbol, arg_params, aux_params = mx.model.load_checkpoint(prefix, 3)
    assert symbol.list_arguments() == mod.symbol.list_arguments()
    assert "fc1_weight" in arg_params
    mod2 = mx.mod.Module(symbol, context=mx.cpu())
    mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod2.set_params(arg_params, aux_params)
    pred2 = mod2.predict(it)
    onp.testing.assert_allclose(pred.asnumpy(), pred2.asnumpy(),
                                rtol=1e-5)


def test_bucketing_module():
    """Reference test_bucketing.py pattern: per-length RNN-ish graphs."""
    def sym_gen(seq_len):
        data = sym.Variable("data")
        flat = sym.Reshape(data, shape=(-1, seq_len * 4), name="flat")
        fc = sym.FullyConnected(flat, num_hidden=8, name="fc_shared")
        out = sym.SoftmaxOutput(fc, sym.Variable("softmax_label"),
                                name="softmax")
        return out, ("data",), ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=6,
                                 context=mx.cpu())
    # the fc weight depends on bucket: shared only when shapes agree —
    # use same in-units via padding to max len like reference bucketing
    def batch(seq_len, bs=4):
        from mxnet_tpu.io import DataBatch, DataDesc

        X = mx.nd.random_uniform(shape=(bs, 6, 4)) * 0 + \
            mx.nd.random_uniform(shape=(bs, 6, 4))
        return DataBatch(
            data=[X], label=[mx.nd.array([0] * bs)],
            bucket_key=seq_len,
            provide_data=[DataDesc("data", (bs, 6, 4))],
            provide_label=[DataDesc("softmax_label", (bs,))])

    b = batch(6)
    mod.bind(data_shapes=b.provide_data, label_shapes=b.provide_label)
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer()
    mod.forward(b)
    out1 = mod.get_outputs()[0]
    assert out1.shape == (4, 8)
    mod.backward()
    mod.update()
    # switch to an identically-shaped bucket: params shared
    b2 = batch(6)
    mod.forward(b2)
    assert mod.get_outputs()[0].shape == (4, 8)


def test_module_multi_context_data_parallel():
    """Module(context=[...N devices]) runs ONE SPMD program over a
    'data' mesh (reference: DataParallelExecutorGroup batch slicing,
    executor_group.py:144, grad reduce :304).  Training must converge
    and match the single-device Module bit-for-bit-ish (same init, same
    data order => same losses up to float reassociation)."""
    import jax

    n_dev = len(jax.devices())
    assert n_dev >= 8, "conftest must provide the virtual 8-device mesh"
    rng = onp.random.RandomState(3)
    w = rng.randn(10, 4).astype("float32")
    X = rng.randn(256, 10).astype("float32")
    y = (X @ w).argmax(axis=1).astype("float32")

    def run(ctx):
        train = mx.io.NDArrayIter(X, y, batch_size=32, shuffle=False)
        mod = mx.mod.Module(_mlp_symbol(), context=ctx)
        mod.bind(data_shapes=train.provide_data,
                 label_shapes=train.provide_label)
        mod.init_params(initializer=mx.init.Xavier(rnd_type="gaussian",
                                                   magnitude=1.0))
        # identical start: overwrite with a deterministic seeded init
        arg, aux = mod.get_params()
        r = onp.random.RandomState(11)
        det = {n: mx.nd.array((r.randn(*v.shape) * 0.3)
                              .astype("float32"))
               for n, v in arg.items()}
        mod.set_params(det, aux)
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params=(("learning_rate", 0.2),
                                             ("momentum", 0.9)))
        for _ in range(3):
            train.reset()
            for batch in train:
                mod.forward(batch, is_train=True)
                mod.backward()
                mod.update()
        m = mx.metric.Accuracy()
        train.reset()
        score = mod.score(train, m)[0][1]
        arg, _ = mod.get_params()
        return score, {n: v.asnumpy() for n, v in arg.items()}

    score_multi, params_multi = run([mx.gpu(i) for i in range(8)])
    score_single, params_single = run(mx.cpu())
    assert score_multi > 0.85, score_multi
    for n in params_single:
        onp.testing.assert_allclose(
            params_multi[n], params_single[n], rtol=2e-4, atol=2e-5,
            err_msg=f"param {n} diverged between mesh and single device")


def test_module_multi_context_batch_divisibility():
    mod = mx.mod.Module(_mlp_symbol(), context=[mx.gpu(i)
                                                for i in range(8)])
    mod.bind(data_shapes=[("data", (12, 10))],
             label_shapes=[("softmax_label", (12,))])
    mod.init_params(initializer=mx.init.Xavier())
    import pytest as _pytest
    from mxnet_tpu.io import DataBatch
    with _pytest.raises(mx.base.MXNetError, match="divide"):
        mod.forward(DataBatch(data=[mx.nd.zeros((12, 10))],
                              label=[mx.nd.zeros((12,))]),
                    is_train=False)


def test_feedforward_legacy_api(tmp_path):
    """FeedForward (reference model.py legacy trainer): fit, predict,
    score, save/load."""
    rng = onp.random.RandomState(2)
    X = rng.randn(128, 8).astype("float32")
    w = rng.randn(8, 3).astype("float32")
    y = (X @ w).argmax(axis=1).astype("float32")
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    ff = mx.model.FeedForward(_mlp_symbol(num_hidden=12, classes=3),
                              ctx=mx.cpu(), num_epoch=8,
                              optimizer="sgd", learning_rate=0.3,
                              momentum=0.9,
                              initializer=mx.init.Xavier())
    ff.fit(it)
    preds = ff.predict(it)
    assert preds.shape == (128, 3)
    acc = ff.score(it)
    assert acc > 0.8, acc
    prefix = str(tmp_path / "ff")
    ff.save(prefix, 8)
    ff2 = mx.model.FeedForward.load(prefix, 8, ctx=mx.cpu())
    assert ff2.arg_params is not None
    assert "fc1_weight" in ff2.arg_params


def test_module_install_monitor_records_stats():
    """install_monitor wires mx.mon.Monitor through the executor
    (reference module install_monitor -> set_monitor_callback): a fit
    step under tic/toc yields per-output stats."""
    rng = onp.random.RandomState(3)
    X = rng.rand(64, 10).astype("float32")
    y = (X.sum(axis=1) > 5).astype("float32")
    train = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    mod.bind(data_shapes=train.provide_data,
             label_shapes=train.provide_label)
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd")
    mon = mx.monitor.Monitor(interval=1)
    mod.install_monitor(mon)
    batch = next(iter(train))
    mon.tic()
    mod.forward(batch, is_train=True)
    mod.backward()
    mod.update()
    stats = mon.toc()
    assert stats, "monitor recorded nothing"
    names = {name for (_, name, _) in stats}
    assert any("output" in n for n in names), names


def test_module_install_monitor_before_bind():
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    mon = mx.monitor.Monitor(interval=1)
    mod.install_monitor(mon)          # pre-bind: deferred
    rng = onp.random.RandomState(4)
    X = rng.rand(32, 10).astype("float32")
    y = onp.zeros(32, "float32")
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=mx.init.Xavier())
    mon.tic()
    mod.forward(next(iter(it)), is_train=False)
    assert mon.toc(), "deferred install did not record"
