"""Tests for runtime/engine/monitor/visualization + round-2 advisor
fixes (trainer state save, AdaGrad rule, parameter re-declaration)."""
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.base import MXNetError


def test_runtime_features():
    feats = mx.runtime.Features()
    assert feats.is_enabled("XLA")
    assert feats.is_enabled("CPU")
    assert not feats.is_enabled("TENSORRT")
    with pytest.raises(RuntimeError):
        feats.is_enabled("NO_SUCH_FEATURE")
    names = [f.name for f in mx.runtime.feature_list()]
    assert "TPU" in names and "DIST_KVSTORE" in names


def test_engine_bulk():
    prev = mx.engine.get_bulk_size()
    with mx.engine.bulk(4):
        assert mx.engine.get_bulk_size() == 4
    assert mx.engine.get_bulk_size() == prev


def test_monitor_block():
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(2))
    net.initialize()
    mon = mx.monitor.Monitor(2, pattern=".*output.*", sort=True)
    mon.install(net)
    mon.tic()
    net(nd.ones((2, 4)))
    stats = mon.toc()
    assert stats and all(s[0] == 1 for s in stats)
    # interval=2: next batch not collected
    mon.tic()
    net(nd.ones((2, 4)))
    assert mon.toc() == []


def test_monitor_executor():
    data = mx.sym.Variable("data")
    out = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    exe = out.simple_bind(ctx=mx.cpu(), data=(2, 4))
    mon = mx.monitor.Monitor(1)
    mon.install(exe)
    mon.tic()
    exe.forward(data=nd.ones((2, 4)))
    stats = mon.toc()
    assert any("fc" in s[1] for s in stats)


def test_print_summary_param_count():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=10, name="fc1")
    act = mx.sym.Activation(fc, act_type="relu", name="relu1")
    out = mx.sym.FullyConnected(act, num_hidden=2, name="fc2")
    total = mx.viz.print_summary(out, shape={"data": (1, 4)})
    # fc1: 4*10+10, fc2: 10*2+2 (reference counting incl. data channels)
    assert total == 72


def test_trainer_save_load_states_keeps_moments(tmp_path):
    """Advisor medium: with a dist kvstore the trainer must still save
    the states of the updater that actually applied the updates."""
    net = gluon.nn.Dense(2, in_units=3)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.1}, kvstore="dist_sync")
    with mx.autograd.record():
        loss = net(nd.ones((4, 3))).sum()
    loss.backward()
    trainer.step(4)
    f = str(tmp_path / "t.states")
    trainer.save_states(f)
    assert os.path.getsize(f) > 0

    net2 = gluon.nn.Dense(2, in_units=3)
    net2.initialize()
    trainer2 = gluon.Trainer(net2.collect_params(), "adam",
                             {"learning_rate": 0.1}, kvstore="dist_sync")
    with mx.autograd.record():
        loss = net2(nd.ones((4, 3))).sum()
    loss.backward()
    trainer2.step(4)
    trainer2.load_states(f)
    # adam moments restored (non-zero after one step pre-save)
    states = trainer2._updaters[0].states
    assert states
    m = next(iter(states.values()))
    arr = m[0] if isinstance(m, (list, tuple)) else m
    while isinstance(arr, (list, tuple)):
        arr = arr[0]
    assert float(nd.sum(nd.abs(arr)).asnumpy()) > 0
    # optimizer's live param_dict reattached, not detached clones
    opt = trainer2._updaters[0].optimizer
    assert opt.param_dict
    live = {id(p) for p in trainer2._params}
    assert all(id(p) in live for p in opt.param_dict.values())


def test_updater_states_do_not_pickle_weights():
    """Advisor low: dump_optimizer must not serialize param_dict."""
    import pickle

    net = gluon.nn.Dense(4, in_units=1000)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    with mx.autograd.record():
        loss = net(nd.ones((2, 1000))).sum()
    loss.backward()
    trainer.step(2)
    blob = trainer._updaters[0].get_states(dump_optimizer=True)
    _, opt = pickle.loads(blob)
    assert opt.param_dict == {}


def test_adagrad_matches_reference_rule():
    """Advisor low: hist accumulates raw grad^2; eps inside sqrt; wd
    decoupled."""
    opt = mx.optimizer.create("adagrad", learning_rate=0.5, wd=0.01,
                              eps=1e-7)
    w = nd.array(onp.array([2.0, -3.0], dtype="float32"))
    g = nd.array(onp.array([0.5, 1.0], dtype="float32"))
    state = opt.create_state(0, w)
    opt.update(0, w, g, state)
    g_np = onp.array([0.5, 1.0], dtype="float32")
    w_np = onp.array([2.0, -3.0], dtype="float32")
    hist = g_np * g_np
    expect = w_np - 0.5 * (g_np / onp.sqrt(hist + 1e-7) + 0.01 * w_np)
    onp.testing.assert_allclose(w.asnumpy(), expect, rtol=1e-5)


def test_parameter_redeclaration_conflict_raises():
    """Advisor low: conflicting kwargs on an existing parameter must
    not pass silently."""
    from mxnet_tpu.gluon.parameter import ParameterDict

    pd = ParameterDict(prefix="net_")
    pd.get("weight", shape=(3, 4), dtype="float32")
    # same attributes: fine
    pd.get("weight", shape=(3, 4), dtype="float32")
    with pytest.raises(MXNetError):
        pd.get("weight", dtype="float16")
    with pytest.raises(MXNetError):
        pd.get("weight", grad_req="add")


def test_attach_grad_null_allocates_nothing():
    x = nd.ones((3,))
    x.attach_grad(grad_req="null")
    assert x._grad is None
    with mx.autograd.record():
        y = (x * 2).sum()
    y.backward()
    assert x.grad is None


def test_attach_grad_add_accumulates():
    x = nd.ones((3,))
    x.attach_grad(grad_req="add")
    for _ in range(2):
        with mx.autograd.record():
            y = (x * 3).sum()
        y.backward()
    onp.testing.assert_allclose(x.grad.asnumpy(), [6.0, 6.0, 6.0])


# ------------------------------------- one process per chip (tools/launch.py)
def _load_launcher():
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "launch.py")
    spec = importlib.util.spec_from_file_location("_launch_under_test",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("workers,cpu,chips,refused", [
    (2, False, 1, True),    # the second worker would die on the lock
    (4, False, 4, True),    # no chip is assigned to a worker either
    (1, False, 1, False),   # one worker owns the chip
    (2, True, 1, False),    # --cpu keeps the workers off the chip
    (2, False, 0, False),   # no chip on this host
])
def test_local_launcher_refuses_workers_sharing_a_chip(
        monkeypatch, workers, cpu, chips, refused):
    import argparse
    import sys

    launch = _load_launcher()
    monkeypatch.setattr(launch, "_local_tpu_chips", lambda: chips)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    args = argparse.Namespace(
        num_workers=workers, cpu=cpu, env=[], max_restarts=0,
        command=[sys.executable, "-c", "pass"])
    if refused:
        with pytest.raises(SystemExit, match="device lock"):
            launch._launch_local(args)
        return
    procs = launch._launch_local(args)
    assert len(procs) == workers
    assert [p.wait() for p in procs] == [0] * workers
