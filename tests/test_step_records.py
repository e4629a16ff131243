"""The compiled train step's record of its own calls
(``parallel.HostStep`` -> ``profiler.StepWatch`` -> ``profiler.step_records``):
one record a call with its stamps in order, a pause of the caller on the
host side of the split, no record for a traced call, the watcher's thread
ended with its step and at exit, the counters read as before, and the
"Slowest steps" table of ``dumps()``."""
import collections
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as cb  # noqa: E402
from mxnet_tpu import gluon, parallel, profiler  # noqa: E402
from mxnet_tpu.gluon import nn  # noqa: E402

KEY = jax.random.key(0)


def _dense_step(net=None):
    net = net or nn.Dense(8, in_units=8)
    net.initialize()
    return parallel.make_train_step(net, gluon.loss.L2Loss(),
                                    optimizer="sgd", learning_rate=0.01)


def _drive(step, p, o, n, sleep_before=None, x=None):
    """``n`` steps as a training loop that reads each loss two steps
    later drives them."""
    x = jnp.ones((4, 8)) if x is None else x
    y = jnp.zeros((4, 8))
    pending = []
    for t in range(n):
        if t == sleep_before:
            time.sleep(0.3)
        loss, p, o = step(p, o, x * (t + 1), y, KEY, float(t + 1))
        pending.append(loss)
        if len(pending) > 2:
            float(pending.pop(0))
    for loss in pending:
        float(loss)
    return p, o


def _split(recs):
    """Host late, device and interval of each step after the first."""
    out = []
    for a, b in zip(recs, recs[1:]):
        out.append((max(0.0, b["t_return"] - a["t_done"]),
                    b["t_done"] - max(a["t_done"], b["t_return"]),
                    b["t_done"] - a["t_done"]))
    return out


def test_every_call_leaves_one_record_with_its_stamps_in_order():
    step, p, o = _dense_step()
    _drive(step, p, o, 7)
    recs = profiler.step_records(last=7)
    assert [r["n"] for r in recs] == list(range(7))
    for r in recs:
        assert r["t_enter"] <= r["t_return"] <= r["t_done"]
        assert r["gc_s"] >= 0.0
        assert all(isinstance(r[k], (int, float)) for k in r)
    done = [r["t_done"] for r in recs]
    assert done == sorted(done)
    # the clock is the profiler's own
    now = profiler.now_us() * 1e-6 + profiler._t0
    assert recs[-1]["t_done"] <= now


def test_a_caller_s_pause_reads_as_host_late():
    step, p, o = _dense_step()
    p, o = _drive(step, p, o, 3)  # compiled and warm
    _drive(step, p, o, 10, sleep_before=5)
    split = _split(profiler.step_records(last=10))
    late, device, _ = split[4]  # the sixth step, after the pause
    assert late >= 0.25
    assert device < 0.25
    assert np.median([d for _, d, _ in split]) < 0.05


def test_a_traced_loss_leaves_no_record():
    step, p, o = _dense_step()
    p, o = _drive(step, p, o, 2)
    newest = profiler._step_counters[-1]
    x, y = jnp.ones((4, 8)), jnp.zeros((4, 8))
    traced = jax.jit(lambda p, o: step(p, o, x, y, KEY, 1.0)[0])
    assert np.isfinite(float(traced(p, o)))
    assert profiler._step_counters[-1] is newest


class _Counted(nn.Dense):
    """A dense layer that counts as a mixture of experts does: of 4
    assignments a step, the batch's mean held."""

    step_counters = {"moe_assignments": "sum", "moe_assignments_held": "sum"}

    def hybrid_forward(self, F, x, weight, bias=None):
        profiler.count("moe_assignments", jnp.float32(4.0))
        profiler.count("moe_assignments_held", jnp.mean(x._data))
        return super().hybrid_forward(F, x, weight, bias)


def test_counters_ride_in_the_record_and_read_as_before():
    step, p, o = _dense_step(_Counted(8, in_units=8))
    assert set(o["_counters"]) == {"moe_assignments",
                                   "moe_assignments_held"}
    # a warm-up step, the window's 3, a traced tail of 1
    _drive(step, p, o, 5, x=jnp.full((4, 8), 0.1))
    assert profiler.step_counters() == pytest.approx(
        {"moe_assignments": 4.0, "moe_assignments_held": 0.5})
    held = [c["moe_assignments_held"] for c in
            profiler.step_counters(last=5)]
    assert held == pytest.approx([0.1, 0.2, 0.3, 0.4, 0.5])
    # the records and the counters are one ring
    assert [r["n"] for r in profiler.step_records(last=5)] == list(range(5))
    run = {"window": {"steps": 3}, "traffic": {"trace_steps": 1},
           "trace": {"steps": 1}}
    share = cb.load_module("metrics", "moe_held_pct.train").read(run)
    assert share == pytest.approx(100.0 * (0.2 + 0.3 + 0.4) / 4 / 3)
    # a step that counts nothing leaves no counters among them
    plain, q, s = _dense_step()
    _drive(plain, q, s, 2)
    assert profiler.step_counters() == pytest.approx(
        {"moe_assignments": 4.0, "moe_assignments_held": 0.5})


def test_dumps_ends_with_the_slowest_steps(monkeypatch):
    # this test's steps alone in the ring
    monkeypatch.setattr(profiler, "_step_counters",
                        collections.deque(maxlen=profiler._RING_STEPS))
    step, p, o = _dense_step()
    p, o = _drive(step, p, o, 3)
    _drive(step, p, o, 8, sleep_before=4)
    rows = profiler._slowest_steps()
    assert len(rows) == 5
    interval, n, late, device, call, gc_s = rows[0]
    assert n == 3 + 4 and interval >= 0.25 and late >= 0.25
    assert device < 0.25 and call >= 0.0 and gc_s >= 0.0
    table = profiler.dumps()
    assert "Slowest steps (ms)" in table
    assert table.splitlines()[-5].split()[0] == str(n)


def test_steps_driven_from_many_threads_each_keep_every_record():
    """Eight threads, each with a step of its own, switching as often as
    the interpreter allows: every call of every step has its record, in
    its order, stamped."""
    n_threads, n_steps = 8, 15
    steps = [_dense_step() for _ in range(n_threads)]
    errors = []

    def drive(i):
        try:
            _drive(*steps[i], n_steps)
        except Exception as e:  # noqa: BLE001 -- reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=drive, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads) and not errors
    finally:
        sys.setswitchinterval(interval)
    profiler.step_records(last=n_threads * n_steps)  # all stamped
    for step, _, _ in steps:
        mine = [r for r in profiler._step_counters
                if r.watch is step._record]
        assert [r.n for r in mine] == list(range(n_steps))
        assert all(r.t_enter <= r.t_return <= r.t_done for r in mine)


WATCHER = textwrap.dedent("""
    import atexit, gc, os, threading
    import jax, jax.numpy as jnp
    from mxnet_tpu import gluon, parallel, profiler
    from mxnet_tpu.gluon import nn

    def alive():
        return sum(t.name == "HostStep-done" for t in threading.enumerate())

    def build():
        net = nn.Dense(4, in_units=4)
        net.initialize()
        return parallel.make_train_step(net, gluon.loss.L2Loss(),
                                        optimizer="sgd")

    def gone_at_exit():
        if alive():
            os._exit(3)

    # registered before the program's own exit hook, so it runs after it
    atexit.register(gone_at_exit)
    x, y, key = jnp.ones((2, 4)), jnp.zeros((2, 4)), jax.random.key(0)
    step, p, o = build()
    assert alive() == 0  # started by the first call, not the build
    for t in range(3):
        loss, p, o = step(p, o, x, y, key, float(t + 1))
    assert alive() == 1
    del step
    gc.collect()
    assert alive() == 0, "the watcher outlived its step"
    # a sentinel alone ends it too
    step, p, o = build()
    loss, p, o = step(p, o, x, y, key, 1.0)
    step._record.close()
    assert alive() == 0, "the watcher outlived its sentinel"
    # and one left running at exit is ended there
    step, p, o = build()
    loss, p, o = step(p, o, x, y, key, 1.0)
    assert alive() == 1
    print("ok", len(profiler.step_records()))
""")


def test_the_watcher_ends_with_its_step_and_at_exit():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    done = subprocess.run([sys.executable, "-c", WATCHER], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-4000:]
    assert done.stdout.split() == ["ok", "5"]
    assert "FATAL" not in done.stderr
