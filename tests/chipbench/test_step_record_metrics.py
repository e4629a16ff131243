"""The four readers of the train step's own record
(``device_step_ms.train``, ``host_late_ms.train``, ``step_excess_ms.train``,
``step_call_ms.train``; ``chipbench/step_record.py``): the window's records
picked by time, nothing where the program keeps no record or the count is
not the window's, the arithmetic on a made-up record, and a real step's
record with a caller that pauses."""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as cb  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402

NAMES = ("device_step_ms.train", "host_late_ms.train",
         "step_excess_ms.train", "step_call_ms.train")

#: four window steps of a device that takes 10 ms a step, 2 in flight,
#: a call of 1 ms; the host hands over the fourth 5 ms after the device
#: finished the third.  Before the window a warm-up step, after it two
#: steps of a traced tail, 1 s late and slow.
WARM = [(-0.1, -0.099, 0.0)]
WINDOW = [(1.000, 1.001, 1.010), (1.002, 1.003, 1.020),
          (1.011, 1.012, 1.030), (1.034, 1.035, 1.045)]
TAIL = [(3.0, 3.5, 4.0), (3.6, 3.7, 5.0)]


def _records(steps):
    return [{"n": n, "t_enter": e, "t_return": r, "t_done": d, "gc_s": 0.0}
            for n, (e, r, d) in enumerate(steps)]


def _run(steps=4, t_start=0.9995, last=1.047):
    return {"window": {"steps": steps, "t_start": t_start,
                       "done_at": np.array([1.01, 1.03, last])}}


def _read(name, run):
    return cb.load_module("metrics", name).read(run)


@pytest.fixture
def recorded(monkeypatch):
    def plant(steps):
        monkeypatch.setattr(profiler, "step_records",
                            lambda last=None: _records(steps))
    plant(WARM + WINDOW + TAIL)
    return plant


@pytest.mark.parametrize("name,expected", [
    # intervals 10, 10, 15 ms; the fourth waited 5 ms for its hand-over
    ("device_step_ms.train", 10.0),
    ("host_late_ms.train", 5.0 / 3),
    ("step_excess_ms.train", 35.0 / 3 - 10.0),
    ("step_call_ms.train", 1.0),
])
def test_reader_arithmetic_on_the_window_s_records(recorded, name,
                                                   expected):
    assert _read(name, _run()) == pytest.approx(expected, rel=1e-6)


@pytest.mark.parametrize("name", NAMES)
def test_the_tail_and_the_warm_up_drop_out_by_time(recorded, name):
    """The same window read without its tail or warm-up reads the same;
    a window that reached into the tail would count 6 and read nothing."""
    before = _read(name, _run())
    recorded(WINDOW)
    assert _read(name, _run()) == pytest.approx(before, rel=1e-12)
    recorded(WARM + WINDOW + TAIL)
    assert _read(name, _run(last=3.7)) is None


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read(recorded, monkeypatch, name):
    assert _read(name, _run(steps=5)) is None  # not the window's count
    assert _read(name, {"window": dict(_run()["window"],
                                       t_start=None)}) is None
    recorded(WINDOW[:1])
    assert _read(name, _run(steps=1)) is None  # no consecutive pair
    recorded([])
    assert _read(name, _run()) is None
    # the program of the PR before: no record at all
    monkeypatch.delattr(profiler, "step_records")
    assert _read(name, _run()) is None


#: the entries the four readers take in ``BENCHMARK.json``'s ``per_layer``
#: once ``test_nemotron3.py`` lets an entry follow the token cell's six
ENTRIES = [{"name": name, "unit": unit, "better": "lower",
            "source": "program_span",
            "layer": "train step (parallel/__init__.py make_train_step)",
            "moves": "images_per_s",
            "workloads": ["vgg16_train", "vgg16_train_4chip",
                          "nemotron3_nano_train"]}
           for name, unit in zip(NAMES, ("ms", "ms/step", "ms/step",
                                         "ms/step"))]


def test_readers_go_through_run_py(recorded):
    got = cb.read_metrics(ENTRIES, _run())
    assert {k: v["unit"] for k, v in got.items()} == {
        "device_step_ms.train": "ms", "host_late_ms.train": "ms/step",
        "step_excess_ms.train": "ms/step", "step_call_ms.train": "ms/step"}
    # the parent's program keeps no record: the line leaves all four out
    recorded([])
    assert cb.read_metrics(ENTRIES, _run()) == {}


def test_a_real_step_s_record_puts_a_pause_on_the_host():
    """A compiled step on the CPU, driven as the benchmark's loop drives
    it (2 in flight), with the caller asleep for 0.3 s before one step:
    the host side reads the pause, the device side does not."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn

    net = nn.Dense(16, in_units=16)
    net.initialize()
    step, p, o = parallel.make_train_step(
        net, gluon.loss.L2Loss(), optimizer="sgd", learning_rate=0.01)
    x, y = jnp.ones((8, 16)), jnp.zeros((8, 16))
    key = jax.random.key(0)
    loss, p, o = step(p, o, x, y, key, 1.0)  # compiled outside the window
    float(loss)
    pending, done_at, n = [], [], 12
    t_start = time.perf_counter()
    for t in range(n):
        if t == 6:
            time.sleep(0.3)
        loss, p, o = step(p, o, x, y, key, float(t + 2))
        pending.append(loss)
        if len(pending) > 2:
            float(pending.pop(0))
            done_at.append(time.perf_counter())
    while pending:
        float(pending.pop(0))
        done_at.append(time.perf_counter())
    run = {"window": {"steps": n, "t_start": t_start,
                      "done_at": np.array(done_at)}}
    late = _read("host_late_ms.train", run)
    excess = _read("step_excess_ms.train", run)
    assert late * (n - 1) >= 250.0
    assert excess * (n - 1) >= 250.0
    # the device's own time stays a CPU step's, far from the pause
    assert _read("device_step_ms.train", run) < 50.0
    assert 0.0 < _read("step_call_ms.train", run) < 300.0
