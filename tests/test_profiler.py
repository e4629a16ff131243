"""Profiler tests (reference: tests/python/profiling/, test_profiler.py)."""
import json
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler


@pytest.fixture(autouse=True)
def _reset_profiler():
    yield
    profiler.set_state("stop")
    profiler._events.clear()
    profiler._agg.clear()
    profiler.set_config(aggregate_stats=False, continuous_dump=False,
                        filename="profile.json")


def test_op_events_and_dump(tmp_path):
    out = str(tmp_path / "trace.json")
    profiler.set_config(filename=out, aggregate_stats=True)
    profiler.set_state("run")
    a = mx.nd.ones((4, 4))
    b = a + 1
    c = mx.nd.dot(b, b)
    c.wait_to_read()
    profiler.set_state("stop")
    path = profiler.dump()
    assert path == out and os.path.exists(out)
    with open(out) as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"]}
    assert "dot" in names
    assert any(e.get("ph") == "X" for e in trace["traceEvents"])
    # chrome trace must be valid for Perfetto: ts/dur are numbers
    for e in trace["traceEvents"]:
        assert isinstance(e["ts"], (int, float))


def test_aggregate_stats_table():
    profiler.set_config(aggregate_stats=True)
    profiler.set_state("run")
    x = mx.nd.ones((8,))
    for _ in range(3):
        x = x * 2
    profiler.set_state("stop")
    table = profiler.dumps(format="table", sort_by="count")
    assert "_mul_scalar" in table
    stats = json.loads(profiler.dumps(reset=True, format="json"))
    assert stats["device"] is None  # no profile_device run to read
    entry = [s for s in stats["ops"] if s["name"] == "_mul_scalar"][0]
    assert entry["count"] == 3
    assert entry["total_us"] >= entry["max_us"] >= entry["min_us"] > 0
    # reset cleared
    assert json.loads(profiler.dumps(format="json")) == {
        "ops": [], "device": None}


def test_pause_resume():
    profiler.set_state("run")
    profiler.pause()
    _ = mx.nd.ones((2,)) + 1
    profiler.resume()
    _ = mx.nd.ones((2,)) * 3
    profiler.set_state("stop")
    names = [e["name"] for e in profiler._events]
    assert "_mul_scalar" in names
    assert "_plus_scalar" not in names


def test_user_scopes_and_counters(tmp_path):
    out = str(tmp_path / "scopes.json")
    profiler.set_config(filename=out)
    profiler.set_state("run")
    dom = profiler.Domain("train")
    task = dom.new_task("epoch")
    with task:
        with profiler.Event("forward"):
            mx.nd.ones((2,)).wait_to_read()
    ctr = dom.new_counter("samples", 0)
    ctr += 5
    ctr -= 2
    dom.new_marker("checkpoint").mark()
    profiler.set_state("stop")
    profiler.dump()
    with open(out) as f:
        evs = json.load(f)["traceEvents"]
    by_name = {e["name"]: e for e in evs}
    assert by_name["epoch"]["cat"] == "task:train"
    assert by_name["forward"]["cat"] == "event"
    assert by_name["checkpoint"]["ph"] == "i"
    counters = [e for e in evs if e["name"] == "samples"]
    assert [c["args"]["samples"] for c in counters] == [0, 5, 3]


def test_train_step_trace_covers_ops(tmp_path):
    """VERDICT requirement: a dumped trace covering one train step."""
    from mxnet_tpu import gluon, autograd

    out = str(tmp_path / "step.json")
    net = gluon.nn.Dense(4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = mx.nd.array(onp.random.rand(8, 3).astype("float32"))
    y = mx.nd.array(onp.random.rand(8, 4).astype("float32"))
    loss_fn = gluon.loss.L2Loss()
    profiler.set_config(filename=out)
    profiler.set_state("run")
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(8)
    profiler.set_state("stop")
    profiler.dump()
    with open(out) as f:
        evs = json.load(f)["traceEvents"]
    names = {e["name"] for e in evs}
    assert "FullyConnected" in names


def test_bad_config_raises():
    with pytest.raises(mx.MXNetError):
        profiler.set_config(nonsense=1)
    with pytest.raises(mx.MXNetError):
        profiler.set_state("bogus")


def test_set_config_refused_while_running():
    """Reference parity (observability round): reconfiguring
    mid-collection (e.g. switching `filename`) would silently split or
    lose events — refuse, like the C++ profiler does."""
    profiler.set_state("run")
    try:
        with pytest.raises(mx.MXNetError, match="running"):
            profiler.set_config(filename="elsewhere.json")
    finally:
        profiler.set_state("stop")


def test_dump_unfinished_keeps_collecting(tmp_path):
    """dump(finished=False) writes a snapshot and KEEPS collecting;
    dump(finished=True) flushes and stops — they are no longer the
    same operation (observability-round satellite)."""
    out = str(tmp_path / "t.json")
    profiler.set_config(filename=out)
    profiler.set_state("run")
    mx.nd.ones((2,)).wait_to_read()
    profiler.dump(finished=False)
    assert profiler.is_running(), "snapshot dump must keep collecting"
    with open(out) as f:
        n_mid = len(json.load(f)["traceEvents"])
    assert n_mid > 0
    (mx.nd.ones((2,)) * 3).wait_to_read()
    profiler.dump()  # finished: flush everything and stop
    assert not profiler.is_running()
    with open(out) as f:
        n_final = len(json.load(f)["traceEvents"])
    # the final dump carries the FULL timeline (snapshot didn't drain)
    assert n_final > n_mid


def test_merged_telemetry_lane(tmp_path):
    """Observability-round acceptance: telemetry step/feed-wait/
    checkpoint spans and the throughput/loss counter tracks land in
    the SAME Chrome trace as the op events — one Perfetto timeline."""
    from mxnet_tpu import telemetry

    out = str(tmp_path / "merged.json")
    profiler.set_config(filename=out)
    profiler.set_state("run")
    rl = telemetry.reset(str(tmp_path / "run.jsonl"))
    try:
        a = mx.nd.dot(mx.nd.ones((4, 4)), mx.nd.ones((4, 4)))
        a.wait_to_read()
        rl.step(0, 0, 0.004, 32, loss=0.5, synced=True,
                feed_wait_s=0.001)
        rl.compile_event("train_step", {"shape": "(32, 6)",
                                        "dtype": "float32"})
        rl.checkpoint_event("pfx", 1, 0.002, 1234)
    finally:
        telemetry.close()
        profiler.set_state("stop")
    profiler.dump()
    with open(out) as f:
        evs = json.load(f)["traceEvents"]

    # the op lane is there...
    assert "dot" in {e["name"] for e in evs}
    # ...and the telemetry lane rides the same timeline
    tele = [e for e in evs if e.get("cat") == "telemetry"]
    spans = {e["name"] for e in tele if e["ph"] == "X"}
    assert "step 0" in spans
    assert "feed_wait" in spans
    assert "checkpoint" in spans
    assert any(e["ph"] == "i" and e["name"] == "compile:train_step"
               for e in tele)
    counters = {e["name"] for e in tele if e["ph"] == "C"}
    assert {"throughput", "loss"} <= counters
    # the lane is named for Perfetto and pinned to its own tid, and
    # every telemetry event actually sits on that tid
    lane_tid = [e for e in evs if e.get("ph") == "M"
                and e.get("args", {}).get("name") == "telemetry"]
    assert lane_tid, "telemetry lane metadata missing"
    tid = lane_tid[0]["tid"]
    assert all(e["tid"] == tid for e in tele)
    # spans are stamped on the profiler clock (ts >= 0, numbers)
    for e in tele:
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0


def test_lazy_namespace():
    assert mx.profiler is profiler
