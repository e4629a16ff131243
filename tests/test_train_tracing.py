"""The train path names itself (ISSUE 25).

(a) the compiled step carries the phase scopes (``mx_forward`` with the
    gluon blocks inside it, its transpose for backward, ``mx_loss``,
    ``mx_guard``, ``mx_optimizer``, ``mx_exchange`` on every
    collective);
(b) under a profiler session the feed and the step leave their ``mx_*``
    host spans on their own threads' lines, and with a RunLog armed the
    same spans reach it with a parent;
(c) the feed's counters say where the feed's time went;
(d) ``mx.profiler.dumps()`` reads a recorded v5e trace back: phase
    seconds sum to the device's busy time, an idle gap goes to the
    ``mx_*`` span that covers it.
"""
import glob
import gzip
import json
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, profiler, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.io.device_feed import DeviceFeedIter
from mxnet_tpu.resilience import faultsim
from mxnet_tpu.telemetry import schema, tracing

_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
BLOCKS = ("tiny0_conv2d0", "tiny0_pool0", "tiny0_dense0")


def _net():
    with nn.default_layout("NHWC"):
        net = nn.HybridSequential(prefix="tiny0_")
        with net.name_scope():
            net.add(nn.Conv2D(8, 3, padding=1, activation="relu"),
                    nn.MaxPool2D(2), nn.Flatten(), nn.Dense(10))
    net.initialize()
    net(mx.nd.ones((8, 8, 8, 3)))
    return net


def _step(**kw):
    return parallel.make_train_step(
        _net(), gluon.loss.SoftmaxCrossEntropyLoss(), optimizer="sgd",
        learning_rate=0.1, momentum=0.9, compute_dtype="bfloat16", **kw)


def _batch():
    return jnp.ones((8, 8, 8, 3)), jnp.zeros((8,))


def _instructions(text):
    """``(opcode, op_name)`` of every instruction of a compiled text."""
    out = []
    for line in text.splitlines():
        if " = " not in line:
            continue
        scope = _OP_NAME.search(line)
        out.append((profiler._opcode(line),
                    scope.group(1) if scope else ""))
    return out


# ------------------------------------------------- (a) the compiled step
@pytest.fixture(scope="module", params=["plain", "nan_guard",
                                        "dynamic_scale", "ps"])
def compiled(request):
    kw = {"plain": {}, "nan_guard": {"nan_guard": True},
          "dynamic_scale": {"loss_scale": "dynamic"},
          "ps": {"optimizer_sharding": "ps"}}[request.param]
    if request.param == "ps":
        kw["mesh"] = parallel.get_mesh((4,), ("data",),
                                       devices=jax.devices()[:4])
    step, p, o = _step(**kw)
    x, y = _batch()
    lowered = step.lower(p, o, x, y, jax.random.key(0), 1.0)
    return request.param, lowered.as_text(debug_info=True), \
        lowered.compile().as_text()


def test_step_carries_phase_and_block_scopes(compiled):
    arm, lowered, text = compiled
    for where in (lowered, text):
        for block in BLOCKS:
            assert re.search(r"jvp\(mx_forward\)/tiny0/" + block + "/",
                             where), (arm, block)
            assert re.search(
                r"transpose\(jvp\(mx_forward\)\)/tiny0/" + block + "/",
                where), (arm, block)
        assert "jvp(mx_loss)/" in where
        assert "transpose(jvp(mx_loss))/" in where
        assert "mx_optimizer/" in where
        assert ("mx_guard/" in where) == (arm in ("nan_guard",
                                                  "dynamic_scale"))
        assert ("mx_exchange/" in where) == (arm == "ps")


def test_products_and_pooling_sit_under_a_block(compiled):
    _, lowered, text = compiled
    # as the program lowered them: every one
    # (a region's arguments carry the bare primitive's name: no path)
    heavy = [scope for scope in re.findall(r'= loc\("([^"]+)"', lowered)
             if "/" in scope and scope.rsplit("/", 1)[-1] in (
                 "conv_general_dilated", "dot_general",
                 "select_and_scatter", "select_and_scatter_add")]
    # as XLA compiled them: every one that kept its metadata (XLA:CPU
    # rewrites the weight gradient's convolution into a new instruction
    # without any)
    heavy += [scope for code, scope in _instructions(text) if scope
              and code in ("convolution", "dot", "select-and-scatter")]
    assert len(heavy) >= 10  # conv and dense both ways, pooling backward
    for scope in heavy:
        phase, block = profiler._phase_and_block(scope)
        assert phase in ("forward", "backward"), scope
        assert block is not None and block.startswith("tiny0_"), scope


def test_paired_arms_sit_under_their_block(monkeypatch):
    """The same tiny net as a TPU lowers it (ISSUE 26): the W-paired
    convolution and the pooling arm's reduction, ``max`` and
    ``select_n`` open no scope of their own, so each sits under its
    ``tiny0_*`` block, forward and backward.  (The probe is answered
    here, and the arms' least map size set aside: the net is 8x8.)"""
    from mxnet_tpu.ops import conv, kernel_target

    monkeypatch.setattr(kernel_target, "on_tpu", lambda: True)
    monkeypatch.setattr(conv, "_WPACK_MIN_PIXELS", 0)
    before = kernel_target.packed_counts()
    step, p, o = _step()
    x, y = _batch()
    lowered = step.lower(p, o, x, y, jax.random.key(0), 1.0).as_text(
        debug_info=True)
    after = kernel_target.packed_counts()
    assert after["Convolution"] > before.get("Convolution", 0)
    assert after["Pooling"] > before.get("Pooling", 0)
    assert "reduce_window" not in lowered
    assert "select_and_scatter" not in lowered
    assert "tensor<8x8x4x6xbf16>" in lowered  # the paired image
    seen = set()
    for scope in re.findall(r'= loc\("([^"]+)"', lowered):
        phase, block = profiler._phase_and_block(scope)
        if block in ("tiny0_conv2d0", "tiny0_pool0"):
            seen.add((phase, block, scope.rsplit("/", 1)[-1]))
        elif "/" in scope:  # nothing of the arms outside their blocks
            assert scope.rsplit("/", 1)[-1] != "conv_general_dilated", scope
    for expected in [("forward", "tiny0_conv2d0", "conv_general_dilated"),
                     ("backward", "tiny0_conv2d0", "conv_general_dilated"),
                     ("forward", "tiny0_pool0", "reduce"),
                     ("forward", "tiny0_pool0", "max"),
                     ("backward", "tiny0_pool0", "select_n")]:
        assert expected in seen, (expected, sorted(seen))


def test_every_collective_is_under_mx_exchange(compiled):
    arm, _, text = compiled
    found = [(code, scope) for code, scope in _instructions(text)
             if code.replace("-start", "") in COLLECTIVES]
    assert bool(found) == (arm == "ps")
    for code, scope in found:
        assert profiler._phase_and_block(scope)[0] == "exchange", \
            (code, scope)


#: what a v5e's compiler leaves of the exchange's scopes (lines of
#: vgg16_train_4chip's compiled step, shortened): the passes that
#: rewrite a collective drop the op_name the program gave it
_REWRITTEN = """HloModule jit__scoped_step, is_scheduled=true

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[]{:T(128)} parameter(0), metadata={op_name="reduce_scatter"}
  %b = f32[]{:T(128)} parameter(1), metadata={op_name="reduce_scatter"}
  ROOT %add.2 = f32[]{:T(128)} add(%a, %b), metadata={op_name="add"}
}

%all-reduce-scatter (input: f32[4096,4096]) -> f32[1120,4096] {
  %input = f32[4096,4096]{1,0:T(8,128)} parameter(0)
  %constant.4 = f32[] constant(0)
  %pad.62 = f32[4480,4096]{1,0:T(8,128)} pad(%input, %constant.4), padding=0_384x0_0
  %all-reduce.24 = f32[4480,4096]{1,0:T(8,128)} all-reduce(%pad.62), channel_id=23, replica_groups={{0,1,2,3}}, to_apply=%region_0.1
  %partition-id.3 = u32[] partition-id()
  ROOT %dynamic-slice.14 = f32[1120,4096]{1,0:T(8,128)S(1)} dynamic-slice(%all-reduce.24, %partition-id.3, %partition-id.3), dynamic_slice_sizes={1120,4096}
}

%fused_computation.9 (p: f32[1408,4096]) -> f32[1024,4096] {
  %p = f32[1408,4096]{1,0:T(8,128)S(1)} parameter(0)
  ROOT %dynamic-slice.3 = f32[1024,4096]{1,0:T(8,128)} slice(%p), slice={[0:1024], [0:4096]}
}

ENTRY %main.59_spmd (param.1: f32[4096,4096]) -> f32[1024,4096] {
  %param.1 = f32[4096,4096]{1,0:T(8,128)} parameter(0)
  %fusion.1 = f32[1120,4096]{1,0:T(8,128)S(1)} fusion(%param.1), kind=kCustom, calls=%all-reduce-scatter
  %slice.138 = f32[288,4096]{1,0:T(8,128)S(1)} slice(%fusion.1), slice={[832:1120], [0:4096]}
  %collective-permute-start = (f32[288,4096]{1,0:T(8,128)S(1)}, f32[288,4096]{1,0:T(8,128)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%slice.138), channel_id=4, source_target_pairs={{0,1},{1,2},{2,3}}
  %collective-permute-done = f32[288,4096]{1,0:T(8,128)S(1)} collective-permute-done(%collective-permute-start)
  %all-reduce.26 = (f32[555328]{0:T(1024)S(1)}, f32[1000]{0:T(1024)S(1)}) all-reduce(%param.1, %param.1), channel_id=18, replica_groups={{0,1,2,3}}, to_apply=%region_0.1
  %all-reduce.25 = f32[512]{0:T(512)} all-reduce(%param.1), channel_id=19, to_apply=%region_0.1, metadata={op_name="jit(_scoped_step)/shard_map/mx_guard/mx_exchange/psum"}
  %get-tuple-element.9 = f32[1000]{0:T(1024)S(1)} get-tuple-element(%all-reduce.26), index=1
  %concatenate.1 = f32[1408,4096]{1,0:T(8,128)S(1)} concatenate(%collective-permute-done, %fusion.1), dimensions={0}
  ROOT %fusion.9 = f32[1024,4096]{1,0:T(8,128)} fusion(%concatenate.1), kind=kLoop, calls=%fused_computation.9
}
"""


def test_a_collective_the_compiler_rewrote_still_reads_as_exchange():
    """``dumps()`` reads scopes from the compiled text.  The compiler
    decomposes a ``psum_scatter`` (an all-reduce and a slice, or its
    fused ``all-reduce-scatter`` with a permute that realigns rows) and
    combines neighbouring all-reduces, and gives the new instructions
    no ``op_name``: they, and an unnamed fusion that holds one, still
    go under ``mx_exchange``; a reference to a collective and the
    compiler's own padding and slicing around it do not."""
    table = profiler._scope_table(_REWRITTEN)

    def phase(name):
        return profiler._phase_and_block(table.get(name, ("",))[0])[0]

    for name in ("all-reduce.24", "fusion.1", "collective-permute-start",
                 "collective-permute-done", "all-reduce.26",
                 "all-reduce.25"):
        assert phase(name) == "exchange", (name, table.get(name))
    assert table["fusion.1"][1] == ("exchange",)
    assert table["all-reduce.25"][0].endswith("mx_guard/mx_exchange/psum")
    for name in ("get-tuple-element.9", "slice.138", "concatenate.1",
                 "fusion.9", "pad.62", "param.1"):
        assert phase(name) == "unscoped", (name, table.get(name))


def test_eager_call_opens_no_scope(monkeypatch):
    opened = []
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: opened.append(name) or real(name))
    net = _net()
    opened.clear()
    net(mx.nd.ones((8, 8, 8, 3)))
    assert not opened
    jax.eval_shape(lambda x: net(mx.nd.NDArray(x))._data,
                   jax.ShapeDtypeStruct((8, 8, 8, 3), jnp.float32))
    assert set(BLOCKS) <= set(opened)


# ------------------------------------------------- (b) the host's spans
def _pool(n):
    return [(np.full((8, 8, 8, 3), i, "float32"),
             np.zeros((8,), "float32")) for i in range(n)]


def _three_steps(step, p, o):
    feed = DeviceFeedIter(iter(_pool(3)), depth=2)
    try:
        for t, (x, y) in enumerate(feed, 1):
            loss, p, o = step(p, o, x._data, y._data, jax.random.key(0),
                              float(t))
        float(loss)
    finally:
        feed.close()
    return p, o  # the step donated the ones that came


def test_host_spans_land_in_the_profilers_trace(tmp_path):
    step, p, o = _step()
    x, y = _batch()
    loss, p, o = step(p, o, x, y, jax.random.key(0), 1.0)  # compiled
    float(loss)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(os.fspath(tmp_path), profiler_options=options)
    try:
        _three_steps(step, p, o)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    lines = {}  # span name -> {index of its thread's line}
    spans = []
    planes = jax.profiler.ProfileData.from_file(path).planes
    host_lines = [ln for pl in planes if pl.name.startswith("/host:")
                  for ln in pl.lines]
    for k, line in enumerate(host_lines):
        for e in line.events:
            if e.name.startswith("mx_"):
                lines.setdefault(e.name, set()).add(k)
                spans.append((e.start_ns, e.name, dict(e.stats)))
    assert set(lines) == {"mx_feed_source", "mx_feed_h2d", "mx_feed_wait",
                          "mx_step"}
    producer, consumer = lines["mx_feed_h2d"], lines["mx_step"]
    assert len(producer) == len(consumer) == 1 and producer != consumer
    assert lines["mx_feed_source"] == producer
    assert lines["mx_feed_wait"] == consumer
    spans.sort()
    on_consumer = [(n, st) for _, n, st in spans
                   if n in ("mx_feed_wait", "mx_step")]
    # each step after the wait for its batch; the fourth wait meets the
    # end of the source
    assert [n for n, _ in on_consumer] == [
        "mx_feed_wait", "mx_step"] * 3 + ["mx_feed_wait"]
    nums = [st["step_num"] for n, st in on_consumer if n == "mx_step"]
    assert nums == [nums[0], nums[0] + 1, nums[0] + 2] and nums[0] >= 1
    assert all("depth" in st for n, st in on_consumer
               if n == "mx_feed_wait")
    h2d = [st for _, n, st in spans if n == "mx_feed_h2d"]
    assert [st["bytes"] for st in h2d] == [8 * 8 * 8 * 3 * 4 + 8 * 4] * 3


def test_host_spans_reach_an_armed_runlog_with_a_parent(tmp_path):
    """On the RunLog's sampled steps alone, and behind its next
    flushing record: the step path pays no syscall for them."""
    step, p, o = _step()
    path = os.fspath(tmp_path / "run.jsonl")
    rl = telemetry.reset(path)
    rl.sample = 2
    try:
        root = tracing.mint()
        with tracing.use(root):
            _three_steps(step, p, o)
    finally:
        telemetry.close()
        telemetry.reset(None)
    with open(path) as f:
        recs, problems = schema.validate_lines(f)
    assert not problems, problems[:3]
    spans = [r for r in recs if r["type"] == "span"
             and r["name"].startswith("mx_")]
    by_name = {}
    for r in spans:
        by_name.setdefault(r["name"], []).append(r)
    # batches and steps 0 and 2 of 0..2; the fourth fetch and the
    # fourth wait meet the end of the source, which is no error
    assert {k: len(v) for k, v in by_name.items()} == {
        "mx_feed_source": 2, "mx_feed_h2d": 2, "mx_feed_wait": 2,
        "mx_step": 2}
    assert not any("error" in r.get("attrs", {}) for r in spans)
    # the producer's too: the feed hands its starter's context over
    assert all(r["trace_id"] == root.trace_id
               and r["parent_span_id"] == root.span_id for r in spans)
    assert [r["attrs"]["step_num"] for r in by_name["mx_step"]] == [0, 2]


def test_profiled_step_notes_its_compiled_text(tmp_path):
    """Under ``mx.profiler`` with ``profile_device`` the step hands the
    profiler the way to its compiled text, which is where ``dumps()``
    finds the scopes of a v5e's traced instructions; on the CPU there
    is no device plane, so ``dumps()`` stays the op table."""
    step, p, o = _step()
    profiler.set_config(profile_device=True, aggregate_stats=True,
                        tensorboard_logdir=os.fspath(tmp_path))
    try:
        profiler.set_state("run")
        try:
            p, o = _three_steps(step, p, o)
        finally:
            profiler.set_state("stop")
        (key, text_fn), = profiler._programs.items()
        assert key[0] == "train_step" and callable(text_fn)
        out = json.loads(profiler.dumps(format="json"))
        assert out["device"] is None and isinstance(out["ops"], list)
        text = profiler._programs[key]
        assert text.startswith("HloModule jit__scoped_step")
        assert "jvp(mx_forward)/tiny0/tiny0_conv2d0/" in text
        # a later run without profile_device starts from no program
        # and no trace: the device section is a run's own
        profiler.set_config(profile_device=False)
        profiler.set_state("run")
        try:
            assert not profiler._programs
            _three_steps(step, p, o)  # tracing off: it notes nothing
        finally:
            profiler.set_state("stop")
        assert not profiler._programs
        assert profiler._device_logdir is None
    finally:
        profiler.set_config(profile_device=False, aggregate_stats=False,
                            tensorboard_logdir=None)
        profiler.dumps(reset=True)


# ------------------------------------------------- (c) the feed's counters
def _slow(items, seconds):
    for item in items:
        time.sleep(seconds)
        yield item


def test_slow_source_shows_in_source_wait_and_an_empty_queue():
    feed = DeviceFeedIter(_slow(_pool(6), 0.03), depth=2)
    n = sum(1 for _ in feed)
    st = feed.stats()
    feed.close()
    assert n == st["batches"] == 6
    assert st["source_wait_s"] >= 6 * 0.03 * 0.9
    assert 0 < 5 * st["producer_busy_s"] < st["source_wait_s"]
    assert st["depth_sum"] / st["batches"] <= 0.5


def test_slow_consumer_shows_in_a_full_queue():
    feed = DeviceFeedIter(iter(_pool(8)), depth=2)
    time.sleep(0.3)  # the producer fills the queue and then waits
    for _ in feed:
        time.sleep(0.03)
    st = feed.stats()
    feed.close()
    assert st["batches"] == 8
    assert st["depth_sum"] / st["batches"] >= 1.5  # of depth 2
    assert st["consumer_wait_s"] < 0.03
    # the harness subtracts every key: plain numbers, and the keys the
    # benchmark's readers were written to
    assert all(isinstance(v, (int, float)) for v in st.values())
    assert set(st) == {"batches", "epochs", "consumer_wait_s",
                       "producer_busy_s", "h2d_bytes", "source_wait_s",
                       "depth_sum"}


def test_busy_is_stamped_off_the_producing_path(monkeypatch):
    """``producer_busy_s`` ends when the batch is on the device, and
    the wait for that is a watcher's: a slow copy holds up neither the
    producer's next fetch nor the consumer."""
    from mxnet_tpu.io import device_feed

    waited = []

    class Slow:  # an array whose copy takes 50 ms after device_put
        nbytes = 4

        def block_until_ready(self):
            waited.append(threading.current_thread().name)
            time.sleep(0.05)

    monkeypatch.setattr(device_feed, "as_device_batch",
                        lambda item, *a: [Slow()])
    t0 = time.perf_counter()
    feed = DeviceFeedIter(iter(range(6)), depth=2)
    n = sum(1 for _ in feed)
    took = time.perf_counter() - t0
    assert n == 6 and took < 0.15  # six copies of 50 ms, nobody waited
    deadline = time.perf_counter() + 5.0
    while feed.stats()["producer_busy_s"] < 0.28 \
            and time.perf_counter() < deadline:
        time.sleep(0.05)
    time.sleep(0.1)  # a seventh stamp would come now
    st = feed.stats()
    feed.close()
    assert set(waited) == {"DeviceFeedIter-ready"} and len(waited) == 6
    # the union of the six copies (0.3 s), not the sum of each one's
    # time since its batch was in hand (1.05 s)
    assert 0.28 <= st["producer_busy_s"] <= 0.6


def test_failing_transfer_says_its_attempts_on_the_span(tmp_path):
    path = os.fspath(tmp_path / "run.jsonl")
    telemetry.reset(path).sample = 1
    faultsim.reset("feed.h2d:raise@1;feed.h2d:raise@3")
    try:
        feed = DeviceFeedIter(iter(_pool(4)), depth=2)
        n = sum(1 for _ in feed)
        feed.close()
    finally:
        faultsim.reset("")
        telemetry.close()
        telemetry.reset(None)
    assert n == 4
    with open(path) as f:
        recs, _ = schema.validate_lines(f)
    h2d = [r["attrs"] for r in recs if r.get("name") == "mx_feed_h2d"]
    assert [a.get("attempt") for a in h2d] == [2, 2, None, None]


# ------------------------------------------------- (d) dumps() on a trace
def _xspace_text(planes):
    """An ``XSpace`` text proto of recorded planes (``tools/
    cut_device_trace.py``): names and stats interned per plane."""
    out = []
    for pid, plane in enumerate(planes, 1):
        names, stats = {}, {}
        body = []
        for lid, line in enumerate(plane["lines"], 1):
            base = min((e[1] for e in line["events"]), default=0)
            body.append(f'lines {{ id: {lid} name: {json.dumps(line["name"])}'
                        f' timestamp_ns: {int(base)}')
            for name, start, dur, st in line["events"]:
                mid = names.setdefault(name, len(names) + 1)
                fields = "".join(
                    f" stats {{ metadata_id: "
                    f"{stats.setdefault(k, len(stats) + 1)} "
                    + (f"str_value: {json.dumps(v)}" if isinstance(v, str)
                       else f"int64_value: {int(v)}") + " }"
                    for k, v in st.items())
                body.append(
                    f"events {{ metadata_id: {mid} offset_ps: "
                    f"{int(round((start - base) * 1000))} duration_ps: "
                    f"{int(round(dur * 1000))}{fields} }}")
            body.append("}")
        for name, mid in names.items():
            body.append(f"event_metadata {{ key: {mid} value {{ id: {mid} "
                        f"name: {json.dumps(name)} }} }}")
        for name, sid in stats.items():
            body.append(f"stat_metadata {{ key: {sid} value {{ id: {sid} "
                        f"name: {json.dumps(name)} }} }}")
        out.append(f'planes {{ id: {pid} name: {json.dumps(plane["name"])} '
                   + " ".join(body) + " }")
    return "\n".join(out)


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """The recorded trace as the ``.xplane.pb`` of a profile_device run
    that just stopped; ``(recording, plant)`` where ``plant(planes)``
    writes changed planes in its place."""
    with gzip.open(os.path.join(
            _DATA, "vgg16_train_2steps_trace.json.gz"), "rt") as f:
        rec = json.load(f)

    def plant(planes):
        path = tmp_path / "plugins" / "profile" / "t" / "cut.xplane.pb"
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_bytes(
            jax.profiler.ProfileData.text_proto_to_serialized_xspace(
                _xspace_text(planes)))

        monkeypatch.setattr(profiler, "_device_read", None)

    plant(rec["planes"])
    monkeypatch.setattr(profiler, "_device_logdir", os.fspath(tmp_path))
    monkeypatch.setattr(profiler, "_device_run_started", time.time() - 5)
    # the step's compiled text, as the step notes it during the run
    monkeypatch.setattr(profiler, "_programs",
                        {"train_step": lambda: rec["hlo_text"]})
    return rec, plant


def test_dumps_reads_phases_blocks_and_gaps_from_a_recorded_trace(
        recorded, monkeypatch):
    rec, _ = recorded
    out = json.loads(profiler.dumps(format="json"))
    assert isinstance(out["ops"], list)
    dev = out["device"]
    assert dev["device"] == "/device:TPU:0" and dev["runs"] == 2
    # the cut kept what the reader reads: the uncut trace gave the same
    known = rec["known"]
    for phase, v in known["phases"].items():
        assert dev["phases"][phase]["seconds"] == pytest.approx(
            v["seconds"], rel=1e-9, abs=1e-12)
    assert dev["blocks"] == pytest.approx(known["blocks"]) \
        or [b["block"] for b in dev["blocks"]] == \
        [b["block"] for b in known["blocks"]]
    # phase seconds sum to the busy time (one core: no two ops at once)
    total = sum(v["seconds"] for v in dev["phases"].values())
    assert total == pytest.approx(dev["busy_s"], rel=1e-6)
    assert dev["phases"]["unscoped"]["share"] < 0.03
    assert dev["phases"]["backward"]["seconds"] > \
        dev["phases"]["forward"]["seconds"] > 0
    # max-pooling's backward and the 224x224 stage, by name
    top = {(b["phase"], b["block"]) for b in dev["blocks"]}
    assert ("backward", "vgg0_pool0") in top
    assert ("backward", "vgg0_conv2d1") in top
    # XLA put the update into the weight gradients' fusions
    assert dev["fused"][0]["phase"] == "backward"
    assert dev["fused"][0]["holds"] == "optimizer"
    table = profiler.dumps()
    assert "forward" in table and "backward:vgg0_conv2d1" in table
    # the trace is read once a run
    assert profiler.device_report() is profiler.device_report()
    # without the compiled text a v5e's events carry no scope at all
    monkeypatch.setattr(profiler, "_programs", {})
    monkeypatch.setattr(profiler, "_device_read", None)
    bare = json.loads(profiler.dumps(format="json"))["device"]
    assert bare["phases"]["unscoped"]["share"] == pytest.approx(1.0)


def test_dumps_puts_a_planted_gap_down_to_the_span_covering_it(recorded):
    rec, plant = recorded
    planes = json.loads(json.dumps(rec["planes"]))
    dev = next(p for p in planes if p["name"].startswith("/device:"))
    ops = next(ln for ln in dev["lines"] if ln["name"] == "XLA Ops")
    mods = next(ln for ln in dev["lines"] if ln["name"] == "XLA Modules")
    # the second run starts 3 ms late, and the feed's wait covers it
    cut_at = mods["events"][1][1]
    for line in (ops, mods):
        for e in line["events"]:
            if e[1] >= cut_at:
                e[1] += 3_000_000
    host = next(p for p in planes if p["name"].startswith("/host:CPU"))
    host["lines"][0]["events"].append(
        ["mx_feed_wait", cut_at - 100_000, 3_050_000, {"depth": 0}])
    plant(planes)
    idle = {r["span"]: r["seconds"] for r in json.loads(profiler.dumps(
        format="json"))["device"]["idle"]}
    assert idle["mx_feed_wait"] == pytest.approx(3e-3, rel=0.01)
    before = {r["span"]: r["seconds"] for r in rec["known"]["idle"]}
    assert "mx_feed_wait" not in before
