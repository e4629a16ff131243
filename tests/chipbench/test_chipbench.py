"""The benchmark's own tests: CPU only, no subprocess, no TPU topology.

What they hold: ``BENCHMARK.json`` resolves to its files and keeps to the
contract's characters; the FLOP count is the published one; the plain
references agree with the zoo's forward pass; the trace reducer gives
known figures on a recorded trace; ``run.py`` measures nothing on a CPU;
each planted fault comes out not correct under the cells' own limits, and
running statistics left unchanged do too; the lower-precision control
reads wider than rounding (it does not fail the limits: PERF.md).

Nothing here knows what a configuration's input is.  A test builds a
configuration at the size its file declares under ``tiny`` and takes the
net, the input, the pool of batches, the specs and the reference's side
from the functions of the cell's kind (``chipbench/kinds/<kind>.py``).
``ROOT`` is wherever this file lies, so that the suite runs as well in a
copy of the tree to which a configuration's files were added
(``test_witness.py``).
"""
import copy
import json
import os
import re
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import compare, flops, hlo_collectives, trace_reduce  # noqa: E402
from chipbench import run as cb  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
#: every configuration kept under chipbench/configs, also those whose
#: cells wait for a repair of the program (PERF.md, Open questions)
CONFIGS = sorted(f[:-len(".json")] for f in os.listdir(
    os.path.join(ROOT, "chipbench", "configs")))
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _chipbench(*parts):
    return os.path.join(ROOT, "chipbench", *parts)


def _kind_of(config_name):
    """The module of the kind that drives ``config_name``'s cells; of
    ``train_closed`` for a configuration that is kept without a cell."""
    for w in BENCH["workloads"]:
        if w["config"] == config_name:
            return cb.load_module("kinds", cb.load_json(
                "traffic", w["traffic"] + ".json")["kind"])
    return cb.load_module("kinds", "train_closed")


def _specs(kind, config, ref):
    return kind.specs_of({"config": config, "reference": ref})


# ------------------------------------------------------- BENCHMARK.json
def test_benchmark_json_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200
    loaded = cb.load_cell(cell)
    assert loaded["config"]["name"] == entry["config"]
    assert loaded["config"]["reduced"] == next(
        c["reduced"] for c in BENCH["configs"]
        if c["name"] == entry["config"])
    assert os.path.exists(_chipbench(
        "kinds", loaded["traffic"]["kind"] + ".py"))
    assert hasattr(loaded["reference"], "forward")
    reported = {m["name"] for m in loaded["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert loaded["per_layer"], "a cell reports a per-layer metric"
    # every number the comparison can yield is named in the traffic file,
    # with its limit or with null (read, not compared)
    limits = loaded["traffic"]["limits"]
    kind = cb.load_module("kinds", loaded["traffic"]["kind"])
    assert set(limits) == kind.compared_names(loaded["traffic"])
    assert sum(v is not None for v in limits.values()) >= 3
    # the worst leaf is held, not only a median
    assert limits["grad_gap_worst"] is not None
    assert limits["change_gap_worst"] is not None


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_keeps_to_the_contract_and_has_a_reader(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert hasattr(cb.load_module("metrics", metric["name"]), "read")
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if metric in BENCH["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert 0 < metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["moves"] in [m["name"] for m in BENCH["end_to_end"]]
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200


def test_names_are_unique_and_plain():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and w["config"] in CONFIGS
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for c in BENCH["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert c["name"] in CONFIGS
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


# ------------------------------------------------------- FLOPs and bytes
def test_resnet50_flops_are_the_published_count():
    config = cb.load_json("configs", "resnet50_v1.json")
    macs = flops.forward_macs_per_image(config)
    assert abs(macs - 3.8e9) / 3.8e9 < 0.03  # arXiv:1512.03385 Table 1
    # three products a layer, the first layer's input gradient left out
    assert 2.9 < flops.step_flops(config, 1) / (2 * macs) < 3.0


def test_vgg16_has_the_published_parameters():
    config = cb.load_json("configs", "vgg16.json")
    ref = cb.load_module("reference", "vgg16")
    specs = _specs(_kind_of("vgg16"), config, ref)
    elements = sum(int(np.prod(s[1])) for s in specs)
    assert elements == config["trainable_elements"]
    assert abs(elements - 138e6) / 138e6 < 0.01  # arXiv:1409.1556 Table 2
    # 13 convolutions of 15.35 GMAC and three dense layers of 0.12
    assert flops.forward_macs_per_image(config) == 15470264320


@pytest.mark.parametrize("config_name", CONFIGS)
def test_layer_table_holds_the_reference_weights(config_name):
    config = cb.load_json("configs", config_name + ".json")
    ref = cb.load_module("reference", config_name)
    specs = _specs(_kind_of(config_name), config, ref)
    in_specs = sum(int(np.prod(s[1])) for s in specs
                   if s[0] in ("conv", "dense"))
    in_table = sum(r["count"] * flops.row_weights(r)
                   for r in flops.rows(config))
    assert in_specs == in_table
    peak = flops.peaks("TPU v5 lite")
    least, by_flops, by_bytes = flops.step_roofline_s(config, 128, peak)
    assert least == pytest.approx(by_flops + by_bytes) and least > 0
    with pytest.raises(KeyError):
        flops.peaks("cpu")


# ---------------------------------------------------------- the reducer
def test_reducer_arithmetic_on_a_known_trace():
    """Two devices, by hand: device 0 runs a convolution fusion 0-4 ms,
    waits in an all-reduce 4-6 ms (its async span opened at 3 ms), idles
    6-7 ms under the host's feed wait, and runs a loop fusion 7-10 ms."""
    hlo = "\n".join([
        "%fused_computation.1 (p: f32[8]) -> f32[8] {",
        "  %c = f32[8] convolution(%p, %p), window={size=1x1}",
        "}",
        "%fused_computation.2 (p: f32[8]) -> f32[8] {",
        "  %m = f32[8] multiply(%p, %p)",
        "}",
        "ENTRY %main (a: f32[8]) -> f32[8] {",
        "  %fusion.1 = f32[8] fusion(%a), kind=kOutput, "
        "calls=%fused_computation.1",
        "  %all-reduce-start.1 = f32[8] all-reduce-start(%fusion.1)",
        "  %all-reduce-done.1 = f32[8] all-reduce-done(%all-reduce-start.1)",
        "  %fusion.2 = f32[8] fusion(%a), kind=kLoop, "
        "calls=%fused_computation.2",
        "}"])
    ms = 1e-3
    ops = [["%fusion.1 = f32[8] fusion(%a), kind=kOutput", 0, 4 * ms],
           ["%all-reduce-done.1 = f32[8] all-reduce-done(%x)", 4 * ms,
            2 * ms],
           ["%fusion.2 = f32[8] fusion(%a), kind=kLoop", 7 * ms, 3 * ms]]
    asyncs = [["%all-reduce-start.1 = f32[8] all-reduce-start(%fusion.1)",
               3 * ms, 3 * ms]]
    events = {"devices": {"/device:TPU:0": {"ops": ops, "async": asyncs},
                          "/device:TPU:1": {"ops": ops, "async": asyncs}},
              "host": [["cb_feed_wait", 5.5 * ms, 2 * ms],
                       ["cb_dispatch", 0.0, 1 * ms]]}
    r = trace_reduce.reduce(events, hlo)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(9 * ms)
    assert r["window_s"] == pytest.approx(10 * ms)
    assert r["by_class_s"]["conv_dot"] == pytest.approx(4 * ms)
    assert r["by_class_s"]["other"] == pytest.approx(3 * ms)
    assert r["collective_s"] == pytest.approx(3 * ms)
    # 3-4 ms hides behind the convolution; 4-6 ms is exposed
    assert r["collective_exposed_s"] == pytest.approx(2 * ms)
    assert r["idle_gaps"] == [["cb_feed_wait", pytest.approx(1 * ms)]]
    assert r["device_ops"][0] == ["class:conv_dot", pytest.approx(4 * ms)]


def test_reducer_on_the_recorded_trace():
    """Two steps of ``resnet50_train`` as the v5e traced them (my chip
    run, PR 24), names cut to their instruction heads."""
    path = _chipbench("testdata", "resnet50_train_2steps.json.gz")
    recorded = trace_reduce.load_events(path)
    r = trace_reduce.reduce(recorded["events"], recorded["hlo_text"])
    known = recorded["known"]
    assert r["devices"] == 1
    for key in ("busy_s", "window_s", "collective_s"):
        assert r[key] == pytest.approx(known[key], rel=1e-9)
    assert r["by_class_s"]["conv_dot"] == pytest.approx(
        known["conv_dot_s"], rel=1e-9)
    assert 0.0 < r["by_class_s"]["conv_dot"] < r["busy_s"] <= r["window_s"]
    # two steps of 46.9 ms, as the trace's own "Steps" line had them
    assert r["window_s"] == pytest.approx(2 * 0.0469, rel=2e-3)
    assert 1.0 - r["busy_s"] / r["window_s"] < 1e-3
    assert r["collective_exposed_s"] == 0.0


def test_collective_parser_reads_tpu_text():
    text = "\n".join([
        "  %all-reduce.3 = (f32[1000]{0:T(1024)}, /*index=1*/f32[24]{0}) "
        "all-reduce(%a, %b), replica_groups={{0,1,2,3}}",
        "  %all-gather-start.1 = (bf16[256]{0}, bf16[1024]{0:T(1024)S(1)}) "
        "all-gather-start(%p), dimensions={0}",
        "  %all-gather-done.1 = bf16[1024]{0} all-gather-done("
        "%all-gather-start.1)",
        "  %gte = f32[24]{0} get-tuple-element(%all-reduce.3), index=1"])
    c = hlo_collectives.collective_bytes(text)
    assert c["counts"]["all-reduce"] == 1 and c["tensors"]["all-reduce"] == 2
    assert c["bytes"]["all-reduce"] == 4096
    assert c["counts"]["all-gather"] == 1
    assert c["bytes"]["all-gather"] == 2048
    assert c["total_bytes"] == 6144


# -------------------------------------------- run.py measures on a TPU only
def test_run_refuses_to_measure_on_a_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        cb.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


# ------------------------------------------- references, control, faults
def _merge(into, overrides):
    for k, v in overrides.items():
        if isinstance(v, dict) and isinstance(into.get(k), dict):
            _merge(into[k], v)
        else:
            into[k] = v
    return into


def _tiny_config(config_name):
    """The configuration under the overrides its own file declares for
    the CPU (``tiny``: a small image, or small widths, depth, vocabulary
    and sequence)."""
    config = cb.load_json("configs", config_name + ".json")
    return _merge(config, config.get("tiny", {}))


def _tiny(cell_name, chips=1, **traffic):
    """The cell at a size a test run can hold: the configuration's
    ``tiny``, 8 rows a chip, float32 compute (so that the program agrees
    with the reference to rounding and only a fault can fail the cell's
    own limits)."""
    cell = cb.load_cell(cell_name)
    tiny = copy.deepcopy({k: v for k, v in cell.items()
                          if k != "reference"})
    tiny["reference"] = cell["reference"]
    _merge(tiny["config"], tiny["config"].get("tiny", {}))
    tiny["config"]["compute_dtype"] = "float32"
    tiny["traffic"].update(batch_per_chip=8, pool=3, warm_steps=1,
                           span_steps=1, **traffic)
    tiny["chips"] = chips
    return tiny


@pytest.mark.parametrize("config_name", CONFIGS)
def test_reference_agrees_with_the_zoo_forward(config_name):
    """The logits, and what the forward pass moves: batch normalisation's
    running statistics as the program's own gluon forward leaves them
    under ``autograd.record()``."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from chipbench import weights
    from mxnet_tpu import autograd, parallel

    kind = _kind_of(config_name)
    config = _tiny_config(config_name)
    ref = cb.load_module("reference", config_name)
    net = kind.build_net(config, 4)
    params, apply_fn = parallel.functionalize(net, train=True)
    names = list(params)
    specs = _specs(kind, config, ref)
    assert [tuple(params[n].shape) for n in names] == \
        [tuple(s[1]) for s in specs]
    w = weights.make(specs, 2 ** 31 + 12345)
    params = dict(zip(names, w))
    x, _ = kind.sample_input(config, 4, jax.random.key(0))
    if jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        ours = jax.jit(apply_fn)(params, x)
    theirs, moved = jax.jit(
        lambda p, v: ref.forward(p, v, config["arch"]))(w, x)
    scale = float(jnp.max(jnp.abs(theirs)))
    assert float(jnp.max(jnp.abs(ours - theirs))) / scale < 1e-3
    assert sorted(moved) == [i for i, s in enumerate(specs)
                             if s[0] in weights.MOVED_BY_FORWARD]
    if not moved:
        return
    held = {p.name: p for p in net.collect_params().values()}
    for n, a in zip(names, w):
        held[n].set_data(mx.nd.NDArray(a))
    with jax.default_matmul_precision("highest"), autograd.record():
        net(mx.nd.NDArray(x))
    for i, new in moved.items():
        got = held[names[i]].data()._data
        assert float(jnp.max(jnp.abs(got - new))) < 1e-3 * (
            1.0 + float(jnp.max(jnp.abs(new)))), names[i]
        assert float(jnp.max(jnp.abs(new - w[i]))) > 1e-3  # it moved


def _state_unchanged(step):
    import jax

    def broken(p, o, x, y, key, t):
        kept = jax.tree_util.tree_map(lambda a: a + 0, (p, o))
        loss, _, _ = step(p, o, x, y, key, t)
        return (loss,) + kept
    return broken


def _half_batch(step):
    import jax
    import jax.numpy as jnp

    def broken(p, o, x, y, key, t):
        h = x.shape[0] // 2  # the second half left out, the mean taken
        # over the rest: twice the first half has its statistics and mean
        xs = jax.device_put(jnp.concatenate([x[:h], x[:h]]), x.sharding)
        ys = jax.device_put(jnp.concatenate([y[:h], y[:h]]), y.sharding)
        return step(p, o, xs, ys, key, t)
    return broken


def _no_exchange(step):
    import jax
    import jax.numpy as jnp

    def broken(p, o, x, y, key, t):
        q = x.shape[0] // 4  # every chip's part is the first chip's: what
        # chip 0 holds after a step whose gradients were never exchanged
        xs = jax.device_put(jnp.concatenate([x[:q]] * 4), x.sharding)
        ys = jax.device_put(jnp.concatenate([y[:q]] * 4), y.sharding)
        return step(p, o, xs, ys, key, t)
    return broken


def _drive(cell, wrap_step=None):
    import jax

    return cb.execute(cell, 7, 0.5, 0, jax.devices(),
                      t0=time.perf_counter(), wrap_step=wrap_step)


def _chips(cell_name):
    return next(w["chips"] for w in BENCH["workloads"]
                if w["name"] == cell_name)


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_run_is_correct_and_each_fault_is_not(cell_name):
    """Under the cell's own limits and on as many (virtual) devices as it
    has chips; the exchange left out has the test below."""
    import jax

    if len(jax.devices()) < _chips(cell_name):
        pytest.skip("needs as many (virtual) devices as the cell has chips")
    cell = _tiny(cell_name, chips=_chips(cell_name))
    sound = _drive(cell)
    assert sound["correct"], sound["compared"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert list(sound)[-1] == "compared"
    assert set(sound["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    for fault in (_state_unchanged, _half_batch):
        broken = _drive(cell, fault)
        assert not broken["correct"], (fault.__name__, broken["compared"])
        if fault is _state_unchanged:
            got = broken["compared"]
            assert got["grad_gap_worst"]["value"] == pytest.approx(1.0)
            assert got["change_gap_worst"]["value"] == pytest.approx(1.0)
            assert got["grad_gap_median"]["value"] > 0.9


@pytest.mark.parametrize("cell_name", [
    c for c in CELLS if _chips(c) == 4] or CELLS[:1])
def test_exchange_left_out_is_not_correct(cell_name):
    """Under the limits of the benchmark's own four-chip cell; where it
    has none, under the first cell's with the exchange switched on."""
    import jax
    from chipbench import refmath

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    cell = _tiny(cell_name, chips=4, optimizer_sharding="ps")
    if refmath.optimizer_rule(cell["config"]["optimizer"])[2]:
        pytest.skip("the sharded exchange keeps its state by bucket, "
                    "where no leaf's first moment can be read by name")
    assert _drive(cell)["correct"]
    assert not _drive(cell, _no_exchange)["correct"]


@pytest.mark.parametrize("cell_name", [
    c for c in CELLS if _chips(c) == 4] or CELLS[:1])
def test_readings_give_every_side_the_harness_s_own_verdict(cell_name):
    """``readings`` (what ``readings.py`` drives on the chip) at a tiny
    size on four virtual devices: the program is correct under the cell's
    own limits, the exchange left out and half a batch left out are not,
    every reading is handed on as it comes, and no seed's program starts
    after its time is up."""
    import jax
    from chipbench import refmath

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    cell = _tiny(cell_name, chips=4, optimizer_sharding="ps")
    if refmath.optimizer_rule(cell["config"]["optimizer"])[2]:
        pytest.skip("the sharded exchange keeps its state by bucket")
    kind = cb.load_module("kinds", cell["traffic"]["kind"])
    flushed = []
    out = kind.readings(
        dict(cell, devices=jax.devices(), say=lambda m: None,
             t0=time.perf_counter()), [11, 12, 13], 1,
        flush=lambda o: flushed.append(sum(len(v) for v in o.values())),
        program_until=0.0)
    assert set(out) == {"names", 11}  # the first seed always runs
    sides = out[11]["correct"]
    assert sides["program"], out[11]["program"]
    assert not sides["fault_no_exchange"], out[11]["fault_no_exchange"]
    assert not sides["fault_half_batch"], out[11]["fault_half_batch"]
    assert set(sides) == set(out[11]["leaves"]) - {"reference"}
    assert len(flushed) == len(sides) and flushed == sorted(flushed)


@pytest.mark.parametrize("config_name", [
    c for c in CONFIGS if "bn_momentum" in
    cb.load_json("configs", c + ".json")["arch"]])
def test_running_statistics_left_unchanged_are_not_correct(config_name):
    """A step that leaves batch normalisation's running statistics as
    they were (what ``make_train_step`` does today: PERF.md, Open
    questions) reads 1 on the worst leaf, over any cell's limit."""
    import jax
    from chipbench import weights

    cell = _tiny(CELLS[0])
    cell["config"] = _tiny_config(config_name)
    cell["reference"] = cb.load_module("reference", config_name)
    kind = cb.load_module("kinds", cell["traffic"]["kind"])
    w0 = [np.asarray(a) for a in weights.make(kind.specs_of(cell), 5)]
    pool = kind.make_pool(cell["config"], 8, 3, 5)
    built = {"devices": jax.devices()[:1], "groups": 1}
    ref = kind.reference_side(cell, built, w0, pool)
    frozen = kind.reference_side(cell, built, w0, pool,
                                 frozen=weights.MOVED_BY_FORWARD)
    values = compare.numbers(frozen, ref)[0]
    assert values["change_gap_worst"] == pytest.approx(1.0)
    assert values["grad_gap_worst"] == pytest.approx(1.0)
    assert values["change_gap_weights_worst"] == 0.0  # nothing else moved
    for name in ("change_gap_worst", "grad_gap_worst"):
        assert values[name] > cell["traffic"]["limits"][name]


@pytest.mark.parametrize("cell_name", CELLS)
def test_lower_precision_control_reads_wider_than_the_reference(cell_name):
    """The reference in float8, put in the program's place, reads wider
    than rounding on every gap.  It does NOT fail ``vgg16_train``'s
    limits, neither here nor at the cell's size on the chip: over 23
    seeds the bf16 program's gaps of norms reach those of the float8
    control (PERF.md, Open questions, second entry).  What the limits do
    fail is in the tests above: a state left unchanged, half a batch left
    out, the exchange left out, running statistics left unchanged."""
    import jax
    from chipbench import refmath, weights

    cell = _tiny(cell_name)
    kind = cb.load_module("kinds", cell["traffic"]["kind"])
    built = {"devices": jax.devices()[:1], "groups": 1}
    w0 = [np.asarray(a) for a in weights.make(kind.specs_of(cell), 4)]
    pool = kind.make_pool(cell["config"], 8, 3, 4)
    ref = kind.reference_side(cell, built, w0, pool)
    control = kind.reference_side(cell, built, w0, pool,
                                  precision="float8")
    values = compare.numbers(control, ref)[0]
    assert set(values) <= set(cell["traffic"]["limits"])
    names = ("grad_gap_median", "grad_gap_quartile", "grad_gap_worst")
    if refmath.optimizer_rule(cell["config"]["optimizer"])[2] is None:
        # where the state holds the gradient (Adam) the change's norm is
        # the rate's whatever the precision (chipbench/compare.py)
        names += ("change_gap_median",)
    for name in names:
        assert 1e-3 < values[name] < 0.5, (name, values)


# -------------------------------- readers, kernels: whatever the configuration
@pytest.mark.parametrize("cell_name", CELLS)
def test_every_reader_reads_a_made_up_run_of_the_cell(cell_name):
    """Each per-layer reader of the cell, on a run made up from the cell's
    own configuration and a two-event trace: a number or nothing, never
    an error (a table of another form, a configuration without a side)."""
    cell = cb.load_cell(cell_name)
    ms = 1e-3
    hlo = "\n".join([
        "%fused_computation.1 (p: f32[8]) -> f32[8] {",
        "  ROOT %c = f32[8] convolution(%p, %p), metadata={op_name="
        '"jit(step)/mx_forward/block0/conv"}',
        "}",
        "ENTRY %main (a: f32[8]) -> f32[8] {",
        "  %fusion.1 = f32[8] fusion(%a), calls=%fused_computation.1",
        "  %all-reduce.1 = f32[8] all-reduce(%fusion.1), metadata={op_name="
        '"jit(step)/mx_exchange/psum"}',
        "}"])
    ops = [["%fusion.1 = f32[8] fusion(%a), kind=kOutput", 0.0, 40 * ms],
           ["%all-reduce.1 = f32[8] all-reduce(%fusion.1)", 40 * ms, ms]]
    trace = trace_reduce.reduce(
        {"devices": {"/device:TPU:0": {"ops": ops, "async": []}},
         "host": []}, hlo)
    trace["steps"] = 1
    batch = cell["traffic"]["batch_per_chip"] * cell["chips"]
    spans = np.array([[0.0, 1 * ms]])
    run = {"config": cell["config"], "traffic": cell["traffic"],
           "chips": cell["chips"], "batch": batch, "setup_s": 1.0,
           "peak": flops.peaks("TPU v5 lite"), "trace": trace,
           "collectives": hlo_collectives.collective_bytes(hlo),
           "window": {"steps": 10, "images": 10 * batch, "seconds": 1.0,
                      "done_at": np.arange(10) * 0.1,
                      "spans": {k: spans for k in
                                ("feed_wait", "dispatch", "loss_read")},
                      "feed": {"consumer_wait_s": 0.01}}}
    got = cb.read_metrics(cell["per_layer"] + cell["end_to_end"], run)
    assert {"forward_ms.train", "step_mfu.train"} <= set(got) or \
        "forward_ms.train" not in [m["name"] for m in cell["per_layer"]]
    assert all(np.isfinite(v["value"]) for v in got.values())
    if "forward_ms.train" in got:
        assert got["forward_ms.train"]["value"] == pytest.approx(40.0)
        assert "backward_ms.train" not in got  # nothing to read: left out


def test_every_listed_kernel_is_a_conv_dot_event():
    """A custom call whose instruction carries a name listed by a file of
    ``chipbench/kernels/`` (``%<name>.N``, which is how a
    ``pl.pallas_call(name=...)`` shows on the chip), and one that names
    it as its ``kernel_name``."""
    names = sorted(trace_reduce.conv_kernels())
    assert names
    for name in names:
        by_name = (f"  %{name}.3 = f32[8] custom-call(%a), "
                   'custom_call_target="tpu_custom_call"')
        by_key = ("  %custom-call.7 = f32[8] custom-call(%a), "
                  'custom_call_target="tpu_custom_call", backend_config='
                  f'{{"kernel_name": "{name}"}}')
        table = trace_reduce.class_table(
            "\n".join(["ENTRY %main (a: f32[8]) -> f32[8] {", by_name,
                       by_key, "}"]))
        assert table[f"{name}.3"] == "conv_dot"
        assert table["custom-call.7"] == "conv_dot"
        # the traced event alone, without the compiled text
        assert trace_reduce.classify(by_name, {}) == "conv_dot"
    other = ('  %other_kernel.1 = f32[8] custom-call(%a), '
             'custom_call_target="tpu_custom_call"')
    assert trace_reduce.classify(other, {}) == "custom_call"
