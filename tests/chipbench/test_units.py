"""The yardstick's arithmetic by hand: a layer table's rows over images
and over a sequence and rows whose work is given outright, weights and
batches from the seed unchanged to the bit, the first gradient handed in against the one derived, the plain
AdamW rule against the program's, seconds by phase, by block and by
kernel on the recorded traces (and the benchmark's reading of a scope
held to the program's), the readers of blocks and of the exchange, and
the host annotations the loader keeps.  CPU only."""
import gzip
import hashlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import compare, flops, refmath, trace_reduce, weights  # noqa: E402
from chipbench import run as cb  # noqa: E402

with open(os.path.join(HERE, "witness", "chipbench", "configs",
                       "tokwit.json")) as _f:
    TOKWIT = json.load(_f)
PEAK = flops.peaks("TPU v5 lite")


def _passes(config, name, batch):
    return {p: (f, b) for n, p, _, f, b in flops.passes(config, batch)
            if n == name}


# --------------------------------------------------------------- the rows
def test_conv_row_over_a_sequence():
    """A dense layer at every position: ``positions`` in place of a
    side, the row an object."""
    got = _passes(TOKWIT, "mlp_up", 3)
    macs = 3 * 12 * 32 * 16
    x, y, w = 3 * 12 * 16 * 2, 3 * 12 * 32 * 2, 32 * 16 * 2
    assert got["forward"] == (2 * macs, x + w + y)
    assert got["backward"] == (4 * macs, x + y + w + w + x)
    # the look-up's row, given outright, holds no weights
    assert [flops.row_weights(r) for r in flops.rows(TOKWIT)] == [
        16 * 32, 32 * 64, 0]
    assert flops.step_flops(TOKWIT, 3) == 6 * 3 * 12 * (16 * 32 + 32 * 64)
    least, by_flops, by_bytes = flops.step_roofline_s(TOKWIT, 3, PEAK)
    assert least == pytest.approx(by_flops + by_bytes) and least > 0


@pytest.mark.parametrize("name,macs,step_flops,step_bytes,least", [
    ("vgg16", 15470264320, 5929483370496, 7781527680,
     0.031720284841746396),
    ("resnet50_v1", 3857973248, 1466355941376, 6737151104,
     0.011063642077746712),
    ("mobilenetv2_1.0", 313619328, 119042555904, 4566915712,
     0.0055864933590474885),
])
def test_conv_rows_read_as_they_did(name, macs, step_flops, step_bytes,
                                    least):
    """What PR 26's tree read, to the last digit (batch 64, one v5e)."""
    config = cb.load_json("configs", name + ".json")
    assert flops.forward_macs_per_image(config) == macs
    assert flops.step_flops(config, 64) == step_flops
    assert sum(c * b for _, _, c, _, b in flops.passes(
        config, 64, flops.is_product)) == step_bytes
    assert flops.step_roofline_s(config, 64, PEAK)[0] == least
    if name == "resnet50_v1":
        assert abs(macs - 3.8e9) / 3.8e9 < 0.03  # arXiv:1512.03385


# ------------------------------------------- rows whose work is given outright
VGG16 = cb.load_json("configs", "vgg16.json")
_GIVEN = {"name": "scores", "count": 3, "blocks": ["attn0"],
          "per_row": {"forward": {"macs": 1000, "bytes": 600},
                      "backward": {"macs": 2000, "bytes": 300}}}


def test_row_given_outright_in_passes_flops_and_rooflines():
    """``per_row`` is of one occurrence and one row of the batch: times
    the batch in ``passes``, times ``count`` in the sums; ``step_flops``
    counts its multiply-adds, ``step_roofline_s`` keeps to the products,
    ``rows_roofline_s`` takes the rows it is asked for, of either form."""
    config = dict(TOKWIT, layers=[r for r in TOKWIT["layers"]
                                  if flops.is_product(r)])
    both = dict(config, layers=config["layers"] + [_GIVEN])
    assert _passes(both, "scores", 5) == {"forward": (2 * 5 * 1000, 5 * 600),
                                          "backward": (2 * 5 * 2000, 5 * 300)}
    assert flops.passes(both, 5)[:-2] == flops.passes(config, 5)
    assert flops.passes(both, 5, flops.is_product) == flops.passes(config, 5)
    assert flops.row_weights(_GIVEN) == 0
    assert flops.step_flops(both, 5) == flops.step_flops(config, 5) \
        + 3 * 2 * 5 * 3000
    assert flops.step_roofline_s(both, 5, PEAK) \
        == flops.step_roofline_s(config, 5, PEAK)
    # at 10 FLOP a byte, bytes bind the forward pass (2000 FLOP, 600 B
    # a row) and operations the backward pass (4000 FLOP, 300 B)
    peak = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e2}
    least, by_flops, by_bytes = flops.rows_roofline_s(both, 5, peak,
                                                      {"scores"})
    assert by_flops == pytest.approx(3 * 2 * 5 * 2000 / 1e3)  # backward
    assert by_bytes == pytest.approx(3 * 5 * 600 / 1e2)  # forward
    assert least == pytest.approx(by_flops + by_bytes)
    products = {r["name"] for r in flops.rows(config)}
    assert flops.rows_roofline_s(both, 5, PEAK, products) \
        == flops.step_roofline_s(config, 5, PEAK)
    assert flops.rows_roofline_s(both, 5, PEAK, set()) == (0.0, 0.0, 0.0)


def test_vgg16_pooling_rows_are_the_hand_count_from_arch():
    """Five max-poolings of 2x2, stride 2, one after each stage: no
    multiply-adds; forward reads the map and writes a quarter of it,
    backward reads the map and the quarter's gradient and writes the
    map's gradient; bf16."""
    arch = VGG16["arch"]
    assert arch["pool"] == {"kernel": 2, "stride": 2}
    side, expected = arch["input_side"], []
    for i, stage in enumerate(arch["stages"]):
        whole = side * side * stage["channels"]
        quarter = (side // 2) ** 2 * stage["channels"]
        expected.append({
            "name": f"pool{i + 1}", "count": 1, "blocks": [f"vgg0_pool{i}"],
            "per_row": {
                "forward": {"macs": 0, "bytes": 2 * (whole + quarter)},
                "backward": {"macs": 0,
                             "bytes": 2 * (whole + quarter + whole)}}})
        side //= 2
    given = [r for r in flops.rows(VGG16) if not flops.is_product(r)]
    assert given == expected
    names = {r["name"] for r in given}
    step_bytes = sum(c * b for _, _, c, _, b in flops.passes(
        VGG16, 64, lambda r: r["name"] in names))
    assert step_bytes == 2742419456  # 2.74 GB a step at batch 64
    least, by_flops, by_bytes = flops.rows_roofline_s(VGG16, 64, PEAK, names)
    assert by_flops == 0.0 and least == by_bytes
    assert least == pytest.approx(step_bytes / 819e9)  # 3.35 ms
    assert flops.rows_roofline_s(VGG16, 64, PEAK, {"pool1"})[0] \
        == pytest.approx(1.7566e-3, rel=1e-4)


def test_a_bank_of_experts_is_a_2d_dense_leaf():
    """``weights._draw`` takes fan-in from all axes but the first: a bank
    kept as ``(members x out, in)`` is drawn with variance 1 / in, one
    stacked ``(members, out, in)`` ``out`` times too small."""
    flat, stacked = (np.asarray(a) for a in weights.make(
        [("dense", (8 * 64, 32)), ("dense", (8, 64, 32))], 7))
    assert flat.std() == pytest.approx(32 ** -0.5, rel=0.02)
    assert stacked.std() == pytest.approx((64 * 32) ** -0.5, rel=0.02)


# ------------------------------------------------ weights and batches, seeded
def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


def test_weights_of_todays_kinds_are_the_same_bits():
    specs = [("conv", (8, 3, 3, 4)), ("bias", (8,)), ("dense", (16, 32)),
             ("gamma", (8,)), ("beta", (8,)), ("gamma_last", (8,)),
             ("running_mean", (8,)), ("running_var", (8,))]
    assert _digest(weights.make(specs, 2 ** 31 + 5)) == (
        "af183b6e6370f6ab159435cf6c404610e89d4d54e67f62d4d92941f47450788f")


def test_weights_a_token_model_holds():
    specs = [("embedding", (512, 64)), ("dense", (32, 16))]
    table, flat = (np.asarray(a) for a in weights.make(specs, 3))
    assert table.std() == pytest.approx(64 ** -0.5, rel=0.02)
    assert flat.std() == pytest.approx(16 ** -0.5, rel=0.1)
    with pytest.raises(ValueError):
        weights.make([("no_such_kind", (2,))], 1)


def test_image_batches_are_the_same_bits_and_token_batches_are_shifted():
    import jax

    kind = cb.load_module("kinds", "train_closed")
    config = cb.load_json("configs", "vgg16.json")
    config["input"].update(height=32, width=32)
    pool = kind.make_pool(config, 2, 2, 2 ** 31 + 5)
    assert _digest(a for xy in pool for a in xy) == (
        "1981424478298691fe59dedf10a25333fe8303c4f07ac6d66925312e2bef9cb6")
    x, y = kind.sample_input(TOKWIT, 64, jax.random.key(1))
    x, y = np.asarray(x), np.asarray(y)
    assert x.shape == y.shape == (64, 12) and x.dtype == np.int32
    assert (x[:, 1:] == y[:, :-1]).all()  # the label is the next token
    assert 0 <= x.min() and x.max() < 64
    # floor(64 ** u) < 8 for u < 1/2
    assert (x < 8).mean() == pytest.approx(0.5, abs=0.06)
    other = json.loads(json.dumps(TOKWIT))
    other["input"]["ids"] = "no_such_draw"
    with pytest.raises(ValueError):
        kind.sample_input(other, 2, jax.random.key(1))


# -------------------------------------------------- the first gradient, AdamW
def test_side_takes_the_gradient_it_is_handed():
    """Under SGD from a momentum of nought the gradient handed in and the
    one derived from the first step's change are the same number."""
    rng = np.random.default_rng(0)
    w0 = [rng.normal(size=s).astype(np.float32) for s in ((4, 3), (5,))]
    g = [rng.normal(size=a.shape).astype(np.float32) for a in w0]
    lr = 0.05
    w1 = [a - lr * b for a, b in zip(w0, g)]
    derived = compare.side([1.0], w0, w1, w1, lr)
    handed = compare.side([1.0], w0, w1, w1, lr, grad=g)
    assert derived["grad"] == pytest.approx(handed["grad"], rel=1e-5)
    assert handed["grad"] == pytest.approx(
        [np.linalg.norm(b) for b in g], rel=1e-6)
    assert derived["change"] == pytest.approx(handed["change"])
    # under Adam the first change is the rate's whatever the gradient
    slots, update, first = refmath.optimizer_rule(TOKWIT["optimizer"])
    assert slots == 2
    state = (np.zeros_like(g[0]),) * 2
    big, _ = update(w0[0] * 0, state, 100.0 * g[0], 1.0)
    small, s1 = update(w0[0] * 0, state, g[0], 1.0)
    assert np.asarray(big) == pytest.approx(np.asarray(small), rel=1e-4)
    assert np.asarray(first(s1)) == pytest.approx(g[0], rel=1e-6)
    assert refmath.optimizer_rule(
        {"name": "sgd", "learning_rate": lr, "momentum": 0.9,
         "wd": 0.0})[2] is None


def test_plain_adamw_is_the_programs_rule():
    from mxnet_tpu.optimizer.optimizer import _adamw_step

    rng = np.random.default_rng(1)
    w, g = (rng.normal(size=(6, 5)).astype(np.float32) for _ in range(2))
    m, v = np.zeros_like(w), np.zeros_like(w)
    pm, pv, pw = m, v, w
    for t in (1.0, 2.0, 3.0):
        w, m, v = refmath.adamw(w, m, v, g, t, 1e-3, 0.9, 0.95, 1e-8, 0.1)
        pw, pm, pv = _adamw_step(pw, pm, pv, g, 1e-3, 1.0, 0.1, 0.9, 0.95,
                                 1e-8, t)
        for a, b in ((w, pw), (m, pm), (v, pv)):
            assert np.asarray(a) == pytest.approx(np.asarray(b), rel=1e-6)


# ------------------------------------------------------------------ phases
def _recording():
    """Two steps of ``vgg16_train`` as the v5e traced them in PR 25, as
    events: the program's own recording (``tests/data``; times in ns
    there)."""
    with gzip.open(os.path.join(ROOT, "tests", "data",
                                "vgg16_train_2steps_trace.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    events = {"devices": {}, "host": []}
    for plane in rec["planes"]:
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        if plane["name"].startswith("/device:"):
            events["devices"][plane["name"]] = {"async": [], "ops": [
                [n, s * 1e-9, d * 1e-9]
                for n, s, d, _ in lines[trace_reduce.OPS_LINE]]}
    return rec, events


def test_seconds_by_phase_on_the_programs_recorded_trace():
    rec, events = _recording()
    r = trace_reduce.reduce(events, rec["hlo_text"])
    # what the program's own reader gave on the uncut trace
    for phase, v in rec["known"]["phases"].items():
        assert r["by_phase_s"].get(phase, 0.0) == pytest.approx(
            v["seconds"], rel=1e-9, abs=1e-12)
    assert sum(r["by_phase_s"].values()) == pytest.approx(r["busy_s"],
                                                          rel=1e-6)
    assert set(r["by_phase_s"]) <= set(trace_reduce.PHASES)
    assert r["by_phase_s"]["backward"] > r["by_phase_s"]["forward"] > 0


def test_the_benchmarks_reading_of_a_scope_is_the_programs():
    """``phase_and_block`` and ``phase_table`` are a copy, on purpose, of
    ``mxnet_tpu/profiler.py``'s: over every instruction of the recorded
    step they give what ``_scope_table`` and ``_phase_and_block`` give
    (the program says ``None`` where an operation names no block)."""
    from mxnet_tpu import profiler

    rec, _ = _recording()
    ours = trace_reduce.phase_table(rec["hlo_text"])
    theirs = profiler._scope_table(rec["hlo_text"])
    assert ours == {name: scope for name, (scope, _) in theirs.items()}
    scopes = set(ours.values())
    assert len(scopes) > 100
    blocks = set()
    for scope in scopes:
        phase, block = profiler._phase_and_block(scope)
        assert trace_reduce.phase_and_block(scope) == (phase, block or ""), \
            scope
        assert trace_reduce.phase_of(scope) == phase
        blocks.add(block)
    assert {trace_reduce.phase_of(s) for s in scopes} >= {
        "forward", "backward", "loss", "optimizer", "unscoped"}
    # convolutions, activations, poolings, dense layers; and none
    assert len(blocks) > 30 and None in blocks
    assert {f"vgg0_pool{i}" for i in range(5)} <= blocks


def _resnet_recording():
    rec = trace_reduce.load_events(os.path.join(
        ROOT, "chipbench", "testdata", "resnet50_train_2steps.json.gz"))
    return rec["events"], rec["hlo_text"]


def _vgg_recording():
    rec, events = _recording()
    return events, rec["hlo_text"]


#: what the parent of the PR that brought ``by_block_s`` and
#: ``by_kernel_s`` read on the two recordings, to the last digit
_AS_BEFORE = {
    "vgg16": (_vgg_recording, {
        "by_class_s": {"conv_dot": 0.097469616,
                       "copy": 0.0022283309999992715,
                       "custom_call": 4.399999997684034e-08,
                       "other": 0.017739172999999067},
        "by_phase_s": {"unscoped": 0.002555498999998372,
                       "loss": 5.24500000002176e-06,
                       "backward": 0.07966058900000003,
                       "forward": 0.03520848099999995,
                       "optimizer": 7.349999999933798e-06},
        "busy_s": 0.11743716399999826, "window_s": 0.11744501700000001,
        "collective_exposed_s": 0.0,
        "idle_gaps": [["between_spans", 5.151999999994383e-06],
                      ["under_2us_between_ops", 2.7010000017424485e-06]]}),
    "resnet50": (_resnet_recording, {
        "by_class_s": {"conv_dot": 0.07093073499999988,
                       "copy": 0.005631808000017058,
                       "custom_call": 1.2900000154192348e-07,
                       "other": 0.017251731000009124},
        "by_phase_s": {"unscoped": 0.09381440300002761},
        "busy_s": 0.09381440300002139, "window_s": 0.09385084200000002,
        "collective_exposed_s": 0.0,
        "idle_gaps": [["cb_loss_read", 2.608300000001007e-05],
                      ["under_2us_between_ops", 1.0355999978617358e-05]]}),
}


@pytest.mark.parametrize("recording", sorted(_AS_BEFORE))
def test_blocks_add_up_to_their_phase_and_the_rest_reads_as_before(
        recording):
    load, before = _AS_BEFORE[recording]
    r = trace_reduce.reduce(*load())
    for key, value in before.items():
        assert r[key] == value, key
    assert r["device_ops"][0] == ["class:conv_dot",
                                  before["by_class_s"]["conv_dot"]]
    assert set(r["by_block_s"]) == set(r["by_phase_s"])
    for phase, seconds in r["by_phase_s"].items():
        for split in ("by_block_s", "by_phase_class_s"):
            assert sum(r[split][phase].values()) == pytest.approx(
                seconds, rel=1e-12)
    for c, seconds in r["by_class_s"].items():  # one device: no average
        assert sum(d.get(c, 0.0) for d in r["by_phase_class_s"].values()) \
            == pytest.approx(seconds, rel=1e-12)
    # a phase that is no pass names no block
    for phase in set(r["by_block_s"]) - {"forward", "backward"}:
        assert set(r["by_block_s"][phase]) == {""}


def test_seconds_by_block_on_the_programs_recorded_trace():
    """What the program's own reader gave on the uncut trace (its ten
    longest), and pooling as ``pool_roofline.train`` reads it: the
    unpaired step of PR 25, 5.77 ms a step where 3.35 would do."""
    rec, events = _recording()
    r = trace_reduce.reduce(events, rec["hlo_text"])
    assert len(rec["known"]["blocks"]) == 10
    for known in rec["known"]["blocks"]:
        assert r["by_block_s"][known["phase"]][known["block"]] == \
            pytest.approx(known["seconds"], rel=1e-9)
    pools = {f"vgg0_pool{i}" for i in range(5)}
    both = trace_reduce.block_seconds(r["by_block_s"], pools)
    forward = trace_reduce.block_seconds(r["by_block_s"], pools,
                                         ("forward",))
    assert forward == pytest.approx(2 * 1.8026e-3, rel=1e-3)
    assert both - forward == pytest.approx(2 * 3.9654e-3, rel=1e-3)
    assert trace_reduce.block_seconds(r["by_block_s"], {"no_such"}) == 0
    run = {"trace": dict(r, steps=2), "config": VGG16, "batch": 64,
           "chips": 1, "peak": PEAK}
    share = cb.load_module("metrics", "pool_roofline.train").read(run)
    assert share == pytest.approx(100 * 3.3485e-3 / 5.7680e-3, rel=1e-3)
    # on four chips a chip pools a quarter of the step's batch
    assert cb.load_module("metrics", "pool_roofline.train").read(
        dict(run, batch=256, chips=4)) == pytest.approx(share)


_KERNEL_HLO = "\n".join([
    "ENTRY %main (a: f32[8]) -> f32[8] {",
    '  %flash_attention_fwd.3 = f32[8] custom-call(%a), '
    'custom_call_target="tpu_custom_call", metadata={op_name='
    '"jit(step)/mx_forward/net0_attn0/pallas_call"}',
    '  %flash_attention_fwd.7 = f32[8] custom-call(%a), '
    'custom_call_target="tpu_custom_call", metadata={op_name='
    '"jit(step)/mx_forward/net0_attn1/pallas_call"}',
    '  %custom-call.9 = f32[8] custom-call(%a), '
    'custom_call_target="ConcatBitcast"',
    '  %all-reduce.1 = f32[8] all-reduce(%a), metadata={op_name='
    '"jit(step)/mx_exchange/psum"}',
    # a collective that XLA rewrote and left without its scope
    "  %all-reduce.2 = f32[8] all-reduce(%a)",
    '  %reshape.5 = f32[8] reshape(%all-reduce.2), metadata={op_name='
    '"jit(step)/mx_exchange/reshape"}',
    "}"])


def _kernel_trace():
    ms = 1e-3
    ops = [['%flash_attention_fwd.3 = f32[8] custom-call(%a), '
            'custom_call_target="tpu_custom_call"', 0.0, 2 * ms],
           ['%flash_attention_fwd.7 = f32[8] custom-call(%a), '
            'custom_call_target="tpu_custom_call"', 2 * ms, 3 * ms],
           ['%custom-call.9 = f32[8] custom-call(%a), '
            'custom_call_target="ConcatBitcast"', 5 * ms, 1 * ms],
           ["%all-reduce.1 = f32[8] all-reduce(%a)", 6 * ms, 4 * ms],
           ["%all-reduce.2 = f32[8] all-reduce(%a)", 10 * ms, 2 * ms],
           ["%reshape.5 = f32[8] reshape(%all-reduce.2)", 12 * ms, 1 * ms]]
    return trace_reduce.reduce(
        {"devices": {"/device:TPU:0": {"ops": ops, "async": []}},
         "host": []}, _KERNEL_HLO)


def test_seconds_by_kernel():
    """Every ``custom-call`` event under its instruction's own name
    without the number, listed by ``kernels/`` or not; the recordings
    hold XLA's own (``%custom-call.N``) and no kernel."""
    r = _kernel_trace()
    assert r["by_kernel_s"] == {
        "flash_attention_fwd": pytest.approx(5e-3),
        "custom-call": pytest.approx(1e-3)}
    assert "flash_attention_fwd" not in trace_reduce.conv_kernels()
    assert r["by_block_s"]["forward"] == {
        "net0_attn0": pytest.approx(2e-3), "net0_attn1": pytest.approx(3e-3)}
    assert r["by_block_s"]["unscoped"] == {"": pytest.approx(3e-3)}
    assert r["by_phase_class_s"] == {
        "forward": {"custom_call": pytest.approx(5e-3)},
        "unscoped": {"custom_call": pytest.approx(1e-3),
                     "collective": pytest.approx(2e-3)},
        "exchange": {"collective": pytest.approx(4e-3),
                     "other": pytest.approx(1e-3)}}
    for load, before in _AS_BEFORE.values():
        events, text = load()
        n = sum("custom-call(" in e[0]
                for d in events["devices"].values() for e in d["ops"])
        assert n > 100
        assert trace_reduce.reduce(events, text)["by_kernel_s"] == {
            "custom-call": before["by_class_s"]["custom_call"]}


@pytest.mark.parametrize("name,per_step", [
    ("exchange_ms.train", 3.5), ("pool_roofline.train", None)])
def test_new_readers_on_a_trace_with_and_without_their_events(name,
                                                              per_step):
    read = cb.load_module("metrics", name).read
    run = {"trace": dict(_kernel_trace(), steps=2), "config": VGG16,
           "batch": 64, "chips": 1, "peak": PEAK}
    # in 2 steps 4 ms of a collective under the exchange, 2 ms of one
    # that lost its scope and 1 ms of the exchange's own reshape (the
    # phase alone holds 5 ms); no block of a pooling row
    assert run["trace"]["by_phase_s"]["exchange"] == pytest.approx(5e-3)
    assert read(run) == (None if per_step is None
                         else pytest.approx(per_step))
    events, text = _vgg_recording()  # one chip: no exchange, but pooling
    there = dict(run, trace=dict(trace_reduce.reduce(events, text), steps=2))
    assert (read(there) is None) == (name == "exchange_ms.train")
    assert read(dict(run, trace=None)) is None
    # a configuration with no pooling row
    assert read(dict(there, config=dict(VGG16, layers=[
        r for r in VGG16["layers"] if not isinstance(r, dict)]))) is None \
        or name == "exchange_ms.train"


def test_phase_of_an_op_name():
    ph = trace_reduce.phase_of
    assert ph("jit(step)/mx_forward/vgg0_conv2d1/conv") == "forward"
    assert ph("jit(step)/transpose(jvp(mx_forward))//vgg0_pool4/ge") == \
        "backward"
    assert ph("jit(step)/mx_optimizer/mul") == "optimizer"
    assert ph("jit(step)/transpose(jvp(mx_loss))/sub") == "backward"
    assert ph("jit(step)/convert") == "unscoped"
    assert ph("") == "unscoped"
    pb = trace_reduce.phase_and_block
    assert pb("jit(step)/mx_forward/vgg0_conv2d1/conv") == (
        "forward", "vgg0_conv2d1")
    assert pb("jit(step)/transpose(jvp(mx_forward))//vgg0_pool4/ge") == (
        "backward", "vgg0_pool4")
    # the innermost block; a wrapper between is none
    assert pb("jit(step)/mx_forward/net0/net0_attn0/jit(relu)/max") == (
        "forward", "net0_attn0")
    assert pb("jit(step)/mx_forward/add") == ("forward", "")
    assert pb("jit(step)/mx_optimizer/bucket0/mul") == ("optimizer", "")


def test_loader_keeps_the_benchmarks_host_spans(tmp_path):
    import jax

    text = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = fusion()" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "cb_dispatch" } }
  event_metadata { key: 2 value { id: 2 name: "mx_step" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(step)" } } }
"""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    events = trace_reduce.load_xplane(os.fspath(path))
    assert [n for n, _, _ in events["host"]] == ["cb_dispatch"]
    assert len(events["devices"]["/device:TPU:0"]["ops"]) == 1
    r = trace_reduce.reduce(events)
    assert r["by_phase_s"] == {"unscoped": pytest.approx(5e-6)}
