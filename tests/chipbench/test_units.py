"""The yardstick's arithmetic by hand: a layer table's rows over images
and over a sequence, weights and batches from the seed unchanged to the
bit, the first gradient handed in against the one derived, the plain
AdamW rule against the program's, seconds by phase on the program's own
recorded trace (and the benchmark's reading of a scope held to the
program's), and the host annotations the loader keeps.  CPU only."""
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
    assert [flops.row_weights(r) for r in flops.rows(TOKWIT)] == [
        16 * 32, 32 * 64]
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
    assert sum(c * b for _, _, c, _, b in flops.passes(config, 64)) \
        == step_bytes
    assert flops.step_roofline_s(config, 64, PEAK)[0] == least
    if name == "resnet50_v1":
        assert abs(macs - 3.8e9) / 3.8e9 < 0.03  # arXiv:1512.03385


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
    """``phase_of`` and ``phase_table`` are a copy, on purpose, of part of
    ``mxnet_tpu/profiler.py``: over every instruction of the recorded
    step they give what ``_scope_table`` and ``_phase_and_block`` give."""
    from mxnet_tpu import profiler

    rec, _ = _recording()
    ours = trace_reduce.phase_table(rec["hlo_text"])
    theirs = profiler._scope_table(rec["hlo_text"])
    assert ours == {name: scope for name, (scope, _) in theirs.items()}
    scopes = set(ours.values())
    assert len(scopes) > 100
    for scope in scopes:
        assert trace_reduce.phase_of(scope) == \
            profiler._phase_and_block(scope)[0], scope
    assert {trace_reduce.phase_of(s) for s in scopes} >= {
        "forward", "backward", "loss", "optimizer", "unscoped"}


def test_phase_of_an_op_name():
    ph = trace_reduce.phase_of
    assert ph("jit(step)/mx_forward/vgg0_conv2d1/conv") == "forward"
    assert ph("jit(step)/transpose(jvp(mx_forward))//vgg0_pool4/ge") == \
        "backward"
    assert ph("jit(step)/mx_optimizer/mul") == "optimizer"
    assert ph("jit(step)/transpose(jvp(mx_loss))/sub") == "backward"
    assert ph("jit(step)/convert") == "unscoped"
    assert ph("") == "unscoped"


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
