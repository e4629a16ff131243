"""``nemotron3_nano_30b_a3b``'s own checks: its file against the public
``config.json``, its ``per_row`` rows recounted from ``arch``, and each of
its metrics' readers over a made-up reduced trace."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import flops  # noqa: E402
from chipbench import run as cb  # noqa: E402

NAME = "nemotron3_nano_30b_a3b"
CELL = "nemotron3_nano_train"
#: huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json,
#: the keys that say something about the model's shape
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 9, "n_routed_experts": 8, "vocab_size": 16384}
#: the per-layer metrics that ``BENCHMARK.json`` lists for this cell alone
OWN_METRICS = ("mamba_ms.train", "ssd_roofline.train", "moe_ms.train",
               "expert_roofline.train", "moe_held_pct.train",
               "attn_ms.train", "moe_grouped_pct.train")


@pytest.fixture(scope="module")
def config():
    return cb.load_json("configs", NAME + ".json")


def test_every_key_is_the_published_one_but_the_three_cuts(config):
    assert config["reduced"] == sorted(REDUCED, key=list(REDUCED).index)
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
        if key in REDUCED:
            assert config[key + "_published"] == value
    # what is run is what is stated: the constructor and the reference
    # read the same widths, and those are the published ones
    kwargs, arch = config["constructor"]["kwargs"], config["arch"]
    n = REDUCED["num_hidden_layers"]
    assert kwargs["pattern"] == arch["pattern"] \
        == PUBLISHED["hybrid_override_pattern"][:n]
    assert kwargs["vocab_size"] == config["input"]["vocab"] \
        == REDUCED["vocab_size"]
    assert kwargs["experts_held"] == arch["experts_held"] \
        == [0, REDUCED["n_routed_experts"]]
    assert kwargs["n_routed_experts"] == arch["n_routed_experts_published"] \
        == PUBLISHED["n_routed_experts"]
    assert kwargs["layer_norm_epsilon"] == arch["norm_eps"] \
        == PUBLISHED["norm_eps"]
    for key in ("hidden_size", "mamba_num_heads", "mamba_head_dim",
                "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "num_experts_per_tok", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size",
                "routed_scaling_factor"):
        assert kwargs[key] == arch[key] == PUBLISHED[key], key
    assert PUBLISHED["expand"] * PUBLISHED["hidden_size"] \
        != arch["mamba_num_heads"] * arch["mamba_head_dim"]  # 4096: heads
    # x head_dim is the inner width, as the family's model code takes it
    for key in ("optimizer", "learning_rate", "rotary_embedding",
                "A_log_dt_bias", "embed", "ssd_scan", "attn_core",
                "moe_experts", "mamba_conv"):
        assert key in config["assumed"], key


def test_it_has_the_parameters_that_were_reckoned(config):
    ref = cb.load_module("reference", NAME)
    vocab = config["input"]["vocab"]
    specs = ref.param_specs(config["arch"], vocab, vocab)
    assert len(specs) == 72
    count = 0
    for _, shape in specs:
        n = 1
        for side in shape:
            n *= side
        count += n
    assert count == config["trainable_elements"] == 666963456


def _row(config, name):
    return next(r for r in flops.rows(config) if r["name"] == name)


def test_per_row_rows_are_the_hand_counts(config):
    """Recounted from ``arch`` and the input, in the compute type's two
    bytes: the look-up, the scan, attention's scores and values."""
    a, seq = config["arch"], config["input"]["seq"]
    width, two = a["hidden_size"], 2
    embed = _row(config, "embed")["per_row"]
    assert embed["forward"] == embed["backward"] == {
        "macs": 0, "bytes": seq * 4 + 2 * seq * width * two}
    heads, p, n = a["mamba_num_heads"], a["mamba_head_dim"], a["ssm_state_size"]
    chunk, groups = a["chunk_size"], a["n_groups"]
    per_chunk = chunk * chunk * (n + p) + 2 * chunk * n * p
    macs = heads * (seq // chunk) * per_chunk
    assert macs == 10737418240
    ins = seq * heads * p * two + 2 * seq * groups * n * two \
        + seq * heads * two
    out = seq * heads * p * two
    scan = _row(config, "ssd_scan")
    assert scan["per_row"] == {
        "forward": {"macs": macs, "bytes": ins + out},
        "backward": {"macs": 2 * macs, "bytes": ins + out + ins}}
    assert scan["count"] == a["pattern"].count("M") == len(scan["blocks"])
    q_heads, kv, dim = (a["num_attention_heads"], a["num_key_value_heads"],
                        a["head_dim"])
    macs = 2 * (seq * seq // 2) * dim * q_heads
    assert macs == 68719476736
    q, k_v = seq * q_heads * dim * two, 2 * seq * kv * dim * two
    core = _row(config, "attn_core")
    assert core["per_row"] == {
        "forward": {"macs": macs, "bytes": q + k_v + q},
        "backward": {"macs": 2 * macs, "bytes": q + k_v + q + q + k_v}}
    assert core["count"] == a["pattern"].count("*") == len(core["blocks"])
    # the banks at their expected positions, the filter as a product
    held = a["experts_held"][1] - a["experts_held"][0]
    for name in ("moe_experts_up", "moe_experts_down"):
        row = _row(config, name)
        assert row["positions"] == seq * a["num_experts_per_tok"] \
            // a["n_routed_experts_published"] == 192
        assert row["count"] == held * a["pattern"].count("E")
    conv = _row(config, "mamba_conv")
    assert flops.row_weights(conv) == (heads * p + 2 * groups * n) \
        * a["conv_kernel"]
    # 15.66 TFLOP of products a step of two sequences, 17.0 with the rest
    products = sum(c * f for _, _, c, f, _ in
                   flops.passes(config, 2, flops.is_product))
    assert products == pytest.approx(15.656e12, rel=1e-3)
    assert flops.step_flops(config, 2) == pytest.approx(16.996e12, rel=1e-3)


# ------------------------------------------------------------ the readers
PRE = "nemotronh0_residuallayer"


def _made_up_run(config):
    fwd = {PRE + "0_mamba2mixer0_dense0": 0.010,
           PRE + "0_mamba2mixer0_ssdscan0": 0.004,
           PRE + "0_mamba2mixer0": 0.002,
           PRE + "1_sparsemoe0_moerouter0": 0.001,
           PRE + "1_sparsemoe0_routedexperts0": 0.013,
           PRE + "1_sparsemoe0_squaredrelumlp0_dense0": 0.005,
           PRE + "5_gqattention0": 0.006,
           PRE + "5_gqattention0_dense0": 0.002,
           "flash_attention_fwd": 0.003,
           "nemotronh0_embedding0": 0.001}
    bwd = {PRE + "0_mamba2mixer0_ssdscan0": 0.008,
           PRE + "1_sparsemoe0_routedexperts0": 0.002,
           "nemotronh0_dense0": 0.007}
    trace = {"steps": 2, "by_block_s": {"forward": fwd, "backward": bwd},
             "by_kernel_s": {"flash_attention_fwd": 0.003}}
    cell = cb.load_cell(CELL)
    return {"config": config, "traffic": cell["traffic"], "chips": 1,
            "batch": 2, "peak": flops.peaks("TPU v5 lite"), "trace": trace,
            "window": {"steps": 3}}


def _read(name, run):
    return cb.load_module("metrics", name).read(run)


def test_each_new_reader_reads_a_made_up_reduced_trace(config):
    run = _made_up_run(config)
    assert _read("mamba_ms.train", run) == pytest.approx(
        (0.010 + 0.004 + 0.002 + 0.008) / 2 * 1e3)
    assert _read("attn_ms.train", run) == pytest.approx(0.011 / 2 * 1e3)
    assert _read("moe_ms.train", run) == pytest.approx(
        (0.001 + 0.013 + 0.005 + 0.002) / 2 * 1e3)
    peak = run["peak"]
    least, _, _ = flops.rows_roofline_s(config, 2, peak, {"ssd_scan"})
    assert _read("ssd_roofline.train", run) == pytest.approx(
        100.0 * least / ((0.004 + 0.008) / 2))
    least, _, _ = flops.rows_roofline_s(
        config, 2, peak, {"moe_experts_up", "moe_experts_down"})
    assert _read("expert_roofline.train", run) == pytest.approx(
        100.0 * least / ((0.013 + 0.002) / 2))
    assert 0.0 < _read("ssd_roofline.train", run) < 100.0
    assert 0.0 < _read("expert_roofline.train", run) < 100.0


@pytest.fixture
def counters():
    """The program's ring of step records, empty for the test, then as it
    was."""
    from mxnet_tpu import profiler

    kept = list(profiler._step_counters)
    profiler._step_counters.clear()
    yield profiler.note_step_counters
    profiler._step_counters.clear()
    profiler._step_counters.extend(kept)


def test_held_share_is_the_window_s_mean_of_the_step_s_counters(
        config, counters):
    run = _made_up_run(config)
    tail = run["traffic"]["trace_steps"]
    assert _read("moe_held_pct.train", run) is None
    shares = [0.5, 0.0625, 0.125, 0.25] + [0.9] * tail
    for share in shares:  # a warm-up step, the window's 3, the tail
        counters({"moe_assignments": 1000.0,
                  "moe_assignments_held": 1000.0 * share})
    assert _read("moe_held_pct.train", run) == pytest.approx(
        100.0 * (0.0625 + 0.125 + 0.25) / 3)
    # an untraced run: the window's steps are the newest
    assert _read("moe_held_pct.train", dict(run, trace=None)) \
        == pytest.approx(90.0)


def test_grouped_share_is_the_window_s_mean_of_the_step_s_counters(
        config, counters):
    """Of four mixture layers a step, those that took the grouped path:
    the window's mean, the warm-up before it and the traced tail after it
    left out; nothing without counters or without a mixture layer."""
    run = _made_up_run(config)
    tail = run["traffic"]["trace_steps"]
    assert _read("moe_grouped_pct.train", run) is None
    grouped = [0, 4, 3, 4] + [2] * tail  # a warm-up step, the window's 3
    for n in grouped:
        counters({"moe_layers": 4.0, "moe_layers_grouped": float(n),
                  "moe_assignments": 1000.0, "moe_assignments_held": 50.0})
    assert _read("moe_grouped_pct.train", run) == pytest.approx(
        100.0 * (1.0 + 0.75 + 1.0) / 3)
    assert _read("moe_grouped_pct.train", dict(run, trace=None)) \
        == pytest.approx(50.0)
    for _ in grouped:  # a step that ran no mixture layer reads nothing
        counters({"moe_layers": 0.0, "moe_layers_grouped": 0.0})
    assert _read("moe_grouped_pct.train", run) is None


@pytest.mark.parametrize("name", [
    "mamba_ms.train", "ssd_roofline.train", "moe_ms.train",
    "expert_roofline.train", "attn_ms.train"])
def test_a_reader_finds_nothing_where_its_block_is_absent(config, name):
    """A convnet's trace, a reduced trace of the parent's form, no trace:
    nothing, never 0."""
    run = _made_up_run(config)
    other = {"steps": 2, "by_block_s": {"forward": {"vgg0_conv2d0": 0.01}},
             "by_kernel_s": {}}
    assert _read(name, dict(run, trace=other)) is None
    assert _read(name, dict(run, trace={"steps": 2})) is None
    assert _read(name, dict(run, trace=None)) is None
    vgg = cb.load_json("configs", "vgg16.json")
    assert _read(name, dict(run, config=vgg, trace=other)) is None


def test_benchmark_entries_name_the_cell(config):
    """The configuration, the cell and the cell's own per-layer entries,
    each found by its name and never by its place: a later PR appends
    configurations, cells and metrics of its own to every list."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    [entry] = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    [cell] = [w for w in bench["workloads"] if w["name"] == CELL]
    assert {k: cell[k] for k in ("config", "traffic", "chips")} == {
        "config": NAME, "traffic": "train_tok4096_bs2", "chips": 1}
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(OWN_METRICS) <= set(mine)
    assert all(mine[n]["moves"] == "images_per_s" for n in OWN_METRICS)


def test_readings_hold_one_copy_of_the_state_and_read_as_from_build():
    """``readings`` (``chipbench/readings.py``) keeps one copy of the
    program's state on the chip, handing each seed the one the seed
    before left, wiped, and draws a seed's weights again for the
    reference: a seed read second reads exactly what the first steps
    from the state that ``build`` makes read, with the same verdict."""
    import time

    import jax
    from chipbench import compare
    from test_chipbench import _tiny

    cell = dict(_tiny(CELL), devices=jax.devices(), say=lambda m: None,
                t0=time.perf_counter())
    kind = cb.load_module("kinds", cell["traffic"]["kind"])
    out = kind.readings(cell, [12, 11], 0)

    built = kind.build(cell)
    loop, feed, w0, pool = kind.start(
        cell, built, 11, built.pop("params"), built.pop("opt_state"),
        cell["traffic"]["check_steps"])
    try:
        prog = kind.first_steps(cell, built, loop, w0)
    finally:
        feed.close()
    ref = kind.reference_side(cell, built, w0, pool)
    values = compare.numbers(prog, ref)[0]
    assert out[11]["program"] == values
    assert out[11]["correct"]["program"] == compare.verdict(
        values, cell["traffic"]["limits"])[0] is True
    assert out[11]["leaves"]["reference"] == kind._plain(ref)
    assert out[11]["leaves"]["program"] == kind._plain(prog)
