"""The witness's own checks, which a ``model_config`` PR brings with its
configuration: its row whose work is given outright against a hand
count, and its two readers (time by block, time by kernel) over its
recorded trace.  The name matches no pattern pytest collects by: it runs
where ``test_witness.py`` names it, in the copy of the tree that holds the
witness's files."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import flops, trace_reduce  # noqa: E402
from chipbench import run as cb  # noqa: E402


def test_embedding_row_is_the_hand_count():
    config = cb.load_json("configs", "tokwit.json")
    seq, width = config["input"]["seq"], config["arch"]["width"]
    ids, rows = seq * 4, seq * width * 2
    given = [r for r in flops.rows(config) if not flops.is_product(r)]
    assert given == [{
        "name": "embed", "count": 1, "blocks": ["tokwit_embedding0"],
        "per_row": {"forward": {"macs": 0, "bytes": ids + rows + rows},
                    "backward": {"macs": 0, "bytes": ids + rows + rows}}}]
    assert "embed" in config["assumed"]
    peak = flops.peaks("TPU v5 lite")
    assert flops.rows_roofline_s(config, 8, peak, {"embed"})[0] == \
        pytest.approx(8 * 2 * 816 / 819e9)
    # no multiply-adds: the step's count is the products'
    assert flops.step_flops(config, 8) == 6 * 8 * seq * (16 * 32 + 32 * 64)


def test_readers_of_a_block_and_of_a_kernel_read_the_recorded_trace():
    cell = cb.load_cell("tokwit_train")
    rec = trace_reduce.load_events(os.path.join(
        ROOT, "chipbench", "testdata", "tokwit_train_2steps.json"))
    known = rec["known"]
    trace = trace_reduce.reduce(rec["events"], rec["hlo_text"])
    trace["steps"] = known["steps"]
    assert trace["by_kernel_s"] == {
        "tokwit_made_up_kernel": pytest.approx(2 * 7e-6)}
    assert trace["by_class_s"]["conv_dot"] == pytest.approx(
        2 * (5 + 7 + 8 + 6) * 1e-6)  # the listed kernel among them
    for phase, seconds in trace["by_phase_s"].items():
        assert sum(trace["by_block_s"][phase].values()) == pytest.approx(
            seconds)
    embed = [r for r in flops.rows(cell["config"]) if r["name"] == "embed"]
    assert trace_reduce.block_seconds(
        trace["by_block_s"], embed[0]["blocks"]) == pytest.approx(
            known["embed_block_s"])
    mine = [m for m in cell["per_layer"]
            if m.get("workloads") == ["tokwit_train"]]
    got = cb.read_metrics(mine, {"trace": trace})
    assert sorted(got) == ["tokwit_dense0_ms.train",
                           "tokwit_kernel_ms.train"]
    for name, reading in got.items():
        assert reading["value"] == pytest.approx(known[name])
    nothing = dict(trace, by_block_s={}, by_kernel_s={})
    assert cb.read_metrics(mine, {"trace": nothing}) == {}
    assert cb.read_metrics(mine, {"trace": None}) == {}
