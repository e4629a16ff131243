"""The feed's three readers (``feed_busy_pct.train``,
``feed_source_ms.train``, ``feed_depth.train``) on a made-up window:
the arithmetic, and nothing to read where the program has no such
counter (the parent of the PR that brought them) or the window no step.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as cb  # noqa: E402

WINDOW = {"steps": 340, "seconds": 20.0,
          "feed": {"batches": 340, "epochs": 0, "consumer_wait_s": 0.03,
                   "producer_busy_s": 3.0, "h2d_bytes": 340 * 19_267_840,
                   "source_wait_s": 0.0068, "depth_sum": 670}}
#: what DeviceFeedIter.stats() held before the counters were added
OLD_KEYS = ("batches", "epochs", "consumer_wait_s", "producer_busy_s",
            "h2d_bytes")


def _read(name, window):
    return cb.load_module("metrics", name).read({"window": window})


@pytest.mark.parametrize("name,expected", [
    ("feed_busy_pct.train", 15.0),
    ("feed_source_ms.train", 0.02),
    ("feed_depth.train", 670 / 340),
])
def test_reader_arithmetic(name, expected):
    assert _read(name, WINDOW) == pytest.approx(expected)


@pytest.mark.parametrize("name", ["feed_busy_pct.train",
                                  "feed_source_ms.train",
                                  "feed_depth.train"])
def test_window_with_no_batches_reads_none(name):
    empty = {"steps": 0, "seconds": 0.0,
             "feed": dict.fromkeys(WINDOW["feed"], 0)}
    assert _read(name, empty) is None


@pytest.mark.parametrize("name,reads", [
    ("feed_busy_pct.train", True),  # producer_busy_s was there before
    ("feed_source_ms.train", False),
    ("feed_depth.train", False),
])
def test_program_without_the_new_counters(name, reads):
    old = dict(WINDOW, feed={k: WINDOW["feed"][k] for k in OLD_KEYS})
    assert (_read(name, old) is not None) == reads


def test_readers_go_through_run_py():
    cell = cb.load_cell("vgg16_train")
    names = {"feed_busy_pct.train", "feed_source_ms.train",
             "feed_depth.train"}
    new = [m for m in cell["per_layer"] if m["name"] in names]
    assert {m["name"] for m in new} == names
    got = cb.read_metrics(new, {"window": WINDOW})
    assert {k: v["unit"] for k, v in got.items()} == {
        "feed_busy_pct.train": "%", "feed_source_ms.train": "ms/step",
        "feed_depth.train": "batches"}
