"""Train step: model FLOPs of the window's steps (forward and backward of
every convolution and dense layer, from the configuration's layer table,
no recomputation counted) over the window and the chips' bf16 peak."""
from chipbench import flops


def read(run):
    if run["peak"] is None:
        return None
    w = run["window"]
    done = flops.step_flops(run["config"], run["batch"]) * w["steps"]
    return 100.0 * done / w["seconds"] \
        / (run["chips"] * run["peak"]["bf16_flops_per_s"])
