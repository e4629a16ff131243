"""Kernels: the least time a chip could take for its share of the step's
convolutions and dense layers (``chipbench/flops.py``, from shapes) over
the device time per step of the traced events that hold a convolution or
a dot.  The work is counted from the configuration, so the share reads
the same whatever implements the layer."""
from chipbench import flops


def read(run):
    tr = run["trace"]
    if not tr or run["peak"] is None:
        return None
    measured = tr["by_class_s"].get("conv_dot", 0.0) / tr["steps"]
    if measured <= 0.0:
        return None
    least, _, _ = flops.step_roofline_s(
        run["config"], run["batch"] // run["chips"], run["peak"])
    return 100.0 * least / measured
