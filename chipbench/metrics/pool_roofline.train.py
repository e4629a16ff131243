"""Kernels (pooling): the least time a chip could take for its share of
the step's pooling rows (the configuration's ``per_row`` rows called
``pool*``: bytes and no multiply-adds, ``chipbench/flops.py``) over the
device time per step of the traced events of the blocks those rows name,
forward and backward (``by_block_s`` of ``chipbench/trace_reduce.py``),
on the busiest device.  A fusion goes under its root's block, so what
XLA fuses of a pooling layer into a neighbouring convolution is timed
with the convolution: the share reads high by that much, never low.
Nothing where the configuration has no such row or the trace no such
block."""
from chipbench import flops, trace_reduce


def read(run):
    tr = run["trace"]
    if not tr or run["peak"] is None:
        return None
    pools = [r for r in flops.rows(run["config"])
             if not flops.is_product(r) and r["name"].startswith("pool")]
    blocks = {b for r in pools for b in r["blocks"]}
    measured = trace_reduce.block_seconds(tr["by_block_s"], blocks) \
        / tr["steps"]
    if measured <= 0.0:
        return None
    least, _, _ = flops.rows_roofline_s(
        run["config"], run["batch"] // run["chips"], run["peak"],
        {r["name"] for r in pools})
    return 100.0 * least / measured
