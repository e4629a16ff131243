"""Kernels (the state-space scan): the least time a chip could take for
the configuration's ``ssd_scan`` row (``per_row``: the chunked scan's
multiply-adds and least bytes, ``chipbench/flops.py``) over the device
time per step of the traced events of the blocks that row names, the
scan's own (``gluon.nn.SSDScan``), forward, backward and the backward
pass's recomputation (``by_block_s``), busiest device.  Recomputed work
is time and no required operation, so it lowers the share.  Nothing where
the configuration has no such row or the trace no such block."""
from chipbench import flops, trace_reduce

ROW = "ssd_scan"


def read(run):
    tr = run["trace"]
    if not tr or run["peak"] is None:
        return None
    rows = [r for r in flops.rows(run["config"]) if r["name"] == ROW]
    if not rows or "by_block_s" not in tr:
        return None
    measured = trace_reduce.block_seconds(
        tr["by_block_s"], set(rows[0]["blocks"])) / tr["steps"]
    if measured <= 0.0:
        return None
    least, _, _ = flops.rows_roofline_s(
        run["config"], run["batch"] // run["chips"], run["peak"], {ROW})
    return 100.0 * least / measured
