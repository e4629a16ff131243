"""Kernels: device time per step of the traced events of the first dense
layer's block, forward and backward (``by_block_s`` of
``chipbench/trace_reduce.py``).  Nothing where the trace names no such
block."""
from chipbench import trace_reduce

BLOCKS = {"tokwit_dense0"}


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    seconds = trace_reduce.block_seconds(tr["by_block_s"], BLOCKS)
    return seconds / tr["steps"] * 1e3 if seconds > 0.0 else None
