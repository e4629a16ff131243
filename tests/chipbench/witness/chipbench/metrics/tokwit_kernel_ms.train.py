"""Kernels: device time per step of the kernel that
``chipbench/kernels/tokwit_kernels.json`` names (``by_kernel_s`` of
``chipbench/trace_reduce.py``: every ``custom-call`` event under its
instruction's own name).  Nothing where no such kernel ran."""
import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    with open(os.path.join(_HERE, os.pardir, "kernels",
                           "tokwit_kernels.json")) as f:
        names = json.load(f)["kernels"]
    seconds = sum(tr["by_kernel_s"].get(name, 0.0) for name in names)
    return seconds / tr["steps"] * 1e3 if seconds > 0.0 else None
