"""Train step: device time per step of the traced events whose phase is
backward, ``transpose(jvp(mx_forward))`` (``by_phase_s`` of
``chipbench/trace_reduce.py``), on the busiest device.  XLA puts a
weight's update into the fusion that makes its gradient, so the
optimizer's work is in here.  Nothing where the program names no such
phase."""


def read(run):
    tr = run["trace"]
    if not tr or tr["by_phase_s"].get("backward", 0.0) <= 0.0:
        return None
    return tr["by_phase_s"]["backward"] / tr["steps"] * 1e3
