"""Train step: device time per step of the traced events whose phase is
forward (``by_phase_s`` of ``chipbench/trace_reduce.py``: every event
under one phase, that of its instruction or of its fusion's root), on
the busiest device.  Nothing where the program names no such phase."""


def read(run):
    tr = run["trace"]
    if not tr or tr["by_phase_s"].get("forward", 0.0) <= 0.0:
        return None
    return tr["by_phase_s"]["forward"] / tr["steps"] * 1e3
