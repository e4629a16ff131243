"""Train step: what the device waited on the host a step, in ms, from
the program's record of each call (``chipbench/step_record.py``): the
mean over the window's consecutive steps of ``max(0, t_return[i] -
t_done[i-1])``, the time from when the device had finished the step
before to when this one was handed to it.  The whole window's idle
share, where ``device_idle_pct.train`` reads the traced tail's.  Nothing
where the program keeps no such record."""
import numpy as np

from chipbench import step_record


def read(run):
    r = step_record.window_records(run)
    if r is None:
        return None
    late = r["t_return"][1:] - r["t_done"][:-1]
    return float(np.mean(np.maximum(0.0, late))) * 1e3
