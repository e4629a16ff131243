"""Train step: the program's own call a step, in ms, from its record of
each call (``chipbench/step_record.py``): the mean over the window's
steps of ``t_return - t_enter``, from the call's start to the step's
hand-over to the runtime, measured inside the program where
``dispatch_ms.train`` reads the benchmark's span around it.  Nothing
where the program keeps no such record."""
import numpy as np

from chipbench import step_record


def read(run):
    r = step_record.window_records(run)
    if r is None:
        return None
    return float(np.mean(r["t_return"] - r["t_enter"])) * 1e3
