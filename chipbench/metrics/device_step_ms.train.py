"""Train step: the device's own time a step, in ms, from the program's
record of each call (``chipbench/step_record.py``): the median over the
window's consecutive steps of ``t_done[i] - max(t_done[i-1],
t_return[i])``, the time from when the device had both the step before
done and this one in hand to when this one's loss was ready.  Nothing
where the program keeps no such record."""
import numpy as np

from chipbench import step_record


def read(run):
    r = step_record.window_records(run)
    if r is None:
        return None
    done = r["t_done"]
    return float(np.median(
        done[1:] - np.maximum(done[:-1], r["t_return"][1:]))) * 1e3
