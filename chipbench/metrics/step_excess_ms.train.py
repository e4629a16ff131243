"""Train step: what the window lost to uneven steps, in ms a step, from
the program's record of each call (``chipbench/step_record.py``): the
mean less the median of ``t_done[i] - t_done[i-1]`` over the window's
consecutive steps, whichever side held a step up.  The rate is about
the batch over (median + this).  Beside ``host_late_ms.train`` it says
where a stall was: as large, on the host; far larger, on the device.
Nothing where the program keeps no such record."""
import numpy as np

from chipbench import step_record


def read(run):
    r = step_record.window_records(run)
    if r is None:
        return None
    gaps = np.diff(r["t_done"])
    return float(np.mean(gaps) - np.median(gaps)) * 1e3
