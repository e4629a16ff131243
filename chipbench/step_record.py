"""The window's steps as the program's train step recorded them itself.

The program keeps a record of every call of its compiled step
(``mxnet_tpu.profiler.step_records()``): ``t_enter`` as the call began,
``t_return`` when it had handed the step to the runtime, ``t_done`` when
a watcher thread saw the step's loss ready on the device, all on
``time.perf_counter()``, which is the clock of the window's ``t_start``
and ``done_at``.  The window's steps are the records whose ``t_enter``
lies in ``[t_start, done_at[-1]]``; the traced tail comes after and drops
out.  Between step ``i - 1`` and step ``i`` the device waited on the
host from ``t_done[i - 1]`` to ``t_return[i]`` where that is later, and
worked on its own from the later of the two to ``t_done[i]``.  The
readers of ``device_step_ms.train``, ``host_late_ms.train``,
``step_excess_ms.train`` and ``step_call_ms.train`` start here.

The same ring holds what the blocks of the compiled step counted
(``profiler.step_counters()``): :func:`window_counters` gives the
window's, which the mixture's readers (``moe_held_pct.train``,
``moe_grouped_pct.train``) average."""
import numpy as np


def window_records(run):
    """``{"t_enter", "t_return", "t_done": arrays in call order}`` of the
    window's steps, or None: where the program keeps no such record, the
    window has no start, fewer than two records are found, or they are
    not as many as the window's steps."""
    try:
        from mxnet_tpu import profiler
    except ImportError:
        return None
    window = run.get("window") or {}
    done_at = window.get("done_at")
    if not hasattr(profiler, "step_records") \
            or window.get("t_start") is None \
            or done_at is None or len(done_at) == 0:
        return None
    t0, t1 = window["t_start"], done_at[-1]
    found = [r for r in profiler.step_records() if t0 <= r["t_enter"] <= t1]
    if len(found) < 2 or len(found) != window["steps"]:
        return None
    return {k: np.array([r[k] for r in found], dtype=np.float64)
            for k in ("t_enter", "t_return", "t_done")}


def window_counters(run):
    """The counters of the window's steps, oldest first, the traced
    tail's (which come after) left out; None where the program keeps no
    such counters or the run has no window."""
    try:
        from mxnet_tpu import profiler
    except ImportError:
        return None
    if not hasattr(profiler, "step_counters") or not run.get("window"):
        return None
    tail = run["traffic"]["trace_steps"] if run.get("trace") else 0
    steps = profiler.step_counters(last=run["window"]["steps"] + tail)
    return steps[:len(steps) - tail] if tail else steps
