"""Mixture of experts: of all the assignments of a token to an expert that
the routers made in the window's steps, the share that fell on the
experts this chip holds, in percent: the mean over the window's steps of
``moe_assignments_held / moe_assignments``.  The program counts both
inside its compiled step and hands them out through the step's state;
its host side keeps every step's (``mxnet_tpu.profiler.step_counters``),
the traced tail's steps after the window's
(``chipbench/step_record.py`` ``window_counters``).  16 experts of 128
held would read 6.25 under even routing; no balancing rule runs, so it
drifts.  Nothing where the program has no such counters (a program
without them, a net without a mixture)."""
from chipbench import step_record


def read(run):
    steps = step_record.window_counters(run) or []
    shares = [s["moe_assignments_held"] / s["moe_assignments"]
              for s in steps if s.get("moe_assignments")]
    return 100.0 * sum(shares) / len(shares) if shares else None
