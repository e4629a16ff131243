"""The 95th percentile of the time per step, over every span of
``span_steps`` successive step completions in the window (each step opens
one span, so spans overlap).  A span and not one step, because the host's
clock is off by some half a millisecond and a step can be 45 ms: the
traffic file sizes ``span_steps`` so that a span lasts 250 ms or more.  A
stall of the feed, a recompilation or a host pause lengthens every span
it falls in."""
import numpy as np


def read(run):
    k = run["traffic"]["span_steps"]
    done = run["window"]["done_at"]
    if len(done) <= k:
        return None
    spans = (done[k:] - done[:-k]) / k
    return float(np.percentile(spans, 95)) * 1e3
