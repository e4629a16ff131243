"""Routed bank: of the mixture layers that the window's steps ran, the
share that took the grouped path (the held rows compacted into a buffer
for the grouped kernels) and not the dense fallback, which the bank's
``lax.cond`` takes for a layer and step whose held rows overflow the
buffer (``ops/routed_experts.py``), in percent: the mean over the
window's steps of ``moe_layers_grouped / moe_layers``, counted inside
the compiled step (``mxnet_tpu.profiler.step_counters``; the traced
tail's steps left out: ``chipbench/step_record.py``
``window_counters``).  Under 100, some layers ran the dense bank, which
multiplies every held expert over all tokens.  Nothing where the program
has no such counters (a program without them, a net without a
mixture)."""
from chipbench import step_record


def read(run):
    steps = step_record.window_counters(run) or []
    shares = [s["moe_layers_grouped"] / s["moe_layers"]
              for s in steps if s.get("moe_layers")]
    return 100.0 * sum(shares) / len(shares) if shares else None
