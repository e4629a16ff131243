"""Mamba-2 mixers: device time per step of the traced events whose block
is a mixer or one of its parts (the projections, the convolution, the
scan, the gated norm), both passes and what the backward pass recomputes
(``by_block_s`` of ``chipbench/trace_reduce.py``, busiest device; the
blocks are ``gluon.nn.Mamba2Mixer``'s, whose names all hold
``mamba2mixer``).  Nothing where the trace holds no such block."""

PART = "mamba2mixer"


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    seconds = sum(s for of_phase in tr.get("by_block_s", {}).values()
                  for block, s in of_phase.items() if PART in block)
    return seconds / tr["steps"] * 1e3 if seconds > 0.0 else None
