"""Mixture of experts: device time per step of the traced events whose
block is a mixture or one of its parts (router, routed bank, shared
expert: ``gluon.nn.SparseMoE``'s names all hold ``sparsemoe``), both
passes and the backward pass's recomputation (``by_block_s`` of
``chipbench/trace_reduce.py``, busiest device).  Nothing where the trace
holds no such block."""

PART = "sparsemoe"


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    seconds = sum(s for of_phase in tr.get("by_block_s", {}).values()
                  for block, s in of_phase.items() if PART in block)
    return seconds / tr["steps"] * 1e3 if seconds > 0.0 else None
