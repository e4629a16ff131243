"""Attention: device time per step of the traced events whose block is
the grouped-query attention block or one of its projections
(``gluon.nn.GQAttention``'s names all hold ``gqattention``) or its kernel
(``flash_attention_fwd``: a Pallas call's events go under its own name,
the innermost scope), the backward pass's recomputation with them
(``by_block_s`` of ``chipbench/trace_reduce.py``, busiest device).
Nothing where the trace holds no such block."""

PART = "gqattention"
KERNEL = "flash_attention_fwd"


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    seconds = sum(s for of_phase in tr.get("by_block_s", {}).values()
                  for block, s in of_phase.items()
                  if PART in block or block == KERNEL)
    return seconds / tr["steps"] * 1e3 if seconds > 0.0 else None
