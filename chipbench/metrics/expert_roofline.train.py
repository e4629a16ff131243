"""Kernels (the routed bank's products): the least time a chip could take
for the rows ``moe_experts_up`` and ``moe_experts_down`` at their
EXPECTED positions (tokens x experts a token / experts of the layer, a
product row each: ``chipbench/flops.py``) over the device time per step
of the routed bank: the traced events whose block is a
``gluon.nn.RoutedExperts`` (names that hold ``routedexperts``), both
passes and the recomputation, busiest device.  It reads low where rows
that no token sent to a held expert are multiplied, which the program
does today (every held expert runs over every token), and where the
routing sends the held experts more than their expected share.  Nothing
where the configuration has no such rows or the trace no such block."""
from chipbench import flops

ROWS = {"moe_experts_up", "moe_experts_down"}
PART = "routedexperts"


def read(run):
    tr = run["trace"]
    if not tr or run["peak"] is None:
        return None
    if not ROWS <= {r["name"] for r in flops.rows(run["config"])}:
        return None
    seconds = sum(s for of_phase in tr.get("by_block_s", {}).values()
                  for block, s in of_phase.items() if PART in block)
    if seconds <= 0.0:
        return None
    least, _, _ = flops.rows_roofline_s(
        run["config"], run["batch"] // run["chips"], run["peak"], ROWS)
    return 100.0 * least / (seconds / tr["steps"])
