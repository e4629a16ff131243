"""ZeRO exchange: device time per step, on the busiest device, of every
traced collective event, whatever phase it stands under, and of every
other event whose phase is the exchange, ``mx_exchange``: the buckets'
packing, scaling and unpacking (``by_phase_class_s`` of
``chipbench/trace_reduce.py``).  Hidden behind other work or not.  The
collectives are taken by class because XLA drops the scope of those it
rewrites: on four chips the gradients' combined ``all-reduce`` carries no
``op_name``, and the phase alone read 8.9 ms where the collectives alone
take 14.5.  Nothing where the trace holds no collective and the program
names no such phase."""


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    seconds = sum(
        s for phase, by_class in tr["by_phase_class_s"].items()
        for c, s in by_class.items()
        if c == "collective" or phase == "exchange")
    return seconds / tr["steps"] * 1e3 if seconds > 0.0 else None
