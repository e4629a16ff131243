"""ZeRO exchange: result bytes per device and step of the collectives in
the compiled step's text (``chipbench/hlo_collectives.py``): a count."""


def read(run):
    c = run["collectives"]
    if not c or c["total_bytes"] <= 0:
        return None
    return c["total_bytes"] / 1e6
