"""ZeRO exchange: device time per step of collective events during which
no operation of another class runs on that device, averaged over the
devices.  Nothing where the trace holds no collective."""


def read(run):
    tr = run["trace"]
    if not tr or tr["collective_s"] <= 0.0:
        return None
    return tr["collective_exposed_s"] / tr["steps"] * 1e3
