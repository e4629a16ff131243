"""Device: 1 - union of the device-operation intervals over the traced
window (first operation's start to the last one's end), on the busiest
device."""


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busiest_busy_s"] / tr["busiest_window_s"])
