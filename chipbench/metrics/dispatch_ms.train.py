"""Train step: host time of the benchmark's own span around each
``step(...)`` call, until the call returns, per step of the window."""


def read(run):
    spans = run["window"]["spans"]["dispatch"]
    return float((spans[:, 1] - spans[:, 0]).sum()) / len(spans) * 1e3
