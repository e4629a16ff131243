"""Input feed: time the feed's producer spent inside ``next(source)``
(``DeviceFeedIter.stats()["source_wait_s"]``), per step of the window:
what decoding and host-side assembly cost, off the step's path."""


def read(run):
    w = run["window"]
    wait = w["feed"].get("source_wait_s")
    if wait is None or not w["steps"]:
        return None
    return wait / w["steps"] * 1e3
