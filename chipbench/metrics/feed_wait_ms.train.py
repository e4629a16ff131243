"""Input feed: ``DeviceFeedIter.stats()["consumer_wait_s"]`` over the
window's steps, the time a step's ``next()`` waited for its batch."""


def read(run):
    w = run["window"]
    return w["feed"]["consumer_wait_s"] / w["steps"] * 1e3
