"""Input feed: batches already on the device when the step asked for
one, averaged over the window's ``next()`` calls
(``DeviceFeedIter.stats()``: ``depth_sum / batches``).  At the feed's
depth the feed is far ahead; near nought the step waits for it."""


def read(run):
    feed = run["window"]["feed"]
    if feed.get("depth_sum") is None or not feed.get("batches"):
        return None
    return feed["depth_sum"] / feed["batches"]
