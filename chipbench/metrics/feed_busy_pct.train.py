"""Input feed: share of the window in which the feed was busy with a
batch, from the host batch in hand to the batch on the device
(``DeviceFeedIter.stats()["producer_busy_s"]``).  Near 100 the feed is
at its wall, whatever ``feed_wait_ms.train`` still reads."""


def read(run):
    w = run["window"]
    busy = w["feed"].get("producer_busy_s")
    if busy is None or not w["seconds"]:
        return None
    return 100.0 * busy / w["seconds"]
