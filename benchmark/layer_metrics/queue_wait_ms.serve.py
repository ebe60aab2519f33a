"""Time a request waited in the server's queue and batcher before its
dispatch began: the program's ``serve.queue_wait`` histogram, the window's
share of it, as the exact mean (sum / count).  The histogram's own
quantiles are bucket midpoints ten to a decade — steps of 26%, too coarse
to show a gain — so the mean is read, not a median."""


def read(facts):
    hist = facts.get("hists", {}).get("serve.queue_wait")
    return hist["sum_ms"] / hist["count"] if hist and hist["count"] else None
