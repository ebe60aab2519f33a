"""Time of one bucket dispatch (stack, host-to-device, execute, fetch): the
program's ``serve.dispatch`` histogram, the window's share of it, as the
exact mean (sum / count; see ``queue_wait_ms.serve`` for why not a
quantile)."""


def read(facts):
    hist = facts.get("hists", {}).get("serve.dispatch")
    return hist["sum_ms"] / hist["count"] if hist and hist["count"] else None
