"""Seconds the program spent building parameters and optimizer state: the
totals of its spans ``gluon.param.init`` (an initializer's host draw and the
first placement), ``gluon.param.place`` (``cast`` / ``reset_ctx``) and
``parallel.state_init`` (master copies and optimizer state in
``DataParallelStep.__init__``).  They time the CALLS, not an asynchronous
transfer's end, and hold the compile seconds of the small programs made
inside them.  None where the run has no steps or the program has none of
the three."""
SPANS = ("gluon.param.init", "gluon.param.place", "parallel.state_init")


def read(facts):
    try:
        from mxnet_tpu.telemetry import snapshot
    except ImportError:
        return None
    spans = snapshot(events=0)["spans"]
    if not facts.get("steps") or not any(name in spans for name in SPANS):
        return None
    return sum(spans[name]["total_ms"] for name in SPANS
               if name in spans) / 1e3
